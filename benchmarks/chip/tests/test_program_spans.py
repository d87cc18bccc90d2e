"""Tests of the readers of the server's own spans and counters, on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests

A tiny cell runs through ``run.serve`` with a server built with a
``TraceRecorder`` and a ``MetricsRegistry``; the window's spans and
counters are what the readers read.  The two keys ``program_spans``
adds beside ``trace_reduce``'s breakdown are checked on synthetic
planes and on the chip trace fixture.
"""
import functools
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import drive  # noqa: E402
import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from test_chip_bench import BIG, DEVICE, on_cpu, root  # noqa: E402,F401

NEW_METRICS = ("admit_pack_ms.backlog", "admit_h2d_ms.backlog",
               "h2d_kib_per_admit.backlog", "step_wait_ms.backlog",
               "heartbeat_self_ms.backlog", "active_slot_share.backlog",
               "d2h_kib_per_heartbeat.backlog")


@pytest.fixture
def program_traced(monkeypatch):
    """``run.serve`` with the server's recorder and registry on and the
    harness's own spans on, without the profiler; returns a dict that
    receives the window (``program_spans.Window``)."""
    from repro.obs import MetricsRegistry, TraceRecorder
    from repro.serve import dataflow_server
    tr, mr, got = TraceRecorder(), MetricsRegistry(), {}
    monkeypatch.setattr(dataflow_server, "DataflowServer", functools.partial(
        dataflow_server.DataflowServer, trace=tr, metrics=mr))
    spans, window = drive.Spans, drive.window
    monkeypatch.setattr(drive, "Spans", lambda enabled: spans(True))

    def traced_window(srv, traffic, arcs, seconds, sp, log):
        before = mr.snapshot()["counters"]
        log = window(srv, traffic, arcs, seconds, sp, log)
        got["obs"] = program_spans.Window.of(
            tr, before, mr.snapshot()["counters"], log.t0,
            log.t0 + log.window_s)
        return log
    monkeypatch.setattr(drive, "window", traced_window)
    return got


def test_readers_on_a_traced_tiny_run(root, on_cpu, program_traced):
    cell = cells.load_cell("tiny.backlog", root)
    s = run.serve(cell, BIG, 0.8, False, DEVICE)
    obs = program_traced["obs"]
    view = run.RunView("backlog", 0.8, s.setup_s, s.log, s.traffic, s.spans,
                       None, {}, 8, 4, None)
    view.obs = obs
    got = {m: cells.reader(m, root)(view) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert obs.heartbeats() == s.log.heartbeats > 0
    # the program's spans sit inside the harness's spans around the
    # engine's slot API
    admit = sum(program_spans.per_heartbeat_ms(obs, f"dataflow.admit.{k}")
                for k in ("pack", "h2d", "dispatch"))
    admit_ms = cells.reader("admit_ms", root)(view)
    assert 0.5 * admit_ms < admit <= admit_ms
    step = sum(program_spans.per_heartbeat_ms(obs, f"dataflow.step.{k}")
               for k in ("dispatch", "wait"))
    assert 0.5 * cells.reader("step_block_ms", root)(view) < step \
        <= cells.reader("step_block_ms", root)(view)
    # every round stages its whole buffer: mask, feeds, lengths, one
    # fresh register row (full and value) and the active mask
    st = s.srv.state
    B, n_in, L = st.fv.shape
    round_bytes = B + 4 * B * n_in * L + 4 * B * n_in \
        + 8 * st.full.shape[1] + 4 * B
    rows = obs.counter("requests_admitted")
    n = lambda name: int((obs.spans["name"] == name).sum())
    assert got["h2d_kib_per_admit.backlog"] == pytest.approx(
        n("dataflow.admit") * round_bytes / rows / 1024, rel=1e-12)
    assert obs.counter("retraces") == 0 == s.compiles_in_window
    # each step reads back fired counts and last progress (one int32 a
    # slot each), each harvest the output registers of every slot
    d2h = n("dataflow.step.wait") * 8 * B \
        + n("dataflow.harvest.d2h") * (st.out_last.nbytes
                                       + st.out_count.nbytes)
    assert got["d2h_kib_per_heartbeat.backlog"] == pytest.approx(
        d2h / obs.heartbeats() / 1024, rel=1e-12)
    # the program counts the slot-cycles the harness's wrapper counts
    assert obs.counters["slot_cycles"] == s.spans.slot_cycles
    assert 0 < got["active_slot_share.backlog"] <= 100
    longest = program_spans.longest_heartbeat(obs)
    names = [n for n, *_ in longest["spans"]]
    assert "dataflow.step.wait" in names
    assert longest["wall_ms"] >= max(w for _, _, w in longest["spans"])
    assert longest["cpu_ms"] > 0


def test_readers_read_nothing_without_program_spans():
    view = run.RunView("backlog", 1.0, 1.0, drive.Log(heartbeats=3), None,
                       drive.Spans(False), None, {}, 1, 1, None)
    for m in NEW_METRICS:
        assert cells.reader(m)(view) is None
        view.obs = None
        assert cells.reader(m)(view) is None
        del view.obs


# -- the two keys beside trace_reduce's breakdown ------------------------------
def _ev(name, start, end):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=end - start)


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def _synthetic():
    """A 100 ns window: one heartbeat (10-90) with an admission round,
    a step and a harvest; the device busy at 0-5, 32-36 (the reset),
    55-75 (the step) and 95-98 (a step after the heartbeat)."""
    host = [_ev("bench.window", 0, 100), _ev("dataflow.heartbeat", 10, 90),
            _ev("dataflow.admit", 12, 40), _ev("dataflow.admit.pack", 12, 25),
            _ev("dataflow.admit.h2d", 25, 30),
            _ev("dataflow.admit.dispatch", 30, 38),
            _ev("dataflow.step", 45, 80),
            _ev("dataflow.step.dispatch", 45, 50),
            _ev("dataflow.step.wait", 50, 80),
            _ev("dataflow.harvest", 82, 88)]
    busy = [(0, 5), (32, 36), (55, 75), (95, 98)]
    ops = [_ev(f"%fusion.{i} = s32[8]{{0}} fusion(s32[8]{{0}} %p)", s, e)
           for i, (s, e) in enumerate(busy)]
    modules = [_ev("jit__slot_reset(11)", 32, 36),
               _ev("jit_dataflow_slot_step(22)", 55, 75),
               _ev("jit_dataflow_slot_step(22)", 95, 98),
               _ev("jit_dataflow_slot_step(22)", 120, 130)]
    return SimpleNamespace(planes=[
        _plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=modules),
        _plane("/host:CPU", python3=host)])


def test_idle_by_program_span_and_device_by_module():
    pd = _synthetic()
    got = program_spans.breakdown(pd)
    want_idle = {"dataflow.heartbeat": 2 + 5 + 2 + 2,
                 "dataflow.admit": 2, "dataflow.admit.pack": 13,
                 "dataflow.admit.h2d": 5, "dataflow.admit.dispatch": 2 + 2,
                 "dataflow.step.dispatch": 5, "dataflow.step.wait": 5 + 5,
                 "dataflow.harvest": 6, "none": 5 + 5 + 2}
    idle = got["idle_by_program_span"]
    assert set(idle) == set(want_idle) | {"dataflow.step"}
    assert idle["dataflow.step"] == 0
    for k, v in want_idle.items():
        assert idle[k] == pytest.approx(v * 1e-9), k
    r = trace_reduce.reduce(pd)
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert got["device_by_module"] == pytest.approx(
        {"jit_dataflow_slot_step": 23e-9, "jit__slot_reset": 4e-9})
    for name in got["device_by_module"]:
        assert not name.startswith(trace_reduce.KERNELS)


def test_program_module_names_are_not_kernel_names():
    for name in ("dataflow_slot_step", "dataflow_slot_step_xla",
                 "jit_dataflow_slot_step", "jit_dataflow_slot_step_xla"):
        assert not name.startswith(trace_reduce.KERNELS)


FIXTURES = sorted((HERE / "fixtures").glob("*.xplane.pb"))


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_breakdown_of_a_chip_trace(path):
    """A trace without program spans puts all its idle time under
    "none"; its modules are the reset and the step."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    got = program_spans.breakdown(pd)
    r = trace_reduce.reduce(pd)
    idle = got["idle_by_program_span"]
    assert list(idle) == ["none"]
    assert idle["none"] == pytest.approx(r["window_s"] - r["busy_s"],
                                         rel=1e-6)
    assert set(got["device_by_module"]) == {"jit_step", "jit__slot_reset"}
    assert 0 < sum(got["device_by_module"].values()) <= r["window_s"]
