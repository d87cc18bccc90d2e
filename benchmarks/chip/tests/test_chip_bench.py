"""Tests of the chip benchmark's harness, on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests

They call the harness's functions directly.  Runs go through the whole
path after the look for a chip (set-up, window, drain, checks, readers)
on a tiny cell that a test adds as files plus an entry, with the Pallas
kernel in interpret mode; the look for a chip itself is tested apart.
"""
import json
import shutil
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import drive  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

from repro.core import asm, library  # noqa: E402
from repro.core.engine import DataflowEngine, run_reference  # noqa: E402

CONFIGS = ("bubble_sort8", "dot_prod32")
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
BIG = 2 ** 40 + 12345          # seeds past 32 bits


# -- generators -------------------------------------------------------------
def mix(**kw):
    m = {"mode": "backlog", "queued_per_slot": 2, "pool": 4096,
         "lengths": [{"share": 1.0, "dist": "pareto", "alpha": 1.0,
                      "lo": 16, "hi": 1024}],
         "tenants": {"count": 64, "zipf_s": 0.99}}
    m.update(kw)
    return m


def test_traffic_is_deterministic_by_seed():
    a = traffic.generate(mix(), 8, 16, BIG, 1.0)
    b = traffic.generate(mix(), 8, 16, BIG, 1.0)
    c = traffic.generate(mix(), 8, 16, BIG + 1, 1.0)
    for x in ("lengths", "tenants", "offsets", "values"):
        assert np.array_equal(getattr(a, x), getattr(b, x))
    assert not np.array_equal(a.values[:, :100], c.values[:, :100])
    assert a.queued == 32
    o = traffic.generate(mix(mode="open", rate_per_s=500.0), 8, 16, 7, 2.0)
    p = traffic.generate(mix(mode="open", rate_per_s=500.0), 8, 16, 7, 2.0)
    assert np.array_equal(o.arrivals, p.arrivals)


def test_every_seed_draws_the_same_work_in_another_order():
    a = traffic.generate(mix(mode="open", rate_per_s=300.0), 2, 4, 1, 2.0)
    b = traffic.generate(mix(mode="open", rate_per_s=300.0), 2, 4, 2, 2.0)
    for x in ("lengths", "tenants"):
        assert not np.array_equal(getattr(a, x), getattr(b, x))
        assert np.array_equal(np.sort(getattr(a, x)), np.sort(getattr(b, x)))
    assert len(a.arrivals) == len(b.arrivals) == 600
    assert np.allclose(np.sort(np.diff(a.arrivals, prepend=0)),
                       np.sort(np.diff(b.arrivals, prepend=0)), rtol=0.05)


def test_lengths_follow_their_distributions():
    rng = np.random.default_rng(0)
    n = 200_000
    # Pareto of shape 1 over 16..1024: P(L > x) = (1/x - 1/1024) /
    # (1/16 - 1/1024), so half the streams are at most 32 tokens long
    p = traffic.draw_lengths(mix(), n, rng)
    assert p.min() == 16 and p.max() in (1023, 1024)
    for x_ in (32, 64, 256):
        want = (1 / x_ - 1 / 1024) / (1 / 16 - 1 / 1024)
        assert abs((p > x_ + 0.5).mean() - want) < 0.01
    m = mix(lengths=[{"share": 0.9, "dist": "uniform", "lo": 1, "hi": 16},
                     {"share": 0.1, "dist": "pareto", "alpha": 1.0,
                      "lo": 256, "hi": 1024}])
    x = traffic.draw_lengths(m, n, rng)
    short = x <= 16
    assert short.mean() == 0.9
    assert set(np.unique(x[short])) == set(range(1, 17))
    assert x[~short].min() >= 256 and x[~short].max() <= 1024


def test_tenants_are_zipf():
    t = traffic.draw_tenants(mix(), 400_000, np.random.default_rng(1))
    w = 1.0 / np.arange(1, 65) ** 0.99
    share = np.bincount(t, minlength=64) / len(t)
    assert np.allclose(share, w / w.sum(), atol=0.003)


def test_poisson_arrivals():
    rng = np.random.default_rng(2)
    t = traffic.poisson_arrivals(2000.0, 10.0, rng)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 10.0
    assert len(t) == 20_000
    gaps = np.diff(t)
    assert abs(gaps.mean() * 2000 - 1) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1) < 0.03     # exponential


def test_feeds_are_views_of_the_pool():
    tr = traffic.generate(mix(), 3, 4, 5, 1.0)
    f = tr.feeds(2, ["a", "b", "c"])
    assert [len(v) for v in f.values()] == [tr.lengths[2]] * 3
    assert np.array_equal(f["b"], tr.values[1, tr.offsets[2]:tr.offsets[3]])
    assert tr.values.dtype == np.int32
    assert tr.values.min() < -2 ** 30 and tr.values.max() > 2 ** 30


# -- end-to-end arithmetic --------------------------------------------------
def _view(mode, log, tr, seconds):
    return run.RunView(mode, seconds, 1.0, log, tr, drive.Spans(False),
                       None, {}, 1, 1, None)


def test_rate_takes_all_the_windows_work():
    tr = traffic.generate(mix(), 1, 1, 0, 1.0)
    log = drive.Log(window_s=2.0)
    for u, (i, t) in enumerate([(0, 0.1), (1, 1.9), (2, 2.0), (3, 2.5)]):
        log.pool[u], log.done[u] = i, t
    got = cells.reader("tokens_per_s")(_view("backlog", log, tr, 2.0))
    want = sum(int(tr.lengths[i]) for i in (0, 1, 2)) / 2.0
    assert got == pytest.approx(want)
    assert cells.reader("tokens_per_s")(_view("open", log, tr, 2.0)) is None


def test_percentile_counts_every_request_and_misses():
    assert stats.percentile(range(1, 101), 99) == 99
    assert stats.percentile(range(1, 101), 50) == 50
    assert stats.percentile([5.0], 99) == 5.0
    log = drive.Log(window_s=1.0, drained_s=61.0)
    for u in range(100):
        log.due[u] = u / 100
        if u != 37:                     # one request never answered
            log.done[u] = u / 100 + 0.01
    log.due[100] = 1.5                  # due after the window: not counted
    lat = drive.latencies_ms(log, 1.0)
    assert len(lat) == 100
    assert max(lat) == pytest.approx((61.0 - 0.37) * 1e3)
    v = _view("open", log, None, 1.0)
    assert cells.reader("latency_p99_ms")(v) == pytest.approx(10.0)
    log.done.pop(38)                    # a second miss reaches the p99
    assert cells.reader("latency_p99_ms")(v) > 60_000
    assert cells.reader("latency_p50_ms")(v) == pytest.approx(10.0)


def test_the_log_keeps_a_uniform_sample_and_the_longest():
    from repro.serve.types import RequestMetrics, Result
    from repro.core.engine import EngineResult
    met = RequestMetrics(0, 0, 0, 0, 0, 0, 0, 0)
    hits = np.zeros(1000)
    for trial in range(300):
        log = drive.Log(keep=10, rng=__import__("random").Random(trial))
        done = [Result(u, engine=EngineResult({}, {}, 5 + (u == 77) * 99, 0),
                       metrics=met) for u in range(1000)]
        drive._record(log, done[:500], 0.1)
        drive._record(log, done[500:], 0.2)
        kept = log.kept()
        assert len(log.sample) == 10 and 77 in kept
        hits[[u for u, _ in log.sample]] += 1
    assert abs(hits[:500].sum() / hits.sum() - 0.5) < 0.05
    assert log.status[3] == "ok" and log.done[999] == 0.2


def test_spread_is_iqr_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# -- trace reduction ---------------------------------------------------------
FIXTURES = sorted((HERE / "fixtures").glob("*.xplane.pb"))


@pytest.mark.parametrize("path", FIXTURES, ids=lambda p: p.name)
def test_trace_reduce_reads_a_chip_trace(path):
    from jax.profiler import ProfileData
    r = trace_reduce.reduce(ProfileData.from_file(str(path)))
    assert 0 < r["busy_s"] < r["window_s"]
    k = r["kernels"]["dataflow_fire_block"]
    assert k["count"] > 0 and 0 < k["seconds"] < r["busy_s"]
    ops = dict(r["breakdown"]["device_ops"])
    assert any(n.startswith("dataflow_fire_block") for n in ops)
    assert sum(ops.values()) <= r["busy_s"] * 1.0001
    idle = dict(r["breakdown"]["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_there_is_a_chip_trace_fixture():
    assert FIXTURES


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert u == [[1, 4], [5, 8]]
    assert trace_reduce.overlap(u, [[0, 2], [3, 6]]) == 1 + 2
    assert trace_reduce.clip(u, 2, 6) == [[2, 4], [5, 6]]


# -- roofline ------------------------------------------------------------------
def test_fire_block_bytes_of_bubble_sort8():
    # 2048 slots x 16 cycles; per slot: 4 x 176 register words,
    # 8 x 16 feed tokens, 3 x 8 feed lengths and pointers, 4 x 8 output
    # words, 3 words of clock gate and progress = 891 words
    assert roofline.fire_block_bytes(176, 8, 8, 2048, 16) == \
        4 * 2048 * 891
    cell = cells.load_cell("bubble_sort8.backlog")
    f = reference.parse(cell.netlist)
    assert (len(f.arcs), len(f.input_arcs()), len(f.output_arcs())) == \
        (176, 8, 8)


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- the frozen fabrics and the frozen reference ------------------------------
@pytest.mark.parametrize("name", CONFIGS)
def test_netlist_parses_and_reemits_unchanged(name):
    text = (HERE / "configs" / f"{name}.asm").read_text()
    assert asm.emit(asm.parse(text)) == text
    cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
    f = reference.parse(text)
    assert (len(f.nodes), len(f.arcs), len(f.input_arcs()),
            len(f.output_arcs())) == tuple(cfg["fabric"][k] for k in (
                "nodes", "arcs", "inputs", "outputs"))


def _same(ans, er):
    return (ans.cycles == er.cycles and ans.fired == er.fired
            and ans.counts == er.counts
            and all(ans.outputs[a] == int(np.asarray(er.outputs[a])
                                          .astype(np.int32))
                    for a in er.counts))


@pytest.mark.parametrize("name", [n for n, b in library.BENCHES.items()
                                  if np.dtype(b().dtype) == np.int32])
def test_reference_matches_the_programs_oracle(name):
    """The frozen copy agrees with today's ``run_reference`` on every
    int32 fabric of the library, control operators included."""
    bench = library.BENCHES[name]()
    fab = reference.parse(asm.emit(bench.graph))
    rng = np.random.default_rng(3)
    feeds = [library.random_feeds(name, bench, k, rng) for k in (1, 3, 9)]
    if name not in library.SINGLE_SHOT:
        feeds.append({a: rng.integers(-2 ** 31, 2 ** 31, 6).astype(np.int32)
                      for a in bench.graph.input_arcs()})
    for f, ans in zip(feeds, reference.run(fab, feeds)):
        assert _same(ans, run_reference(bench.graph, f))


def test_reference_in_a_narrower_type_differs():
    fab = reference.parse((HERE / "configs" / "bubble_sort8.asm")
                          .read_text())
    rng = np.random.default_rng(4)
    feeds = [{f"x{i}": rng.integers(-2 ** 31, 2 ** 31, 5).astype(np.int32)
              for i in range(8)}]
    wide, = reference.run(fab, feeds, np.int32)
    narrow, = reference.run(fab, feeds, np.int16)
    assert wide.outputs != narrow.outputs
    assert wide.cycles == narrow.cycles


# -- a cell added as files plus an entry ----------------------------------------
TINY_ASM = asm.emit(library.bubble_sort_graph(4).graph)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with one more configuration, two more mixes, one more
    metric and three more cells, each added as files and entries."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(HERE, r / cells.REL, ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures"))
    d = r / cells.REL
    (d / "configs" / "tiny.asm").write_text(TINY_ASM)
    cfg = json.loads((d / "configs" / "bubble_sort8.json").read_text())
    cfg.update(name="tiny", netlist="tiny.asm", slots=8, block_cycles=4)
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (d / "mixes" / "tiny_backlog.json").write_text(json.dumps(mix(
        pool=64, lengths=[{"share": 1, "dist": "uniform", "lo": 1,
                           "hi": 6}])))
    (d / "mixes" / "tiny_open.json").write_text(json.dumps(mix(
        mode="open", rate_per_s=300.0, pool=64,
        lengths=[{"share": 1, "dist": "uniform", "lo": 1, "hi": 4}])))
    (d / "metrics" / "answered_total.py").write_text(
        "def read(run):\n    return len(run.log.done)\n")
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": f"{cells.REL}/configs/tiny.json",
                            "reduced": [], "why": "test"})
    for n, t in (("tiny.backlog", "tiny_backlog"), ("tiny.open", "tiny_open"),
                 ("tiny.open_again", "tiny_open")):
        spec["workloads"].append({"name": n, "config": "tiny",
                                  "traffic": t, "chips": 1, "why": "test"})
    names = {m["name"]: m for m in spec["end_to_end"]}
    names["tokens_per_s"]["workloads"].append("tiny.backlog")
    for lat in ("latency_p50_ms", "latency_p99_ms"):
        m = names.get(lat) or {"name": lat, "unit": "ms", "better": "lower",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": []}
        m["workloads"] += ["tiny.open", "tiny.open_again"]
        if lat not in names:
            spec["end_to_end"].append(m)
    spec["per_layer"].append({
        "name": "answered_total", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "client",
        "moves": "tokens_per_s", "workloads": ["tiny.backlog"]})
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


def test_a_new_cell_is_found_by_name(root):
    c = cells.load_cell("tiny.backlog", root)
    assert c.config["slots"] == 8 and c.netlist == TINY_ASM
    assert c.mix["pool"] == 64
    assert [m["name"] for m in c.end_to_end] == ["tokens_per_s", "setup_s"]
    assert "answered_total" in [m["name"] for m in c.per_layer]
    assert cells.reader("answered_total", root)(
        _view("backlog", drive.Log(done={1: 0.1, 2: 0.2}), None, 1)) == 2
    o = cells.load_cell("tiny.open_again", root)
    assert {m["name"] for m in o.end_to_end} >= {"latency_p99_ms",
                                                 "setup_s"}
    # a dotted name without a file of its own reads its base's file
    assert cells.reader("admit_ms.open", root) is not None
    with pytest.raises(KeyError):
        cells.load_cell("tiny.nothing", root)


@pytest.fixture
def on_cpu(monkeypatch):
    """The CPU stands in for the chip: the Pallas kernel runs in
    interpret mode, which lowers to no Mosaic kernel."""
    monkeypatch.setattr(run, "mosaic_missing", lambda srv, arcs: 0)
    monkeypatch.setattr(drive, "DRAIN_S", 5.0)


@pytest.mark.parametrize("name", ["tiny.backlog", "tiny.open"])
def test_a_run_on_the_cpu_is_correct(name, root, on_cpu):
    out = run.measure(cells.load_cell(name, root), BIG, 0.6, False, DEVICE)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["mismatched"]["value"] == 0
    want = {"tokens_per_s"} if name == "tiny.backlog" else {
        "latency_p50_ms", "latency_p99_ms"}
    assert set(out["metrics"]) == want | {"setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_the_control_fails_the_check(root, on_cpu):
    """The reference computed in int16 in the program's place reads
    mismatches on answers the int32 reference passes."""
    cell = cells.load_cell("tiny.backlog", root)
    s = run.serve(cell, 11, 0.5, False, DEVICE)
    s.srv = None
    fab = reference.parse(cell.netlist)
    assert run.compare(fab, s.traffic, s.arcs, s.log) == 0
    assert run.compare(fab, s.traffic, s.arcs, s.log,
                       np.int16) == len(s.log.kept()) > 0


def _unchanged(out, state, odd):
    return (*state, jnp.zeros_like(out[5]), jnp.zeros_like(out[6]))


def _half(out, state, odd):
    keep = lambda new, old: jnp.where(
        odd.reshape((-1,) + (1,) * (new.ndim - 1)), old, new)
    return (*(keep(n, o) for n, o in zip(out[:5], state)),
            keep(out[5], jnp.zeros_like(out[5])),
            keep(out[6], jnp.zeros_like(out[6])))


def _altered(out, state, odd):
    return (*out[:3], out[3].at[:, 0].add(1), *out[4:])


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_the_slots",
                              "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, root, on_cpu,
                                            monkeypatch):
    """The step under the server returns its state unchanged, leaves
    half of the slots out, or alters an output token where it is made."""
    real = DataflowEngine._slot_step

    def broken(self, n_cycles):
        step = real(self, n_cycles)

        def call(fv, fl, full, val, ptr, out_last, out_count, active):
            out = step(fv, fl, full, val, ptr, out_last, out_count, active)
            odd = jnp.arange(active.shape[0]) % 2 == 1
            return fault(out, (full, val, ptr, out_last, out_count), odd)
        return call
    monkeypatch.setattr(DataflowEngine, "_slot_step", broken)
    out = run.measure(cells.load_cell("tiny.backlog", root), 5, 0.5, False,
                      DEVICE)
    assert not out["correct"]
    assert out["checks"]["mismatched"]["value"] > 0


def test_run_refuses_to_measure_without_a_tpu(capsys):
    import jax
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        with pytest.raises(SystemExit) as e:
            run.main(["--workload", "bubble_sort8.backlog", "--seed", "1",
                      "--seconds", "1"])
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
    assert e.value.code == 2
    out, err = capsys.readouterr()
    assert "{" not in out and "needs 1 TPU" in err


def test_half_fault_helper_keeps_odd_rows():
    out = tuple(jnp.arange(4) + 10 * k for k in range(7))
    st = tuple(jnp.arange(4) * 0 - 1 for _ in range(5))
    got = _half(out, st, jnp.arange(4) % 2 == 1)
    assert list(got[0]) == [0, -1, 2, -1] and list(got[5]) == [50, 0, 52, 0]


def test_warm_up_grows_the_feed_buffer(root, on_cpu):
    cell = cells.load_cell("tiny.backlog", root)
    srv = run.build_server(cell, asm.parse(cell.netlist))
    run.warm_up(srv, reference.parse(cell.netlist).input_arcs(), 7)
    assert srv.state.fv.shape[2] == 8 and not srv.pending
