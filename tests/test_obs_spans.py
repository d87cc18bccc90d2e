"""Spans and counters on the serving hot path (DESIGN.md §12).

* the span tree of a heartbeat nests and is ordered, one
  ``dataflow.heartbeat`` per ``step()``;
* a step that raises inside the engine, retried or degraded, leaves no
  span open and the tree keeps its shape;
* ``h2d_bytes{site=admit}`` is the bytes of the arrays an admission
  round stages;
* with neither recorder nor registry the hot path reads no clock, builds
  no annotation and records nothing, and results are bit-identical with
  tracing on and off;
* under ``jax.profiler`` the ``dataflow.*`` host events of the trace file
  are the recorded spans, in count and nesting;
* the wall-clock Chrome export carries the spans and stays valid;
* the queue-depth gauges hold what rewriting every tenant's gauge held;
* the hot path's jitted programs carry stable module names.
"""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import library
from repro.core.engine import DataflowEngine, feed_capacity
from repro.obs import MetricsRegistry, Probe, TraceRecorder, validate_chrome
from repro.obs import trace as trace_mod
from repro.serve.dataflow_server import DataflowServer
from repro.serve.types import Request

BACKENDS = ("xla", "pallas")

# the span each span opens inside: a fabric's build at the server's
# construction, a slot step's first compile inside its dispatch
PARENT = {
    "dataflow.build": (None, "dataflow.step.dispatch"),
    "dataflow.heartbeat": None,
    "dataflow.admit": "dataflow.heartbeat",
    "dataflow.admit.pack": "dataflow.admit",
    "dataflow.admit.h2d": "dataflow.admit",
    "dataflow.admit.dispatch": "dataflow.admit",
    "dataflow.step": "dataflow.heartbeat",
    "dataflow.step.dispatch": "dataflow.step",
    "dataflow.step.wait": "dataflow.step",
    "dataflow.harvest": "dataflow.heartbeat",
    "dataflow.harvest.d2h": "dataflow.harvest",
    "dataflow.harvest.results": "dataflow.harvest",
}


def _bench():
    return library.BENCHES["vector_sum"]()


def _serve(backend, trace=None, metrics=None, n=14, slots=4, seed=0):
    """Serve ``n`` requests of three tenants; returns (results by uid,
    the number of step() calls)."""
    b = _bench()
    srv = DataflowServer(b.graph, slots=slots, block_cycles=4,
                         backend=backend, trace=trace, metrics=metrics)
    rng = np.random.default_rng(seed)
    for u in range(n):
        srv.submit(Request(uid=u, tenant=u % 3, feeds=library.random_feeds(
            "vector_sum", b, 2 + u % 6, rng)))
    out, steps = [], 0
    while srv.pending:
        out += srv.step()
        steps += 1
    return {r.uid: r for r in out}, steps


def _key(res):
    e = res.engine
    return (res.status, e.cycles, e.fired, e.counts,
            {a: int(np.asarray(v)) for a, v in e.outputs.items()})


@pytest.mark.parametrize("backend", BACKENDS)
def test_span_tree_nests_and_counts_heartbeats(backend):
    tr = TraceRecorder()
    res, steps = _serve(backend, trace=tr)
    a = tr.span_arrays()
    names = a["name"]
    assert set(names) == set(PARENT)
    assert (names == "dataflow.heartbeat").sum() == steps
    assert (a["t1_ns"] >= a["t0_ns"]).all()            # every span closed
    assert (np.diff(a["t0_ns"]) >= 0).all()            # in order of begin
    for i, name in enumerate(names):
        p = a["parent"][i]
        assert _parent_ok(names, p, name), (i, name)
        if p >= 0:
            assert a["t0_ns"][p] <= a["t0_ns"][i] <= a["t1_ns"][i] \
                <= a["t1_ns"][p]
            assert a["block"][i] == a["block"][p]      # inherits the block
    assert (a["self_ns"] >= 0).all()
    beats = names == "dataflow.heartbeat"
    assert list(a["block"][beats]) == list(range(steps))
    assert a["finished"][beats].sum() == len(res) == 14
    assert a["admitted"][beats].sum() == 14
    assert a["rows"][names == "dataflow.admit"].sum() == 14
    assert set(a["kind"][names == "dataflow.harvest"]) == {"ok"}
    # the thread CPU clock is read at the root spans' ends only: the
    # heartbeats and the server's build
    roots = a["parent"] < 0
    assert (names[roots & ~beats] == "dataflow.build").all()
    assert (a["cpu_ns"][roots] > 0).all()
    assert (a["cpu_ns"][~roots] == -1).all()


def _parent_ok(names, p, name) -> bool:
    want = PARENT[name]
    wants = want if isinstance(want, tuple) else (want,)
    return (names[p] if p >= 0 else None) in wants


def _check_tree(a):
    """Every span closed, inside the span PARENT names, within its
    parent's interval."""
    names = a["name"]
    assert (a["t1_ns"] >= a["t0_ns"]).all()
    for i, name in enumerate(names):
        p = a["parent"][i]
        assert _parent_ok(names, p, name), (i, name)
        if p >= 0:
            assert a["t0_ns"][p] <= a["t0_ns"][i] <= a["t1_ns"][i] \
                <= a["t1_ns"][p]


def _failing_steps(monkeypatch, srv, failures):
    """The engine's jitted step raises on its first ``failures`` calls,
    inside the span ``dataflow.step.dispatch``."""
    eng, calls = srv.engine, []
    slot_step = eng._slot_step

    def flaky(nb):
        calls.append(nb)
        if len(calls) <= failures:
            raise RuntimeError("injected step fault")
        return slot_step(nb)
    monkeypatch.setattr(eng, "_slot_step", flaky)
    return calls


def _submit(srv, n, seed=7):
    b, rng = _bench(), np.random.default_rng(seed)
    for u in range(n):
        srv.submit(Request(uid=u, feeds=library.random_feeds(
            "vector_sum", b, 3, rng)))


def test_a_retried_step_leaves_no_span_open(monkeypatch):
    """A step that raises inside the engine closes its dispatch span, so
    the retry's spans sit beside it under ``dataflow.step``."""
    tr = TraceRecorder()
    srv = DataflowServer(_bench().graph, slots=2, block_cycles=4,
                         backend="xla", trace=tr, max_retries=2)
    _submit(srv, 2)
    calls = _failing_steps(monkeypatch, srv, 1)
    srv.step()
    assert len(calls) == 2 and tr.depth == 0
    a = tr.span_arrays()
    _check_tree(a)
    step, = np.flatnonzero(a["name"] == "dataflow.step")
    kids = [a["name"][i] for i in np.flatnonzero(a["parent"] == step)]
    assert kids == ["dataflow.step.dispatch", "dataflow.step.dispatch",
                    "dataflow.step.wait"]
    monkeypatch.undo()
    assert len(srv.drain()) == 2


def test_a_degraded_step_closes_its_span_before_the_requeue(monkeypatch):
    tr = TraceRecorder()
    srv = DataflowServer(_bench().graph, slots=2, block_cycles=4,
                         backend="xla", trace=tr, max_retries=1)
    _submit(srv, 2)
    _failing_steps(monkeypatch, srv, 10)
    srv.step()
    assert srv.degraded and tr.depth == 0
    a = tr.span_arrays()
    _check_tree(a)
    step, = np.flatnonzero(a["name"] == "dataflow.step")
    assert list(a["name"][a["parent"] == step]) == \
        ["dataflow.step.dispatch"] * 2
    degrade, = [e for e in tr.events if e.kind == "degrade"]
    assert a["t1_ns"][step] <= tr._t0_ns + round(degrade.wall_s * 1e9) + 1
    monkeypatch.undo()
    assert all(r.status == "ok" for r in srv.drain())


def test_h2d_bytes_are_the_staged_arrays():
    """One admission round stages one int32 buffer: the admitted
    streams, back to back in ``feed_capacity(n_in, L)`` words, then the
    slots' stream lengths, mask, order and active flags, 4 (n_in + 3) B
    bytes.  Nothing scales with B n_in L, and the fresh arc registers
    stay on the device from the first round on."""
    b = _bench()
    eng = DataflowEngine(b.graph, backend="xla", block_cycles=4)
    B, n_in = 6, len(b.graph.input_arcs())
    mr = MetricsRegistry()
    obs = Probe(metrics=mr)
    st = eng.init_state(B)
    rng = np.random.default_rng(1)
    feeds = [library.random_feeds("vector_sum", b, k, rng) for k in (3, 5)]
    st = eng.reset_slots(st, [0, 4], feeds, obs=obs)
    L = st.fv.shape[2]
    assert L == 8                               # grown to a power of two
    want = 4 * feed_capacity(n_in, L) + 4 * (n_in + 3) * B
    c = mr.snapshot()["counters"]
    assert c["h2d_bytes{site=admit}"] == want
    assert c["retraces{what=feed_buffer}"] == 1
    st = eng.reset_slots(st, [1], feeds[:1], obs=obs)
    assert mr.snapshot()["counters"]["h2d_bytes{site=admit}"] == 2 * want
    # ten times the slots add only their per-slot words
    mr10 = MetricsRegistry()
    eng.reset_slots(eng.init_state(10 * B), [0, 4], feeds,
                    obs=Probe(metrics=mr10))
    assert mr10.snapshot()["counters"]["h2d_bytes{site=admit}"] == \
        want + 4 * (n_in + 3) * 9 * B
    st = eng.step_block(st, obs=obs)
    c = mr.snapshot()["counters"]
    assert c["slot_cycles"] == B * 4 and c["active_slot_cycles"] == 3 * 4
    assert c["d2h_bytes{site=step}"] == 2 * 4 * B


def test_tracing_off_reads_no_clock_and_builds_no_annotation(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("the hot path touched the tracer")
    feeds = library.random_feeds("vector_sum", _bench(), 3,
                                 np.random.default_rng(0))
    monkeypatch.setattr(trace_mod, "_wall", boom)
    monkeypatch.setattr(trace_mod, "_cpu", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    srv = DataflowServer(_bench().graph, slots=2, block_cycles=4,
                         backend="xla")
    assert srv._obs is None
    srv.submit(feeds)
    assert len(srv.drain()) == 1
    monkeypatch.undo()
    # the control: a recorder whose annotation factory raises is reached
    tr = TraceRecorder()
    srv = DataflowServer(_bench().graph, slots=2, block_cycles=4,
                         backend="xla", trace=tr)
    tr.annotate = boom
    srv.submit(feeds)
    with pytest.raises(AssertionError, match="touched the tracer"):
        srv.step()


@pytest.mark.parametrize("backend", BACKENDS)
def test_results_are_bit_identical_with_tracing_on_and_off(backend):
    off, _ = _serve(backend, seed=3)
    on, _ = _serve(backend, TraceRecorder(), MetricsRegistry(), seed=3)
    assert sorted(off) == sorted(on)
    for u in off:
        assert _key(off[u]) == _key(on[u]), u


def test_profiler_trace_holds_the_spans(tmp_path):
    """The annotations land in the ``.xplane.pb`` the profiler writes,
    one host event per span, nested as the spans are."""
    from jax.profiler import ProfileData
    tr = TraceRecorder()
    _serve("xla", seed=4)                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve("xla", trace=tr, seed=4)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dataflow."):
                    events.append((e.start_ns, -e.duration_ns, e.name,
                                   e.start_ns + e.duration_ns))
    events.sort()
    pairs, stack = collections.Counter(), []
    for s, _, name, end in events:
        while stack and stack[-1][1] < end:
            stack.pop()
        pairs[(name, stack[-1][0] if stack else None)] += 1
        stack.append((name, end))
    a = tr.span_arrays()
    want = collections.Counter(
        (n, a["name"][p] if p >= 0 else None)
        for n, p in zip(a["name"], a["parent"]))
    assert pairs == want


def test_wall_clock_export_carries_the_spans():
    tr = TraceRecorder()
    _serve("xla", trace=tr, seed=5)
    trace = tr.to_chrome("wall")
    info = validate_chrome(trace)
    assert info["uids"] == 14
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == tr.n_spans
    assert {e["name"] for e in spans} == set(PARENT)
    assert all("block" in e["args"] and e["dur"] >= 0 for e in spans)
    assert all(("cpu_us" in e["args"])
               == (e["name"] in ("dataflow.heartbeat", "dataflow.build"))
               for e in spans)
    life = [e for e in trace["traceEvents"]
            if e["ph"] == "i" and "uid" in e["args"]]
    assert life and all("block" in e["args"] for e in life)
    # spans and lifecycle events share one clock: each admit instant
    # falls inside an admit span of its block
    admits = [e for e in spans if e["name"] == "dataflow.admit"]
    for ev in (e for e in life if e["name"] == "admit"):
        assert any(s["ts"] <= ev["ts"] <= s["ts"] + s["dur"]
                   and s["args"]["block"] == ev["args"]["block"]
                   for s in admits)
    # the block-clock export stays free of wall-clock spans
    assert not [e for e in tr.to_chrome("block")["traceEvents"]
                if e["ph"] == "X"]


def test_span_api():
    tr = TraceRecorder()
    beat = tr.begin("beat", block=7)
    inner = tr.begin("inner", rows=3, kind="ok")
    tr.begin("left_open")
    tr.end(beat, finished=2)            # closes what it holds
    assert tr.n_spans == 3
    with pytest.raises(ValueError):
        tr.end(inner)
    with tr.span("beat", block=8) as i:
        assert i == 3
    a = tr.span_arrays()
    assert list(a["block"]) == [7, 7, 7, 8]
    assert list(a["parent"]) == [-1, 0, 1, -1]
    assert a["rows"][1] == 3 and a["kind"][1] == "ok" and a["kind"][0] is None
    assert a["finished"][0] == 2
    assert (a["t1_ns"][:3] == a["t1_ns"][0]).all()
    assert a["self_ns"][0] == a["wall_ns"][0] - a["wall_ns"][1]
    assert list(a["cpu_ns"] >= 0) == [True, False, False, True]
    with pytest.raises(ValueError, match="at most"):
        tr.begin("beat", a=1, b=2, c=3)


def test_metrics_handles_are_found_again():
    mr = MetricsRegistry()
    c = mr.counter("x", site="a", what="b")
    assert mr.counter("x", what="b", site="a") is c
    assert mr.counter("x", site="a", what="b") is c
    assert mr.gauge("x", site="a") is not mr.counter("x", site="a")


# -- the queue gauges ---------------------------------------------------------
def _rewrite_every_tenant(srv, seen):
    """The gauges as rewriting every tenant seen so far set them."""
    def update(_tenants=()):
        m = srv.metrics
        m.gauge("queue_depth").set(len(srv.queue))
        depths = {str(t): d for t, d in srv.queue.depths().items()}
        seen.update(depths)
        for t in seen:
            m.gauge("queue_depth", tenant=t).set(depths.get(t, 0))
    return update


def _mixed(srv):
    rng = np.random.default_rng(6)
    b = _bench()
    for u in range(40):
        srv.submit(Request(
            uid=u, tenant=("a", "b", "c", 3)[u % 4 if u < 30 else 0],
            deadline_blocks=2 if u % 7 == 0 else None,
            feeds=library.random_feeds("vector_sum", b, 1 + u % 9, rng)))
        if u % 5 == 4:
            srv.step()
            yield
    while srv.pending:
        srv.step()
        yield


def test_queue_gauges_hold_what_a_full_rewrite_held():
    mk = lambda: DataflowServer(_bench().graph, slots=3, block_cycles=4,
                                backend="xla", max_queue=12,
                                policy="drop-oldest",
                                metrics=MetricsRegistry())
    srv, ref = mk(), mk()
    seen = set()
    ref._update_queue_metrics = _rewrite_every_tenant(ref, seen)
    for _ in zip(_mixed(srv), _mixed(ref)):
        g = srv.metrics.snapshot()["gauges"]
        assert g == ref.metrics.snapshot()["gauges"]
        depths = srv.queue.depths()
        for k, v in g.items():
            if k.startswith("queue_depth{"):
                t = k[len("queue_depth{tenant="):-1]
                assert v["value"] == next(
                    (d for x, d in depths.items() if str(x) == t), 0)
    assert seen == {"a", "b", "c", "3"}


# -- module names -------------------------------------------------------------
def _slot_args(eng, B=8):
    st = eng.init_state(B)
    return (st.fv, st.fl, st.full, st.val, st.ptr, st.out_last,
            st.out_count, jnp.ones((B,), jnp.int32))


@pytest.mark.parametrize("backend,module", [
    ("pallas", "jit_dataflow_slot_step"),
    ("xla", "jit_dataflow_slot_step_xla")])
def test_slot_step_modules_carry_stable_names(backend, module):
    eng = DataflowEngine(_bench().graph, backend=backend, block_cycles=4)
    text = eng._slot_step(4).lower(*_slot_args(eng)).as_text()
    assert f"module @{module} " in text
    from repro.core.engine import _slot_reset, _staged_size
    st = eng.init_state(8)
    B, n_in, A2 = 8, st.fv.shape[1], st.full.shape[1]
    reset = _slot_reset.lower(
        st.fv, st.fl, st.full, st.val, st.ptr, st.out_last, st.out_count,
        jnp.zeros((_staged_size(B, n_in, st.fv.shape[2]),), jnp.int32),
        jnp.zeros((A2,), jnp.int32), jnp.zeros((A2,), jnp.int32)).as_text()
    assert "module @jit__slot_reset " in reset and n_in >= 1


@pytest.mark.parametrize("name,kernel", [("vector_sum", "rows 1x1"),
                                         ("array_multiplier", "rows 1x1")])
def test_build_spans_carry_the_fabric_and_the_kernel(name, kernel):
    """One ``dataflow.build`` at the server's construction and one
    around each slot step's first compile (a fresh engine's), with the
    fabric's nodes and arcs and the kernel's choice of path and tiles."""
    b = library.BENCHES[name]()
    tr = TraceRecorder()
    eng = DataflowEngine(b.graph, backend="pallas", block_cycles=4)
    srv = DataflowServer(b.graph, slots=4, engine=eng, trace=tr)
    srv.submit(library.random_feeds(name, b, 2, np.random.default_rng(0)))
    srv.drain()
    a = tr.span_arrays()
    names = a["name"]
    build = np.flatnonzero(names == "dataflow.build")
    assert len(build) == 2
    assert [names[p] if p >= 0 else None for p in a["parent"][build]] == \
        [None, "dataflow.step.dispatch"]
    assert list(a["nodes"][build]) == [len(b.graph.nodes)] * 2
    assert list(a["arcs"][build]) == [len(b.graph.arcs)] * 2
    assert list(a["kernel"][build]) == [kernel] * 2
    assert srv.engine.kernel_choice(4) == kernel


@pytest.mark.parametrize("backend", BACKENDS)
def test_node_firings_count_the_answers_firings(backend):
    mr = MetricsRegistry()
    res, _ = _serve(backend, metrics=mr, seed=6)
    fired = sum(r.engine.fired for r in res.values())
    assert mr.snapshot()["counters"]["node_firings"] == fired > 0
