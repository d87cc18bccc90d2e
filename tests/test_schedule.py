"""Static firing schedules (DESIGN.md §13): scheduled execution must be
bit-identical to the dynamic engine and the run_reference oracle in
every observable — values, token counts, cycles, node_fires, §12
profiles, and per-arc registers at block boundaries."""
import dataclasses

import numpy as np
import pytest

from repro.core import library, passes
from repro.core.compile import OPTIMIZE_LEVELS, compile
from repro.core.engine import DataflowEngine, run_reference
from repro.core.graph import Graph, Op
from repro.core.schedule import schedulable, schedule_blockers

CAP = 4096
SCHED_BENCHES = ("fir", "dot_prod", "horner", "bubble_sort")


def _feeds(name, bench, k, seed=0):
    return library.random_feeds(name, bench, k,
                                np.random.default_rng(seed))


def _check(tag, ref, got, profile=False):
    assert got.cycles == ref.cycles, (tag, got.cycles, ref.cycles)
    assert got.fired == ref.fired, (tag, got.fired, ref.fired)
    assert got.counts == ref.counts, tag
    for a, c in ref.counts.items():
        if c:
            assert np.asarray(got.outputs[a]).tobytes() == \
                np.asarray(ref.outputs[a]).tobytes(), (tag, a)
    if profile:
        assert np.array_equal(got.node_fires, ref.node_fires), tag
        _check_profile(tag, ref.profile, got.profile)


def _check_profile(tag, ref, got, with_dispatches=False):
    for f in dataclasses.fields(ref):
        if f.name == "dispatches" and not with_dispatches:
            continue    # run(): oracle profiles carry 0, engines 1
        x, y = getattr(ref, f.name), getattr(got, f.name)
        if isinstance(x, np.ndarray):
            assert np.array_equal(x, y), (tag, f.name)
        else:
            assert x == y, (tag, f.name, x, y)


# ---------------------------------------------------------------------------
# the property matrix: benches x backends x K, bit-identity vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", SCHED_BENCHES)
@pytest.mark.parametrize("backend", ("reference", "xla", "pallas"))
def test_scheduled_matches_oracle(name, backend):
    bench = library.BENCHES[name]()
    g, _ = passes.optimize_graph(bench.graph)
    assert schedulable(g), f"{name} should be statically schedulable"
    feeds = _feeds(name, bench, 12)
    ref = run_reference(g, feeds, max_cycles=CAP, profile=True)
    for K in (1, 4, 16):
        eng = DataflowEngine(g, backend=backend, block_cycles=K,
                             max_cycles=CAP, schedule=True, profile=True)
        _check((name, backend, K), ref, eng.run(feeds), profile=True)


@pytest.mark.parametrize("dtype", (np.uint32, np.float32))
def test_scheduled_dtypes(dtype):
    bench = library.BENCHES["fir"]()
    g, _ = passes.optimize_graph(bench.graph, dtype=np.dtype(dtype))
    feeds = _feeds("fir", bench, 12, seed=3)
    ref = run_reference(g, feeds, dtype=dtype, max_cycles=CAP)
    eng = DataflowEngine(g, dtype=dtype, backend="xla", block_cycles=4,
                         max_cycles=CAP, schedule=True)
    _check(("fir", dtype), ref, eng.run(feeds))


def test_scheduled_max_cycles_truncation():
    bench = library.BENCHES["fir"]()
    g, _ = passes.optimize_graph(bench.graph)
    feeds = _feeds("fir", bench, 32, seed=5)
    for mc in (3, 17, 40):
        ref = run_reference(g, feeds, max_cycles=mc)
        for backend in ("reference", "xla", "pallas"):
            eng = DataflowEngine(g, backend=backend, block_cycles=4,
                                 max_cycles=mc, schedule=True)
            _check(("trunc", backend, mc), ref, eng.run(feeds))


def test_scheduled_run_batch():
    bench = library.BENCHES["dot_prod"]()
    g, _ = passes.optimize_graph(bench.graph)
    same = [_feeds("dot_prod", bench, 8, seed=s) for s in range(3)]
    mixed = [_feeds("dot_prod", bench, k, seed=k) for k in (4, 8, 2)]
    for lbl, fb in (("same", same), ("mixed", mixed)):
        refs = [run_reference(g, f, max_cycles=CAP) for f in fb]
        for backend in ("xla", "pallas"):
            eng = DataflowEngine(g, backend=backend, block_cycles=4,
                                 max_cycles=CAP, schedule=True)
            for i, got in enumerate(eng.run_batch(fb)):
                _check((lbl, backend, i), refs[i], got)


def test_free_running_fabric_schedules():
    """A const-fed fabric never quiesces: the plan locks onto a
    free-running period and the scheduled run truncates at max_cycles
    exactly like the dynamic engine."""
    g = Graph(name="free_run")
    g.add(Op.ADD, ["c1", "c2"], ["z"])
    g.const("c1", 3)
    g.const("c2", 4)
    assert schedulable(g)
    ref = run_reference(g, {}, max_cycles=41)
    assert ref.cycles == 41     # never quiesces
    for backend in ("reference", "xla", "pallas"):
        eng = DataflowEngine(g, backend=backend, block_cycles=4,
                             max_cycles=41, schedule=True)
        _check(("free", backend), ref, eng.run({}))


# ---------------------------------------------------------------------------
# schedulability gate
# ---------------------------------------------------------------------------
def test_schedule_true_raises_on_control_graph():
    bench = library.BENCHES["fibonacci"]()
    blockers = schedule_blockers(bench.graph)
    assert blockers
    with pytest.raises(ValueError) as ei:
        DataflowEngine(bench.graph, schedule=True)
    for b in blockers:      # the error must name every blocker
        assert b in str(ei.value)
    # "auto" on the same fabric silently runs dynamic, bit-identically
    feeds = _feeds("fibonacci", bench, 8)
    eng = DataflowEngine(bench.graph, schedule="auto", max_cycles=CAP)
    assert not eng._sched_on
    _check(("fib", "auto"),
           run_reference(bench.graph, feeds, max_cycles=CAP),
           eng.run(feeds))


def test_schedule_arg_validated():
    bench = library.BENCHES["fir"]()
    with pytest.raises(ValueError):
        DataflowEngine(bench.graph, schedule="yes")


# ---------------------------------------------------------------------------
# the slot API: block-boundary state + clock parity vs the dynamic engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_slot_parity_with_dynamic(backend):
    import jax
    bench = library.BENCHES["fir"]()
    g, _ = passes.optimize_graph(bench.graph)
    feeds = [_feeds("fir", bench, k, seed=k) for k in (8, 16, 4)]
    for K in (1, 4, 16):
        dyn = DataflowEngine(g, backend=backend, block_cycles=K,
                             max_cycles=CAP, profile=True)
        sch = DataflowEngine(g, backend=backend, block_cycles=K,
                             max_cycles=CAP, profile=True, schedule=True)
        sd = dyn.reset_slots(dyn.init_state(3), [0, 1, 2], feeds)
        ss = sch.reset_slots(sch.init_state(3), [0, 1, 2], feeds)
        for blk in range(12):
            sd = dyn.step_block(sd)
            ss = sch.step_block(ss)
            # per-arc registers at block boundaries — bit-identical
            regs = lambda eng, st: dict(
                zip(("full", "val"), eng.slot_registers(st)),
                ptr=st.ptr, out_last=st.out_last, out_count=st.out_count)
            rd, rs = regs(dyn, sd), regs(sch, ss)
            for fld in ("full", "val", "ptr", "out_last", "out_count"):
                a, b = jax.device_get((rd[fld], rs[fld]))
                assert np.array_equal(a, b), (backend, K, blk, fld)
            # per-slot clocks advance by schedule position
            for fld in ("base", "last", "fired", "quiesced",
                        "dispatches", "stalled"):
                assert np.array_equal(getattr(sd, fld),
                                      getattr(ss, fld)), \
                    (backend, K, blk, fld)
            if sd.quiesced.all():
                break
        sd, rd = dyn.harvest(sd, [0, 1, 2])
        ss, rs = sch.harvest(ss, [0, 1, 2])
        for i, (r, s) in enumerate(zip(rd, rs)):
            assert r.cycles == s.cycles and r.fired == s.fired
            assert r.counts == s.counts
            assert np.array_equal(r.node_fires, s.node_fires)
            _check_profile((backend, K, i), r.profile, s.profile,
                           with_dispatches=True)
        # slot reuse: readmit on a harvested slot rebinds its plan
        f2 = [_feeds("fir", bench, 6, seed=99)]
        sd = dyn.reset_slots(sd, [1], f2)
        ss = sch.reset_slots(ss, [1], f2)
        while not sd.quiesced[sd.active > 0].all():
            sd = dyn.step_block(sd)
            ss = sch.step_block(ss)
        sd, rd = dyn.harvest(sd, [1])
        ss, rs = sch.harvest(ss, [1])
        assert rd[0].cycles == rs[0].cycles
        assert rd[0].counts == rs[0].counts
        _check_profile((backend, K, "readmit"), rd[0].profile,
                       rs[0].profile, with_dispatches=True)


# ---------------------------------------------------------------------------
# compile() + serve-layer integration
# ---------------------------------------------------------------------------
def test_compile_sched_level():
    assert "sched" in OPTIMIZE_LEVELS
    bench = library.BENCHES["fir"]()
    feeds = _feeds("fir", bench, 8, seed=2)
    run = compile(bench.graph, backend="xla", optimize="sched",
                  max_cycles=CAP)
    assert run.engine._sched_on
    ref = run_reference(passes.optimize_graph(bench.graph)[0], feeds,
                        max_cycles=CAP)
    _check(("compile", "sched"), ref, run(feeds))
    # cyclic/control-bearing fabrics fall back to the dynamic engine
    gcd = library.BENCHES["gcd"]()
    run2 = compile(gcd.graph, backend="xla", optimize="sched",
                   max_cycles=CAP)
    assert not run2.engine._sched_on
    f2 = _feeds("gcd", gcd, 8, seed=2)
    _check(("compile", "fallback"),
           compile(gcd.graph, backend="xla", optimize="full",
                   max_cycles=CAP)(f2), run2(f2))
    # SSA executors have no plan to schedule
    with pytest.raises(ValueError, match="engine backend"):
        compile(bench.graph, backend="dag", optimize="sched")


def test_cached_engine_schedule_no_alias():
    from repro.serve.dataflow_server import cached_engine, \
        clear_engine_cache
    bench = library.BENCHES["fir"]()
    clear_engine_cache()
    dyn = cached_engine(bench.graph, backend="xla", optimize=True)
    sch = cached_engine(bench.graph, backend="xla", optimize=True,
                        schedule="auto")
    assert dyn is not sch, "scheduled and dynamic engines must not alias"
    assert sch._sched_on and not dyn._sched_on
    assert cached_engine(bench.graph, backend="xla",
                         optimize=True) is dyn
    assert cached_engine(bench.graph, backend="xla", optimize=True,
                         schedule="auto") is sch
    clear_engine_cache()


def test_server_serves_scheduled_fabric():
    from repro.serve.dataflow_server import DataflowServer
    bench = library.BENCHES["fir"]()
    reqs = [_feeds("fir", bench, 8, seed=s) for s in range(5)]
    srv_d = DataflowServer(bench.graph, slots=2, backend="xla",
                           optimize=True, max_cycles=CAP)
    srv_s = DataflowServer(bench.graph, slots=2, backend="xla",
                           optimize=True, schedule="auto",
                           max_cycles=CAP)
    assert srv_s.engine._sched_on
    uids = {srv_d.submit(f): i for i, f in enumerate(reqs)}
    uids_s = {srv_s.submit(f): i for i, f in enumerate(reqs)}
    got_d, got_s = {}, {}
    for _ in range(300):
        for r in srv_d.step():
            got_d[uids[r.uid]] = r
        for r in srv_s.step():
            got_s[uids_s[r.uid]] = r
        if len(got_d) == 5 and len(got_s) == 5:
            break
    assert len(got_d) == len(got_s) == 5
    for i in range(5):
        d, s = got_d[i], got_s[i]
        assert d.status == s.status == "ok"
        assert d.engine.cycles == s.engine.cycles
        assert d.engine.counts == s.engine.counts
        for a, c in d.engine.counts.items():
            if c:
                assert np.asarray(d.engine.outputs[a]).tobytes() == \
                    np.asarray(s.engine.outputs[a]).tobytes(), (i, a)
