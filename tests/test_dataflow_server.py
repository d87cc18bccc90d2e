"""Continuous-batching server vs solo runs: the bit-identical property.

Acceptance property (ISSUE 2): for any mix of requests and admission
order, each request's EngineResult (out values, token counts, fired
count, cycles) from the continuous-batching server equals running that
request alone via DataflowEngine.run — across benches x K in {1, 4, 16}
x slots in {2, 8}, including mid-flight admissions and unequal stream
lengths.  Admissions happen only at block boundaries and every slot
carries its own cycle clock, so nothing a neighbouring slot does can
leak in (DESIGN.md §7).
"""
import functools

import numpy as np
import pytest

from repro.core import library
from repro.core.engine import DataflowEngine
from repro.serve.dataflow_server import (CACHE_STATS, DataflowServer,
                                         cached_engine, clear_engine_cache,
                                         graph_signature)
from repro.serve.types import Request

KS = [1, 4, 16]
SLOTS = [2, 8]


def _bench(name):
    # full-size graphs except bubble_sort (8 -> 6 keeps wall-time sane)
    return library.bubble_sort_graph(6) if name == "bubble_sort" \
        else library.BENCHES[name]()


def _mixed_feeds(name, bench, n, base_seed=0):
    """n requests with unequal stream lengths 1..8 (fibonacci: loop
    iteration counts), deterministic per index."""
    return [library.random_feeds(name, bench, 1 + (3 * i + base_seed) % 8,
                                 np.random.default_rng(base_seed + i))
            for i in range(n)]


@functools.lru_cache(maxsize=None)
def _eng_and_solos(name, K):
    """One engine + solo-run oracle per (bench, K), shared across the
    slots parametrization (jit compilations dominate the wall time)."""
    bench = _bench(name)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=K)
    feeds = _mixed_feeds(name, bench, 6)
    solos = [eng.run(f) for f in feeds]
    return bench, eng, feeds, solos


def _check(got, want, tag):
    assert got.cycles == want.cycles, (tag, got.cycles, want.cycles)
    assert got.fired == want.fired, (tag, got.fired, want.fired)
    for a, c in want.counts.items():
        assert got.counts[a] == c, (tag, a)
        if c:
            assert int(np.asarray(got.outputs[a])) == \
                int(np.asarray(want.outputs[a])), (tag, a)


@functools.lru_cache(maxsize=None)
def _bench_dtype(name):
    return np.dtype(_bench(name).dtype)


@pytest.mark.parametrize("name", sorted(library.BENCHES))
@pytest.mark.parametrize("K", KS)
@pytest.mark.parametrize("slots", SLOTS)
def test_continuous_matches_solo_runs(name, K, slots):
    if _bench_dtype(name) != np.int32:
        pytest.skip(f"{name}: the resumable slot API is int32-only")
    bench, eng, feeds, solos = _eng_and_solos(name, K)
    srv = DataflowServer(bench.graph, slots=slots, engine=eng)
    # mid-flight admission: 3 requests up front, the rest arrive while
    # the fabric is running
    for f in feeds[:3]:
        srv.submit(f)
    got = srv.step() + srv.step()
    for f in feeds[3:]:
        srv.submit(f)
    got += srv.drain()
    got.sort(key=lambda r: r.uid)
    assert len(got) == len(feeds)
    for r, want in zip(got, solos):
        _check(r.engine, want, (name, K, slots, r.uid))


@pytest.mark.parametrize("name", ["fibonacci", "dot_prod", "pop_count"])
def test_continuous_matches_solo_runs_pallas(name):
    """Same property through the masked Pallas kernel (reduced matrix —
    interpret mode is slow on CPU)."""
    bench = _bench(name)
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=4)
    feeds = _mixed_feeds(name, bench, 5, base_seed=3)
    solos = [eng.run(f) for f in feeds]
    srv = DataflowServer(bench.graph, slots=2, engine=eng)
    for f in feeds[:2]:
        srv.submit(f)
    got = srv.step()
    for f in feeds[2:]:
        srv.submit(f)
    got += srv.drain()
    got.sort(key=lambda r: r.uid)
    for r, want in zip(got, solos):
        _check(r.engine, want, (name, "pallas", r.uid))


def test_admission_order_does_not_change_results():
    """Permuting what rides alongside never changes a request's result."""
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    feeds = _mixed_feeds("vector_sum", bench, 6, base_seed=5)
    solos = [eng.run(f) for f in feeds]
    for order in ([0, 1, 2, 3, 4, 5], [5, 3, 1, 0, 2, 4]):
        srv = DataflowServer(bench.graph, slots=2, engine=eng)
        uids = {srv.submit(feeds[i]): i for i in order}
        for r in srv.drain():
            _check(r.engine, solos[uids[r.uid]], ("order", order, r.uid))


def test_active_mask_freezes_parked_slots():
    """A quiesced/free slot's registers stay bit-frozen while neighbours
    run (the per-slot clock gate of fire_block_batched_pallas)."""
    bench = library.popcount_graph(8)
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=4)
    st = eng.init_state(2)
    st = eng.reset_slots(st, [0], [bench.make_feeds([3])])
    while not st.quiesced_slots():
        st = eng.step_block(st)
    regs = lambda st: (*eng.slot_registers(st), st.ptr, st.out_last,
                       st.out_count)
    frozen = [np.asarray(x)[0].copy() for x in regs(st)]
    st, [res0] = eng.harvest(st, [0])
    fired0, base0 = int(st.fired[0]), int(st.base[0])
    st = eng.reset_slots(st, [1], [bench.make_feeds([255, 16, 7])])
    for _ in range(5):
        st = eng.step_block(st)
    for name_, x, w in zip(("full", "val", "ptr", "out_last", "out_count"),
                           regs(st), frozen):
        np.testing.assert_array_equal(np.asarray(x)[0], w, err_msg=name_)
    # the parked slot's clock did not advance while slot 1 ran 5 blocks
    assert int(st.fired[0]) == fired0 and int(st.base[0]) == base0
    assert int(st.fired[1]) > 0 and int(st.base[1]) == 5 * 4


def test_slot_lifecycle_errors():
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    st = eng.init_state(2)
    st = eng.reset_slots(st, [0], [_mixed_feeds("vector_sum", bench, 1)[0]])
    with pytest.raises(ValueError, match="unharvested"):
        eng.reset_slots(st, [0], [{}])
    with pytest.raises(ValueError, match="free"):
        eng.harvest(st, [1])
    ref_eng = DataflowEngine(bench.graph, backend="reference")
    with pytest.raises(ValueError, match="reference"):
        ref_eng.init_state(2)


def test_cap_truncated_requests_match_solo_runs():
    """A request that exhausts max_cycles un-quiesced is force-harvested
    with outputs/counts/fired bit-identical to a solo run under the same
    cap: heartbeat blocks shrink near the cap so the slot simulates
    EXACTLY max_cycles cycles (never a partial block past it)."""
    bench = library.BENCHES["fibonacci"]()
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=16,
                         max_cycles=10)
    feeds = [bench.make_feeds(1000), bench.make_feeds(2)]
    solos = [eng.run(f) for f in feeds]
    srv = DataflowServer(bench.graph, slots=2, engine=eng)
    uids = [srv.submit(f) for f in feeds]
    got = {r.uid: r for r in srv.drain()}
    assert sorted(got) == sorted(uids)
    for uid, want in zip(uids, solos):
        _check(got[uid].engine, want, ("cap", uid))


def test_step_block_rejects_zero_cycles():
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    st = eng.reset_slots(eng.init_state(1), [0],
                         [_mixed_feeds("vector_sum", bench, 1)[0]])
    with pytest.raises(ValueError, match="n_cycles"):
        eng.step_block(st, n_cycles=0)


def test_engine_validation_errors():
    from repro.core.compile import compile_graph
    bench = library.vector_sum_graph(8)
    with pytest.raises(ValueError, match="block_cycles"):
        compile_graph(bench.graph, backend="xla", block_cycles=0)
    with pytest.raises(ValueError, match="block_cycles"):
        DataflowEngine(bench.graph, block_cycles=0)
    eng = DataflowEngine(bench.graph, backend="xla")
    with pytest.raises(ValueError, match="feeds_batch is empty"):
        eng.run_batch([])


def _engine_stats():
    # CACHE_STATS also carries the live "plan" memo counters (PR 9);
    # these tests pin only the engine-cache event counts
    return {k: CACHE_STATS[k] for k in ("hits", "misses", "evictions")}


def test_plan_cache_shares_engines_across_requests():
    clear_engine_cache()
    g1 = library.vector_sum_graph(8).graph
    g2 = library.vector_sum_graph(8).graph      # same signature, new obj
    assert graph_signature(g1) == graph_signature(g2)
    e1 = cached_engine(g1, backend="xla", block_cycles=4)
    e2 = cached_engine(g2, backend="xla", block_cycles=4)
    assert e1 is e2
    assert _engine_stats() == {"hits": 1, "misses": 1, "evictions": 0}
    e3 = cached_engine(g1, backend="xla", block_cycles=8)  # new K -> miss
    assert e3 is not e1
    assert CACHE_STATS["misses"] == 2


def test_plan_cache_key_includes_shape_dtype_and_opt():
    """Regression: the cache key once omitted token_shape and dtype, so
    two servers over one fabric signature with different token shapes
    collided on a single compiled engine.  token_shape, dtype and the
    optimize flag all split the key now."""
    clear_engine_cache()
    g = library.vector_sum_graph(8).graph
    base = cached_engine(g, backend="xla", block_cycles=4)
    shaped = cached_engine(g, backend="xla", block_cycles=4,
                           token_shape=(4,))
    floated = cached_engine(g, backend="xla", block_cycles=4,
                            dtype=np.float32)
    opt = cached_engine(g, backend="xla", block_cycles=4, optimize=True)
    assert len({id(base), id(shaped), id(floated), id(opt)}) == 4
    assert CACHE_STATS["misses"] == 4 and CACHE_STATS["hits"] == 0
    assert shaped.token_shape == (4,)
    assert floated.dtype == np.float32
    assert opt.optimize and opt.p["class_slices"] is not None
    # and each variant is a hit the second time around
    assert cached_engine(g, backend="xla", block_cycles=4,
                         token_shape=(4,)) is shaped
    assert cached_engine(g, backend="xla", block_cycles=4,
                         optimize=True) is opt
    assert CACHE_STATS["hits"] == 2


def test_plan_cache_lru_eviction_order(monkeypatch):
    """LRU semantics under interleaved hits/misses/evictions: a hit
    refreshes recency, the oldest-unused entry is the eviction victim,
    and CACHE_STATS tracks all three event kinds exactly."""
    import repro.serve.dataflow_server as ds
    clear_engine_cache()
    monkeypatch.setattr(ds, "_ENGINE_CACHE_MAX", 2)
    g = library.vector_sum_graph(8).graph
    e1 = cached_engine(g, backend="xla", block_cycles=1)
    e2 = cached_engine(g, backend="xla", block_cycles=2)
    assert _engine_stats() == {"hits": 0, "misses": 2, "evictions": 0}
    # a hit refreshes e1's recency, making e2 the LRU victim
    assert cached_engine(g, backend="xla", block_cycles=1) is e1
    e3 = cached_engine(g, backend="xla", block_cycles=3)
    assert _engine_stats() == {"hits": 1, "misses": 3, "evictions": 1}
    # e1 survived the eviction (it was refreshed)...
    assert cached_engine(g, backend="xla", block_cycles=1) is e1
    assert CACHE_STATS["hits"] == 2
    # ...e2 did not: asking again recompiles (a miss), evicting e3
    assert cached_engine(g, backend="xla", block_cycles=2) is not e2
    assert _engine_stats() == {"hits": 2, "misses": 4, "evictions": 2}
    assert cached_engine(g, backend="xla", block_cycles=3) is not e3
    assert len(ds._ENGINE_CACHE) == 2


def test_server_optimized_matches_solo_dense_runs():
    """optimize=True on the server specializes the shared plan; every
    request's result stays bit-identical to a dense solo run."""
    bench = library.vector_sum_graph(8)
    dense = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    feeds = _mixed_feeds("vector_sum", bench, 5, base_seed=21)
    solos = [dense.run(f) for f in feeds]
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         backend="xla", optimize=True)
    assert srv.engine.optimize
    uids = [srv.submit(f) for f in feeds]
    got = {r.uid: r for r in srv.drain()}
    for uid, want in zip(uids, solos):
        _check(got[uid].engine, want, ("opt-server", uid))


def test_metrics_account_for_queueing_and_residency():
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    srv = DataflowServer(bench.graph, slots=2, engine=eng)
    feeds = _mixed_feeds("vector_sum", bench, 5, base_seed=9)
    for f in feeds:
        srv.submit(f)
    results = sorted(srv.drain(), key=lambda r: r.uid)
    for r in results:
        m = r.metrics
        assert m.queue_wait_blocks == m.admitted_block - m.queued_block >= 0
        assert m.residency_blocks == r.engine.dispatches > 0
        assert m.residency_cycles == r.engine.cycles
        assert m.tokens_out == sum(r.engine.counts.values()) > 0
        assert m.finished_block > m.admitted_block >= 0
    # the first two admissions happen before any block ran
    assert sorted(m.queue_wait_blocks
                  for m in (r.metrics for r in results))[:2] == [0, 0]


def test_submit_accepts_request_objects_and_dicts():
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    srv = DataflowServer(bench.graph, slots=2, engine=eng)
    feeds = _mixed_feeds("vector_sum", bench, 2)
    uid_a = srv.submit(feeds[0])                         # bare dict
    uid_b = srv.submit(Request(uid=77, feeds=feeds[1]))  # dataclass
    assert uid_b == 77 and uid_a != uid_b
    with pytest.raises(ValueError, match="no feeds"):
        srv.submit(Request(uid=78, prompt=np.array([1, 2])))
    with pytest.raises(ValueError, match="in flight"):
        srv.submit(Request(uid=77, feeds=feeds[0]))      # duplicate uid
    # auto uids skip caller-claimed ones instead of colliding
    srv2 = DataflowServer(bench.graph, slots=2, engine=eng)
    srv2.submit(Request(uid=1, feeds=feeds[0]))
    assert srv2.submit(feeds[1]) == 2
    results = srv.drain()
    assert sorted(r.uid for r in results) == sorted([uid_a, uid_b])


def test_submit_rejects_unknown_feed_arcs_before_queueing():
    """A bad request is rejected at submit() and cannot poison the
    fused admission round of its co-batched neighbours."""
    bench = library.vector_sum_graph(8)
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    srv = DataflowServer(bench.graph, slots=2, engine=eng)
    good = srv.submit(_mixed_feeds("vector_sum", bench, 1)[0])
    with pytest.raises(ValueError, match="non-input arcs"):
        srv.submit({"typo_arc": [1]})
    results = srv.drain()          # the good request still completes
    assert [r.uid for r in results] == [good]


def test_server_rejects_engine_for_other_fabric():
    eng = DataflowEngine(library.vector_sum_graph(8).graph,
                         backend="xla", block_cycles=4)
    with pytest.raises(ValueError, match="different fabric"):
        DataflowServer(library.popcount_graph(8).graph, slots=2,
                       engine=eng)
