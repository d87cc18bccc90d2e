"""Block-fused + batched engine executors vs the numpy reference oracle.

Acceptance property (ISSUE 1): for every graph in library.BENCHES, the
block-fused engine (K cycles per dispatch) and the batched stream
executor (B independent streams through one fabric) produce outputs,
drain counts, firing totals AND cycle counts bit-identical to
run_reference — including streams of unequal length within a batch.
"""
import numpy as np
import pytest

from repro.core import library
from repro.core.compile import compile_graph
from repro.core.engine import DataflowEngine, run_reference

KS = [1, 4, 16]
BACKENDS = ["xla", "pallas"]


def _bench(name):
    # full-size graphs except bubble_sort (8 -> 6 keeps the 112-node
    # fabric's test wall-time sane; the schema is identical)
    return library.bubble_sort_graph(6) if name == "bubble_sort" \
        else library.BENCHES[name]()


def _bench_for(name, backend):
    """Bench + execution dtype; skip executor/dtype combos that cannot
    exist (the pallas kernels are scalar-int32-only)."""
    bench = _bench(name)
    dt = np.dtype(bench.dtype)
    if backend == "pallas" and dt != np.int32:
        pytest.skip(f"{name} runs at {dt}; pallas is int32-only")
    return bench, dt


def _feeds(name, bench, k, seed):
    return library.random_feeds(name, bench, k,
                                np.random.default_rng(seed))


def _check(got, want, tag):
    assert got.cycles == want.cycles, (tag, got.cycles, want.cycles)
    assert got.fired == want.fired, (tag, got.fired, want.fired)
    for a, c in want.counts.items():
        assert got.counts[a] == c, (tag, a)
        if c:
            assert np.asarray(got.outputs[a]).item() == \
                np.asarray(want.outputs[a]).item(), (tag, a)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(library.BENCHES))
def test_block_fused_matches_reference(name, backend):
    bench, dt = _bench_for(name, backend)
    feeds = _feeds(name, bench, 5, seed=0)
    want = run_reference(bench.graph, feeds, dtype=dt)
    for K in KS:
        eng = DataflowEngine(bench.graph, dtype=dt, backend=backend,
                             block_cycles=K)
        _check(eng.run(feeds), want, (name, backend, K))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(library.BENCHES))
def test_batched_streams_match_reference(name, backend):
    bench, dt = _bench_for(name, backend)
    for B in (1, 8):
        # unequal stream lengths: stream b carries 1 + (b % 4) tokens
        # (loop fabrics: the trip count varies per stream instead)
        lens = [1 + (b % 4) for b in range(B)]
        fb = [_feeds(name, bench, k, seed=10 + b)
              for b, k in enumerate(lens)]
        wants = [run_reference(bench.graph, f, dtype=dt) for f in fb]
        eng = DataflowEngine(bench.graph, dtype=dt, backend=backend,
                             block_cycles=8)
        got = eng.run_batch(fb)
        assert len(got) == B
        for b in range(B):
            _check(got[b], wants[b], (name, backend, B, b))


def test_batched_pallas_kernel_matches_vmap():
    """The explicit batch grid in the Pallas kernel == vmap over the
    fused block step (the two batching implementations agree)."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import pack_feeds
    from repro.kernels.dataflow_fire import (FabricSpec, block_plan_arrays,
                                             fire_block_ref, make_block_step)

    bench = library.popcount_graph(8)
    tables = block_plan_arrays(bench.graph)
    bstep = make_block_step(FabricSpec(tables), 8)
    p = tables["plan"]
    B, L = 4, 4   # L = longest stream (stream b carries 1+b tokens)
    packed = [pack_feeds(p["input_arcs"],
                         _feeds("pop_count", bench, 1 + b, seed=b),
                         pad_rows=1, min_len=L) for b in range(B)]
    fv = jnp.asarray(np.stack([x for x, _ in packed]))
    fl = jnp.asarray(np.stack([x for _, x in packed]))
    A2 = p["A"] + 2
    n_in = max(len(p["input_arcs"]), 1)
    n_out = max(len(p["output_arcs"]), 1)
    full = np.zeros((B, A2), np.int32)
    val = np.zeros((B, A2), np.int32)
    full[:, p["FULL_PAD"]] = 1
    for a, v in bench.graph.consts.items():
        full[:, p["aidx"][a]] = 1
        val[:, p["aidx"][a]] = int(v)
    state = (jnp.asarray(full), jnp.asarray(val),
             jnp.zeros((B, n_in), jnp.int32),
             jnp.zeros((B, n_out), jnp.int32),
             jnp.zeros((B, n_out), jnp.int32))
    active = jnp.ones((B,), jnp.int32)
    got = bstep(fv, fl, *state, active)
    want = jax.vmap(
        lambda fv1, fl1, *s: fire_block_ref(
            tables, fv1, fl1, *s, n_cycles=8))(fv, fl, *state)
    for g, w in zip(got[:5], want[:5]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got[5]).ravel(),
                                  np.asarray(want[5]).ravel())
    np.testing.assert_array_equal(np.asarray(got[6]).ravel(),
                                  np.asarray(want[6]).ravel())


def test_block_fusion_cuts_dispatches_10x():
    """K=16 fused blocks need >= 10x fewer device dispatches than the
    seed per-cycle kernel driver (one dispatch per cycle)."""
    bench = library.fibonacci_graph()
    feeds = bench.make_feeds(30)
    per_cycle_dispatches = run_reference(bench.graph, feeds).cycles
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=16)
    res = eng.run(feeds)
    assert res.dispatches * 10 <= per_cycle_dispatches, \
        (res.dispatches, per_cycle_dispatches)


def test_max_cycles_cutoff_is_exact():
    """Truncating a still-active fabric mid-block simulates EXACTLY
    max_cycles cycles: fired/counts bit-identical to the per-cycle
    reference, for caps both off and on block boundaries."""
    bench = library.fibonacci_graph()
    feeds = bench.make_feeds(1000)   # still running at every cap below
    for cap in (50, 48, 7):
        want = run_reference(bench.graph, feeds, max_cycles=cap)
        for backend in BACKENDS:
            eng = DataflowEngine(bench.graph, backend=backend,
                                 block_cycles=16)
            _check(eng.run(feeds, max_cycles=cap), want,
                   ("cutoff", backend, cap))


def test_compile_graph_backend_dispatch():
    bench = library.fibonacci_graph()
    feeds = bench.make_feeds(7)
    want = run_reference(bench.graph, feeds)
    for backend in ("xla", "pallas", "reference"):
        run = compile_graph(bench.graph, backend=backend, block_cycles=4)
        _check(run(feeds), want, backend)
        assert hasattr(run.engine, "run_batch")


def test_run_batch_matches_solo_runs():
    """A stream's result is independent of what rides alongside it."""
    bench = library.vector_sum_graph(8)
    rng = np.random.default_rng(3)
    fb = [bench.make_feeds(rng.integers(0, 99, (k, 8)))
          for k in (4, 1, 7)]
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    batched = eng.run_batch(fb)
    for f, got in zip(fb, batched):
        _check(got, eng.run(f), "solo-vs-batch")
