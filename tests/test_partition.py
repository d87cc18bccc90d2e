"""Multi-fabric sharding (DESIGN.md §14): partition properties +
sharded-vs-solo bit-identity.

The sharded runtime must be indistinguishable from the single-fabric
engine in EVERY EngineResult field — outputs, counts, cycles, fired,
node_fires, and the merged FabricProfile — because the lockstep channel
exchange reproduces the global cycle exactly (the K-deep channel history
only batches the *communication*, never the *semantics*).  These tests
pin that equivalence against the numpy oracle across partition widths,
block depths, optimize levels, and the slot/serve layers, plus the
partition pass's own invariants (valid cover, loop cycles never cut,
init tokens preserved).

In-process this host exposes a single jax device, so the engine takes
the vmap spmd fallback; the shard_map path over real host devices runs
in a subprocess that sets ``--xla_force_host_platform_device_count``
before importing jax (same pattern as test_pipeline.py).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import compile as C
from repro.core import library
from repro.core.engine import DataflowEngine, run_reference
from repro.core.plan import PLAN_CACHE_STATS, clear_plan_cache
from repro.core.graph import Graph, Op
from repro.core.partition import (Partition, auto_partition,
                                  partition_graph, resolve_partition)
from repro.serve.dataflow_server import (CACHE_STATS, DataflowServer,
                                         cached_engine,
                                         clear_engine_cache)


def _chain_graph():
    """4-node pipeline with a const — every 2-way partition cuts it."""
    g = Graph(name="chain")
    g.const("c", 1)
    g.add(Op.ADD, ["x", "c"], ["a1"])
    g.add(Op.MUL, ["a1", "c"], ["a2"])
    g.add(Op.ADD, ["a2", "c"], ["a3"])
    g.add(Op.MUL, ["a3", "c"], ["o"])
    g.validate()
    return g


def _loop_graph():
    """Init-bearing accumulator loop + acyclic post-chain: the loop SCC
    pins one region, the cut lands on the post-chain."""
    g = Graph(name="loop_post")
    g.const("one", 1)
    g.init("acc", 0)
    g.add(Op.ADD, ["acc", "inc"], ["s"])
    g.add(Op.COPY, ["s"], ["acc", "tap"])
    g.add(Op.MUL, ["tap", "one"], ["post1"])
    g.add(Op.ADD, ["post1", "one"], ["out"])
    g.validate()
    return g


def _assert_identical(r, q, *, profile=False):
    assert set(r.outputs) == set(q.outputs)
    for a in q.outputs:
        np.testing.assert_array_equal(np.asarray(r.outputs[a]),
                                      np.asarray(q.outputs[a]))
    assert r.counts == q.counts
    assert r.cycles == q.cycles
    assert r.fired == q.fired
    if profile:
        assert (r.node_fires == q.node_fires).all()
        assert (r.profile.node_fires == q.profile.node_fires).all()
        r.profile.check()


# ---------------------------------------------------------------------------
# Partition pass properties
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["vector_sum", "pop_count", "gcd",
                                  "fibonacci"])
@pytest.mark.parametrize("P", [2, 3])
def test_partition_valid_cover(name, P):
    g = library.BENCHES[name]().graph
    try:
        part = partition_graph(g, P)
    except ValueError as e:
        # legal refusal: fewer SCC supernodes than regions
        assert "loop cycles" in str(e)
        return
    part.validate(g)                      # cover + no cut SCC + range
    assign = np.asarray(part.assign)
    assert assign.shape == (len(g.nodes),)        # each node exactly once
    assert sorted(set(assign.tolist())) == list(range(P))  # none empty
    # determinism: the pass is a pure function of (graph, P)
    assert partition_graph(g, P).assign == part.assign


def test_partition_never_cuts_loops():
    g = _loop_graph()
    part = partition_graph(g, 2)
    part.validate(g)
    # nodes 0 (ADD) and 1 (COPY) form the loop SCC — same region
    assert part.assign[0] == part.assign[1]
    # hand-built partition that cuts the SCC must be rejected
    bad = Partition(2, (0, 1, 1, 1))
    with pytest.raises(ValueError, match="cycle"):
        bad.validate(g)
    # more regions than supernodes: impossible without cutting
    with pytest.raises(ValueError, match="[Ll]oop cycles|supernode"):
        partition_graph(g, len(g.nodes) + 1)


def test_partition_p1_and_resolve():
    g = _chain_graph()
    p1 = partition_graph(g, 1)
    assert p1.P == 1 and p1.cut_arcs(g) == []
    eng = DataflowEngine(g, partition=p1)
    assert not eng._part_on               # degenerate: plain engine
    assert resolve_partition(g, None) is None
    assert resolve_partition(g, 2).P == 2
    assert resolve_partition(g, "auto").P == auto_partition(g).P
    with pytest.raises(ValueError):
        resolve_partition(g, "bogus")


def test_partition_spec_is_assignment_hash():
    g = _chain_graph()
    a = Partition(2, (0, 0, 1, 1))
    b = Partition(2, (0, 1, 1, 1))
    assert a.spec() != b.spec()
    assert a.spec() == Partition(2, (0, 0, 1, 1)).spec()
    assert a.spec().startswith("2:")


# ---------------------------------------------------------------------------
# Sharded vs solo bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P", [2, 4])
@pytest.mark.parametrize("K", [1, 4, 16])
def test_bit_identity_dag(P, K):
    bench = library.vector_sum_graph(16)
    feeds = library.random_feeds("vector_sum", bench, 5,
                                 rng=np.random.default_rng(P * 100 + K))
    ref = run_reference(bench.graph, feeds)
    eng = DataflowEngine(bench.graph, block_cycles=K, partition=P)
    _assert_identical(eng.run(feeds), ref)


@pytest.mark.parametrize("optimize", [False, True])
def test_bit_identity_optimize_levels(optimize):
    bench = library.popcount_graph(8)
    feeds = bench.make_feeds([7, 255, 0, 41])
    ref = run_reference(bench.graph, feeds)
    eng = DataflowEngine(bench.graph, block_cycles=4, partition=2,
                         optimize=optimize, profile=True)
    _assert_identical(eng.run(feeds), ref, profile=False)


@pytest.mark.parametrize("K", [1, 16])
def test_bit_identity_cyclic(K):
    bench = library.gcd_graph()
    feeds = bench.make_feeds(21, 6)
    ref = run_reference(bench.graph, feeds)
    eng = DataflowEngine(bench.graph, block_cycles=K, partition=2)
    r = eng.run(feeds)
    _assert_identical(r, ref)
    assert int(np.asarray(r.outputs[bench.out_arc])) == 3


def test_bit_identity_inits_preserved():
    g = _loop_graph()
    feeds = {"inc": [1, 2, 3, 4, 5]}
    ref = run_reference(g, feeds)
    for P in (2, 3):
        try:
            part = partition_graph(g, P)
        except ValueError:
            continue
        eng = DataflowEngine(g, block_cycles=4, partition=part)
        _assert_identical(eng.run(feeds), ref)


def test_bit_identity_pallas_backend():
    bench = library.vector_sum_graph(8)
    feeds = library.random_feeds("vector_sum", bench, 3,
                                 rng=np.random.default_rng(7))
    ref = run_reference(bench.graph, feeds)
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=4,
                         partition=2)
    _assert_identical(eng.run(feeds), ref)


def test_bit_identity_batch():
    g = _chain_graph()
    batch = [{"x": [1, 2, 3]}, {"x": [9]}, {"x": [4, 5]}]
    eng = DataflowEngine(g, block_cycles=4, partition=2)
    rs = eng.run_batch(batch)
    for r, feeds in zip(rs, batch):
        _assert_identical(r, run_reference(g, feeds))


# ---------------------------------------------------------------------------
# Merged profile
# ---------------------------------------------------------------------------
def test_profile_merge_exact_at_k1():
    g = _chain_graph()
    feeds = {"x": list(range(8))}
    ref = run_reference(g, feeds, profile=True)
    eng = DataflowEngine(g, block_cycles=1, partition=2, profile=True)
    r = eng.run(feeds)
    _assert_identical(r, ref, profile=True)
    p, q = r.profile, ref.profile
    assert p.cycles == q.cycles
    assert (p.stall_in == q.stall_in).all()
    assert (p.stall_out == q.stall_out).all()
    assert (p.arc_busy == q.arc_busy).all()
    assert (p.arc_hw == q.arc_hw).all()
    # channel counters: one cut arc, a token crossing every stream elem
    assert p.ch_names and p.ch_depth == 1
    assert (p.ch_pushes >= 1).all() and (p.ch_hw <= 1).all()
    assert "channels" in p.to_json()


def test_profile_merge_invariants_at_k4():
    g = _loop_graph()
    feeds = {"inc": [1, 2, 3]}
    ref = run_reference(g, feeds, profile=True)
    eng = DataflowEngine(g, block_cycles=4, partition=2, profile=True)
    r = eng.run(feeds)
    _assert_identical(r, ref, profile=True)
    p, q = r.profile, ref.profile
    # node_fires exact; stall_in absorbs the uniform idle tail K leaves
    tail = p.cycles - q.cycles
    assert tail >= 0
    assert (p.stall_in - q.stall_in == tail).all()
    assert (p.stall_out == q.stall_out).all()
    assert (p.arc_hw == q.arc_hw).all()


# ---------------------------------------------------------------------------
# compile() / slot API / server threading
# ---------------------------------------------------------------------------
def test_compile_partition_threading():
    g = _chain_graph()
    feeds = {"x": [3, 4, 5]}
    ref = run_reference(g, feeds, profile=True)
    run = C.compile(g, backend="auto", partition=2, profile=True)
    assert run.partition.P == 2
    assert run.engine.backend == "xla"    # auto routed off the SSA path
    r = run(feeds)
    _assert_identical(r, ref, profile=True)
    # degenerate resolution falls back to the traits dispatch (dag here)
    run1 = C.compile(g, partition=1)
    assert run1.partition.P == 1 and not hasattr(run1, "engine")
    # partition="auto" resolves from the device count (>=1 everywhere)
    runa = C.compile(g, backend="xla", partition="auto")
    assert runa.partition is None or runa.partition.P >= 1


def test_compile_partition_errors():
    g = _chain_graph()
    with pytest.raises(ValueError, match="shard"):
        C.compile(g, backend="dag", partition=2)
    with pytest.raises(ValueError, match="shard"):
        C.compile(g, backend="unrolled", partition=2)
    with pytest.raises(ValueError, match="reference"):
        DataflowEngine(g, backend="reference", partition=2)
    with pytest.raises(ValueError, match="schedule"):
        DataflowEngine(g, schedule=True, partition=2)


def test_slot_api_sharded():
    g = _chain_graph()
    eng = DataflowEngine(g, block_cycles=4, partition=2, profile=True)
    st = eng.init_state(slots=3)
    st = eng.reset_slots(st, [0, 2], [{"x": [1, 2, 3]}, {"x": [10]}])
    while not st.quiesced[st.active > 0].all():
        st = eng.step_block(st)
    st, res = eng.harvest(st, [0, 2])
    for r, feeds in zip(res, [{"x": [1, 2, 3]}, {"x": [10]}]):
        _assert_identical(r, run_reference(g, feeds, profile=True),
                          profile=True)
    # freed slots readmit cleanly (channel registers reset per slot)
    st = eng.reset_slots(st, [0], [{"x": [7, 8]}])
    while not st.quiesced[st.active > 0].all():
        st = eng.step_block(st)
    st, res2 = eng.harvest(st, [0])
    _assert_identical(res2[0], run_reference(g, {"x": [7, 8]},
                                             profile=True), profile=True)


def test_server_sharded():
    g = _chain_graph()
    srv = DataflowServer(g, slots=4, block_cycles=4, backend="xla",
                         partition=2, profile=True)
    assert srv.engine._part_on
    batches = [{"x": [1, 2]}, {"x": [9]}, {"x": [3, 1, 4]}]
    uids = [srv.submit(f) for f in batches]
    by = {r.uid: r for r in srv.drain()}
    for uid, feeds in zip(uids, batches):
        assert by[uid].status == "ok"
        _assert_identical(by[uid].engine,
                          run_reference(g, feeds, profile=True),
                          profile=True)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def test_cached_engine_partition_collision():
    """PR-3-style collision regression: sharded and unsharded compiles
    of the same asm signature must never alias one engine — and two
    different region assignments must not alias each other."""
    g = _chain_graph()
    clear_engine_cache()
    solo = cached_engine(g, block_cycles=4)
    p2 = cached_engine(g, block_cycles=4, partition=2)
    assert solo is not p2
    assert not solo._part_on and p2._part_on
    other = cached_engine(g, block_cycles=4,
                          partition=Partition(2, (0, 1, 1, 1)))
    assert other is not p2
    # same spec hits; P=1 degenerates to the unsharded key
    assert cached_engine(g, block_cycles=4, partition=2) is p2
    assert cached_engine(g, block_cycles=4, partition=1) is solo
    assert CACHE_STATS["hits"] >= 2


def test_plan_memo_hits():
    g = _chain_graph()
    clear_plan_cache()
    assert PLAN_CACHE_STATS == {"hits": 0, "misses": 0, "evictions": 0}
    DataflowEngine(g).run({"x": [1]})
    m0 = PLAN_CACHE_STATS["misses"]
    assert m0 >= 1
    DataflowEngine(g).run({"x": [2]})
    assert PLAN_CACHE_STATS["hits"] >= 1
    assert PLAN_CACHE_STATS["misses"] == m0   # second build: all hits
    # the serve-layer stats expose the same live dict
    assert CACHE_STATS["plan"] is PLAN_CACHE_STATS


# ---------------------------------------------------------------------------
# shard_map over real host devices (subprocess: XLA_FLAGS before import)
# ---------------------------------------------------------------------------
_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import numpy as np
import jax
assert len(jax.devices()) == 2, jax.devices()
from repro.core import library
from repro.core.engine import DataflowEngine, run_reference

bench = library.vector_sum_graph(16)
feeds = library.random_feeds("vector_sum", bench, 4,
                             rng=np.random.default_rng(0))
ref = run_reference(bench.graph, feeds)
eng = DataflowEngine(bench.graph, block_cycles=8, partition=2,
                     profile=True)
mf = eng._mf_ctx()
assert mf.use_shard_map, "2 devices present: shard_map path expected"
r = eng.run(feeds)
assert r.counts == ref.counts and r.cycles == ref.cycles
assert r.fired == ref.fired
for a in ref.outputs:
    np.testing.assert_array_equal(np.asarray(r.outputs[a]),
                                  np.asarray(ref.outputs[a]))
r.profile.check()
print("OK shard_map")
"""


def test_shard_map_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK shard_map" in r.stdout
