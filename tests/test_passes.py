"""Pass-pipeline correctness (ISSUE 3).

Two layers, two contracts (DESIGN.md §8):

* **opcode-class specialization** (plan-level, ``optimize="spec"`` /
  ``DataflowEngine(optimize=True)``) is a pure layout permutation: EVERY
  EngineResult field — outputs, counts, cycles, fired — and every
  per-arc register must be bit-identical to the unoptimized engine,
  across every library bench x backend {reference, xla, pallas} x
  K in {1, 4, 16}.
* **graph rewrites** (constant folding / identity elimination / dead
  code elimination, ``optimize="full"``) shrink the fabric: for fabrics
  that quiesce, every surviving output arc must drain bit-identical
  last values and token counts, including graphs where folding
  eliminates the nodes feeding output arcs.  ``cycles``/``fired`` may
  shrink — the optimized fabric does less work.
"""
import functools

import numpy as np
import pytest

from repro.core import library, passes
from repro.core.compile import compile_graph
from repro.core.engine import DataflowEngine, run_reference
from repro.core.graph import Graph, Op

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # CI installs hypothesis; local runs may not
    HAVE_HYPOTHESIS = False

KS = [1, 4, 16]
BACKENDS = ["reference", "xla", "pallas"]


def _bench(name):
    # full-size graphs except bubble_sort (8 -> 6 keeps wall-time sane)
    return library.bubble_sort_graph(6) if name == "bubble_sort" \
        else library.BENCHES[name]()


def _feeds(name, bench, k, seed=0):
    return library.random_feeds(name, bench, k,
                                np.random.default_rng(seed))


def _check_full(got, want, tag):
    """All EngineResult fields bit-identical (the spec contract)."""
    assert got.cycles == want.cycles, (tag, got.cycles, want.cycles)
    assert got.fired == want.fired, (tag, got.fired, want.fired)
    _check_observables(got, want, tag)


def _check_observables(got, want, tag):
    """Last values + token counts on every output arc of `want` (the
    rewrite contract)."""
    for a, c in want.counts.items():
        assert got.counts[a] == c, (tag, a, got.counts[a], c)
        if c:
            assert np.asarray(got.outputs[a]).item() == \
                np.asarray(want.outputs[a]).item(), (tag, a)


# ---------------------------------------------------------------------------
# specialization: full-field bit-identity across the whole matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(library.BENCHES))
def test_specialized_plan_bit_identical(name, backend):
    bench = _bench(name)
    dt = np.dtype(bench.dtype)
    if backend == "pallas" and dt != np.int32:
        pytest.skip(f"{name} runs at {dt}; pallas is int32-only")
    k = 10 if name == "fibonacci" else 3
    feeds = _feeds(name, bench, k)
    want = run_reference(bench.graph, feeds, dtype=dt)
    for K in KS:
        eng = DataflowEngine(bench.graph, dtype=dt, backend=backend,
                             block_cycles=K, optimize=True)
        _check_full(eng.run(feeds), want, (name, backend, K))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_specialized_per_arc_state_identical(backend):
    """Mid-flight arc registers (not just drained results) match the
    dense engine's, mapped through the plan's arc permutation."""
    bench = library.fibonacci_graph()
    feeds = bench.make_feeds(40)          # still running after 3 blocks

    def arc_state(eng, st):
        full, val = (np.asarray(x)[0] for x in eng.slot_registers(st))
        return {a: (int(full[eng.p["aidx"][a]]),
                    int(val[eng.p["aidx"][a]]))
                for a in eng.p["arcs"]}

    states = []
    for optimize in (False, True):
        eng = DataflowEngine(bench.graph, backend=backend,
                             block_cycles=4, optimize=optimize)
        st_ = eng.reset_slots(eng.init_state(1), [0], [feeds])
        for _ in range(3):
            st_ = eng.step_block(st_)
        states.append(arc_state(eng, st_))
    assert states[0] == states[1]


def test_specialized_tensor_tokens_bit_identical():
    """The xla spec path generalizes to tensor tokens and float dtypes
    like the dense one."""
    g = Graph(name="tensor")
    g.add(Op.ADD, ["a", "b"], ["s"])
    g.add(Op.MUL, ["s", "c"], ["z"])
    feeds = {"a": np.full((2, 4), 3.0), "b": np.full((2, 4), 4.0),
             "c": np.full((2, 4), 2.0)}
    runs = []
    for opt in (False, True):
        eng = DataflowEngine(g, token_shape=(4,), dtype=np.float32,
                             backend="xla", block_cycles=4, optimize=opt)
        runs.append(eng.run(feeds))
    dense, spec = runs
    assert spec.cycles == dense.cycles and spec.fired == dense.fired
    assert spec.counts == dense.counts
    np.testing.assert_array_equal(np.asarray(spec.outputs["z"]),
                                  np.asarray(dense.outputs["z"]))


def test_plan_permutations_are_inverses():
    for name in sorted(library.BENCHES):
        p = DataflowEngine(_bench(name).graph, optimize=True).p
        assert (p["node_perm"][p["node_inv"]]
                == np.arange(len(p["node_perm"]))).all()
        assert (p["arc_perm"][p["arc_inv"]]
                == np.arange(len(p["arc_perm"]))).all()
        # class slices tile [0, N) and each bucket is opcode-pure
        edges = [0]
        for op, lo, hi in p["class_slices"]:
            assert lo == edges[-1] and hi > lo
            assert (p["opcode"][lo:hi] == op).all()
            edges.append(hi)
        assert edges[-1] == len(p["opcode"])


def test_specialized_batched_and_server_paths():
    """run_batch and the continuous-batching server ride the same
    specialized plan and stay bit-identical to solo dense runs."""
    from repro.serve.dataflow_server import DataflowServer
    bench = _bench("fir")
    fb = [_feeds("fir", bench, 1 + i % 3, seed=i) for i in range(5)]
    dense = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    solos = [dense.run(f) for f in fb]
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4,
                         optimize=True)
    for got, want in zip(eng.run_batch(fb), solos):
        _check_full(got, want, "run_batch")
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         backend="xla", optimize=True)
    uids = [srv.submit(f) for f in fb]
    got = {r.uid: r.engine for r in srv.drain()}
    for uid, want in zip(uids, solos):
        _check_full(got[uid], want, ("server", uid))


# ---------------------------------------------------------------------------
# rewrite passes: observable identity on quiescing fabrics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(library.BENCHES))
def test_rewrites_preserve_observables(name):
    bench = _bench(name)
    dt = np.dtype(bench.dtype)
    opt, report = passes.optimize_graph(bench.graph, dtype=dt)
    assert report.nodes_after <= report.nodes_before
    k = 10 if name == "fibonacci" else 4
    feeds = _feeds(name, bench, k, seed=7)
    want = run_reference(bench.graph, feeds, dtype=dt)
    got = run_reference(opt, feeds, dtype=dt)
    _check_observables(got, want, (name, "reference"))
    eng = DataflowEngine(opt, dtype=dt, backend="xla", block_cycles=4,
                         optimize=True)
    _check_observables(eng.run(feeds), want, (name, "xla"))


def test_constant_folding_collapses_chains():
    g = Graph(name="foldme")
    g.const("c2", 2)
    g.const("c3", 3)
    g.const("c5", 5)
    g.add(Op.ADD, ["c2", "c3"], ["t"])       # t = 5
    g.add(Op.MUL, ["t", "c5"], ["u"])        # u = 25
    g.add(Op.ADD, ["u", "x"], ["out"])
    opt, report = passes.optimize_graph(g)
    assert report.folded == 2
    assert len(opt.nodes) == 1 and opt.consts["u"] == 25
    feeds = {"x": np.arange(4)}
    _check_observables(run_reference(opt, feeds),
                       run_reference(g, feeds), "fold-chain")


def test_folding_eliminates_nodes_feeding_outputs():
    """The folded node fed an output arc directly: the arc survives as
    a const bus and drains the same value at the same cadence (both
    fabrics free-run on it, so counts and cycles agree even at a cap)."""
    g = Graph(name="foldout")
    g.const("a", 7)
    g.const("b", 6)
    g.add(Op.MUL, ["a", "b"], ["y"])         # y: output arc, = 42
    g.add(Op.ADD, ["x", "a"], ["z"])         # stream-gated second output
    opt, report = passes.optimize_graph(g)
    assert report.folded == 1
    assert "y" in opt.output_arcs() and opt.consts["y"] == 42
    feeds = {"x": [1, 2]}
    want = run_reference(g, feeds, max_cycles=60)
    got = run_reference(opt, feeds, max_cycles=60)
    assert got.cycles == want.cycles
    _check_observables(got, want, "fold-to-output")


def test_copy_of_const_is_never_folded():
    """COPY's two outputs share one firing rule (both must be empty), so
    folding it to two independent always-full const buses would remove
    that backpressure coupling — here it would flip a quiescing fabric
    into a free-running one.  The folder must leave it alone."""
    g = Graph(name="foldcopy")
    g.const("c", 9)
    g.add(Op.COPY, ["c"], ["y1", "y2"])      # y2: env-drained output
    g.add(Op.ADD, ["y1", "x"], ["z"])        # y1: gated by the stream
    opt, report = passes.optimize_graph(g)
    assert report.folded == 0 and len(opt.nodes) == 2
    feeds = {"x": [3, 4]}
    want = run_reference(g, feeds)
    got = run_reference(opt, feeds)
    assert got.cycles == want.cycles < 100_000   # still quiesces
    _check_observables(got, want, "copy-kept")


def test_folding_uses_execution_dtype():
    """Folded constants wrap exactly like fired int32 results."""
    g = Graph(name="wrap")
    g.const("big", 70_000)
    g.add(Op.MUL, ["big", "big"], ["y"])
    g.add(Op.ADD, ["y", "x"], ["out"])
    with np.errstate(over="ignore"):
        opt, _ = passes.optimize_graph(g, dtype=np.int32)
        assert opt.consts["y"] == int(np.int32(70_000) * np.int32(70_000))


def test_identity_elimination_is_dtype_aware():
    g = Graph(name="ident")
    g.const("z0", 0)
    g.const("k", 5)
    g.add(Op.XOR, ["x", "z0"], ["m"])        # x ^ 0 == x only for ints
    g.add(Op.ADD, ["m", "k"], ["out"])
    opt_i, rep_i = passes.optimize_graph(g, dtype=np.int32)
    assert rep_i.identities == 1 and len(opt_i.nodes) == 1
    opt_f, rep_f = passes.optimize_graph(g, dtype=np.float32)
    assert rep_f.identities == 0 and len(opt_f.nodes) == 2
    # the guard case: an identity between an environment input and an
    # environment output is kept (both interface arcs must survive)
    g3 = Graph(name="ident3")
    g3.const("z0", 0)
    g3.add(Op.ADD, ["x", "z0"], ["out"])
    opt3, rep3 = passes.optimize_graph(g3)
    assert rep3.identities == 0 and len(opt3.nodes) == 1
    # and the splice preserves the stream (internal-wire case)
    g2 = Graph(name="ident2")
    g2.const("one", 1)
    g2.const("z0", 0)
    g2.add(Op.MUL, ["x", "one"], ["m"])
    g2.add(Op.ADD, ["m", "z0"], ["n"])
    g2.add(Op.SUB, ["n", "one"], ["out"])
    opt2, rep2 = passes.optimize_graph(g2)
    assert rep2.identities == 2 and len(opt2.nodes) == 1
    feeds = {"x": [5, 6, 7]}
    _check_observables(run_reference(opt2, feeds),
                       run_reference(g2, feeds), "identity-splice")


def test_const_fed_ndmerge_race_is_not_folded():
    """NDMERGE arbitration depends on token *arrival timing*, so folding
    must bail on merge-bearing graphs.  Regression for the review case:
    with feeds w=[7], s=[100] the authored fabric drains 107 (the merge
    takes ``w`` during ``m``'s one-cycle refill gap) but a folded fabric
    would drain 110 (``m`` always full as a const bus; tie picks a, so
    ``w`` is never consumed) — bit-identity would be violated."""
    g = Graph(name="merge_race")
    g.const("c", 5)
    g.add(Op.ADD, ["c", "c"], ["m"])         # all-const, but feeds a race
    g.add(Op.NDMERGE, ["m", "w"], ["y"])
    g.add(Op.ADD, ["y", "s"], ["out"])
    opt, report = passes.optimize_graph(g)
    assert not report.changed and len(opt.nodes) == 3
    feeds = {"w": [7], "s": [100]}
    want = run_reference(g, feeds, max_cycles=500)
    got = run_reference(opt, feeds, max_cycles=500)
    assert want.cycles < 500                 # both fabrics quiesce
    _check_observables(got, want, "merge-race")
    # the stream token must have won its race in both fabrics
    assert int(np.asarray(want.outputs["out"])) == 107


def test_identity_feeding_ndmerge_cone_is_kept():
    """An identity node is a one-token pipeline register; splicing it
    out shifts downstream arrivals a cycle earlier, which can flip an
    NDMERGE race — the pass bails on merge-bearing graphs."""
    g = Graph(name="merge_ident")
    g.const("z0", 0)
    g.add(Op.ADD, ["x", "z0"], ["m"])        # no-op, but a register
    g.add(Op.NDMERGE, ["m", "w"], ["out"])
    opt, report = passes.optimize_graph(g)
    assert report.identities == 0 and len(opt.nodes) == 2


def test_identity_on_cyclic_fabric_is_kept():
    """On a cyclic path the spliced register's lost capacity can change
    blocking behavior, so the identity pass is restricted to DAGs."""
    g = Graph(name="cyc_ident")
    g.const("z0", 0)
    g.add(Op.ADD, ["x", "fb"], ["m"])
    g.add(Op.COPY, ["m"], ["t", "out"])
    g.add(Op.ADD, ["t", "z0"], ["fb"])       # identity on the loop
    assert g.is_cyclic()
    opt, report = passes.optimize_graph(g)
    assert report.identities == 0 and len(opt.nodes) == 3


def test_region_scoped_fold_runs_beside_loop_entry_merges():
    """ISSUE 5: with only loop-entry NDMERGEs (on a cycle through
    exactly one input), the fold/splice passes run region-scoped
    instead of bailing out — const cones outside the loop fold, and
    the loop's outputs/token counts are untouched."""
    g = Graph(name="loop_fold")
    g.const("one", 1)
    g.const("c2", 2)
    g.const("c3", 3)
    # foldable cone OUTSIDE the loop feeds the environment
    g.add(Op.ADD, ["c2", "c3"], ["t"])          # -> const 5
    g.add(Op.MUL, ["t", "x"], ["pre"])
    # counter loop: NDMERGE entry, IFGT decider, BRANCH back edge
    g.add(Op.NDMERGE, ["i_fb", "i0"], ["i"])
    g.add(Op.COPY, ["i"], ["i_c", "i_d"])
    g.add(Op.IFGT, ["pre", "i_c"], ["cond"])
    g.add(Op.BRANCH, ["i_d", "cond"], ["i_live", "out"])
    g.add(Op.ADD, ["i_live", "one"], ["i_fb"])
    g.init("i0", 0)
    g.validate()
    opt, report = passes.optimize_graph(g)
    assert report.folded == 1, report.summary()     # the c2+c3 cone
    assert opt.consts["t"] == 5 and opt.inits == {"i0": 0}
    # the decider consumes one `pre` token per iteration, so the
    # environment presents x persistently (one per firing, like the
    # fibonacci bench's n_in bus): trip count = 5*2 = 10
    feeds = {"x": [2] * 12}
    want = run_reference(g, feeds, max_cycles=400)
    got = run_reference(opt, feeds, max_cycles=400)
    assert want.cycles < 400                        # both quiesce
    _check_observables(got, want, "loop-fold")
    assert want.counts["out"] == 1
    assert np.asarray(got.outputs["out"]).item() == 10  # trip count


def test_fold_never_turns_an_ndmerge_input_into_a_const_bus():
    """Folding a node whose output feeds an NDMERGE would replace a
    one-shot/periodic arc with an always-full bus and re-fire the
    merge every refill window — the folder must keep it even when the
    graph's merges are all loop entries."""
    g = Graph(name="merge_feed")
    g.const("one", 1)
    g.const("c2", 2)
    g.const("c3", 3)
    g.add(Op.ADD, ["c2", "c3"], ["seed"])       # all-const, feeds merge
    g.add(Op.NDMERGE, ["i_fb", "seed"], ["i"])
    g.add(Op.COPY, ["i"], ["i_c", "i_d"])
    g.add(Op.IFGT, ["n", "i_c"], ["cond"])
    g.add(Op.BRANCH, ["i_d", "cond"], ["i_live", "out"])
    g.add(Op.ADD, ["i_live", "one"], ["i_fb"])
    g.validate()
    opt, report = passes.optimize_graph(g)
    assert report.folded == 0
    assert "seed" not in opt.consts
    # the const-fed seed producer free-runs (re-initiating the merge),
    # so the fabric never quiesces — compare capped runs, which would
    # diverge if the fold had made seed an always-full const bus
    feeds = {"n": [8] * 6}      # one decider token per iteration
    want = run_reference(g, feeds, max_cycles=400)
    got = run_reference(opt, feeds, max_cycles=400)
    _check_observables(got, want, "merge-feed")


def test_off_cycle_identity_splices_in_cyclic_graphs():
    """The blanket acyclic restriction is gone: an identity on a wire
    OUTSIDE every cycle splices even when the graph has loops, while
    on-cycle identities stay (loop token capacity)."""
    g = Graph(name="cyc_mixed")
    g.const("z0", 0)
    g.const("one", 1)
    # off-cycle identity feeding the loop's decider input
    g.add(Op.ADD, ["x", "z0"], ["n"])           # spliceable no-op
    g.add(Op.NDMERGE, ["i_fb", "i0"], ["i"])
    g.add(Op.COPY, ["i"], ["i_c", "i_d"])
    g.add(Op.IFGT, ["n", "i_c"], ["cond"])
    g.add(Op.BRANCH, ["i_d", "cond"], ["i_live", "out"])
    # on-cycle identity: the back-edge register must survive
    g.add(Op.ADD, ["i_live", "one"], ["i_pre"])
    g.add(Op.XOR, ["i_pre", "z0"], ["i_fb"])    # no-op, but on the loop
    g.init("i0", 0)
    g.validate()
    opt, report = passes.optimize_graph(g)
    assert report.identities == 1, report.summary()
    assert any(n.op == Op.XOR for n in opt.nodes)      # on-cycle kept
    assert not any(n.op == Op.ADD and "z0" in n.inputs
                   for n in opt.nodes)                 # off-cycle gone
    feeds = {"x": [5] * 8}      # one decider token per iteration
    want = run_reference(g, feeds, max_cycles=400)
    got = run_reference(opt, feeds, max_cycles=400)
    assert want.cycles < 400
    _check_observables(got, want, "cyc-mixed")


def test_racy_ndmerge_still_bails_out_everything():
    """Two back edges into one NDMERGE (or an acyclic merge — covered
    by the PR 3 regression above) is racy: fold and splice both bail."""
    g = Graph(name="two_backs")
    g.const("one", 1)
    g.const("z0", 0)
    g.const("c_extra", 4)
    g.add(Op.ADD, ["c_extra", "z0"], ["w"])     # would-be fold target
    g.add(Op.NDMERGE, ["fb_a", "fb_b"], ["m"])  # merged by TWO cycles
    g.add(Op.COPY, ["m"], ["m1", "m2"])
    g.add(Op.COPY, ["m1"], ["out", "m3"])       # live: env-drained out
    g.add(Op.ADD, ["m3", "one"], ["fb_a"])
    g.add(Op.SUB, ["m2", "w"], ["fb_b"])
    g.validate()
    opt, report = passes.optimize_graph(g)
    assert report.folded == 0 and report.identities == 0
    assert len(opt.nodes) == len(g.nodes)


def test_dce_removes_closed_dead_region_only():
    g = Graph(name="dce")
    g.const("c1", 3)
    g.const("c2", 4)
    g.add(Op.ADD, ["x", "c1"], ["out"])      # live
    g.add(Op.NDMERGE, ["c1", "c2"], ["m"])   # dead, const-fed (unfoldable)
    g.add(Op.SINK, ["m"], [])                # dead drain
    opt, report = passes.optimize_graph(g)
    assert report.dead == 2 and len(opt.nodes) == 1
    assert "c2" not in opt.consts            # dead const arc dropped
    # the dead NDMERGE free-runs in the original (it never quiesces, so
    # cap both runs); the optimized fabric quiesces on its own
    want = run_reference(g, {"x": [1, 2, 3]}, max_cycles=300)
    got = run_reference(opt, {"x": [1, 2, 3]}, max_cycles=300)
    _check_observables(got, want, "dce")
    assert got.cycles < want.cycles == 300   # dead region free-ran
    # a SINK fed by a LIVE producer is kept: removing it would strand
    # the producer's arc as a new environment-drained output
    fib = library.fibonacci_graph().graph
    opt_fib, rep_fib = passes.optimize_graph(fib)
    assert not rep_fib.changed
    assert len(opt_fib.nodes) == len(fib.nodes)


def test_dce_keeps_env_fed_dead_regions_for_feed_compat():
    """A dead region fed by an environment input arc is kept: deleting
    the arc would make feeds that were valid for the authored graph
    start raising in pack_feeds."""
    g = Graph(name="dce_env")
    g.const("k", 3)
    g.add(Op.ADD, ["x", "k"], ["out"])       # live
    g.add(Op.MUL, ["d", "k"], ["dd"])        # dead, fed by env input d
    g.add(Op.SINK, ["dd"], [])
    opt, report = passes.optimize_graph(g)
    assert report.dead == 0 and sorted(opt.input_arcs()) == ["d", "x"]
    feeds = {"x": [1, 2, 3], "d": [9]}       # authored-interface feeds
    run = compile_graph(g, backend="xla", block_cycles=4, optimize=True)
    _check_full(run(feeds), run_reference(g, feeds), "env-fed-dce")


def test_float_constant_folding_is_exact():
    """Folded float constants must not be truncated through int()."""
    g = Graph(name="ffold")
    g.const("h", 0.5)
    g.const("q", 0.25)
    g.add(Op.ADD, ["h", "q"], ["s"])         # s = 0.75
    g.add(Op.ADD, ["s", "x"], ["out"])
    opt, report = passes.optimize_graph(g, dtype=np.float32)
    assert report.folded == 1 and opt.consts["s"] == 0.75
    feeds = {"x": np.asarray([1.0, 2.0], np.float32)}
    want = run_reference(g, feeds, dtype=np.float32)
    got = run_reference(opt, feeds, dtype=np.float32)
    for a, c in want.counts.items():
        assert got.counts[a] == c
        np.testing.assert_array_equal(np.asarray(got.outputs[a]),
                                      np.asarray(want.outputs[a]))
    # ...and a float const that truncates to an identity value is NOT
    # treated as one: x + 0.5 stays
    g2 = Graph(name="fident")
    g2.const("h", 0.5)
    g2.const("k", 2.0)
    g2.add(Op.ADD, ["x", "h"], ["m"])
    g2.add(Op.MUL, ["m", "k"], ["out"])
    _, rep2 = passes.optimize_graph(g2, dtype=np.float32)
    assert rep2.identities == 0


def test_float_add_zero_is_not_spliced_signed_zero():
    """x + 0.0 is not a BIT-exact identity: -0.0 + 0.0 == +0.0 per IEEE
    754, so splicing the ADD would propagate -0.0 where the authored
    fabric drains +0.0.  Float identities are restricted to *1 /1."""
    g = Graph(name="szero")
    g.const("z0", 0.0)
    g.const("k", 2.0)
    g.add(Op.ADD, ["x", "z0"], ["t"])
    g.add(Op.MUL, ["t", "k"], ["out"])
    opt, report = passes.optimize_graph(g, dtype=np.float32)
    assert report.identities == 0 and len(opt.nodes) == 2
    feeds = {"x": np.asarray([-0.0], np.float32)}
    want = run_reference(g, feeds, dtype=np.float32)
    got = run_reference(opt, feeds, dtype=np.float32)
    assert (np.signbit(np.asarray(got.outputs["out"]))
            == np.signbit(np.asarray(want.outputs["out"])))
    # MUL/DIV by one stay bit-exact spliceable for floats
    g2 = Graph(name="fmul1")
    g2.const("one", 1.0)
    g2.const("k", 2.0)
    g2.add(Op.MUL, ["x", "one"], ["t"])
    g2.add(Op.ADD, ["t", "k"], ["out"])
    opt2, rep2 = passes.optimize_graph(g2, dtype=np.float32)
    assert rep2.identities == 1 and len(opt2.nodes) == 1
    got2 = run_reference(opt2, feeds, dtype=np.float32)
    want2 = run_reference(g2, feeds, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(got2.outputs["out"]),
                                  np.asarray(want2.outputs["out"]))
    assert (np.signbit(np.asarray(got2.outputs["out"]))
            == np.signbit(np.asarray(want2.outputs["out"])))


def test_float_shr_underflow_guard_matches_jax_alus():
    """alu_numpy (the reference engine's fire math AND the folder's
    compile-time evaluator) guards float SHR's exp2 underflow exactly
    like the jax `_alu`/`_alu_op` paths: exp2(-200) underflows float32
    to 0, and a/0 would fold to inf where the live engines produce a."""
    from repro.core.engine import alu_numpy
    a = np.float32(3.0)
    assert alu_numpy(Op.SHR, a, np.float32(-200.0), np.float32) == a
    g = Graph(name="shr_fold")
    g.const("a", 3.0)
    g.const("b", -200.0)
    g.add(Op.SHR, ["a", "b"], ["s"])
    g.add(Op.ADD, ["s", "x"], ["out"])
    opt, report = passes.optimize_graph(g, dtype=np.float32)
    assert report.folded == 1 and opt.consts["s"] == 3.0
    feeds = {"x": np.asarray([1.0, 2.0], np.float32)}
    want = DataflowEngine(g, dtype=np.float32, backend="xla",
                          block_cycles=4).run(feeds)
    for run in (run_reference(opt, feeds, dtype=np.float32),
                DataflowEngine(opt, dtype=np.float32, backend="xla",
                               block_cycles=4, optimize=True).run(feeds)):
        for arc, c in want.counts.items():
            assert run.counts[arc] == c
            np.testing.assert_array_equal(np.asarray(run.outputs[arc]),
                                          np.asarray(want.outputs[arc]))


def test_alu_numpy_matches_jax_alu_on_edge_operands():
    """alu_numpy (fold / reference fire math) and the jax `_alu_op`
    (specialized fire) are hand-synced copies of one formula table, and
    the float-SHR underflow drift shipped because no test compared them
    on edge operands.  Pin bit-for-bit parity across every value op x
    dtype on the historical drift points: zero divisors, signed zeros,
    shift over/underflow, extreme magnitudes — plus the integer edges
    (ISSUE 4): INT_MIN negation/abs wrap, DIV by -1 at INT_MIN,
    shift-by->=-width (clipped, both directions), and unsigned
    wraparound on a uint32 fabric."""
    import jax.numpy as jnp
    from repro.core.engine import _alu_op, alu_numpy
    cases = {
        np.int32: [-(2 ** 31), -(2 ** 31) + 1, -40, -2, -1, 0, 1, 5,
                   31, 32, 33, 40, 2 ** 31 - 1],
        np.uint32: [0, 1, 2, 5, 7, 31, 32, 40, 2 ** 31, 2 ** 32 - 1],
        np.float32: [-np.inf, -200.0, -1.5, -0.0, 0.0, 0.5, 1.0,
                     200.0, np.inf],
    }
    ops = [op for op in Op if op not in (Op.DMERGE, Op.NDMERGE)]
    for dt, vals in cases.items():
        A, B = np.meshgrid(np.asarray(vals, dt), np.asarray(vals, dt))
        a, b = A.ravel(), B.ravel()
        is_f = np.issubdtype(dt, np.floating)
        uview = np.dtype(f"u{np.dtype(dt).itemsize}")
        for op in ops:
            with np.errstate(all="ignore"):
                want = np.asarray(alu_numpy(op, a, b, dt), dt)
            got = np.asarray(
                _alu_op(op, jnp.asarray(a), jnp.asarray(b), dt)
            ).astype(dt, copy=False)
            nan = np.isnan(want) if is_f else np.zeros(want.shape, bool)
            assert (got.view(uview)[~nan]
                    == want.view(uview)[~nan]).all(), (op, dt)
            assert np.isnan(got[nan]).all(), (op, dt)


def test_alu_integer_edge_regressions_pin_exact_values():
    """The specific integer edges, asserted against their expected
    two's-complement results so a 'both drifted the same way' bug in
    the parity test above cannot hide them: INT_MIN // -1 wraps to
    INT_MIN (and never traps), shifts by >= width clip to 31, negative
    shift counts clip to 0, and uint32 SUB wraps."""
    import jax.numpy as jnp
    from repro.core.engine import _alu_op, alu_numpy
    INT_MIN = np.int32(-(2 ** 31))
    checks = [
        (Op.DIV, np.int32, INT_MIN, np.int32(-1), INT_MIN),
        (Op.DIV, np.int32, INT_MIN, np.int32(0), np.int32(0)),
        (Op.SUB, np.int32, np.int32(0), INT_MIN, INT_MIN),
        (Op.SHL, np.int32, np.int32(1), np.int32(40), INT_MIN),
        (Op.SHR, np.int32, INT_MIN, np.int32(40), np.int32(-1)),
        (Op.SHL, np.int32, np.int32(1), np.int32(-5), np.int32(1)),
        (Op.SUB, np.uint32, np.uint32(0), np.uint32(1),
         np.uint32(2 ** 32 - 1)),
        (Op.ADD, np.uint32, np.uint32(2 ** 32 - 1), np.uint32(2),
         np.uint32(1)),
        (Op.SHR, np.uint32, np.uint32(2 ** 32 - 1), np.uint32(31),
         np.uint32(1)),
    ]
    for op, dt, a, b, want in checks:
        with np.errstate(all="ignore"):
            got_np = np.asarray(alu_numpy(op, a, b, dt), dt).reshape(())
        got_jx = np.asarray(
            _alu_op(op, jnp.asarray(a), jnp.asarray(b), dt)
        ).astype(dt).reshape(())
        assert got_np == want, (op, dt, "alu_numpy")
        assert got_jx == want, (op, dt, "_alu_op")


def test_uint32_fabric_runs_bit_identical_across_engines():
    """Unsigned execution end to end, not just ALU formulas: a
    wraparound-heavy uint32 fabric drains identical results from the
    reference oracle and the xla engine (dense and specialized)."""
    g = Graph(name="u32")
    g.const("m1", 2 ** 32 - 1)               # UINT_MAX
    g.add(Op.ADD, ["x", "m1"], ["t"])        # x - 1 mod 2^32
    g.add(Op.SHR, ["t", "s"], ["z"])
    feeds = {"x": np.asarray([0, 1, 2 ** 31], np.uint32),
             "s": np.asarray([1, 31, 40], np.uint32)}
    want = run_reference(g, feeds, dtype=np.uint32)
    for opt in (False, True):
        eng = DataflowEngine(g, dtype=np.uint32, backend="xla",
                             block_cycles=4, optimize=opt)
        got = eng.run(feeds)
        assert got.counts == want.counts and got.cycles == want.cycles
        np.testing.assert_array_equal(
            np.asarray(got.outputs["z"], np.uint32),
            np.asarray(want.outputs["z"], np.uint32))


def test_optimize_graph_rejects_unknown_pass():
    with pytest.raises(ValueError, match="unknown passes"):
        passes.optimize_graph(Graph(), passes=("fold", "bogus"))
    with pytest.raises(ValueError, match="optimize"):
        compile_graph(library.vector_sum_graph(8).graph,
                      backend="xla", optimize="bogus")
    # plan-level specialization needs a plan: auto backends have none,
    # and silently measuring an unoptimized runner would be worse
    with pytest.raises(ValueError, match="engine backend"):
        compile_graph(library.vector_sum_graph(8).graph, optimize="spec")


def test_compile_graph_full_pipeline_reports():
    bench = _bench("fir")
    run = compile_graph(bench.graph, backend="xla", block_cycles=4,
                        optimize=True)
    assert run.report is not None and run.report.identities >= 1
    assert len(run.graph.nodes) < len(bench.graph.nodes)
    feeds = _feeds("fir", bench, 3, seed=2)
    _check_observables(run(feeds),
                       run_reference(bench.graph, feeds), "full")


# ---------------------------------------------------------------------------
# hypothesis property layer (CI; local runs without hypothesis skip it)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    @functools.lru_cache(maxsize=None)
    def _engines(name):
        bench = _bench(name)
        dt = np.dtype(bench.dtype)
        dense = DataflowEngine(bench.graph, dtype=dt, backend="xla",
                               block_cycles=4)
        spec = DataflowEngine(bench.graph, dtype=dt, backend="xla",
                              block_cycles=4, optimize=True)
        rewritten, _ = passes.optimize_graph(bench.graph, dtype=dt)
        full = DataflowEngine(rewritten, dtype=dt, backend="xla",
                              block_cycles=4, optimize=True)
        return bench, dense, spec, full

    @settings(max_examples=15, deadline=None)
    @given(name=st.sampled_from(sorted(library.BENCHES)),
           k=st.integers(min_value=1, max_value=6),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_property_optimized_runs_bit_identical(name, k, seed):
        bench, dense, spec, full = _engines(name)
        feeds = _feeds(name, bench, k, seed=seed)
        want = dense.run(feeds)
        _check_full(spec.run(feeds), want, (name, k, seed, "spec"))
        _check_observables(full.run(feeds), want,
                           (name, k, seed, "full"))

    @settings(max_examples=10, deadline=None)
    @given(c1=st.integers(min_value=-50, max_value=50),
           c2=st.integers(min_value=-50, max_value=50),
           xs=st.lists(st.integers(min_value=-99, max_value=99),
                       min_size=1, max_size=6))
    def test_property_folding_output_feeds(c1, c2, xs):
        """Folding nodes that feed outputs keeps observables for any
        constants and any gating stream."""
        g = Graph(name="prop_fold")
        g.const("c1", c1)
        g.const("c2", c2)
        g.add(Op.ADD, ["c1", "c2"], ["s"])
        g.add(Op.MUL, ["s", "x"], ["out"])
        opt, report = passes.optimize_graph(g)
        assert report.folded == 1 and opt.consts["s"] == c1 + c2
        feeds = {"x": np.asarray(xs, np.int32)}
        _check_observables(run_reference(opt, feeds),
                           run_reference(g, feeds), (c1, c2))
