"""Expression-to-fabric frontend (ISSUE 4).

Acceptance contract: for every program here, the traced fabric is
bit-identical to a plain-numpy reference of the same expression —
last drained value and token count per output arc — across ALL three
backends (reference, xla, pallas) with ``optimize="full"`` (graph
rewrites + specialized plan).  The matrix includes a ``jnp.where``
select lowering and a const-heavy program whose PassReport shows the
PR 3 folding pass visibly shrinking the synthesized fabric.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import asm, library
from repro.core.compile import compile_fn, compile_graph
from repro.core.engine import DataflowEngine, run_reference
from repro.front import LoweringError, trace

BACKENDS = ["reference", "xla", "pallas"]
I32 = np.int32


# ---------------------------------------------------------------------------
# the acceptance program suite: (name, traced fn, numpy reference, streams)
# every reference computes in int32 so wraparound matches the fabric
# ---------------------------------------------------------------------------
def _i32(*vs):
    return [np.asarray(v, I32) for v in vs]


def _prog_where(x, y):
    return jnp.where(x > y, x - y, y - x)


def _ref_where(x, y):
    return np.where(x > y, x - y, y - x)


def _prog_horner(x):
    return ((2 * x + 3) * x - 7) * x + 5


def _ref_horner(x):
    return ((I32(2) * x + I32(3)) * x - I32(7)) * x + I32(5)


def _prog_saxpy(x, y):
    return 3 * x + y


def _prog_popc8(x):
    acc = (x >> 0) & 1
    for k in range(1, 8):
        acc = acc + ((x >> k) & 1)
    return acc


def _ref_popc8(x):
    acc = (x >> 0) & I32(1)
    for k in range(1, 8):
        acc = acc + ((x >> k) & I32(1))
    return acc


def _prog_clamp_relu(x):
    return jnp.clip(jnp.maximum(x, 0) * 3, 0, 100)


def _ref_clamp_relu(x):
    return np.clip(np.maximum(x, I32(0)) * I32(3), 0, 100)


def _prog_logic(x, y):
    return ((x ^ y) | (x & 3)) + (x > y)


def _ref_logic(x, y):
    return ((x ^ y) | (x & I32(3))) + (x > y).astype(I32)


def _prog_powsum(x):
    return x ** 3 + x ** 2 - x


def _ref_powsum(x):
    return x ** 2 * x + x ** 2 - x


def _prog_negabs(x, y):
    return -x + abs(y) * 2


def _ref_negabs(x, y):
    return -x + np.abs(y) * I32(2)


def _prog_minmax(x, y):
    return jnp.minimum(jnp.maximum(x, y) - jnp.minimum(x, y), 1000)


def _ref_minmax(x, y):
    return np.minimum(np.maximum(x, y) - np.minimum(x, y), I32(1000))


PROGRAMS = {
    # name: (fn, numpy ref, list of argument streams)
    "where_absdiff": (_prog_where, _ref_where,
                      _i32([5, 1, 7, -4, 0], [2, 9, 7, -4, 1])),
    "horner": (_prog_horner, _ref_horner, _i32([0, 1, -3, 12, 99])),
    "saxpy": (_prog_saxpy, lambda x, y: I32(3) * x + y,
              _i32([1, -2, 50, 0, 7], [10, 20, -30, 0, 1])),
    "popc8": (_prog_popc8, _ref_popc8, _i32([0, 1, 255, 170, 99])),
    "clamp_relu": (_prog_clamp_relu, _ref_clamp_relu,
                   _i32([-5, 2, 50, 7, -1])),
    "logic_mix": (_prog_logic, _ref_logic,
                  _i32([5, 0, -7, 31, 12], [3, 0, 7, -31, 12])),
    "powsum": (_prog_powsum, _ref_powsum, _i32([0, 2, -3, 9, 40])),
    "negabs": (_prog_negabs, _ref_negabs,
               _i32([4, -4, 0, 99, -2], [-3, 3, 0, -99, 2])),
    "minmax_span": (_prog_minmax, _ref_minmax,
                    _i32([9, -9, 0, 4, 2], [1, 9, 0, -4, 2])),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_traced_program_matches_numpy_reference(name, backend):
    fn, ref, streams = PROGRAMS[name]
    want = np.asarray(ref(*streams), I32)
    run = compile_fn(fn, *([I32] * len(streams)), backend=backend,
                     block_cycles=4, optimize="full")
    res = run(run.make_feeds(*streams))
    out = run.out_arcs[0]
    assert res.counts[out] == len(want), (name, backend)
    assert int(np.asarray(res.outputs[out])) == int(want[-1]), \
        (name, backend)


def test_traced_program_full_stream_bit_identical():
    """The auto backend (vmapped SSA) exposes every stream element, so
    the whole stream — not just the last drained token — is checked
    bit-for-bit against numpy for the select-free programs."""
    for name in ("horner", "saxpy", "popc8", "clamp_relu", "logic_mix",
                 "powsum", "negabs", "minmax_span"):
        fn, ref, streams = PROGRAMS[name]
        want = np.asarray(ref(*streams), I32)
        run = compile_fn(fn, *([I32] * len(streams)), backend="auto")
        got = run(run.make_feeds(*streams))
        if hasattr(got, "outputs"):         # select lowering -> cyclic
            continue
        np.testing.assert_array_equal(
            np.asarray(got[run.out_arcs[0]], I32), want, err_msg=name)


def test_where_lowering_consumes_both_sides_per_token():
    """The select schema must consume BOTH operands every firing (the
    untaken side rides a BRANCH into a SINK) — alternating predicates
    over a long stream would otherwise deadlock on stale tokens."""
    prog = trace(_prog_where, I32, I32, name="where")
    ops = [n.op.name for n in prog.nodes]
    assert ops.count("BRANCH") == 2 and ops.count("DMERGE") == 1
    assert ops.count("SINK") == 2
    x = np.asarray([5, 1, 7, -9, 0, 3, 3, 100], I32)
    y = np.asarray([2, 9, 7, 4, -1, 3, 4, -100], I32)
    want = _ref_where(x, y)
    for backend in BACKENDS:
        eng = DataflowEngine(prog, backend=backend, block_cycles=4)
        # per-token: feed one token at a time so every element of the
        # stream is observable, not just the last drained value
        for i in range(len(x)):
            r = eng.run(prog.make_feeds(x[i:i + 1], y[i:i + 1]))
            assert r.counts[prog.out_arc] == 1
            assert int(np.asarray(r.outputs[prog.out_arc])) == \
                int(want[i]), (backend, i)


def test_const_heavy_program_folds_visibly():
    """Const-bound arguments (the paper's sticky input buses) become
    genuine const-fed operators, and the PR 3 folding pass collapses
    them at compile time — asserted through the PassReport."""
    def poly(x, a, b):
        return (a * b + a) * x + (a - b) * x

    run = compile_fn(poly, I32, I32, I32, backend="xla",
                     block_cycles=4, optimize="full",
                     const_args={1: 6, 2: 7})
    rep = run.report
    assert rep is not None and rep.folded >= 2
    assert rep.nodes_after < rep.nodes_before
    assert len(run.graph.nodes) < len(run.traced.nodes)
    x = np.asarray([0, 1, -2, 10], I32)
    want = I32(6 * 7 + 6) * x + I32(6 - 7) * x
    res = run(run.make_feeds(x))
    out = run.out_arcs[0]
    assert res.counts[out] == 4
    assert int(np.asarray(res.outputs[out])) == int(want[-1])
    # the authored (unoptimized) fabric agrees with the folded one
    want_ref = run_reference(run.traced, run.make_feeds(x))
    assert want_ref.counts[out] == 4
    assert int(np.asarray(want_ref.outputs[out])) == int(want[-1])


def test_float_programs_reference_and_xla():
    """Float fabrics (pallas is int32-only) stay bit-identical to the
    engines' float ALU semantics, including -0.0 through neg."""
    def f(x, y):
        return 2.5 * x + y / 2.0 - jnp.maximum(-x, y)

    prog = trace(f, np.float32, np.float32)
    x = np.asarray([1.5, -2.0, 0.0, -0.0], np.float32)
    y = np.asarray([0.5, 0.25, -1.0, 4.0], np.float32)
    want = (np.float32(2.5) * x + y / np.float32(2.0)
            - np.maximum(-x, y)).astype(np.float32)
    feeds = prog.make_feeds(x, y)
    ref = run_reference(prog, feeds, dtype=np.float32)
    eng = DataflowEngine(prog, dtype=np.float32, backend="xla",
                         block_cycles=4, optimize=True)
    for res in (ref, eng.run(feeds)):
        assert res.counts[prog.out_arc] == 4
        got = np.asarray(res.outputs[prog.out_arc], np.float32)
        np.testing.assert_array_equal(got, want[-1])
    # neg of +0.0 must produce -0.0 (MUL by -1, not SUB from 0)
    pneg = trace(lambda x: -x, np.float32)
    rneg = run_reference(pneg, pneg.make_feeds(
        np.asarray([0.0], np.float32)), dtype=np.float32)
    assert np.signbit(np.asarray(rneg.outputs[pneg.out_arc]))


def test_float_consts_roundtrip_through_asm_signature():
    prog = trace(lambda x: 2.5 * x - 0.75, np.float32)
    text = asm.emit(prog)
    g2 = asm.parse(text)
    assert sorted(g2.consts.values()) == sorted(prog.consts.values())
    assert asm.emit(g2) == text         # emit is a fixed point


# ---------------------------------------------------------------------------
# traced regenerations of hand-assembled library benches
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hand,traced", [
    ("dot_prod", "dot_prod_traced"),
    ("pop_count", "pop_count_traced"),
    ("fir", "fir_traced"),
])
def test_traced_bench_matches_hand_built(hand, traced):
    hb = library.BENCHES[hand]()
    tb = library.BENCHES[traced]()
    rng = np.random.default_rng(11)
    fh = library.random_feeds(hand, hb, 4, rng)
    rng = np.random.default_rng(11)     # same arguments for both
    ft = library.random_feeds(traced, tb, 4, rng)
    want = run_reference(hb.graph, fh)
    got = run_reference(tb.graph, ft)
    assert got.counts[tb.out_arc] == want.counts[hb.out_arc] == 4
    assert int(np.asarray(got.outputs[tb.out_arc])) == \
        int(np.asarray(want.outputs[hb.out_arc]))


def test_traced_benches_run_every_backend_optimized():
    for name in ("horner", "saxpy", "relu_chain", "fir_traced"):
        bench = library.BENCHES[name]()
        feeds = library.random_feeds(name, bench, 3,
                                     np.random.default_rng(5))
        want = run_reference(bench.graph, feeds)
        for backend in ("xla", "pallas"):
            run = compile_graph(bench.graph, backend=backend,
                                block_cycles=4, optimize="full")
            got = run(feeds)
            for a, c in want.counts.items():
                assert got.counts[a] == c, (name, backend, a)
                if c:
                    assert int(np.asarray(got.outputs[a])) == \
                        int(np.asarray(want.outputs[a])), (name, backend)


def test_fir_traced_identity_splice_visible():
    """fir_traced's c0 == 1 tap is a MUL-by-one the identity pass
    splices out, mirroring the hand-built fir bench's contract."""
    bench = library.BENCHES["fir_traced"]()
    run = compile_graph(bench.graph, backend="xla", block_cycles=4,
                        optimize="full")
    assert run.report.identities >= 1
    assert len(run.graph.nodes) < len(bench.graph.nodes)


# ---------------------------------------------------------------------------
# serving integration: a traced program is just another asm signature
# ---------------------------------------------------------------------------
def test_traced_program_through_dataflow_server():
    from repro.serve.dataflow_server import (cached_engine,
                                             clear_engine_cache)
    from repro.serve.dataflow_server import DataflowServer
    clear_engine_cache()
    prog = trace(_prog_where, I32, I32, name="where_srv")
    prog2 = trace(_prog_where, I32, I32, name="where_srv")
    # structurally-equal traces share one compiled engine via the
    # signature cache
    e1 = cached_engine(prog, backend="xla", block_cycles=4)
    e2 = cached_engine(prog2, backend="xla", block_cycles=4)
    assert e1 is e2
    srv = DataflowServer(prog, slots=2, block_cycles=4, backend="xla")
    rng = np.random.default_rng(3)
    reqs = [prog.make_feeds(rng.integers(-99, 99, (k,)),
                            rng.integers(-99, 99, (k,)))
            for k in (1, 4, 2, 6, 3)]
    uids = [srv.submit(f) for f in reqs]
    got = {r.uid: r for r in srv.drain()}
    eng = DataflowEngine(prog, backend="xla", block_cycles=4)
    for uid, feeds in zip(uids, reqs):
        solo = eng.run(feeds)
        r = got[uid].engine
        assert r.counts == solo.counts and r.cycles == solo.cycles \
            and r.fired == solo.fired
        assert int(np.asarray(r.outputs[prog.out_arc])) == \
            int(np.asarray(solo.outputs[prog.out_arc]))
        assert got[uid].metrics.tokens_out == sum(solo.counts.values())


def test_dataflow_server_for_fn():
    from repro.serve.dataflow_server import DataflowServer
    srv = DataflowServer.for_fn(_prog_where, I32, I32, slots=2,
                                block_cycles=4, backend="xla")
    x = np.asarray([5, 1, 7], I32)
    y = np.asarray([2, 9, 7], I32)
    srv.submit(srv.make_feeds(x, y))
    (r,) = srv.drain()
    out = srv.traced.out_arc
    assert r.metrics.tokens_out == 3
    assert int(np.asarray(r.engine.outputs[out])) == \
        int(_ref_where(x, y)[-1])


# ---------------------------------------------------------------------------
# precise rejection + feed adapter behavior
# ---------------------------------------------------------------------------
def test_lowering_errors_name_the_primitive():
    with pytest.raises(LoweringError, match="'div'"):
        trace(lambda x, y: x // y, I32, I32)
    with pytest.raises(LoweringError, match="'sin'"):
        trace(lambda x: jnp.sin(x), np.float32)
    with pytest.raises(LoweringError, match="'rem'"):
        trace(lambda x, y: jnp.maximum(x % y, 0), I32, I32)
    with pytest.raises(LoweringError, match="'integer_pow'"):
        trace(lambda x: x ** 3, np.float32)
    with pytest.raises(LoweringError, match="shift_right_logical"):
        trace(lambda x, y: jax.lax.shift_right_logical(x, y), I32, I32)
    with pytest.raises(LoweringError, match="compile-time constant"):
        trace(lambda x: 5, I32)
    with pytest.raises(LoweringError, match="mixed aval dtypes"):
        trace(lambda x, y: x + y, I32, np.float32)
    with pytest.raises(LoweringError, match="shape"):
        trace(lambda x: x, jax.ShapeDtypeStruct((4,), I32))
    with pytest.raises(LoweringError, match="at least one aval"):
        trace(lambda: 1)
    with pytest.raises(LoweringError, match="const-bound"):
        trace(lambda x: x + 1, I32, const_args={0: 3})
    with pytest.raises(LoweringError, match="out of range"):
        trace(lambda x, y: x + y, I32, I32, const_args={7: 3})


def test_nested_jit_call_is_inlined():
    """A nested ``jax.jit`` traces to a ``jit`` call primitive; the
    frontend inlines its body like any other call."""
    inner = jax.jit(lambda a, b: jnp.maximum(a - b, 0) * 2)

    def f(x, y):
        return inner(x, y) + jax.jit(lambda z: z + 1)(y)

    prog = trace(f, I32, I32)
    xs, ys = _i32([7, 1, 9, -3], [2, 5, 9, 4])
    feeds = prog.make_feeds(xs, ys)
    want = np.maximum(xs - ys, 0) * 2 + ys + 1
    r = run_reference(prog, feeds)
    assert r.counts[prog.out_arc] == len(xs)
    assert int(np.asarray(r.outputs[prog.out_arc])) == int(want[-1])
    got = DataflowEngine(prog, backend="xla", block_cycles=4).run(feeds)
    assert int(np.asarray(got.outputs[prog.out_arc])) == int(want[-1])


def test_feed_adapter_contract():
    prog = trace(lambda x, y: x + y, I32, I32)
    with pytest.raises(ValueError, match="expected 2 argument streams"):
        prog.make_feeds([1, 2])
    with pytest.raises(ValueError, match="tokens"):
        prog.make_feeds([1, 2, 3], [1, 2])
    with pytest.raises(ValueError, match="shape"):
        prog.make_feeds(np.zeros((2, 2)), [1, 2])
    # scalars broadcast to the common stream length
    feeds = prog.make_feeds(7, [1, 2, 3])
    assert feeds["in0"].shape == (3,) and (feeds["in0"] == 7).all()
    # unused arguments take (and ignore) a stream slot
    p2 = trace(lambda x, y: x * 2, I32, I32)
    assert p2.arg_arcs[1] is None
    r = run_reference(p2, p2.make_feeds([1, 2], [9, 9]))
    assert int(np.asarray(r.outputs[p2.out_arc])) == 4


def test_multi_output_and_duplicate_outputs():
    prog = trace(lambda x, y: (x + y, x - y, x + y), I32, I32)
    assert len(prog.out_arcs) == 3
    assert len(set(prog.out_arcs)) == 3     # duplicates get own buses
    feeds = prog.make_feeds([5, 8], [2, 3])
    r = run_reference(prog, feeds)
    vals = [int(np.asarray(r.outputs[a])) for a in prog.out_arcs]
    assert vals == [11, 5, 11]
    assert all(r.counts[a] == 2 for a in prog.out_arcs)


def test_passthrough_output_keeps_arc_classes_disjoint():
    prog = trace(lambda x, y: x, I32, I32)
    prog.validate()
    assert set(prog.input_arcs()).isdisjoint(prog.output_arcs())
    r = run_reference(prog, prog.make_feeds([3, 1, 4], [0, 0, 0]))
    assert r.counts[prog.out_arc] == 3
    assert int(np.asarray(r.outputs[prog.out_arc])) == 4


def test_trace_is_deterministic():
    a = asm.emit(trace(_prog_clamp_relu, I32))
    b = asm.emit(trace(_prog_clamp_relu, I32))
    assert a == b
