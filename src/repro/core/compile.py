"""One compile pipeline for static dataflow graphs (DESIGN.md §10).

:func:`compile` is the single entry point.  It probes the graph's
capabilities (:class:`GraphTraits`: cyclic? control operators?
initial-token annotations?) and selects an executor — replacing the
scattered per-function op-set checks the stack grew across PRs 1–4:

* ``"dag"``      — lockstep SSA: nodes scheduled in topological order
  into a pure function, ``vmap`` over the token stream (the TPU
  analogue of the paper's spatial pipelining).  Legal only when
  ``traits.tokens_out_static`` — acyclic, control-free, init-free — so
  every stream element fires every node exactly once.
* ``"unrolled"`` — token-presence SSA: the engine cycle unrolled over
  arcs at trace time; arc registers become loop-carried SSA values and
  every fire/consume/produce a masked ``jnp.where``.  Handles cycles,
  BRANCH/NDMERGE/DMERGE, and initial tokens, bit-identical to
  :class:`repro.core.engine.DataflowEngine` (property-tested).
* ``"xla" | "pallas" | "reference"`` — the cycle-accurate block-fused
  engines (resumable slots, batching, serving).
* ``"auto"``     — ``"dag"`` when the traits allow it, else
  ``"unrolled"`` (the historical ``compile_graph`` dispatch).

``compile_fn`` goes one step earlier: it traces an ordinary scalar jax
program (loops included — the frontend lowers ``lax.while_loop`` /
``fori_loop`` / carry-only ``scan`` onto the paper's cyclic loop
schema) through :mod:`repro.front` and hands the synthesized fabric to
the same probe.  ``compile_graph`` / ``compile_cyclic`` remain as thin
deprecated wrappers over :func:`compile` and the unrolled executor.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alu import alu
from repro.core.engine import BACKENDS, EngineResult, pack_feeds
from repro.core.graph import Graph, Op


def _truthy1(v):
    return jnp.reshape(v, (-1,))[0] != 0


# ---------------------------------------------------------------------------
# Capability probe
# ---------------------------------------------------------------------------
_CONTROL_OPS = (Op.BRANCH, Op.NDMERGE, Op.DMERGE)


@dataclasses.dataclass(frozen=True)
class GraphTraits:
    """What a fabric demands of its executor (the :func:`compile` probe).

    cyclic       — the graph has feedback arcs (the paper's loop schema).
    control_ops  — names of token-routing operators present.  DMERGE
      counts: it consumes only its CHOSEN input token, so under
      data-dependent control the input streams advance unevenly — only
      token-presence execution reproduces that.
    has_inits    — initial-token annotations (one-shot pre-loaded arc
      registers, the loop back-edge delays of DESIGN.md §10).

    ``tokens_out_static`` is the lockstep property the "dag" executor
    needs: every stream element fires every node exactly once, so each
    output arc drains exactly one token per input element and the token
    counts are static in the stream length.
    """
    cyclic: bool
    control_ops: tuple[str, ...]
    has_inits: bool

    @classmethod
    def probe(cls, graph: Graph) -> "GraphTraits":
        return cls(
            cyclic=graph.is_cyclic(),
            control_ops=tuple(sorted({n.op.name for n in graph.nodes
                                      if n.op in _CONTROL_OPS})),
            has_inits=bool(graph.inits))

    @property
    def tokens_out_static(self) -> bool:
        return not (self.cyclic or self.control_ops or self.has_inits)

    def blockers(self) -> str:
        """The trait names that rule out lockstep execution."""
        why = []
        if self.cyclic:
            why.append("cyclic=True")
        if self.control_ops:
            why.append(f"control_ops={list(self.control_ops)}")
        if self.has_inits:
            why.append("has_inits=True")
        return ", ".join(why) or "none"


# ---------------------------------------------------------------------------
# DAG (lockstep SSA) executor
# ---------------------------------------------------------------------------
def compile_dag(graph: Graph, dtype=jnp.int32):
    """Return ``fn(inputs: dict) -> dict`` evaluating the fabric once.

    Supports primitive/decider/copy/dmerge/sink nodes.  ``branch`` and
    ``ndmerge`` (and initial-token annotations) need token-presence
    semantics — use the unrolled executor or an engine backend.
    Note ``dmerge`` here is a pure per-element select (both inputs
    advance together); that matches the engine only when every stream
    element fires every node once, which is why :func:`compile`'s
    auto dispatch sends DMERGE-bearing graphs to the unrolled executor.
    """
    order = graph.try_topo_order()
    if order is None:
        raise ValueError(f"{graph.name}: cyclic — use compile_cyclic")
    if graph.inits:
        raise ValueError(
            f"{graph.name}: initial-token annotations (has_inits) need "
            "token-presence semantics — use the unrolled executor")
    for n in graph.nodes:
        if n.op in (Op.BRANCH, Op.NDMERGE):
            raise ValueError(
                f"{graph.name}: {n.op.name} requires the cyclic backend")
    input_arcs = graph.input_arcs()
    output_arcs = graph.output_arcs()
    nodes = [graph.nodes[i] for i in order]
    consts = dict(graph.consts)

    def fn(inputs: Mapping[str, object]) -> dict:
        env = {a: jnp.asarray(v, dtype) for a, v in consts.items()}
        for a in input_arcs:
            env[a] = jnp.asarray(inputs[a], dtype)
        for n in nodes:
            a = env[n.inputs[0]]
            if n.op == Op.COPY:
                env[n.outputs[0]] = a
                env[n.outputs[1]] = a
            elif n.op == Op.SINK:
                pass
            elif n.op == Op.DMERGE:
                c = _truthy1(env[n.inputs[2]])
                env[n.outputs[0]] = jnp.where(c, a, env[n.inputs[1]])
            else:
                b = env[n.inputs[1]] if len(n.inputs) > 1 else a
                env[n.outputs[0]] = alu(n.op, a, b, dtype)
        return {a: env[a] for a in output_arcs}

    return fn


def compile_dag_stream(graph: Graph, dtype=jnp.int32):
    """vmap the DAG fabric over a token stream (throughput mode)."""
    fn = compile_dag(graph, dtype)
    return jax.jit(lambda feeds: jax.vmap(fn)(feeds))


# ---------------------------------------------------------------------------
# Unrolled (token-presence SSA) executor
# ---------------------------------------------------------------------------
def compile_cyclic(graph: Graph, token_shape=(), dtype=jnp.int32,
                   max_cycles: int = 100_000):
    """Return ``fn(feeds: dict[str, [k,*ts] stream]) -> EngineResult``.

    This is the "unrolled" executor of :func:`compile` (kept under its
    historical name as a deprecated public entry point — new code
    should call ``compile(graph, backend="unrolled")``)."""
    graph.validate()
    ts = tuple(token_shape)
    dtype = jnp.dtype(dtype)
    arcs = graph.arcs
    input_arcs = graph.input_arcs()
    output_arcs = graph.output_arcs()
    consts = dict(graph.consts)
    inits = dict(graph.inits)
    nodes = list(graph.nodes)

    def run(feeds: Mapping[str, object], max_cycles: int = max_cycles):
        fv, fl = pack_feeds(input_arcs, feeds, ts, dtype)
        out_last, out_count, cycles, fired = _compiled(
            jnp.asarray(fv), jnp.asarray(fl), max_cycles)
        return EngineResult(
            outputs={a: out_last[i] for i, a in enumerate(output_arcs)},
            counts={a: int(out_count[i]) for i, a in enumerate(output_arcs)},
            cycles=int(cycles), fired=int(fired))

    @functools.partial(jax.jit, static_argnums=(2,))
    def _compiled(feed_vals, feed_len, max_cycles):
        zero = jnp.zeros(ts, dtype)
        full0 = {a: jnp.bool_(a in consts or a in inits) for a in arcs}
        val0 = {a: (jnp.asarray(np.broadcast_to(consts[a], ts), dtype)
                    if a in consts else
                    jnp.asarray(np.broadcast_to(inits[a], ts), dtype)
                    if a in inits else zero) for a in arcs}
        state0 = dict(
            full=full0, val=val0,
            ptr=jnp.zeros((max(n_in_ := len(input_arcs), 1),), jnp.int32),
            out_last=[zero] * max(len(output_arcs), 1),
            out_count=jnp.zeros((max(len(output_arcs), 1),), jnp.int32),
            cycles=jnp.int32(0), fired=jnp.int32(0),
            progress=jnp.bool_(True))

        def cycle(s):
            full, val = dict(s["full"]), dict(s["val"])
            progress = jnp.bool_(False)
            # 1. strobe inputs
            ptr = s["ptr"]
            for k, a in enumerate(input_arcs):
                can = (~full[a]) & (ptr[k] < feed_len[k])
                nxt = jax.lax.dynamic_index_in_dim(
                    feed_vals[k], jnp.clip(ptr[k], 0, feed_vals.shape[1] - 1),
                    keepdims=False)
                val[a] = jnp.where(can, nxt, val[a])
                full[a] = full[a] | can
                ptr = ptr.at[k].add(can.astype(jnp.int32))
                progress = progress | can
            # 2. fire all ready nodes against the cycle-start snapshot
            sfull, sval = dict(full), dict(val)
            consumed = {a: jnp.bool_(False) for a in arcs}
            produced = {a: jnp.bool_(False) for a in arcs}
            pval = dict(sval)
            n_fired = jnp.int32(0)
            for n in nodes:
                i, o = n.inputs, n.outputs
                a_v = sval[i[0]]
                if n.op == Op.NDMERGE:
                    rdy = (sfull[i[0]] | sfull[i[1]]) & ~sfull[o[0]]
                    pick0 = sfull[i[0]]
                    consumed[i[0]] |= rdy & pick0
                    consumed[i[1]] |= rdy & ~pick0
                    produced[o[0]] |= rdy
                    pval[o[0]] = jnp.where(
                        rdy, jnp.where(pick0, a_v, sval[i[1]]), pval[o[0]])
                elif n.op == Op.DMERGE:
                    c = _truthy1(sval[i[2]])
                    rdy = (sfull[i[2]]
                           & jnp.where(c, sfull[i[0]], sfull[i[1]])
                           & ~sfull[o[0]])
                    consumed[i[0]] |= rdy & c
                    consumed[i[1]] |= rdy & ~c
                    consumed[i[2]] |= rdy
                    produced[o[0]] |= rdy
                    pval[o[0]] = jnp.where(
                        rdy, jnp.where(c, a_v, sval[i[1]]), pval[o[0]])
                elif n.op == Op.BRANCH:
                    c = _truthy1(sval[i[1]])
                    rdy = (sfull[i[0]] & sfull[i[1]]
                           & jnp.where(c, ~sfull[o[0]], ~sfull[o[1]]))
                    consumed[i[0]] |= rdy
                    consumed[i[1]] |= rdy
                    produced[o[0]] |= rdy & c
                    produced[o[1]] |= rdy & ~c
                    pval[o[0]] = jnp.where(rdy & c, a_v, pval[o[0]])
                    pval[o[1]] = jnp.where(rdy & ~c, a_v, pval[o[1]])
                else:
                    rdy = functools.reduce(
                        jnp.logical_and, [sfull[x] for x in i],
                        jnp.bool_(True))
                    for x in o:
                        rdy = rdy & ~sfull[x]
                    for x in i:
                        consumed[x] |= rdy
                    if n.op == Op.COPY:
                        z = a_v
                    elif n.op == Op.SINK:
                        z = a_v
                    else:
                        z = alu(n.op, a_v,
                                sval[i[1]] if len(i) > 1 else a_v, dtype)
                    for x in o:
                        produced[x] |= rdy
                        pval[x] = jnp.where(rdy, z, pval[x])
                n_fired = n_fired + rdy.astype(jnp.int32)
            for a in arcs:
                if a in consts:
                    full[a] = jnp.bool_(True)
                else:
                    full[a] = (sfull[a] & ~consumed[a]) | produced[a]
                val[a] = jnp.where(produced[a], pval[a], sval[a])
            progress = progress | (n_fired > 0)
            # 3. drain outputs
            out_last = list(s["out_last"])
            out_count = s["out_count"]
            for k, a in enumerate(output_arcs):
                got = full[a]
                out_last[k] = jnp.where(got, val[a], out_last[k])
                out_count = out_count.at[k].add(got.astype(jnp.int32))
                full[a] = jnp.bool_(False)
                progress = progress | got
            return dict(full=full, val=val, ptr=ptr, out_last=out_last,
                        out_count=out_count, cycles=s["cycles"] + 1,
                        fired=s["fired"] + n_fired, progress=progress)

        s = jax.lax.while_loop(
            lambda s: s["progress"] & (s["cycles"] < max_cycles),
            cycle, state0)
        return (jnp.stack(s["out_last"]), s["out_count"], s["cycles"],
                s["fired"])

    return run


OPTIMIZE_LEVELS = (False, "spec", "full", True, "sched")
BACKENDS_NOTE = "xla | pallas | reference"
EXECUTORS = ("auto", "dag", "unrolled", *BACKENDS)


def compile(graph: Graph, token_shape=(), dtype=jnp.int32,     # noqa: A001
            max_cycles: int = 100_000, backend: str = "auto",
            block_cycles: int = 16, optimize=False,
            profile: bool = False, partition=None):
    """THE compile pipeline: probe traits, pick a legal executor +
    optimize level, return ``run(feeds) -> EngineResult`` (or the
    vmapped stream fn for the "dag" executor).

    backend:
      * ``"auto"``     — ``"dag"`` when ``GraphTraits.tokens_out_static``
        holds, else ``"unrolled"`` (the historical shape-directed
        dispatch, now trait-driven);
      * ``"dag"``      — lockstep stream-vmapped SSA
        (``compile_dag_stream``).  Raises, naming the blocking traits,
        for any graph that needs token-presence semantics — asking for
        lockstep on such a fabric would silently compute wrong token
        counts, not a slower right answer;
      * ``"unrolled"`` — trace-time unrolled token-presence SSA
        (``compile_cyclic``): cycles, control ops, initial tokens;
      * any :data:`repro.core.engine.BACKENDS` name — a cycle-accurate
        block-fused engine callable (plus ``.engine`` exposing the
        resumable slot API and ``run_batch``).

    optimize selects the compiler pipeline (DESIGN.md §8):
      * ``False``  — run the graph exactly as authored;
      * ``"spec"`` — opcode-class-specialized plan only: a pure layout
        permutation, every EngineResult field bit-identical to the
        unoptimized engine.  Engine backends only (the SSA executors
        have no plan to specialize);
      * ``True`` / ``"full"`` — graph rewrite passes (region-scoped
        constant folding, identity elimination, DCE;
        :func:`repro.core.passes.optimize_graph` — loop regions and
        their timing are left untouched) *then* the specialized plan
        where a plan exists.  For fabrics that quiesce the surviving
        output arcs drain bit-identical values and token counts while
        ``cycles``/``fired`` may shrink;
      * ``"sched"`` — everything ``"full"`` does, plus static firing
        schedules (DESIGN.md §13): when the rewritten graph is
        statically schedulable (``GraphTraits.tokens_out_static``) the
        engine compiles the per-cycle fire sets out of the run loop —
        no ready-mask reduction — and falls back to the dynamic engine
        otherwise (cyclic / control-bearing fabrics, §10).  Engine
        backends only, bit-identical results either way.

    profile=True turns on the DESIGN.md §12 fabric counters: every
    EngineResult carries ``node_fires`` and a
    :class:`repro.obs.FabricProfile`.  Engine backends only — the SSA
    executors have no fabric to count, so asking is an error, not a
    silent no-op.

    partition shards the fabric across regions (DESIGN.md §14):
      * ``None``   — single fabric (default);
      * ``int P``  — :func:`repro.core.partition.partition_graph` splits
        the (post-rewrite) graph into P cost-balanced regions, never
        cutting a loop cycle;
      * ``"auto"`` — :func:`repro.core.partition.auto_partition` picks P
        from the device count and graph size;
      * a :class:`repro.core.partition.Partition` — used as given
        (validated).
    A resolved P>1 partition needs a cycle-accurate engine: with
    ``backend="auto"`` the probe routes to the ``"xla"`` engine instead
    of the SSA executors; asking for ``"dag"``/``"unrolled"`` raises.
    Execution stays bit-identical to the single-fabric engine in every
    EngineResult field.  P=1 (or an ``"auto"`` resolution of 1) is a
    pass-through to the ordinary engine.

    The returned callable exposes the (possibly rewritten) graph as
    ``.graph``, the rewrite report as ``.report`` (None when no
    rewrites ran), the capability probe as ``.traits``, and the
    resolved partition (or None) as ``.partition``.
    """
    if block_cycles < 1:
        raise ValueError(
            f"block_cycles must be >= 1, got {block_cycles}")
    if optimize not in OPTIMIZE_LEVELS:
        raise ValueError(f"optimize {optimize!r} not in {OPTIMIZE_LEVELS}")
    if backend not in EXECUTORS:
        raise ValueError(f"backend {backend!r} not in {EXECUTORS}")
    if optimize in ("spec", "sched") and backend in ("auto", "dag",
                                                     "unrolled"):
        # specialization/scheduling is plan-level; the SSA executors
        # have no plan, so either would silently measure an
        # unoptimized runner
        raise ValueError(
            f'optimize={optimize!r} needs an engine backend '
            f'({BACKENDS_NOTE}); backend={backend!r} only supports the '
            'rewrite pipeline (optimize="full"/True)')
    if profile and backend not in BACKENDS and not (
            backend == "auto" and partition is not None):
        # (auto + partition defers: a resolved P>1 routes to the engine)
        raise ValueError(
            f"profile=True needs an engine backend ({BACKENDS_NOTE}); "
            f"backend={backend!r} runs SSA semantics with no fabric "
            "cycles to count")
    report = None
    if optimize in (True, "full", "sched"):
        from repro.core import passes
        graph, report = passes.optimize_graph(graph, dtype=np.dtype(
            str(jnp.dtype(dtype))))
    traits = GraphTraits.probe(graph)
    part = None
    if partition is not None:
        # resolve against the post-rewrite graph: node indices in the
        # assignment must name the fabric that actually runs
        from repro.core.partition import resolve_partition
        part = resolve_partition(graph, partition)
    if part is not None and part.P > 1:
        if backend in ("dag", "unrolled"):
            raise ValueError(
                f"{graph.name}: partition={partition!r} needs a "
                f"cycle-accurate engine backend ({BACKENDS_NOTE}); the "
                f"{backend!r} SSA executor has no fabric to shard")
        if backend == "auto":
            backend = "xla"
    if backend == "auto":
        backend = "dag" if traits.tokens_out_static else "unrolled"
        if profile and backend not in BACKENDS:
            # the deferred check above: partition resolved to P=1, so
            # auto landed on an SSA executor after all
            raise ValueError(
                f"profile=True needs an engine backend ({BACKENDS_NOTE});"
                f" backend={backend!r} runs SSA semantics with no fabric "
                "cycles to count")
    if backend == "dag" and not traits.tokens_out_static:
        raise ValueError(
            f"{graph.name}: backend='dag' runs lockstep SSA semantics "
            f"(one firing per node per stream element), but the "
            f"GraphTraits probe found {traits.blockers()} — these need "
            f"token-presence execution: backend='unrolled' or an "
            f"engine backend ({BACKENDS_NOTE})")
    if backend in BACKENDS:
        from repro.core.engine import DataflowEngine
        eng = DataflowEngine(graph, token_shape, dtype, max_cycles,
                             backend=backend, block_cycles=block_cycles,
                             optimize=optimize is not False,
                             profile=profile,
                             schedule="auto" if optimize == "sched"
                             else False, partition=part)
        run = lambda feeds, max_cycles=None: eng.run(feeds, max_cycles)
        run.engine = eng
    elif backend == "unrolled":
        # DMERGE joins BRANCH/NDMERGE in needing this executor:
        # compile_dag's DMERGE is a pure per-element select (both input
        # streams advance in lockstep), but the engine's DMERGE
        # consumes only the CHOSEN input token, so the streams advance
        # unevenly under data-dependent control — only token-presence
        # execution reproduces that
        run = compile_cyclic(graph, token_shape, dtype, max_cycles)
    else:
        fn = compile_dag_stream(graph, dtype)
        run = lambda feeds: fn(feeds)   # jit fns reject new attributes
    run.graph = graph
    run.report = report
    run.traits = traits
    run.partition = part
    return run


def compile_graph(graph: Graph, token_shape=(), dtype=jnp.int32,
                  max_cycles: int = 100_000, backend: str = "auto",
                  block_cycles: int = 16, optimize=False,
                  profile: bool = False, partition=None):
    """Deprecated name for :func:`compile` (kept as a thin wrapper —
    the historical PR 1–4 entry point).  New code should call
    ``compile`` directly."""
    return compile(graph, token_shape, dtype, max_cycles, backend,
                   block_cycles, optimize, profile, partition)


def compile_fn(fn, *avals, backend: str = "xla", block_cycles: int = 16,
               optimize=False, max_cycles: int = 100_000,
               name: str | None = None, const_args: dict | None = None,
               profile: bool = False):
    """Trace a scalar jax program (:func:`repro.front.trace`) and hand
    the synthesized fabric to :func:`compile` in one step.

    The fabric is routed through the :class:`GraphTraits` probe like
    any other graph, so a traced program that needs token-presence
    semantics (loops, ``jnp.where`` control, initial tokens) either
    gets an executor that provides them (the default ``backend="xla"``
    engine and ``"auto"`` both do) or a precise error naming the
    blocking trait — never a silently-lockstep compilation.

    Returns the executor callable with the frontend bookkeeping
    attached: ``run.make_feeds(*streams)`` is the positional feed
    adapter, ``run.out_arcs`` the result arcs in return order,
    ``run.traced`` the :class:`~repro.front.TracedProgram` as authored
    (``run.graph`` is the post-rewrite fabric when ``optimize`` folds
    it).  The execution dtype is the avals' common dtype::

        run = compile_fn(lambda x, y: jnp.where(x > y, x - y, y - x),
                         np.int32, np.int32,
                         backend="pallas", optimize="full")
        res = run(run.make_feeds([5, 1], [2, 9]))
        res.outputs[run.out_arcs[0]]        # -> 8 (last token)
    """
    from repro.front import trace
    prog = trace(fn, *avals, name=name, const_args=const_args)
    run = compile(prog, token_shape=(),
                  dtype=jnp.dtype(str(prog.dtype)),
                  max_cycles=max_cycles, backend=backend,
                  block_cycles=block_cycles, optimize=optimize,
                  profile=profile)
    run.traced = prog
    run.make_feeds = prog.make_feeds
    run.out_arcs = list(prog.out_arcs)
    return run
