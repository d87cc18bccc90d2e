"""Pallas lowering of static firing schedules (DESIGN.md §13).

Two entry points, on the lane layout of the dynamic kernels in
dataflow_fire.py (slots/streams on lanes, one row per arc, every node
known at trace time):

* :func:`make_sched_run` lowers the schedule context's straight-line
  scheduled program (prologue unrolled, each steady-state period fused
  into one ``fori_loop`` body) into a single ``pallas_call``: the whole
  run is one kernel, with no ready-mask anywhere.  Every stream of a
  call shares one schedule, so the feed pointers are scalars and each
  fed token is one dynamic-row load of a ``[n_in, L, streams]`` feed
  block.
* :func:`make_sched_slot_step` is the scheduled block step for the
  resumable slot API.  Every slot follows its own host-computed pattern
  sequence, so the per-cycle pattern id is looked up (outside the
  kernel) as a bitmask of which nodes fire and which feed/drain rows
  move; the kernel applies each node's ALU to its operand rows and
  commits the result where its bit is set.  Inactive slots ride pid 0
  (a no-op pattern) with ``fsel == -1`` gating the post-block register
  update, exactly like the dynamic kernels' clock gate.

Scalar int32 tokens only — the pallas backend's standing contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.graph import Op
from repro.kernels.dataflow_fire import (LANES, compiler_params, feed_window,
                                         from_lanes, interpret_mode, pick,
                                         slot_tiles, to_lanes)


def _alu(op, a, b):
    from repro.core.engine import _alu_op
    return _alu_op(Op(op), a, b, jnp.int32)


def _needs_b(op) -> bool:
    return Op(op) not in (Op.COPY, Op.NOT, Op.SINK, Op.BRANCH)


# ---------------------------------------------------------------------------
# run path: one kernel per schedule structure
# ---------------------------------------------------------------------------
def _sched_run_kernel(ctx, struct, reps_ref, fv_ref, ol_ref, oc_ref,
                      val_ref):
    """The straight-line scheduled program over one tile of 128 streams.
    ``val_ref`` is the [A2, 128] register file (values only — presence
    is implicit in the schedule); feed pointers are traced scalars."""
    reg = ctx.registry
    row = lambda a: pl.ds(int(a), 1)
    val_ref[...] = jnp.zeros(val_ref.shape, jnp.int32)
    for a, v in ctx.graph.consts.items():
        val_ref[row(ctx.p["aidx"][a]), :] = jnp.full((1, LANES), int(v),
                                                     jnp.int32)
    ol_ref[...] = jnp.zeros(ol_ref.shape, jnp.int32)
    oc_ref[...] = jnp.zeros(oc_ref.shape, jnp.int32)

    def apply(pat, ptrs):
        ptrs = list(ptrs)
        for r, a in zip(pat.fed.tolist(), pat.fed_arcs.tolist()):
            val_ref[row(a), :] = fv_ref[r, pl.ds(ptrs[r], 1), :]
            ptrs[r] = ptrs[r] + 1
        zs = []
        for op, i0, i1, out in pat.bundles:
            for x, y, o2 in zip(i0.tolist(), i1.tolist(),
                                out.reshape(-1, 2).tolist()):
                zs.append((o2, _alu(op, val_ref[row(x), :],
                                    val_ref[row(y), :])))
        for o2, z in zs:
            for o in o2:
                if o < ctx.A2:          # A2: the missing-output sentinel
                    val_ref[row(o), :] = z
        for r, a in zip(pat.drain.tolist(), pat.drain_arcs.tolist()):
            ol_ref[row(r), :] = val_ref[row(a), :]
            oc_ref[row(r), :] = oc_ref[row(r), :] + 1
        return tuple(ptrs)

    ptrs = (0,) * fv_ref.shape[0]       # static until the first loop
    r = 0
    for pids, dyn in struct:
        pats = [reg[pid] for pid in pids]
        if not dyn:
            for pat in pats:
                ptrs = apply(pat, ptrs)
        else:
            def body(_, ps, pats=pats):
                for pat in pats:
                    ps = apply(pat, ps)
                return ps
            ptrs = jax.lax.fori_loop(
                0, reps_ref[r], body,
                tuple(jnp.asarray(x, jnp.int32) for x in ptrs))
            r += 1


def make_sched_run(ctx, struct, batched: bool):
    """Pallas kernel for the scheduled program of one ``struct``:
    ``run(fv, reps) -> (out_last, out_count)``.

    fv[n_in, L] int32 (leading B axis when batched); reps int32[R]
    carries the traced fori_loop trip counts, so one kernel serves
    every feed-length tuple that shares the schedule structure.
    Compiled callables cache per operand shape."""
    from jax.experimental.pallas import tpu as pltpu
    n_out = max(ctx.out_arc.size, 1)
    a2_rows = -(-ctx.A2 // 8) * 8
    kernel = functools.partial(_sched_run_kernel, ctx, struct)

    @jax.jit
    def run(fv, reps):
        fvb = fv if batched else fv[None]
        B, n_in, L = fvb.shape
        m = -(-B // LANES)
        fvl = to_lanes(fvb, m).reshape(n_in, L, m * LANES)
        ol, oc = pl.pallas_call(
            kernel, grid=(m,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((n_in, L, LANES), lambda i: (0, 0, i))],
            out_specs=[pl.BlockSpec((n_out, LANES), lambda i: (0, i))] * 2,
            out_shape=[jax.ShapeDtypeStruct((n_out, m * LANES),
                                            jnp.int32)] * 2,
            scratch_shapes=[pltpu.VMEM((a2_rows, LANES), jnp.int32)],
            interpret=interpret_mode(),
            compiler_params=None if interpret_mode()
            else compiler_params((n_in * L + 2 * n_out) // 8 + 1, 8),
            name="dataflow_sched_run")(reps, fvl)
        ol, oc = ol[:, :B].T, oc[:, :B].T
        return (ol, oc) if batched else (ol[0], oc[0])
    return run


# ---------------------------------------------------------------------------
# slot path: per-slot pattern bits, K cycles per dispatch
# ---------------------------------------------------------------------------
def _sched_slot_kernel(sp, n_cycles, n_words, bits, win, val_i, ptr_i,
                       ol_i, oc_i, val, ptr, ol, oc):
    """K table-driven scheduled cycles for one tile of slots.  ``bits``
    [K, W] holds, per cycle and slot, the pattern's fire bits (plan node
    rows), then its feed-row bits, then its drain-row bits."""
    for r_in, r_out in ((val_i, val), (ptr_i, ptr), (ol_i, ol), (oc_i, oc)):
        r_out[...] = r_in[...]
    n_fire, n_feed = sp["n_nodes"], len(sp["in_arcs"])

    def cycle(c, _):
        words = [bits[c, w] for w in range(n_words)]

        def bit(i):
            w = words[i // 32]
            return ((w >> (i % 32)) & 1 if i % 32 else w & 1) != 0

        vc = {}

        def V(a):
            if a not in vc:
                vc[a] = val[a]
            return vc[a]
        # 1. feed: the pattern says which rows load their next token
        for r, a in enumerate(sp["in_arcs"]):
            fm = bit(n_fire + r)
            nxt = pick([win[r, j] for j in range(n_cycles)],
                       ptr[r] - ptr_i[r])
            vc[a] = jnp.where(fm, nxt, V(a))
            ptr[r] = ptr[r] + fm.astype(jnp.int32)
        # 2. fire: every ALU reads the post-feed snapshot; a node's
        # result lands on its output rows where its fire bit is set
        zs = [(n, outs, _alu(op, V(i0), V(i1) if _needs_b(op) else None))
              for n, op, i0, i1, outs in sp["nodes"]]
        new = {a: vc[a] for a in sp["in_arcs"]}
        for n, outs, z in zs:
            f = bit(n)
            for o in outs:
                new[o] = jnp.where(f, z, V(o))
        for a, x in new.items():
            val[a] = x
        # 3. drain: post-fire output registers
        for r, a in enumerate(sp["out_arcs"]):
            dm = bit(n_fire + n_feed + r)
            ol[r] = jnp.where(dm, new[a] if a in new else V(a), ol[r])
            oc[r] = oc[r] + dm.astype(jnp.int32)
        return 0

    jax.lax.fori_loop(0, n_cycles, cycle, 0)


def pattern_bits(ctx) -> np.ndarray:
    """[P, W] int32 bitmask per registered pattern (P padded to a power
    of two, like the xla slot tables, so registry growth rarely changes
    the operand shape)."""
    reg = ctx.registry
    n_nodes, n_feed = ctx.n_nodes, ctx.in_arc.size
    n_bits = n_nodes + n_feed + ctx.out_arc.size
    P = 1 << max(0, int(len(reg) - 1).bit_length())
    W = -(-max(n_bits, 1) // 32)
    flags = np.zeros((P, W * 32), np.uint64)
    for pat in reg:
        flags[pat.pid, pat.fire] = 1
        flags[pat.pid, n_nodes + pat.fed] = 1
        flags[pat.pid, n_nodes + n_feed + pat.drain] = 1
    words = (flags.reshape(P, W, 32) << np.arange(32, dtype=np.uint64)
             ).sum(axis=-1)
    return words.astype(np.uint32).view(np.int32)


def make_sched_slot_step(ctx, n_cycles: int):
    """Scheduled slot block step: each slot row executes ``n_cycles``
    table-driven scheduled cycles (its host-computed pid sequence) and
    lands on the pattern-exact post-block registers.

    Call signature (mirrors the xla vmapped stepper):
    (fv[B,n_in,L], pids[B,K], fsel[B], full[B,A2], val[B,A2],
    ptr[B,n_in], out_last[B,n_out], out_count[B,n_out], *tables)
    -> (full', val', ptr', out_last', out_count')."""
    p = ctx.p
    sp = dict(
        n_nodes=ctx.n_nodes,
        in_arcs=ctx.in_arc.tolist(), out_arcs=ctx.out_arc.tolist(),
        nodes=[(n, int(op), int(p["in_idx"][n, 0]), int(p["in_idx"][n, 1]),
                [int(o) for o in p["out_idx"][n] if o != p["EMPTY_PAD"]])
               for n, op in enumerate(p["opcode"])
               if Op(int(op)) != Op.SINK])
    sp["nodes"] = [x for x in sp["nodes"] if x[4]]
    n_in, n_out = ctx.ia_pad.size, ctx.oa_pad.size
    cached = {"len": -1, "bits": None}

    @jax.jit
    def core(bits_tab, t_full, fv, pids, fsel, full, val, ptr, ol, oc):
        B = full.shape[0]
        m, s = slot_tiles(B)
        W = bits_tab.shape[1]
        bits = to_lanes(bits_tab[pids], m)              # [K, W, m, 128]
        win = to_lanes(feed_window(fv, ptr, n_cycles), m)

        def spec(*lead):
            return pl.BlockSpec((*lead, s, LANES),
                                lambda i, k=len(lead): (0,) * k + (i, 0))
        st_rows = (ctx.A2, n_in, n_out, n_out)
        state = [to_lanes(x, m) for x in (val, ptr, ol, oc)]
        rows = n_cycles * (W + n_in) + 4 * sum(st_rows)
        res = pl.pallas_call(
            functools.partial(_sched_slot_kernel, sp, n_cycles, W),
            grid=(m // s,),
            in_specs=[spec(n_cycles, W), spec(n_in, n_cycles)]
            + [spec(r) for r in st_rows],
            out_specs=[spec(r) for r in st_rows],
            out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.int32)
                       for x in state],
            interpret=interpret_mode(),
            compiler_params=None if interpret_mode()
            else compiler_params(rows, s),
            name="dataflow_sched_slot")(bits, win, *state)
        val, ptr, ol, oc = (from_lanes(x, B) for x in res)
        full = jnp.where((fsel >= 0)[:, None],
                         t_full[jnp.maximum(fsel, 0)], full)
        return full, val, ptr, ol, oc

    def runner(fv, pids, fsel, full, val, ptr, ol, oc, *tabs):
        if cached["len"] != len(ctx.registry):
            cached["bits"] = jnp.asarray(pattern_bits(ctx))
            cached["len"] = len(ctx.registry)
        return core(cached["bits"], tabs[7], fv, pids, fsel, full, val,
                    ptr, ol, oc)
    runner.core = core
    return runner
