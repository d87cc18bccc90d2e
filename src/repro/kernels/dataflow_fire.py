"""Static-dataflow engine cycles ("fire blocks"): the Pallas kernel and
its pure-jnp mirror.

The paper's FPGA executes all ready operators concurrently; on a TPU a
cycle is one vectorized pass, and a block of K cycles runs in one
dispatch.  The environment runs inside the block: input arcs are
strobed from per-arc feed streams, output arcs drain into last-value +
token-count accumulators.  Quiescence is only observable at block
granularity — a block reports the relative cycle of its last progress
(``last_prog``), and the host stops when a block's tail goes idle (idle
is absorbing: no feed, no fire, no drain can re-arm without one of the
others).

Two implementations of the same block:

* :func:`_block_body` — pure jnp over flat tables (``opcode[N2]``,
  ``in_idx[N2,3]``, ``out_idx[N2,2]``, arc adjacency, environment
  maps).  Gather-only: node-side arrays compute readiness and results,
  then each arc pulls its next state from its unique producer/consumer
  (the paper's one-sender/one-receiver rule).  The xla backend vmaps it
  over slots.
* :func:`fire_block_batched_pallas` — the Pallas kernel on the TPU lane
  layout (one row per arc, slots on lanes; see the section below), with
  every node emitted as static code.  :func:`fire_block_pallas` is the
  same kernel with one live slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.graph import Op


def _ready_and_z(opcode, in_idx, out_idx, full, val, class_slices=None):
    """Vectorized firing rule of :func:`_block_body`.

    class_slices — static ``((opcode, start, stop), ...)`` from an
    opcode-specialized plan (DESIGN.md §8).  When given, the node table
    is permuted so equal opcodes are contiguous and the rule unrolls a
    static loop over only the classes present: each bucket computes its
    exact ALU result on its slice instead of the dense ~20-way
    ``where``-chain, and the shift/div guards are only traced for
    SHL/SHR/DIV buckets.  Bit-identical to the dense rule."""
    if class_slices is not None:
        return _ready_and_z_spec(class_slices, in_idx, out_idx, full, val)
    inf = full[in_idx] > 0                    # [N,3]
    oute = full[out_idx] == 0                 # [N,2]
    a = val[in_idx[:, 0]]
    b = val[in_idx[:, 1]]
    c = val[in_idx[:, 2]]
    all_in = inf.all(axis=1)
    all_out = oute.all(axis=1)

    is_nd = opcode == int(Op.NDMERGE)
    is_dm = opcode == int(Op.DMERGE)
    is_br = opcode == int(Op.BRANCH)
    ctrl3 = c != 0
    ctrl2 = b != 0

    dm_chosen = jnp.where(ctrl3, inf[:, 0], inf[:, 1])
    ready = all_in & all_out
    ready = jnp.where(is_nd, (inf[:, 0] | inf[:, 1]) & all_out, ready)
    ready = jnp.where(is_dm, inf[:, 2] & dm_chosen & all_out, ready)
    ready = jnp.where(is_br, inf[:, 0] & inf[:, 1] &
                      jnp.where(ctrl2, oute[:, 0], oute[:, 1]), ready)

    bs = jnp.clip(b, 0, 31)
    safe_b = jnp.where(b == 0, 1, b)
    zs = {
        Op.ADD: a + b, Op.SUB: a - b, Op.MUL: a * b,
        Op.DIV: jnp.where(b == 0, 0, a // safe_b),
        Op.AND: a & b, Op.OR: a | b, Op.XOR: a ^ b,
        Op.MAX: jnp.maximum(a, b), Op.MIN: jnp.minimum(a, b),
        Op.SHL: a << bs, Op.SHR: a >> bs,
        Op.NOT: (a == 0).astype(a.dtype),
        Op.IFGT: (a > b).astype(a.dtype), Op.IFGE: (a >= b).astype(a.dtype),
        Op.IFLT: (a < b).astype(a.dtype), Op.IFLE: (a <= b).astype(a.dtype),
        Op.IFEQ: (a == b).astype(a.dtype),
        Op.IFDF: (a != b).astype(a.dtype),
        Op.NDMERGE: jnp.where(inf[:, 0], a, b),
        Op.DMERGE: jnp.where(ctrl3, a, b),
    }
    z = a
    for op, r in zs.items():
        z = jnp.where(opcode == int(op), r, z)

    # per-slot consume/produce masks
    nd_pick = jnp.stack([inf[:, 0], ~inf[:, 0],
                         jnp.zeros_like(inf[:, 0])], 1)
    dm_pick = jnp.stack([ctrl3, ~ctrl3, jnp.ones_like(ctrl3)], 1)
    consume = jnp.ones_like(inf)
    consume = jnp.where(is_nd[:, None], nd_pick, consume)
    consume = jnp.where(is_dm[:, None], dm_pick, consume)
    consume &= ready[:, None]
    br_pick = jnp.stack([ctrl2, ~ctrl2], 1)
    produce = jnp.ones_like(oute)
    produce = jnp.where(is_br[:, None], br_pick, produce)
    produce &= ready[:, None]
    return ready, z, consume, produce


_CTRL_OPS = (int(Op.NDMERGE), int(Op.DMERGE), int(Op.BRANCH))


def _ready_and_z_spec(class_slices, in_idx, out_idx, full, val):
    """Opcode-class-specialized firing rule (scalar int32 fabric).
    Control-free fabrics keep uniform ready/consume/produce masks as
    whole-array ops; only the ALU result is bucketed."""
    from repro.core.engine import _alu_op
    inf = full[in_idx] > 0                    # [N,3]
    oute = full[out_idx] == 0                 # [N,2]
    a = val[in_idx[:, 0]]
    b = val[in_idx[:, 1]]
    all_in = inf.all(axis=1)
    all_out = oute.all(axis=1)
    base = all_in & all_out
    if not any(op in _CTRL_OPS for op, _, _ in class_slices):
        z_p = [_alu_op(Op(op), a[lo:hi], b[lo:hi], jnp.int32)
               for op, lo, hi in class_slices]
        z = z_p[0] if len(z_p) == 1 else jnp.concatenate(z_p)
        return (base, z, base[:, None] & jnp.ones_like(inf),
                base[:, None] & jnp.ones_like(oute))
    r_p, z_p, c_p, p_p = [], [], [], []
    for opi, lo, hi in class_slices:
        op = Op(opi)
        ak, bk = a[lo:hi], b[lo:hi]
        infk, outek = inf[lo:hi], oute[lo:hi]
        if op == Op.NDMERGE:
            rk = (infk[:, 0] | infk[:, 1]) & all_out[lo:hi]
            zk = jnp.where(infk[:, 0], ak, bk)
            ck = rk[:, None] & jnp.stack(
                [infk[:, 0], ~infk[:, 0], jnp.zeros_like(infk[:, 0])], 1)
            pk = rk[:, None] & jnp.ones_like(outek)
        elif op == Op.DMERGE:
            c3 = val[in_idx[lo:hi, 2]] != 0
            rk = (infk[:, 2] & jnp.where(c3, infk[:, 0], infk[:, 1])
                  & all_out[lo:hi])
            zk = jnp.where(c3, ak, bk)
            ck = rk[:, None] & jnp.stack([c3, ~c3, jnp.ones_like(c3)], 1)
            pk = rk[:, None] & jnp.ones_like(outek)
        elif op == Op.BRANCH:
            c2 = bk != 0
            rk = (infk[:, 0] & infk[:, 1]
                  & jnp.where(c2, outek[:, 0], outek[:, 1]))
            zk = ak
            ck = rk[:, None] & jnp.ones_like(infk)
            pk = rk[:, None] & jnp.stack([c2, ~c2], 1)
        else:
            rk = base[lo:hi]
            zk = _alu_op(op, ak, bk, jnp.int32)
            ck = rk[:, None] & jnp.ones_like(infk)
            pk = rk[:, None] & jnp.ones_like(outek)
        r_p.append(rk)
        z_p.append(zk)
        c_p.append(ck)
        p_p.append(pk)
    return (jnp.concatenate(r_p), jnp.concatenate(z_p),
            jnp.concatenate(c_p), jnp.concatenate(p_p))


def _fire_parts(opcode, in_idx, out_idx, prod_node, prod_slot, cons_node,
                cons_slot, const_mask, full, val, class_slices=None):
    """One fire step: (full', val', per-node ``ready``)."""
    ready, z, consume, produce = _ready_and_z(opcode, in_idx, out_idx,
                                              full, val, class_slices)
    # arc-side gather (single producer / single consumer per channel)
    produced = produce[prod_node, prod_slot]
    consumed = consume[cons_node, cons_slot]
    new_full = ((full > 0) & ~consumed) | produced
    new_full = new_full | (const_mask > 0)
    new_val = jnp.where(produced, z[prod_node], val)
    return new_full.astype(full.dtype), new_val, ready


def plan_arrays(graph, optimize: bool = False):
    """Static numpy tables incl. arc adjacency (dummy node N = never
    ready; dummy slots pad).  With ``optimize=True`` the node table is
    opcode-bucketed (see ``_plan``) and ``class_slices`` records the
    static per-class ranges — the dummy node rides as a trailing
    one-row SINK bucket so the specialized rule covers all N+1 rows."""
    import numpy as np
    from repro.core.engine import _plan
    p = _plan(graph, optimize=optimize)
    A2 = p["A"] + 2
    N = len(graph.nodes)
    opcode = np.concatenate([p["opcode"], [int(Op.SINK)]]).astype(np.int32)
    in_idx = np.concatenate(
        [p["in_idx"], [[p["EMPTY_PAD"]] * 3]]).astype(np.int32)
    out_idx = np.concatenate(
        [p["out_idx"], [[p["EMPTY_PAD"]] * 2]]).astype(np.int32)
    prod_node = np.full((A2,), N, np.int32)
    prod_slot = np.zeros((A2,), np.int32)
    cons_node = np.full((A2,), N, np.int32)
    cons_slot = np.zeros((A2,), np.int32)
    node_row = p["node_inv"]    # original node index -> plan row
    for i, n in enumerate(graph.nodes):
        for s, arc in enumerate(n.outputs):
            prod_node[p["aidx"][arc]] = node_row[i]
            prod_slot[p["aidx"][arc]] = s
        for s, arc in enumerate(n.inputs):
            if arc not in graph.consts:      # consts are never consumed
                cons_node[p["aidx"][arc]] = node_row[i]
                cons_slot[p["aidx"][arc]] = s
    const_mask = p["const_mask"].astype(np.int32)
    class_slices = None
    if p["class_slices"] is not None:
        class_slices = (*p["class_slices"], (int(Op.SINK), N, N + 1))
    return dict(opcode=opcode, in_idx=in_idx, out_idx=out_idx,
                prod_node=prod_node, prod_slot=prod_slot,
                cons_node=cons_node, cons_slot=cons_slot,
                const_mask=const_mask, plan=p, class_slices=class_slices)


# ---------------------------------------------------------------------------
# Block-fused execution: K cycles + environment per pallas_call
# ---------------------------------------------------------------------------
_TABLE_KEYS = ("opcode", "in_idx", "out_idx", "prod_node", "prod_slot",
               "cons_node", "cons_slot", "const_mask", "env_row",
               "in_arc_idx", "out_arc_idx", "out_mask")


def block_plan_arrays(graph, optimize: bool = False):
    """plan_arrays + environment maps for in-kernel feed/drain.

    env_row[A2]     row into the feed table for input arcs, n_in (a pad
                    row with feed_len 0) otherwise — makes the input
                    strobe a pure gather.
    in_arc_idx[n_in]  arc slot of each feed row (EMPTY_PAD pad rows).
    out_arc_idx[n_out] arc slot of each output accumulator row.
    out_mask[A2]    1 on output arcs (drained unconditionally each cycle).
    n_in/n_out are padded to at least 1 so the kernel never sees a
    zero-length axis.
    """
    import numpy as np
    t = plan_arrays(graph, optimize=optimize)
    p = t["plan"]
    A2 = p["A"] + 2
    n_in = max(len(p["input_arcs"]), 1)
    n_out = max(len(p["output_arcs"]), 1)
    env_row = np.full((A2,), n_in, np.int32)
    in_arc_idx = np.full((n_in,), p["EMPTY_PAD"], np.int32)
    for r, a in enumerate(p["input_arcs"]):
        env_row[p["aidx"][a]] = r
        in_arc_idx[r] = p["aidx"][a]
    out_arc_idx = np.full((n_out,), p["EMPTY_PAD"], np.int32)
    out_mask = np.zeros((A2,), np.int32)
    for r, a in enumerate(p["output_arcs"]):
        out_arc_idx[r] = p["aidx"][a]
        out_mask[p["aidx"][a]] = 1
    t.update(env_row=env_row, in_arc_idx=in_arc_idx,
             out_arc_idx=out_arc_idx, out_mask=out_mask)
    return t


def _env_cycle(tab, feed_vals, feed_len, carry, class_slices=None,
               profile=False):
    """One full engine cycle (feed -> fire -> drain), gather-only.

    tab: dict of the _TABLE_KEYS arrays.  carry: (full, val, ptr,
    out_last, out_count, fired, last_prog, cyc) — with ``profile=True``
    five counter arrays (nf, si, so, ab, ahw; DESIGN.md §12) ride at
    the end of the carry and accumulate in-kernel.  Ordering matches
    `repro.core.engine.run_reference` exactly: inputs strobe first, the
    fire rule sees the post-feed registers, outputs drain post-fire;
    the occupancy sample point is post-fire, pre-drain.
    class_slices selects the opcode-specialized fire rule.
    """
    (full, val, ptr, out_last, out_count, fired, last_prog, cyc,
     *prof) = carry
    L = feed_vals.shape[1]
    # 1. strobe environment input buses (pad row: feed_len 0, never fires)
    can_feed = (full[tab["in_arc_idx"]] == 0) & (ptr < feed_len)
    nxt = jnp.take_along_axis(
        feed_vals, jnp.clip(ptr, 0, L - 1)[:, None], axis=1)[:, 0]
    can_p = jnp.concatenate([can_feed, jnp.zeros((1,), bool)])
    nxt_p = jnp.concatenate([nxt, jnp.zeros((1,), nxt.dtype)])
    fed_arc = can_p[tab["env_row"]]
    val = jnp.where(fed_arc, nxt_p[tab["env_row"]], val)
    full = jnp.where(fed_arc, 1, full)
    ptr = ptr + can_feed.astype(ptr.dtype)
    # 2. fire every ready node
    if profile:
        from repro.core.engine import _node_inputs_ready
        ir = _node_inputs_ready(tab["opcode"], tab["in_idx"], full, val)
    full, val, ready = _fire_parts(
        tab["opcode"], tab["in_idx"], tab["out_idx"], tab["prod_node"],
        tab["prod_slot"], tab["cons_node"], tab["cons_slot"],
        tab["const_mask"], full, val, class_slices)
    n_fired = ready.astype(jnp.int32).sum()
    if profile:
        nf, si, so, ab, ahw = prof
        occ = (full > 0).astype(jnp.int32)
        prof = (nf + ready, si + ~ir, so + (ir & ~ready),
                ab + occ, jnp.maximum(ahw, occ))
    # 3. environment drains output buses
    got = full[tab["out_arc_idx"]] > 0
    out_last = jnp.where(got, val[tab["out_arc_idx"]], out_last)
    out_count = out_count + got.astype(out_count.dtype)
    full = jnp.where(tab["out_mask"] > 0, 0, full)
    progress = jnp.any(can_feed) | (n_fired > 0) | jnp.any(got)
    return (full, val, ptr, out_last, out_count, fired + n_fired,
            jnp.where(progress, cyc + 1, last_prog), cyc + 1, *prof)


def _block_body(tab, feed_vals, feed_len, full, val, ptr, out_last,
                out_count, n_cycles: int, class_slices=None, prof=None):
    """Run `n_cycles` engine cycles; pure jnp (the xla slot step).

    Returns (full, val, ptr, out_last, out_count, fired, last_prog)
    where fired counts firings within this block and last_prog is the
    1-based relative index of the last cycle that made progress (0 if
    the whole block was idle).  last_prog < n_cycles implies the fabric
    is quiescent — idle is absorbing.  ``prof`` (optional tuple of the
    5 §12 counter arrays) rides the carry and is returned after
    last_prog — counters accumulate across blocks because the caller
    passes the previous block's counters back in."""
    profile = prof is not None
    carry = (full, val, ptr, out_last, out_count,
             jnp.int32(0), jnp.int32(0), jnp.int32(0),
             *(prof if profile else ()))
    carry = jax.lax.fori_loop(
        0, n_cycles,
        lambda i, c: _env_cycle(tab, feed_vals, feed_len, c, class_slices,
                                profile=profile),
        carry)
    return carry[:7] + tuple(carry[8:])


# ---------------------------------------------------------------------------
# TPU lane layout shared by every Pallas kernel of the fabric
# ---------------------------------------------------------------------------
# A register file is stored row-major by arc, with the slots (independent
# token streams) on the two minor axes: ``[A2, m, 128]`` int32, slot
# ``b`` at ``[:, b // 128, b % 128]``.  Every node of the fabric is known
# at trace time, so each one reads its operand rows with static leading
# indices and computes one lane-dense vector op across all slots of a
# tile — no gathers, no scatters.  A grid step covers ``s`` sublane rows
# (s * 128 slots); ``s`` is 8 (one full vreg per row) once there are
# more than 1024 slots.
LANES = 128
_SUBLANES = 8


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode exactly when JAX's default
    backend is the CPU (tests and rehearsals under ``JAX_PLATFORMS=cpu``);
    on a TPU every kernel is compiled by Mosaic."""
    return jax.default_backend() == "cpu"


def slot_tiles(batch: int) -> tuple[int, int]:
    """(m, s): ``batch`` slots padded to ``m`` rows of 128 lanes, split
    into grid tiles of ``s`` rows (m % s == 0)."""
    m = -(-batch // LANES)
    if m <= _SUBLANES:
        return m, m
    return -(-m // _SUBLANES) * _SUBLANES, _SUBLANES


def to_lanes(x, m: int):
    """[B, *R] -> [*R, m, 128]: slots onto the minor axes, zero-padded."""
    B = x.shape[0]
    x = jnp.pad(x, [(0, m * LANES - B)] + [(0, 0)] * (x.ndim - 1))
    x = jnp.moveaxis(x, 0, -1)
    return x.reshape(*x.shape[:-1], m, LANES)


def from_lanes(x, batch: int):
    """Inverse of :func:`to_lanes`: [*R, m, 128] -> [batch, *R]."""
    x = x.reshape(*x.shape[:-2], -1)[..., :batch]
    return jnp.moveaxis(x, -1, 0)


def feed_window(feed_vals, ptr, n_cycles: int):
    """[B, n_in, K] next-K-token window of every feed row from its
    pointer (clamped like the reference gather).  A slot feeds at most
    one token per row per cycle, so a K-cycle block only ever reads
    ``window[..., ptr_now - ptr_block_start]``: the kernel selects it
    lane-wise instead of gathering from the whole stream."""
    L = feed_vals.shape[2]
    idx = jnp.clip(ptr[:, :, None] + jnp.arange(n_cycles, dtype=ptr.dtype),
                   0, L - 1)
    return jnp.take_along_axis(feed_vals, idx, axis=2)


def pick(rows, d):
    """rows[d] lane-wise for a static list of rows: a select chain."""
    out = rows[0]
    for j in range(1, len(rows)):
        out = jnp.where(d == j, rows[j], out)
    return out


def compiler_params(block_rows: int, sublanes: int):
    """Mosaic parameters for a kernel whose in+out blocks hold
    ``block_rows`` rows of ``sublanes`` x 128 int32: the slot-tile grid
    is parallel, and the scoped VMEM limit covers the double-buffered
    blocks (sublane rows pad to 8)."""
    from jax.experimental.pallas import tpu as pltpu
    need = 2 * block_rows * max(sublanes, _SUBLANES) * LANES * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=int(min(max(need + (8 << 20), 32 << 20),
                                 100 << 20)))


# -- trace-time boolean folding: pad rows and const arcs are static ----
def _and(*xs):
    acc = True
    for x in xs:
        if x is False:
            return False
        if x is not True:
            acc = x if acc is True else acc & x
    return acc


def _or(*xs):
    acc = False
    for x in xs:
        if x is True:
            return True
        if x is not False:
            acc = x if acc is False else acc | x
    return acc


def _not(x):
    return (not x) if isinstance(x, bool) else ~x


def _sel(c, a, b):
    if c is True or a is b:
        return a
    if c is False:
        return b
    return jnp.where(c, a, b)


def _msel(c, a, b):
    """_sel for masks (Mosaic selects no i1 vectors)."""
    return _or(_and(c, a), _and(_not(c), b))


def _i32(x, shape):
    if isinstance(x, bool):
        return jnp.full(shape, int(x), jnp.int32)
    return x.astype(jnp.int32)


class FabricSpec:
    """The node/arc tables as Python ints: what the kernels bake in as
    static row indices and per-node code."""

    def __init__(self, tables):
        import numpy as np
        p = tables["plan"]
        self.A = int(p["A"])
        self.A2 = self.A + 2
        self.FULL_PAD = int(p["FULL_PAD"])
        self.EMPTY_PAD = int(p["EMPTY_PAD"])
        self.opcode = [int(x) for x in np.asarray(tables["opcode"])]
        self.N2 = len(self.opcode)
        self.in_idx = np.asarray(tables["in_idx"]).tolist()
        self.out_idx = np.asarray(tables["out_idx"]).tolist()
        self.prod = list(zip(np.asarray(tables["prod_node"]).tolist(),
                             np.asarray(tables["prod_slot"]).tolist()))
        self.cons = list(zip(np.asarray(tables["cons_node"]).tolist(),
                             np.asarray(tables["cons_slot"]).tolist()))
        self.const = [bool(x) for x in np.asarray(tables["const_mask"])]
        self.in_arcs = [int(p["aidx"][a]) for a in p["input_arcs"]]
        self.out_arcs = [int(p["aidx"][a]) for a in p["output_arcs"]]
        self.n_in = max(len(self.in_arcs), 1)
        self.n_out = max(len(self.out_arcs), 1)


def _fire_node(sp, n, F, V):
    """One node's firing rule on post-feed rows (the lane form of
    :func:`_ready_and_z`): (ready, z thunk, consume[3], produce[2],
    inputs_ready).  Rows are bool arrays or static Python bools."""
    op = sp.opcode[n]
    i0, i1, i2 = sp.in_idx[n]
    o0, o1 = sp.out_idx[n]
    inf = (F(i0), F(i1), F(i2))
    oute = (_not(F(o0)), _not(F(o1)))
    all_out = _and(*oute)
    if op == int(Op.NDMERGE):
        ready = _and(_or(inf[0], inf[1]), all_out)
        z = lambda: _sel(inf[0], V(i0), V(i1))
        consume = (_and(ready, inf[0]), _and(ready, _not(inf[0])), False)
        return ready, z, consume, (ready, ready), _or(inf[0], inf[1])
    if op == int(Op.DMERGE):
        c3 = V(i2) != 0
        ir = _and(inf[2], _msel(c3, inf[0], inf[1]))
        ready = _and(ir, all_out)
        z = lambda: _sel(c3, V(i0), V(i1))
        consume = (_and(ready, c3), _and(ready, ~c3), ready)
        return ready, z, consume, (ready, ready), ir
    ir = _and(*inf)
    if op == int(Op.BRANCH):
        c2 = V(i1) != 0
        ready = _and(inf[0], inf[1], _msel(c2, oute[0], oute[1]))
        return (ready, lambda: V(i0), (ready,) * 3,
                (_and(ready, c2), _and(ready, ~c2)), ir)
    from repro.core.engine import _alu_op
    ready = _and(ir, all_out)
    z = lambda: _alu_op(Op(op), V(i0), V(i1), jnp.int32)
    return ready, z, (ready,) * 3, (ready, ready), ir


def _dyn_block_kernel(sp, n_cycles, profile, *refs):
    """K dynamic engine cycles (feed -> fire -> drain) for one tile of
    slots.  Refs in: window[n_in, K], feed_len[n_in], active, then the
    state (full, val [A2], ptr [n_in], out_last, out_count [n_out]) and,
    profiled, the §12 counters (nf, si, so [N2], ab, ahw [A2]); out: the
    state, fired, last_prog and the counters.  Each cycle loads the
    rows it reads, and stores every row it changes after all loads, so
    every node fires against one snapshot.  Inactive slots keep their
    input state and report 0 fired / 0 last_prog."""
    n_st = 10 if profile else 5
    win, fl, active = refs[:3]
    st_in = refs[3:3 + n_st]
    outs = refs[3 + n_st:]
    full, val, ptr, ol, oc, fired, lastp = outs[:7]
    prof = outs[7:]
    st_out = (full, val, ptr, ol, oc, *prof)
    for r_in, r_out in zip(st_in, st_out):
        r_out[...] = r_in[...]
    shape = active.shape
    N = sp.N2 - 1                       # the last node row is the dummy
    fed = set(sp.in_arcs)

    def cycle(c, carry):
        nfired, lp = carry
        fc, vc = {}, {}

        def F(a):
            if a == sp.FULL_PAD or (a < sp.A and sp.const[a]):
                return True
            if a == sp.EMPTY_PAD:
                return False
            if a not in fc:
                fc[a] = full[a] != 0
            return fc[a]

        def V(a):
            if a not in vc:
                vc[a] = val[a]
            return vc[a]

        # 1. strobe the input buses
        prog = False
        for r, a in enumerate(sp.in_arcs):
            p_r = ptr[r]
            can = _and(_not(F(a)), p_r < fl[r])
            nxt = pick([win[r, j] for j in range(n_cycles)],
                       p_r - st_in[2][r])
            vc[a] = _sel(can, nxt, V(a))
            fc[a] = _or(F(a), can)
            ptr[r] = p_r + _i32(can, shape)
            prog = _or(prog, can)
        # 2. fire every ready node against the post-feed snapshot
        rules = [_fire_node(sp, n, F, V) for n in range(sp.N2)]
        zs = {}
        nf_new, nv_new = {}, {}
        for a in range(sp.A):
            if sp.const[a]:
                continue
            cn, cs = sp.cons[a]
            pn, ps = sp.prod[a]
            consumed = rules[cn][2][cs] if cn < N else False
            produced = rules[pn][3][ps] if pn < N else False
            nf_new[a] = _or(_and(F(a), _not(consumed)), produced)
            if produced is not False:
                if pn not in zs:
                    zs[pn] = rules[pn][1]()
                nv_new[a] = _sel(produced, zs[pn], V(a))
            elif a in fed:
                nv_new[a] = V(a)
        n_now = None
        for rdy, *_ in rules:
            if rdy is not False:
                n_now = _i32(rdy, shape) if n_now is None \
                    else n_now + _i32(rdy, shape)
        if n_now is not None:
            nfired = nfired + n_now
            prog = _or(prog, n_now > 0)
        if profile:
            nf_r, si_r, so_r, ab_r, ahw_r = prof
            for n, (rdy, _, _, _, ir) in enumerate(rules):
                if rdy is not False:
                    nf_r[n] = nf_r[n] + _i32(rdy, shape)
                if ir is not True:
                    si_r[n] = si_r[n] + _i32(_not(ir), shape)
                stall = _and(ir, _not(rdy))
                if stall is not False:
                    so_r[n] = so_r[n] + _i32(stall, shape)
            for a in range(sp.A2):
                occ = nf_new.get(a, F(a))
                if occ is not False:
                    occ = _i32(occ, shape)
                    ab_r[a] = ab_r[a] + occ
                    ahw_r[a] = jnp.maximum(ahw_r[a], occ)
        # 3. the environment drains the output buses
        for r, a in enumerate(sp.out_arcs):
            got = nf_new.get(a, F(a))
            if got is False:
                continue
            ol[r] = _sel(got, nv_new.get(a, V(a)), ol[r])
            oc[r] = oc[r] + _i32(got, shape)
            nf_new[a] = False
            prog = _or(prog, got)
        for a, x in nf_new.items():
            full[a] = _i32(x, shape)
        for a, x in nv_new.items():
            val[a] = x
        if prog is not False:
            lp = _sel(prog, jnp.broadcast_to(c + 1, shape), lp)
        return nfired, lp

    zero = jnp.zeros(shape, jnp.int32)
    nfired, lp = jax.lax.fori_loop(0, n_cycles, cycle, (zero, zero))
    act = active[...] != 0
    for r_in, r_out in zip(st_in, st_out):
        r_out[...] = jnp.where(act, r_out[...], r_in[...])
    fired[...] = jnp.where(act, nfired, 0)
    lastp[...] = jnp.where(act, lp, 0)


def _dyn_block_call(sp, n_cycles, profile, batch):
    """The pallas_call of :func:`_dyn_block_kernel` for ``batch`` slots
    in lane layout (operands already [R, m, 128])."""
    m, s = slot_tiles(batch)
    grid = (m // s,)

    def spec(*lead):
        return pl.BlockSpec((*lead, s, LANES),
                            lambda i, k=len(lead): (0,) * k + (i, 0))

    st_rows = [sp.A2, sp.A2, sp.n_in, sp.n_out, sp.n_out]
    if profile:
        st_rows += [sp.N2] * 3 + [sp.A2] * 2
    in_specs = [spec(sp.n_in, n_cycles), spec(sp.n_in), spec()] \
        + [spec(r) for r in st_rows]
    out_rows = st_rows[:5] + [None, None] + st_rows[5:]
    out_specs = [spec() if r is None else spec(r) for r in out_rows]
    out_shape = [jax.ShapeDtypeStruct(
        (m, LANES) if r is None else (r, m, LANES), jnp.int32)
        for r in out_rows]
    rows = sp.n_in * (n_cycles + 1) + 1 + 2 * sum(st_rows) + 2
    return pl.pallas_call(
        functools.partial(_dyn_block_kernel, sp, n_cycles, profile),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret_mode(),
        compiler_params=None if interpret_mode()
        else compiler_params(rows, s),
        name="dataflow_fire_block")


def fire_block_batched_pallas(sp: FabricSpec, feed_vals, feed_len, full,
                              val, ptr, out_last, out_count, *,
                              n_cycles: int, active=None, prof=None):
    """Batched block step: B independent streams through one fabric in a
    single dispatch.  All state/feed arrays carry a leading batch axis;
    the node/arc tables (``sp``) are baked into the kernel.  ``active``
    (int32[B], default all-ones) is the per-stream clock gate: slots
    with active == 0 keep their state and report fired/last_prog = 0, so
    a serving layer can park quiesced slots without a global barrier.
    Returns (full', val', ptr', out_last', out_count', fired[B, 1],
    last_prog[B, 1]).  prof: optional 5-tuple of per-stream §12 counter
    arrays ([B, N2] / [B, A2] int32), accumulated in-kernel per active
    stream and returned after last_prog.

    The kernel runs on the lane layout (:func:`to_lanes`): the caller's
    [B, ...] arrays are transposed in and out around the call."""
    B = full.shape[0]
    m, _ = slot_tiles(B)
    if active is None:
        active = jnp.ones((B,), jnp.int32)
    state = [full, val, ptr, out_last, out_count, *(prof or ())]
    win = feed_window(feed_vals, ptr, n_cycles)
    args = [to_lanes(win, m), to_lanes(feed_len, m),
            to_lanes(active, m)] + [to_lanes(x, m) for x in state]
    res = _dyn_block_call(sp, n_cycles, prof is not None, B)(*args)
    out = [from_lanes(x, B) for x in res]
    out[5] = out[5][:, None]
    out[6] = out[6][:, None]
    return tuple(out)


def fire_block_pallas(sp: FabricSpec, feed_vals, feed_len, full, val, ptr,
                      out_last, out_count, *, n_cycles: int, prof=None):
    """K fused engine cycles (environment included) for one stream: the
    batched kernel with one live slot.

    feed_vals[n_in, L] int32, feed_len[n_in] int32.
    State: full/val[A2], ptr[n_in], out_last/out_count[n_out], int32.
    Returns (full', val', ptr', out_last', out_count', fired[1],
    last_prog[1]).  prof: optional 5-tuple of §12 counter arrays
    (nf/si/so[N2], ab/ahw[A2] int32) — accumulated in-kernel and
    returned after last_prog."""
    one = lambda x: x[None]
    res = fire_block_batched_pallas(
        sp, one(feed_vals), one(feed_len), one(full), one(val),
        one(ptr), one(out_last), one(out_count), n_cycles=n_cycles,
        prof=None if prof is None else tuple(map(one, prof)))
    return tuple(x[0] for x in res)
