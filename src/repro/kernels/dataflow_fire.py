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
* :func:`fire_block_batched_pallas` — the Pallas kernel (the row
  kernel, see its section below): the fabric as tables in SMEM, one
  register row per arc reader, slots on sublanes and lanes.
  :func:`fire_block_pallas` is the same kernel with one live slot.
"""
from __future__ import annotations

import dataclasses
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
# TPU lane layout shared by the Pallas kernels of the fabric
# ---------------------------------------------------------------------------
# Slots (independent token streams) sit on the two minor axes: an array
# ``[R, m, 128]`` int32 holds slot ``b`` at ``[:, b // 128, b % 128]``,
# and a grid step covers ``s`` sublane rows (s * 128 slots).
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
    lane-wise instead of gathering from the whole stream.

    The window is a select and a sum over the stream axis, which XLA
    fuses into one pass over the feed buffer: on a TPU a gather of the
    same elements costs about 12 ns each (PERF.md §6)."""
    L = feed_vals.shape[2]
    idx = jnp.clip(ptr[:, :, None] + jnp.arange(n_cycles, dtype=ptr.dtype),
                   0, L - 1)
    hit = jnp.arange(L, dtype=idx.dtype)[:, None] == idx[:, :, None, :]
    return jnp.where(hit, feed_vals[..., None], 0).sum(
        axis=2, dtype=feed_vals.dtype)


def pick(rows, d):
    """rows[d] lane-wise for a static list of rows: a select chain."""
    out = rows[0]
    for j in range(1, len(rows)):
        out = jnp.where(d == j, rows[j], out)
    return out


VMEM_LIMIT = 100 << 20      # the most scoped VMEM a fire kernel asks for
_VMEM_SLACK = 8 << 20       # headroom over a kernel's own blocks


def vmem_params(need: int):
    """Mosaic parameters for a kernel whose blocks and scratch take
    ``need`` bytes of VMEM: the slot-tile grid is parallel, and the
    scoped VMEM limit covers the blocks with headroom."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=int(min(max(need + _VMEM_SLACK, 32 << 20),
                                 VMEM_LIMIT)))


def compiler_params(block_rows: int, sublanes: int):
    """:func:`vmem_params` for a kernel whose in+out blocks hold
    ``block_rows`` rows of ``sublanes`` x 128 int32, double-buffered
    (sublane rows pad to 8)."""
    return vmem_params(2 * block_rows * max(sublanes, _SUBLANES) * LANES * 4)


# -- trace-time boolean folding: missing inputs and outputs are static --
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
    """The node/arc tables as Python ints, from which the row kernel's
    layout (:attr:`rows`) is built."""

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
        self.const = [bool(x) for x in np.asarray(tables["const_mask"])]
        self.in_arcs = [int(p["aidx"][a]) for a in p["input_arcs"]]
        self.out_arcs = [int(p["aidx"][a]) for a in p["output_arcs"]]
        self.n_in = max(len(self.in_arcs), 1)
        self.n_out = max(len(self.out_arcs), 1)

    @functools.cached_property
    def rows(self) -> "RowLayout":
        """The fabric's tables for the row kernel."""
        return RowLayout(self)


def _node_rule(op, inf, oute, v):
    """The firing rule of one opcode over operand rows (the lane form of
    :func:`_ready_and_z`): ``inf`` the three inputs' full flags, ``oute``
    the two outputs' empty flags (bool arrays, or static Python bools
    for the inputs and outputs an opcode lacks), ``v`` three thunks of
    the input values.  Returns (ready, z thunk, consume[3], produce[2],
    inputs_ready)."""
    all_out = _and(*oute)
    if op == int(Op.NDMERGE):
        ready = _and(_or(inf[0], inf[1]), all_out)
        z = lambda: _sel(inf[0], v[0](), v[1]())
        consume = (_and(ready, inf[0]), _and(ready, _not(inf[0])), False)
        return ready, z, consume, (ready, ready), _or(inf[0], inf[1])
    if op == int(Op.DMERGE):
        c3 = v[2]() != 0
        ir = _and(inf[2], _msel(c3, inf[0], inf[1]))
        ready = _and(ir, all_out)
        z = lambda: _sel(c3, v[0](), v[1]())
        consume = (_and(ready, c3), _and(ready, ~c3), ready)
        return ready, z, consume, (ready, ready), ir
    ir = _and(*inf)
    if op == int(Op.BRANCH):
        c2 = v[1]() != 0
        ready = _and(inf[0], inf[1], _msel(c2, oute[0], oute[1]))
        return (ready, v[0], (ready,) * 3,
                (_and(ready, c2), _and(ready, ~c2)), ir)
    from repro.core.engine import _alu_op
    ready = _and(ir, all_out)
    z = lambda: _alu_op(Op(op), v[0](), v[1](), jnp.int32)
    return ready, z, (ready,) * 3, (ready, ready), ir


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


# ---------------------------------------------------------------------------
# The row kernel: the fabric as tables
# ---------------------------------------------------------------------------
# A kernel that emits every node as code lowers in time that grows with
# the fabric, and a register file held as [A2, s, 128] blocks takes 8
# sublanes of VMEM whatever s: ISCAS-85 c6288 (9.3k arcs) neither
# lowered in reasonable time nor fit.  The row kernel takes the fabric
# as data (DESIGN.md §3):
#
# * a slot tile's registers are rows of a 2-D [R * s, 128] block, s
#   sublanes of 128 slots a row (s in 1, 2, 4, 8), nothing padded;
# * rows are ordered by each arc's single receiver: input k of the j-th
#   node (nodes ordered by opcode class) is row ``base_k + j``, so a
#   class's operands are contiguous rows, and one vector step fires 8
#   nodes of a class on every slot of the tile;
# * the producer side is a permutation: a loop over (arc row, node)
#   pairs read from SMEM copies each output arc's full flag out before
#   the cycle fires, and delivers the produced token after it.
#
# Its code is a few vector ops per opcode class, whatever the fabric's
# size.
_CH = 8                 # nodes, and register rows, per vector step


def _class_rank(op: int) -> int:
    """Opcode classes in row order: the two-output ops first, so their
    second output flag covers a prefix of the nodes; the 2- and 3-input
    ops together, so each input's rows are one contiguous range."""
    return {int(Op.COPY): 0, int(Op.BRANCH): 1, int(Op.DMERGE): 3,
            int(Op.NOT): 4, int(Op.SINK): 5}.get(op, 2)


@dataclasses.dataclass(frozen=True)
class _Segment:
    op: int
    const: tuple          # per input: a const bus (never consumed)
    lo: int               # node positions [lo, hi), multiples of _CH
    hi: int


class RowLayout:
    """A fabric's tables for the row kernel, from a :class:`FabricSpec`.

    Node positions: plan nodes (the dummy row included) sorted by
    (opcode class, opcode, which inputs are const), each run padded to a
    multiple of ``_CH`` with pad nodes that are never ready.  Register
    rows: the consumed arcs by receiver (input k of position j at
    ``base[k] + j - lo[k]``), then the output arcs (drained in plan
    order), the FULL_PAD and EMPTY_PAD rows, and pad rows (empty).
    ``src[R]`` is each row's plan column, ``dst[A2]`` each plan column's
    row (a const bus read by several nodes has a row per reader)."""

    def __init__(self, sp: FabricSpec):
        import numpy as np
        from repro.core.graph import ARITY
        arity = [ARITY[Op(op)] for op in sp.opcode]
        keys = [(_class_rank(op), op, tuple(
            sp.const[sp.in_idx[n][k]] for k in range(arity[n][0])))
            for n, op in enumerate(sp.opcode)]
        order = sorted(range(sp.N2), key=keys.__getitem__)
        node_at, segs = [], []
        i = 0
        while i < len(order):
            e = i
            while e < len(order) and keys[order[e]] == keys[order[i]]:
                e += 1
            lo = len(node_at)
            node_at += order[i:e] + [-1] * (-(e - i) % _CH)
            _, op, const = keys[order[i]]
            segs.append(_Segment(op, const, lo, len(node_at)))
            i = e
        self.segments = tuple(segs)
        self.Np = Np = len(node_at)
        n_ins = lambda sg: ARITY[Op(sg.op)][0]
        # input k's rows: the positions of ops with more than k inputs
        self.base, self.lo = [], []
        src = []
        for k in range(3):
            span = [sg for sg in segs if n_ins(sg) > k]
            lo = span[0].lo if span else 0
            hi = span[-1].hi if span else 0
            assert sum(sg.hi - sg.lo for sg in span) == hi - lo
            self.base.append(len(src))
            self.lo.append(lo)
            src += [sp.in_idx[n][k] if n >= 0 else sp.EMPTY_PAD
                    for n in node_at[lo:hi]]
        two = [sg for sg in segs if ARITY[Op(sg.op)][1] == 2]
        self.n2o = two[-1].hi if two else 0          # a prefix (rank 0, 1)
        self.out_base = len(src)
        self.n_out = len(sp.out_arcs)
        self.n_out_p = -(-max(self.n_out, 1) // _CH) * _CH
        src += sp.out_arcs + [sp.EMPTY_PAD] * (self.n_out_p - self.n_out)
        full_row, empty_row = len(src), len(src) + 1
        src += [sp.FULL_PAD, sp.EMPTY_PAD]
        src += [sp.EMPTY_PAD] * (-len(src) % _CH)
        self.R = len(src)
        dst = np.full((sp.A2,), -1, np.int64)
        for r in range(self.out_base + self.n_out - 1, -1, -1):
            dst[src[r]] = r                  # a const's first reader
        dst[sp.FULL_PAD], dst[sp.EMPTY_PAD] = full_row, empty_row
        if (dst < 0).any() or any(sp.const[a] for a in sp.out_arcs):
            raise ValueError("the row kernel needs every arc read by a "
                             "node or drained, and no const bus drained")
        self.src = np.asarray(src, np.int32)
        self.dst = dst.astype(np.int32)
        self.n_in = len(sp.in_arcs)
        self.n_in_p = -(-max(self.n_in, 1) // _CH) * _CH
        in_row = [int(dst[a]) for a in sp.in_arcs]
        in_row += [empty_row] * (self.n_in_p - self.n_in)
        prow, pdst, pz = [], [], []
        for j, n in enumerate(node_at):
            for k in range(arity[n][1] if n >= 0 else 0):
                prow.append(int(dst[sp.out_idx[n][k]]))
                pdst.append(j + k * Np)
                pz.append(j)
        # pad entries: the empty row's flag into a spare flag row, which
        # no node writes, so delivering from it changes nothing
        pad = -len(prow) % _CH
        prow += [empty_row] * pad
        pdst += [Np + self.n2o] * pad
        pz += [0] * pad
        self.E = len(prow)
        self.o_prow = self.n_in_p
        self.o_pdst = self.o_prow + self.E
        self.o_pz = self.o_pdst + self.E
        self.table = np.asarray(in_row + prow + pdst + pz, np.int32)
        node_at = np.asarray(node_at)
        self.node_src = np.where(node_at < 0, sp.N2, node_at).astype(
            np.int32)
        self.node_dst = np.empty((sp.N2,), np.int32)
        self.node_dst[node_at[node_at >= 0]] = np.flatnonzero(node_at >= 0)

    @property
    def flag_rows(self) -> int:
        """Rows of the output-flag scratch: first outputs, second
        outputs of the two-output prefix, and the spare row."""
        return self.Np + self.n2o + _CH

    def state_rows(self, profile: bool) -> list:
        rows = [self.R, self.R, self.n_in_p, self.n_out_p, self.n_out_p]
        return rows + ([self.Np] * 3 + [self.R] * 2 if profile else [])

    def vmem_bytes(self, s: int, n_cycles: int, profile: bool) -> int:
        """VMEM of one slot tile of ``s`` sublanes: the feed blocks
        double-buffered, the rest of the state in and out once, and the
        scratch: the register file (copied in from HBM and back), the
        output flags, the produced values and the firing count."""
        feed = self.n_in_p * (n_cycles + 1) + _CH
        st = sum(self.state_rows(profile)[2:])
        scratch = 2 * self.R + self.flag_rows + self.Np + _CH
        return (2 * feed + 2 * st + scratch) * s * LANES * 4 \
            + 2 * max(s, _SUBLANES) * LANES * 4

    def tiles(self, batch: int, n_cycles: int, profile: bool):
        """(T, s): the most sublanes a slot tile may take within the
        VMEM limit, and the number of tiles that cover ``batch``."""
        m = -(-batch // LANES)
        for s in (8, 4, 2, 1):
            if s <= m and self.vmem_bytes(s, n_cycles, profile) \
                    + _VMEM_SLACK <= VMEM_LIMIT:
                return -(-m // s), s
        raise ValueError(
            f"the row kernel needs {self.vmem_bytes(1, n_cycles, profile)}"
            f" B of VMEM for 128 slots, over the {VMEM_LIMIT} B limit")


def to_tiles(x, T: int, s: int, cols=None):
    """[B, C] -> [T, C' * s, 128]: slot tile t holds slots t*s*128 ..,
    column c at rows c*s .. c*s+s-1 (zero-padded); ``cols`` (C' column
    indices) picks and orders the columns, as whole rows of the tiles."""
    B, C = x.shape
    x = jnp.pad(x, ((0, T * s * LANES - B), (0, 0)))
    x = x.reshape(T, s, LANES, C).transpose(0, 3, 1, 2)
    if cols is not None:
        x = x[:, cols]
    return x.reshape(T, -1, LANES)


def from_tiles(y, batch: int, C: int, cols=None):
    """Inverse of :func:`to_tiles`: [T, C * s, 128] -> [batch, C'],
    taking columns ``cols`` (row indices or a slice) when given."""
    T, Cs, _ = y.shape
    y = y.reshape(T, C, Cs // C, LANES)
    if cols is not None:
        y = y[:, cols]
    return y.transpose(0, 2, 3, 1).reshape(-1, y.shape[1])[:batch]


def _row_block_kernel(lay, n_cycles, profile, s, *refs):
    """K engine cycles (feed -> fire -> drain) of one slot tile, the
    fabric read from tables.  Refs in: the SMEM table, window[n_in, K],
    feed_len[n_in], active (repeated for a chunk of nodes), then the
    state (full, val [R], ptr [n_in], out_last, out_count [n_out]) and,
    profiled, the §12 counters (nf, si, so [Np], ab, ahw [R]); out: the
    state, fired, last_prog and the counters, full and val in HBM (in
    and out aliased); scratch: the output arcs' flags by producer (then
    the produced masks), the produced values, the firing count, and the
    tile's full and val rows.  Rows of s sublanes; active == 0 gates a
    slot's feed, firing and drain, so it keeps its state."""
    from jax.experimental.pallas import tpu as pltpu
    n_st = 10 if profile else 5
    tab, win, fl, actv = refs[:4]
    st_in = refs[4:4 + n_st]
    outs = refs[4 + n_st:6 + 2 * n_st]
    oe, zb, acc, F, V = refs[6 + 2 * n_st:]
    F_hbm, V_hbm, ptr, ol, oc, fired, lastp = outs[:7]
    prof = outs[7:]
    K, Np = n_cycles, lay.Np
    tile = pl.program_id(0)
    # the register file stays in HBM, in place; the tile's rows are
    # copied into VMEM once a call, and back at its end
    pltpu.sync_copy(F_hbm.at[tile], F)
    pltpu.sync_copy(V_hbm.at[tile], V)
    one, many = (s, LANES), (_CH * s, LANES)

    def row(r):
        return pl.ds(pl.multiple_of(r * s, s), s)

    def chunk(r):
        return pl.ds(pl.multiple_of(r * s, _CH * s), _CH * s)

    def each_chunk(n_rows, body):
        def step(i, c):
            body(i * _CH)
            return c
        jax.lax.fori_loop(0, n_rows // _CH, step, 0)

    def each_row(n_rows, body, carry):
        """body(r, carry) for r < n_rows (a multiple of _CH), _CH
        independent rows a loop step, for the scheduler to overlap."""
        def step(i, c):
            for u in range(_CH):
                c = body(i * _CH + u, c)
            return c
        return jax.lax.fori_loop(0, n_rows // _CH, step, carry)

    def copy_in(src, dst):
        def body(r):
            dst[chunk(r)] = src[chunk(r)]
        each_chunk(src.shape[0] // s, body)

    for r_in, r_out in zip(st_in[2:], (ptr, ol, oc, *prof)):
        copy_in(r_in, r_out)

    def zero(ref):
        def body(r):
            ref[chunk(r)] = jnp.zeros(many, jnp.int32)
        each_chunk(ref.shape[0] // s, body)

    zero(oe)
    acc[...] = jnp.zeros(many, jnp.int32)
    act1 = actv[pl.ds(0, s), :] != 0
    actc = actv[...] != 0

    def fire(sg, i, c):
        from repro.core.graph import ARITY
        n_in, n_out = ARITY[Op(sg.op)]
        j0 = sg.lo + i * _CH
        at = [lay.base[k] - lay.lo[k] + j0 for k in range(n_in)]
        Fk = [F[chunk(r)] for r in at]
        inf = tuple(Fk[k] != 0 if k < n_in else True for k in range(3))
        oute = tuple(oe[chunk(j0 + k * Np)] == 0 if k < n_out else True
                     for k in range(2))
        v = [functools.partial(lambda r: V[chunk(r)], r) for r in at]
        v += [v[0]] * (3 - n_in)
        ready, z, consume, produce, ir = _node_rule(sg.op, inf, oute, v)
        ready = _and(ready, actc)
        for k in range(n_in):
            ck = _and(consume[k], actc)
            if not sg.const[k] and ck is not False:
                F[chunk(at[k])] = jnp.where(ck, 0, Fk[k])
        produce = [_and(produce[k], actc) for k in range(n_out)]
        if any(p is not False for p in produce):
            zb[chunk(j0)] = z()
        for k, p in enumerate(produce):
            oe[chunk(j0 + k * Np)] = _i32(p, many)
        acc[...] = acc[...] + _i32(ready, many)
        if profile:
            nf, si, so = prof[:3]
            nf[chunk(j0)] = nf[chunk(j0)] + _i32(ready, many)
            si[chunk(j0)] = si[chunk(j0)] + _i32(_and(actc, _not(ir)),
                                                 many)
            so[chunk(j0)] = so[chunk(j0)] + _i32(
                _and(actc, ir, _not(ready)), many)
        return c

    def fold():
        tot = acc[pl.ds(0, s), :]
        for i in range(1, _CH):
            tot = tot + acc[pl.ds(i * s, s), :]
        return tot

    def cycle(c, carry):
        lp, before = carry

        # 1. strobe the input buses
        def feed(r, prog):
            a = tab[r]
            f = F[row(a)]
            p = ptr[row(r)]
            can = (f == 0) & (p < fl[row(r)]) & act1
            nxt = pick([win[row(r * K + j)] for j in range(K)],
                       p - st_in[2][row(r)])
            V[row(a)] = jnp.where(can, nxt, V[row(a)])
            F[row(a)] = jnp.where(can, 1, f)
            ptr[row(r)] = p + can.astype(jnp.int32)
            return prog | can.astype(jnp.int32)
        prog = each_row(lay.n_in_p, feed, jnp.zeros(one, jnp.int32))

        # 2. the output arcs' full flags, by producer, before any fires
        def look(e, x):
            oe[row(tab[lay.o_pdst + e])] = F[row(tab[lay.o_prow + e])]
            return x
        each_row(lay.E, look, 0)
        # 3. fire every class against that snapshot (consuming in place)
        for sg in lay.segments:
            jax.lax.fori_loop(0, (sg.hi - sg.lo) // _CH,
                              functools.partial(fire, sg), 0)

        # 4. deliver the produced tokens
        def put(e, x):
            a = tab[lay.o_prow + e]
            p = oe[row(tab[lay.o_pdst + e])] != 0
            F[row(a)] = jnp.where(p, 1, F[row(a)])
            V[row(a)] = jnp.where(p, zb[row(tab[lay.o_pz + e])], V[row(a)])
            return x
        each_row(lay.E, put, 0)
        if profile:
            ab, ahw = prof[3:]

            def occupancy(r):
                occ = _i32((F[chunk(r)] != 0) & actc, many)
                ab[chunk(r)] = ab[chunk(r)] + occ
                ahw[chunk(r)] = jnp.maximum(ahw[chunk(r)], occ)
            each_chunk(lay.R, occupancy)

        # 5. the environment drains the output buses
        def drain(r, prog):
            a = lay.out_base + r
            f = F[row(a)]
            got = (f != 0) & act1
            ol[row(r)] = jnp.where(got, V[row(a)], ol[row(r)])
            oc[row(r)] = oc[row(r)] + got.astype(jnp.int32)
            F[row(a)] = jnp.where(got, 0, f)
            return prog | got.astype(jnp.int32)
        prog = each_row(lay.n_out_p, drain, prog)
        now = fold()
        prog = prog | (now != before).astype(jnp.int32)
        return jnp.where(prog != 0, c + 1, lp), now

    zeros = jnp.zeros(one, jnp.int32)
    lp, tot = jax.lax.fori_loop(0, K, cycle, (zeros, zeros))
    fired[...] = tot
    lastp[...] = lp
    pltpu.sync_copy(F, F_hbm.at[tile])
    pltpu.sync_copy(V, V_hbm.at[tile])


def _row_block_call(lay, n_cycles, profile, T, s):
    """The pallas_call of :func:`_row_block_kernel` over ``T`` slot
    tiles of ``s`` sublanes (operands in :func:`to_tiles` layout)."""
    from jax.experimental.pallas import tpu as pltpu

    def spec(rows, buffers=2):
        return pl.BlockSpec((None, rows * s, LANES), lambda i: (i, 0, 0),
                            pipeline_mode=pl.Buffered(buffers))

    st = lay.state_rows(profile)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                spec(lay.n_in_p * n_cycles), spec(lay.n_in_p), spec(_CH),
                hbm, hbm] + [spec(r, 1) for r in st[2:]]
    out_rows = st[:5] + [1, 1] + st[5:]
    scratch = [pltpu.VMEM((lay.flag_rows * s, LANES), jnp.int32),
               pltpu.VMEM((lay.Np * s, LANES), jnp.int32),
               pltpu.VMEM((_CH * s, LANES), jnp.int32),
               pltpu.VMEM((lay.R * s, LANES), jnp.int32),
               pltpu.VMEM((lay.R * s, LANES), jnp.int32)]
    return pl.pallas_call(
        functools.partial(_row_block_kernel, lay, n_cycles, profile, s),
        grid=(T,), in_specs=in_specs,
        out_specs=[hbm, hbm] + [spec(r, 1) for r in out_rows[2:]],
        input_output_aliases={4: 0, 5: 1},
        out_shape=[jax.ShapeDtypeStruct((T, r * s, LANES), jnp.int32)
                   for r in out_rows],
        scratch_shapes=scratch, interpret=interpret_mode(),
        compiler_params=None if interpret_mode()
        else vmem_params(lay.vmem_bytes(s, n_cycles, profile)),
        name="dataflow_fire_block")


def fire_block_batched_pallas(sp: FabricSpec, feed_vals, feed_len, full,
                              val, ptr, out_last, out_count, *,
                              n_cycles: int, active=None, prof=None):
    """Batched block step: B independent streams through one fabric in a
    single dispatch.  All state/feed arrays carry a leading batch axis;
    the fabric's tables (``sp.rows``) ride in SMEM, its opcode classes
    in the kernel's code.  ``active``
    (int32[B], default all-ones) is the per-stream clock gate: slots
    with active == 0 keep their state and report fired/last_prog = 0, so
    a serving layer can park quiesced slots without a global barrier.
    Returns (full', val', ptr', out_last', out_count', fired[B, 1],
    last_prog[B, 1]).  prof: optional 5-tuple of per-stream §12 counter
    arrays ([B, N2] / [B, A2] int32), accumulated in-kernel per active
    stream and returned after last_prog.

    The kernel (:func:`_row_block_kernel`) runs on its own tile layout:
    the caller's [B, ...] arrays are permuted and transposed in and out
    around the call, except ``full``/``val`` already in that layout
    ([T, R, s, 128], as a slot state keeps them), which pass through."""
    B = ptr.shape[0]
    if active is None:
        active = jnp.ones((B,), jnp.int32)
    lay = sp.rows
    tiled = full.ndim == 4          # already the kernel's [T, R, s, 128]
    if tiled:
        T, _, s, _ = full.shape
    else:
        T, s = lay.tiles(B, n_cycles, prof is not None)
    tiles = functools.partial(to_tiles, T=T, s=s)

    def regs(x):
        return x.reshape(T, -1, LANES) if tiled else tiles(x, cols=lay.src)

    def rows_of(x, n):
        return jnp.pad(x, ((0, 0), (0, n - x.shape[1])))

    def nodes(x):
        return tiles(jnp.pad(x, ((0, 0), (0, 1))), cols=lay.node_src)

    win = feed_window(feed_vals, ptr, n_cycles)
    win = jnp.pad(win, ((0, 0), (0, lay.n_in_p - win.shape[1]), (0, 0)))
    args = [jnp.asarray(lay.table), tiles(win.reshape(B, -1)),
            tiles(rows_of(feed_len, lay.n_in_p)),
            jnp.tile(tiles(active[:, None]), (1, _CH, 1)),
            regs(full), regs(val),
            tiles(rows_of(ptr, lay.n_in_p)),
            tiles(rows_of(out_last, lay.n_out_p)),
            tiles(rows_of(out_count, lay.n_out_p))]
    if prof is not None:
        nf, si, so, ab, ahw = prof
        args += [nodes(nf), nodes(si), nodes(so),
                 tiles(ab, cols=lay.src), tiles(ahw, cols=lay.src)]
    res = _row_block_call(lay, n_cycles, prof is not None, T, s)(*args)
    st = lay.state_rows(prof is not None)
    cols = [lay.dst, lay.dst, slice(sp.n_in), slice(sp.n_out),
            slice(sp.n_out), None, None]
    if prof is not None:
        cols += [lay.node_dst] * 3 + [lay.dst] * 2
    rows = st[:5] + [1, 1] + st[5:]
    out = [y.reshape(full.shape) if tiled and i < 2
           else from_tiles(y, B, r, c)
           for i, (y, r, c) in enumerate(zip(res, rows, cols))]
    return tuple(out)
