"""The plan: the arc/register layout every executor of a fabric shares.

:func:`plan` turns a :class:`~repro.core.graph.Graph` into static numpy
arrays — the node table (``opcode[N]``, ``in_idx[N,3]``,
``out_idx[N,2]``), the arc index with its two pad slots, the const mask
and the environment buses — which the xla cycle body, the kernels'
tables, the schedules, the partitioned regions and the reference oracle
all read.  Plans are memoized process-wide.
"""
from __future__ import annotations

import collections
import hashlib

import numpy as np

from repro.core import asm
from repro.core.graph import Graph

MAX_IN = 3
MAX_OUT = 2

# Plan construction walks the whole graph in python and dominates engine
# construction cost; the result depends only on the graph's asm
# signature and the optimize flag (schedule state is built separately
# and never alters the plan), so one process-wide LRU serves every
# engine/backend/reference run of the same fabric.  The cached dict's
# numpy arrays are frozen read-only: sharing is safe because no
# consumer mutates a plan, and the flag turns any future mutation into
# an immediate error instead of silent cross-engine corruption.
_PLAN_CACHE: collections.OrderedDict = collections.OrderedDict()
_PLAN_CACHE_MAX = 256
PLAN_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    for k in PLAN_CACHE_STATS:
        PLAN_CACHE_STATS[k] = 0


def plan(graph: Graph, optimize: bool = False):
    """Memoized :func:`_plan_build` keyed on (asm signature, optimize).

    The signature is the full textual serialization (nodes, consts,
    inits), so a mutated Graph re-keys automatically; hits skip both
    validation and array construction."""
    sig = hashlib.sha256(asm.emit(graph).encode()).hexdigest()
    key = (sig, bool(optimize))
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        PLAN_CACHE_STATS["hits"] += 1
        _PLAN_CACHE.move_to_end(key)
        return hit
    PLAN_CACHE_STATS["misses"] += 1
    p = _plan_build(graph, optimize)
    for v in p.values():
        if isinstance(v, np.ndarray):
            v.flags.writeable = False
    _PLAN_CACHE[key] = p
    if len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
        PLAN_CACHE_STATS["evictions"] += 1
    return p


def _plan_build(graph: Graph, optimize: bool = False):
    """Static (numpy) arrays describing the fabric.

    With ``optimize=True`` the plan is *opcode-class specialized*
    (DESIGN.md §8): arcs are permuted into role order (inputs, outputs,
    internal, consts) and nodes are permuted so that equal opcodes are
    contiguous, with the per-class slice ranges recorded in
    ``class_slices`` — the fire body can then unroll a static loop over
    only the opcode classes present instead of evaluating the full ALU
    ``where``-chain for every node.  The permutation is pure layout:
    every node still fires against the same snapshot, so results are
    bit-identical to the unoptimized plan.  ``node_perm``/``arc_perm``
    map plan row -> original index and ``node_inv``/``arc_inv`` are the
    inverses (original index -> plan row).
    """
    graph.validate()
    arcs = graph.arcs
    input_arcs = graph.input_arcs()
    output_arcs = graph.output_arcs()
    if optimize:
        # arc permutation: environment buses first (inputs, then
        # outputs), then internal arcs, then consts — role-contiguous
        # so environment gathers walk compact index ranges
        ordered: dict[str, None] = {}
        for a in (*input_arcs, *output_arcs):
            ordered.setdefault(a, None)
        for a in arcs:
            if a not in graph.consts:
                ordered.setdefault(a, None)
        for a in arcs:
            ordered.setdefault(a, None)
        old_pos = {a: i for i, a in enumerate(arcs)}
        arcs = list(ordered)
        arc_perm = np.asarray([old_pos[a] for a in arcs], np.int32)
    else:
        arc_perm = np.arange(len(arcs), dtype=np.int32)
    arc_inv = np.empty_like(arc_perm)
    arc_inv[arc_perm] = np.arange(len(arcs), dtype=np.int32)
    aidx = {a: i for i, a in enumerate(arcs)}
    A = len(arcs)
    FULL_PAD = A        # dummy slot, always full (pads missing inputs)
    EMPTY_PAD = A + 1   # dummy slot, always empty (pads missing outputs)

    N = len(graph.nodes)
    opcode = np.zeros((N,), np.int32)
    in_idx = np.full((N, MAX_IN), FULL_PAD, np.int32)
    out_idx = np.full((N, MAX_OUT), EMPTY_PAD, np.int32)
    for i, n in enumerate(graph.nodes):
        opcode[i] = int(n.op)
        for k, a in enumerate(n.inputs):
            in_idx[i, k] = aidx[a]
        for k, a in enumerate(n.outputs):
            out_idx[i, k] = aidx[a]

    if optimize:
        node_perm = np.argsort(opcode, kind="stable").astype(np.int32)
        opcode = opcode[node_perm]
        in_idx = in_idx[node_perm]
        out_idx = out_idx[node_perm]
        class_slices = []
        s = 0
        while s < N:
            e = s
            while e < N and opcode[e] == opcode[s]:
                e += 1
            class_slices.append((int(opcode[s]), s, e))
            s = e
        class_slices = tuple(class_slices) or None
    else:
        node_perm = np.arange(N, dtype=np.int32)
        class_slices = None
    node_inv = np.empty_like(node_perm)
    node_inv[node_perm] = np.arange(N, dtype=np.int32)

    const_mask = np.zeros((A + 2,), bool)
    for a in graph.consts:
        const_mask[aidx[a]] = True

    return dict(
        arcs=arcs, aidx=aidx, A=A, FULL_PAD=FULL_PAD, EMPTY_PAD=EMPTY_PAD,
        opcode=opcode, in_idx=in_idx, out_idx=out_idx,
        const_mask=const_mask, input_arcs=input_arcs,
        output_arcs=output_arcs, class_slices=class_slices,
        node_perm=node_perm, node_inv=node_inv,
        arc_perm=arc_perm, arc_inv=arc_inv,
    )
