"""What a node computes and when it may fire: the opcode semantics every
executor shares.

:func:`alu` is the jnp ALU of every device path (the xla cycle bodies,
the Pallas kernels, the scheduled programs, the compiled DAG functions);
:func:`alu_numpy` is its numpy mirror, the reference oracle's fire math
and the constant folder's evaluator.  Both wrap integers two's-complement
and follow IEEE for floats, so a folded or oracle value is bit-identical
to a fired one.  A new opcode is defined here once (the row kernel's
per-class rule, ``kernels.dataflow_fire._node_rule``, adds its firing
rule only if it routes tokens).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.graph import Op

# opcodes whose result is a formula of operands a and b (COPY, BRANCH and
# SINK pass a on; the merges pick an operand by arrival or by control)
ALU_OPS = tuple(
    op for op in Op
    if op not in (Op.COPY, Op.BRANCH, Op.SINK, Op.NDMERGE, Op.DMERGE))


def alu(op, a, b, dtype):
    """The result of opcode ``op`` on operands a, b.  Only the requested
    opcode is traced, so the ``b == 0`` and shift-clamp guards exist
    solely where DIV/SHL/SHR do."""
    is_int = jnp.issubdtype(dtype, jnp.integer)
    if op in (Op.COPY, Op.BRANCH, Op.SINK):
        return a
    if op == Op.ADD:
        return a + b
    if op == Op.SUB:
        return a - b
    if op == Op.MUL:
        return a * b
    if op == Op.DIV:
        if is_int:
            return jnp.where(b == 0, 0, a // jnp.where(b == 0, 1, b))
        return jnp.where(b == 0, 0.0, a / jnp.where(b == 0, 1.0, b))
    if op == Op.AND:
        return (a & b) if is_int else ((a != 0) & (b != 0)).astype(dtype)
    if op == Op.OR:
        return (a | b) if is_int else ((a != 0) | (b != 0)).astype(dtype)
    if op == Op.XOR:
        return (a ^ b) if is_int else ((a != 0) ^ (b != 0)).astype(dtype)
    if op == Op.MAX:
        return jnp.maximum(a, b)
    if op == Op.MIN:
        return jnp.minimum(a, b)
    if op == Op.SHL:
        return (a << jnp.clip(b, 0, 31)) if is_int else a * jnp.exp2(b)
    if op == Op.SHR:
        if is_int:
            return a >> jnp.clip(b, 0, 31)
        two_b = jnp.exp2(b)
        return a / jnp.where(two_b == 0, 1, two_b)
    if op == Op.NOT:
        return (a == 0).astype(dtype)
    if op == Op.IFGT:
        return (a > b).astype(dtype)
    if op == Op.IFGE:
        return (a >= b).astype(dtype)
    if op == Op.IFLT:
        return (a < b).astype(dtype)
    if op == Op.IFLE:
        return (a <= b).astype(dtype)
    if op == Op.IFEQ:
        return (a == b).astype(dtype)
    if op == Op.IFDF:
        return (a != b).astype(dtype)
    raise AssertionError(op)


def truthy(v):
    """Scalar truth of a (possibly tensor) control token: element 0."""
    flat = v.reshape(v.shape[0], -1)
    return flat[:, 0] != 0


def inputs_ready(opcode, in_idx, full, val):
    """Per-node "all (selected) inputs present" on the post-feed
    registers — the stall-attribution predicate (DESIGN.md §12).

    ``ready`` (the fire rule) implies inputs-ready, so a profiled cycle
    partitions every node into exactly one of fired / blocked-on-input
    (``~inputs_ready``) / blocked-on-output (``inputs_ready & ~ready``).
    Shared by the xla cycle bodies (``full`` may be bool or int32; pads
    make the generic all-inputs reduction correct for BRANCH)."""
    inf = full[in_idx].astype(bool)              # [N,3]
    ir = inf.all(axis=1)
    is_nd = opcode == int(Op.NDMERGE)
    is_dm = opcode == int(Op.DMERGE)
    ir = jnp.where(is_nd, inf[:, 0] | inf[:, 1], ir)
    ctrl3 = truthy(val[in_idx[:, 2]])
    ir = jnp.where(is_dm,
                   inf[:, 2] & jnp.where(ctrl3, inf[:, 0], inf[:, 1]), ir)
    return ir


def alu_numpy(op, a, b, dtype):
    """Numpy mirror of :func:`alu` — the reference engine's fire math
    and the constant-folding pass's compile-time evaluator (sharing one
    implementation keeps folded values bit-identical to fired ones).

    Integer overflow wraps two's-complement and float specials follow
    IEEE, exactly like the jax ALU — numpy's over/invalid warnings are
    suppressed because that wrapping IS the contract (the fuzz harness
    feeds INT_MIN/INT_MAX operands on purpose).  Hot loops
    (``run_reference``'s fire step) enter one errstate around the
    whole run and call :func:`_alu_numpy` directly instead of paying
    the context-manager round-trip per firing."""
    with np.errstate(all="ignore"):
        return _alu_numpy(op, a, b, dtype)


def _alu_numpy(op, a, b, dtype):
    is_int = np.issubdtype(dtype, np.integer)
    if op in (Op.COPY, Op.BRANCH, Op.SINK):
        return a
    if op == Op.ADD: return a + b
    if op == Op.SUB: return a - b
    if op == Op.MUL: return a * b
    if op == Op.DIV:
        return np.where(b == 0, 0, a // np.where(b == 0, 1, b)) if is_int \
            else np.where(b == 0, 0.0, a / np.where(b == 0, 1.0, b))
    if op == Op.AND:
        return (a & b) if is_int else ((a != 0) & (b != 0)).astype(dtype)
    if op == Op.OR:
        return (a | b) if is_int else ((a != 0) | (b != 0)).astype(dtype)
    if op == Op.XOR:
        return (a ^ b) if is_int else ((a != 0) ^ (b != 0)).astype(dtype)
    if op == Op.MAX:
        if is_int:
            return np.maximum(a, b)
        # match the jax ALU's signed-zero tie: max(+0., -0.) is +0. in
        # either order, where np.maximum keeps b's zero
        return np.where((a == 0) & (b == 0), a + b, np.maximum(a, b))
    if op == Op.MIN:
        if is_int:
            return np.minimum(a, b)
        # dually min(+0., -0.) is -0. in either order
        return np.where((a == 0) & (b == 0), -(-a + -b), np.minimum(a, b))
    if op == Op.SHL:
        return (a << np.clip(b, 0, 31)) if is_int else a * np.exp2(b)
    if op == Op.SHR:
        if is_int:
            return a >> np.clip(b, 0, 31)
        two_b = np.exp2(b)
        return a / np.where(two_b == 0, 1, two_b)
    if op == Op.NOT: return (a == 0).astype(dtype)
    if op == Op.IFGT: return (a > b).astype(dtype)
    if op == Op.IFGE: return (a >= b).astype(dtype)
    if op == Op.IFLT: return (a < b).astype(dtype)
    if op == Op.IFLE: return (a <= b).astype(dtype)
    if op == Op.IFEQ: return (a == b).astype(dtype)
    if op == Op.IFDF: return (a != b).astype(dtype)
    raise AssertionError(op)
