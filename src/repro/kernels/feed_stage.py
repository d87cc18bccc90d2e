"""Ragged admission staging: admitted feed streams into the slot feed buffer.

An admission round ships the tokens of the requests it admits as one
flat int32 buffer: admitted slots in order, each slot's input arcs in
plan order, each stream back to back.  :func:`place_feeds` writes those
slots' rows of the feed buffer ``fv[B, n_in, L]`` from it on the device:
row ``(b, k)`` takes its ``fl[b, k]`` tokens from the running offset and
zeros from there to ``L``, so a slot's older, longer stream leaves
nothing behind.  Rows of slots not in the round are not touched: the
kernel writes the feed buffer in place (aliased), one DMA per admitted
slot, and never reads or writes the others.

Within the kernel the flat buffer sits in VMEM as rows of ``L`` lanes.
A stream starting at offset ``o`` spans rows ``o // L`` and the next; it
is picked out of an aligned 16-row window, rotated by ``o % L`` lanes
and masked past its length: vector work per row, no gather, no scatter.
On a v5e an XLA gather, row slices or a scatter from the flat buffer
took 0.4-30 ms a round at the benchmark's sizes, this kernel 20-170 us
(PERF.md §6).

Mosaic rotates lanes in whole vregs only, so a feed buffer shorter than
128 lanes, or not a multiple of them, is placed by one XLA scatter of
the flat buffer instead: its capacity, and so the scatter, is small.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dataflow_fire import LANES, interpret_mode

_ROWS_UNROLLED = 8     # feed rows built per step of the row loop
_WINDOW = 16           # flat-buffer rows read per feed row (2 x 8 sublanes)


def _place_kernel(n_ref, order_ref, fl_ref, flat_ref, fv_in, fv_out, buf,
                  sem):
    """Build each admitted slot's [n_in, L] block in VMEM and DMA it to
    its row of the feed buffer; two blocks alternate so building one
    overlaps the other's copy."""
    del fv_in                  # aliased to fv_out
    _, n_in, L = buf.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    sub = lax.broadcasted_iota(jnp.int32, (_WINDOW, L), 0)

    def copy(i, j):
        return pltpu.make_async_copy(buf.at[j], fv_out.at[order_ref[i]],
                                     sem.at[j])

    def row(j, b, k, off):
        n = fl_ref[b * n_in + k]
        q = off // L
        r = off - q * L
        q8 = pl.multiple_of((q // 8) * 8, 8)
        w = flat_ref[pl.ds(q8, _WINDOW), :]
        s = q - q8
        head = jnp.sum(jnp.where(sub == s, w, 0), axis=0, keepdims=True)
        tail = jnp.sum(jnp.where(sub == s + 1, w, 0), axis=0, keepdims=True)
        shift = (L - r) % L
        v = jnp.where(lane < L - r, pltpu.roll(head, shift, 1),
                      pltpu.roll(tail, shift, 1))
        buf[j, pl.ds(k, 1), :] = jnp.where(lane < n, v, 0)
        return off + n

    def rows(j, b, first, count, off):
        for u in range(count):
            off = row(j, b, first + u, off)
        return off

    def slot(i, off):
        j = i % 2

        @pl.when(i >= 2)
        def _():
            copy(i - 2, j).wait()
        b = order_ref[i]
        U = min(_ROWS_UNROLLED, n_in)
        off = lax.fori_loop(0, n_in // U,
                            lambda c, o: rows(j, b, c * U, U, o), off)
        off = rows(j, b, n_in // U * U, n_in % U, off)
        copy(i, j).start()
        return off

    n = n_ref[0]
    lax.fori_loop(0, n, slot, jnp.int32(0))
    for back in (2, 1):
        @pl.when(n >= back)
        def _():
            copy(n - back, (n - back) % 2).wait()


def place_feeds(fv, flat, mask, order, fl):
    """The feed buffer with the masked slots refilled from ``flat``.

    fv[B, n_in, L] int32 (written in place when donated by the caller's
    jit); flat[C] int32 holds the masked slots' streams back to back, in
    ``order`` (C a multiple of L); mask[B] bool; order[B] int32 the
    masked slot ids, ascending, first; fl[B, n_in] int32 the stream
    lengths, read for the masked slots only.  Callable inside a jit."""
    if fv.shape[2] % LANES:
        return _place_scatter(fv, flat, mask, fl)
    n = jnp.sum(mask, dtype=jnp.int32).reshape(1)
    _, n_in, L = fv.shape
    flat2 = jnp.pad(flat, (0, _WINDOW * L)).reshape(-1, L)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    interp = interpret_mode()
    return pl.pallas_call(
        _place_kernel,
        in_specs=[smem, smem, smem, pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(fv.shape, fv.dtype),
        scratch_shapes=[pltpu.VMEM((2, n_in, L), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={4: 0},
        interpret=interp,
        compiler_params=None if interp else pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(flat2.shape[0], n_in, L)),
        name="slot_feed_place",
    )(n, order, fl.reshape(-1), flat2, fv)


def _place_scatter(fv, flat, mask, fl):
    """:func:`place_feeds` for a feed buffer not lane-aligned: zero the
    masked rows, then scatter each staged token to its row and lane."""
    B, n_in, L = fv.shape
    C = flat.shape[0]
    lens = jnp.where(mask[:, None], fl, 0).reshape(-1)
    ends = jnp.cumsum(lens)
    starts = ends - lens
    # the row of each staged token: the last non-empty row starting at
    # or before it (rows start in ascending order)
    first = jnp.zeros((C,), jnp.int32).at[
        jnp.where(lens > 0, starts, C)].max(
        jnp.arange(B * n_in, dtype=jnp.int32), mode="drop")
    row = lax.cummax(first)
    pos = jnp.arange(C, dtype=jnp.int32)
    dest = jnp.where(pos < ends[-1], row * L + pos - starts[row], B * n_in * L)
    out = jnp.where(mask[:, None, None], 0, fv).reshape(-1)
    return out.at[dest].set(flat, mode="drop",
                            unique_indices=True).reshape(fv.shape)


def _vmem_bytes(flat_rows: int, n_in: int, L: int) -> int:
    """Scoped VMEM for the flat buffer, the two blocks and headroom."""
    need = 4 * L * (flat_rows + 2 * max(n_in, 8))
    return int(min(max(need + (8 << 20), 32 << 20), 100 << 20))

