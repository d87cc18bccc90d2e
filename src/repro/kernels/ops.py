"""Jitted public wrappers for the Pallas kernels.

On a TPU these call the kernels as Mosaic compiles them; under
``JAX_PLATFORMS=cpu`` they run in interpret mode (same kernel body,
evaluated as plain JAX), which is how the tests check them.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.dataflow_fire import (FabricSpec, block_plan_arrays,
                                         fire_block_batched_pallas,
                                         fire_block_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, bq=128, bk=128):
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk)


@functools.partial(jax.jit, static_argnames=("eps", "rows_blk"))
def rmsnorm(x, w, eps=1e-5, rows_blk=256):
    return rmsnorm_pallas(x, w, eps=eps, rows_blk=rows_blk)


def make_block_step(graph, n_cycles: int, batched: bool = False,
                    tables=None, optimize: bool = False,
                    profile: bool = False, spec=None):
    """Compile the fused K-cycle fire-block kernel for a fabric.

    Returns (tables, jitted step).  Single-stream step signature:
      step(feed_vals, feed_len, full, val, ptr, out_last, out_count)
        -> (full', val', ptr', out_last', out_count', fired[1],
            last_prog[1])
    With batched=True every array gains a leading B axis (grid over
    streams inside the kernel; one dispatch for all B) and the step
    takes a trailing ``active`` int32[B] clock gate: slots with
    active == 0 skip the block entirely (state frozen, fired/last_prog
    0) — pass ``jnp.ones((B,), jnp.int32)`` for the plain wave-batch
    semantics.  Pass a prior call's `tables` to reuse the plan instead
    of rebuilding it; ``optimize=True`` builds opcode-class-specialized
    tables (ignored when `tables` is given — the tables carry their own
    ``class_slices``).  With profile=True the step takes five trailing
    §12 counter arrays (nf, si, so, ab, ahw — per-stream rows when
    batched) and returns them, accumulated in-kernel, after last_prog:
    profiling adds zero extra dispatches.  ``spec``: the tables'
    :class:`FabricSpec`, when the caller keeps one."""
    if tables is None:
        tables = block_plan_arrays(graph, optimize=optimize)
    if spec is None:
        spec = FabricSpec(tables)

    # the batched step is the server's slot step: its jitted module is
    # named ``jit_dataflow_slot_step`` in the profiler's trace
    if batched:
        if profile:
            def dataflow_slot_step(feed_vals, feed_len, full, val, ptr,
                                   out_last, out_count, active,
                                   nf, si, so, ab, ahw):
                return fire_block_batched_pallas(
                    spec, feed_vals, feed_len, full, val, ptr, out_last,
                    out_count, n_cycles=n_cycles, active=active,
                    prof=(nf, si, so, ab, ahw))
        else:
            def dataflow_slot_step(feed_vals, feed_len, full, val, ptr,
                                   out_last, out_count, active):
                return fire_block_batched_pallas(
                    spec, feed_vals, feed_len, full, val, ptr, out_last,
                    out_count, n_cycles=n_cycles, active=active)
        step = jax.jit(dataflow_slot_step)
    elif profile:
        @jax.jit
        def step(feed_vals, feed_len, full, val, ptr, out_last, out_count,
                 nf, si, so, ab, ahw):
            return fire_block_pallas(
                spec, feed_vals, feed_len, full, val, ptr, out_last,
                out_count, n_cycles=n_cycles, prof=(nf, si, so, ab, ahw))
    else:
        @jax.jit
        def step(feed_vals, feed_len, full, val, ptr, out_last, out_count):
            return fire_block_pallas(
                spec, feed_vals, feed_len, full, val, ptr, out_last,
                out_count, n_cycles=n_cycles)

    return tables, step
