"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import naive_attention
from repro.kernels.dataflow_fire import _TABLE_KEYS, _block_body


def flash_attention_ref(q, k, v, *, causal=True):
    return naive_attention(q, k, v, causal=causal)


def rmsnorm_ref(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) *
            w.astype(jnp.float32)).astype(x.dtype)


def fire_block_ref(tables, feed_vals, feed_len, full, val, ptr, out_last,
                   out_count, *, n_cycles: int, prof=None):
    """Same math as the fused block kernel, plain jnp (no pallas_call).
    Also the vmap target for the batched-stream path.  ``prof`` is an
    optional 5-tuple of §12 counter arrays (nf, si, so, ab, ahw); when
    given the return tuple gains the accumulated counters after
    last_prog."""
    tab = {k: jnp.asarray(tables[k]) for k in _TABLE_KEYS}
    return _block_body(tab, jnp.asarray(feed_vals), jnp.asarray(feed_len),
                       full, val, ptr, out_last, out_count,
                       n_cycles=n_cycles,
                       class_slices=tables.get("class_slices")
                       if hasattr(tables, "get") else None,
                       prof=prof)


def fire_block_masked_ref(tables, feed_vals, feed_len, full, val, ptr,
                          out_last, out_count, active, *, n_cycles: int):
    """Single-stream block step gated by a scalar ``active`` flag — the
    pure-jnp mirror of the batched kernel's per-stream clock gate.  When
    active == 0 the state passes through untouched and fired/last_prog
    report 0.  vmapping this over a leading B axis gives the xla
    backend's slot stepper (a `where`-select per row; the Pallas kernel
    genuinely skips the block via `lax.cond`)."""
    res = fire_block_ref(tables, feed_vals, feed_len, full, val, ptr,
                         out_last, out_count, n_cycles=n_cycles)
    keep = active != 0
    old = (full, val, ptr, out_last, out_count)
    kept = tuple(jnp.where(keep, n, o) for n, o in zip(res[:5], old))
    return (*kept, jnp.where(keep, res[5], 0), jnp.where(keep, res[6], 0))


def fire_block_masked_prof_ref(tables, feed_vals, feed_len, full, val, ptr,
                               out_last, out_count, active, nf, si, so, ab,
                               ahw, *, n_cycles: int):
    """Profiled variant of fire_block_masked_ref: threads the §12 fabric
    counters (nf, si, so, ab, ahw) through the block and returns them
    after last_prog.  Clock-gated slots (active == 0) keep their old
    counters untouched — their block never happened, so the per-slot
    partition invariant nf+si+so == profiled-cycles holds."""
    prof = (nf, si, so, ab, ahw)
    res = fire_block_ref(tables, feed_vals, feed_len, full, val, ptr,
                         out_last, out_count, n_cycles=n_cycles, prof=prof)
    keep = active != 0
    old = (full, val, ptr, out_last, out_count)
    kept = tuple(jnp.where(keep, n, o) for n, o in zip(res[:5], old))
    kept_prof = tuple(jnp.where(keep, n, o)
                      for n, o in zip(res[7:12], prof))
    return (*kept, jnp.where(keep, res[5], 0), jnp.where(keep, res[6], 0),
            *kept_prof)
