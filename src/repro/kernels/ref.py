"""Pure-jnp oracles for the LM Pallas kernels (the allclose ground
truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import naive_attention


def flash_attention_ref(q, k, v, *, causal=True):
    return naive_attention(q, k, v, causal=causal)


def rmsnorm_ref(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) *
            w.astype(jnp.float32)).astype(x.dtype)
