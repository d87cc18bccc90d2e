"""Jitted public wrappers for the LM Pallas kernels (flash attention,
RMSNorm).

On a TPU these call the kernels as Mosaic compiles them; under
``JAX_PLATFORMS=cpu`` they run in interpret mode (same kernel body,
evaluated as plain JAX), which is how the tests check them.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, bq=128, bk=128):
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk)


@functools.partial(jax.jit, static_argnames=("eps", "rows_blk"))
def rmsnorm(x, w, eps=1e-5, rows_blk=256):
    return rmsnorm_pallas(x, w, eps=eps, rows_blk=rows_blk)
