"""LM Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per assignment: shape/dtype sweeps with hypothesis, assert_allclose
against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 2),
    Sq=st.sampled_from([8, 33, 128]),
    Skv=st.sampled_from([16, 64, 130]),
    Hkv=st.sampled_from([1, 2]),
    G=st.sampled_from([1, 4]),
    hd=st.sampled_from([16, 64]),
    causal=st.booleans(),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_kernel_sweep(B, Sq, Skv, Hkv, G, hd, causal,
                                      dtype):
    if causal and Skv != Sq:
        Skv = Sq  # causal self-attention case
    key = jax.random.key(Sq * 131 + Skv)
    k1, k2, k3 = jax.random.split(key, 3)
    H = Hkv * G
    q = jax.random.normal(k1, (B, Sq, H, hd), dtype)
    k = jax.random.normal(k2, (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(k3, (B, Skv, Hkv, hd), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, bq=32, bk=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bk", [(16, 64), (64, 16), (128, 128)])
def test_flash_attention_block_shape_invariance(bq, bk):
    key = jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (1, 96, 4, 32))
    k = jax.random.normal(k2, (1, 96, 2, 32))
    v = jax.random.normal(k3, (1, 96, 2, 32))
    out = flash_attention_pallas(q, k, v, causal=True, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    rows=st.sampled_from([1, 7, 64, 300]),
    d=st.sampled_from([32, 128, 512]),
    rows_blk=st.sampled_from([8, 256]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_rmsnorm_kernel_sweep(rows, d, rows_blk, dtype):
    key = jax.random.key(rows * 7 + d)
    x = jax.random.normal(key, (rows, d), dtype) * 3
    w = jax.random.normal(jax.random.key(d), (d,), dtype)
    out = rmsnorm_pallas(x, w, rows_blk=rows_blk)
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_3d_batch():
    x = jax.random.normal(jax.random.key(1), (2, 17, 64))
    w = jnp.ones((64,))
    np.testing.assert_allclose(np.asarray(rmsnorm_pallas(x, w)),
                               np.asarray(ref.rmsnorm_ref(x, w)),
                               rtol=1e-5, atol=1e-5)
