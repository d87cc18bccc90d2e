"""Kernel micro-benchmarks: Pallas vs jnp reference.

Under ``JAX_PLATFORMS=cpu`` the Pallas kernels run in interpret mode, so
those wall times are host timings of the interpreter, not device speeds.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.core import library
from repro.kernels import ops as kops
from repro.kernels.dataflow_fire import FabricSpec


def _time(fn, reps=5):
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def main():
    key = jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)
    B, S, H, hd = 1, 512, 8, 64
    q = jax.random.normal(k1, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(k2, (B, S, 2, hd), jnp.float32)
    v = jax.random.normal(k3, (B, S, 2, hd), jnp.float32)
    ref_fa = jax.jit(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=True))
    us = _time(lambda: ref_fa(q, k, v))
    flops = 2 * 2 * B * H * S * S * hd
    print(f"kernel_flash_ref_jnp,{us:.1f},"
          f"gflops={flops / us / 1e3:.1f};shape={B}x{S}x{H}x{hd}")
    # pallas interpret (correctness path; slow on CPU by design)
    us_p = _time(lambda: flash_attention_pallas(
        q[:, :128], k[:, :128], v[:, :128], causal=True, bq=64, bk=64))
    print(f"kernel_flash_pallas_interpret,{us_p:.1f},"
          f"note=interpret-mode;vmem_tile=64x{hd}")

    x = jax.random.normal(k1, (4096, 1024), jnp.float32)
    w = jnp.ones((1024,))
    ref_rn = jax.jit(lambda x, w: ref.rmsnorm_ref(x, w))
    us = _time(lambda: ref_rn(x, w))
    gbs = 2 * x.size * 4 / us / 1e3
    print(f"kernel_rmsnorm_ref_jnp,{us:.1f},gbps={gbs:.1f}")
    us_p = _time(lambda: rmsnorm_pallas(x[:256], w, rows_blk=256))
    print(f"kernel_rmsnorm_pallas_interpret,{us_p:.1f},"
          f"note=interpret-mode;vmem_tile=256x1024")

    # dataflow fire block (K=16 cycles of the popcount fabric, 128 slots)
    bench = library.popcount_graph(16)
    B, K = 128, 16
    tables, step = kops.make_block_step(bench.graph, K, batched=True)
    sp = FabricSpec(tables)
    full = jnp.zeros((B, sp.A2), jnp.int32).at[:, sp.FULL_PAD].set(1)
    z = lambda *s: jnp.zeros((B, *s), jnp.int32)
    args = (z(sp.n_in, K), z(sp.n_in), full, z(sp.A2), z(sp.n_in),
            z(sp.n_out), z(sp.n_out), jnp.ones((B,), jnp.int32))
    us = _time(lambda: step(*args))
    print(f"kernel_dataflow_fire_block_{jax.default_backend()},{us:.1f},"
          f"nodes={len(bench.graph.nodes)};arcs={sp.A};slots={B};"
          f"cycles={K}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
