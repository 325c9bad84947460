"""Kernel-level microbenchmarks.

Wall-clock on this container measures the jnp reference implementations
(XLA:CPU); the Pallas kernels themselves are validated in interpret mode
(tests/) and characterised here by their *structural* roofline: VMEM
working set and the HBM-traffic saving of the fragmentation static region.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.streamed_matmul import vmem_bytes

from .common import emit, timeit


def run() -> None:
    key = jax.random.PRNGKey(0)

    # streamed matmul ref throughput + fragmentation traffic model
    M, K, N = 256, 4096, 4096
    x = jax.random.normal(key, (M, K), jnp.float32)
    for frac in (0.0, 0.5, 1.0):
        ks = max(int(K * frac) // 128 * 128, 128)
        ks = min(ks, K - 128)
        ws = jax.random.normal(key, (ks, N), jnp.float32)
        wd = jax.random.normal(key, (K - ks, N), jnp.float32)
        f = jax.jit(lambda a, b, c: ref.streamed_matmul_ref(a, b, c))
        f(x, ws, wd).block_until_ready()
        us = timeit(lambda: f(x, ws, wd).block_until_ready())
        nm = M // 128
        traffic_full = nm * K * N * 2                  # every panel re-read
        traffic_frag = (ks * N + nm * (K - ks) * N) * 2
        emit(f"kernel/streamed_matmul_static{frac:.1f}", us,
             f"flops={2 * M * K * N / 1e9:.2f}G "
             f"hbm_traffic_saving={1 - traffic_frag / traffic_full:.2f} "
             f"vmem_claim_mb={vmem_bytes(ks, N, 128, 128, 128) / 2 ** 20:.1f}")

    # bfp8 codec
    xx = jax.random.normal(key, (1024, 1024), jnp.float32)
    qf = jax.jit(lambda a: ref.bfp8_quant_ref(a))
    qf(xx)[0].block_until_ready()
    us = timeit(lambda: qf(xx)[0].block_until_ready())
    emit("kernel/bfp8_quant_ref_1M", us,
         f"ratio={(8 + 8 / 32) / 16:.3f} throughput_gbps="
         f"{xx.size * 4 / (us / 1e6) / 1e9:.1f}")


if __name__ == "__main__":
    run()
