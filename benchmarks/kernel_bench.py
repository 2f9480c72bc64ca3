"""Per-kernel microbenchmarks: Pallas (interpret on CPU) vs jnp ref.

CSV: name,shape,us_per_call. On CPU the interesting derived number is
correctness-at-scale + the ref timing; Pallas wall-times are interpret
mode (not hardware).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.attention import mha as mha_kernel


def _timeit(fn, *args, iters=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main():
    rows = []
    key = jax.random.PRNGKey(0)

    for m, k, n in ((256, 256, 256), (512, 512, 512)):
        a = jax.random.normal(key, (m, k), jnp.float32)
        b = jax.random.normal(key, (k, n), jnp.float32)
        rows.append((f"gemm_ref_{m}x{k}x{n}", _timeit(
            jax.jit(ref.matmul), a, b)))
        rows.append((f"gemm_pallas_{m}x{k}x{n}", _timeit(
            lambda a, b: ops.matmul(a, b, block_m=128, block_n=128,
                                    block_k=128), a, b)))

    b_, h, s, d = 1, 4, 256, 64
    q = jax.random.normal(key, (b_, h, s, d), jnp.float32)
    kk = jax.random.normal(key, (b_, h, s, d), jnp.float32)
    v = jax.random.normal(key, (b_, h, s, d), jnp.float32)
    rows.append((f"flash_ref_{s}", _timeit(
        jax.jit(lambda q, k, v: ref.mha(q, k, v, causal=True)),
        q, kk, v)))
    rows.append((f"flash_pallas_{s}", _timeit(
        lambda q, k, v: mha_kernel(q, k, v, causal=True, block_q=128,
                                   block_k=128), q, kk, v)))

    for name, us in rows:
        print(f"{name},-,{us:.1f}")
    return rows


if __name__ == "__main__":
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])
    main()
