"""API-overhead benchmark: what does the `repro.blas` front door cost
per call over the raw jitted kernel?

Rows (CSV: name,n,us_per_call):

  dot_raw_jit    — jax.jit(ops.dot), the floor: kernel + dispatch
  dot_blas_fn    — blas.dot(x, y), the cached function layer
  dot_executable — a pre-compiled Executable's run()/one()

The function layer memoizes its lowered program per (dtype, mode,
interpret), so the delta over the raw kernel is pure Python dispatch
(signature bind + dict hop) — it must stay within a few microseconds,
i.e. negligible against any real kernel. On CPU the kernels run in
interpret mode; the *deltas* are the interesting numbers, not the
absolute times.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import blas
from repro.kernels import ops

DEFAULT_SIZES = (2 ** 12, 2 ** 16)


def _timeit(fn, iters=50, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def main(sizes=DEFAULT_SIZES, iters=50):
    rows = []
    exe = blas.compile(
        {"name": "dot", "routines": [
            {"blas": "dot", "name": "dot",
             "inputs": {"x": "x", "y": "y"},
             "outputs": {"out": "out"}}]})
    for n in sizes:
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k1, (n,), jnp.float32)
        y = jax.random.normal(k2, (n,), jnp.float32)

        raw = jax.jit(lambda x, y: ops.dot(x, y))
        rows.append(("dot_raw_jit", n,
                     _timeit(lambda: raw(x, y), iters)))
        rows.append(("dot_blas_fn", n,
                     _timeit(lambda: blas.dot(x, y), iters)))
        rows.append(("dot_executable", n,
                     _timeit(lambda: exe.one(x=x, y=y), iters)))
    for name, n, us in rows:
        print(f"{name},{n},{us:.2f}")
    return rows


if __name__ == "__main__":
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])
    main()
