"""Paper Fig. 3 reproduction: axpy, gemv, axpydot across input sizes.

Variants mirror the paper's evaluation matrix:
  - PL movers  vs on-chip data  -> host-resident operands vs operands
    generated inside the jitted program ("no PL": no off-chip reads)
  - w/ DF vs w/o DF (axpydot)   -> fused dataflow kernel vs two
    kernels with an HBM round-trip for z
  - CPU baseline                -> the jnp/XLA reference path (the
    OpenBLAS analogue on this host)

Prints ``name,n,us_per_call,derived`` CSV rows like the other
benchmarks. On CPU the Pallas kernels run in interpret mode, so
absolute times are NOT hardware numbers; the *ratios* between DF and
no-DF variants reproduce the paper's qualitative result and the same
harness runs unmodified on real TPU.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import axpydot_program
from repro.kernels import ops, ref


def _timeit(fn, *args, iters=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _vecs(n, k, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), k)
    return [jax.random.normal(kk, (n,), dtype=jnp.float32)
            for kk in keys]


def bench_axpy(sizes, rows):
    for n in sizes:
        x, y = _vecs(n, 2)
        alpha = jnp.float32(1.5)
        ker = jax.jit(lambda a, x, y: ops.axpy(a, x, y))
        rows.append(("axpy_kernel_pl", n, _timeit(ker, alpha, x, y)))
        cpu = jax.jit(lambda a, x, y: ref.axpy(a, x, y))
        rows.append(("axpy_cpu_ref", n, _timeit(cpu, alpha, x, y)))

        # on-chip data generation (paper's "no PL"): operands produced
        # inside the program, no host->HBM transfer
        @jax.jit
        def onchip(a, n=n):
            i = jnp.arange(n, dtype=jnp.float32)
            return ops.axpy(a, jnp.sin(i * 1e-3), jnp.cos(i * 1e-3))
        rows.append(("axpy_kernel_nopl", n, _timeit(onchip, alpha)))


def bench_gemv(sizes, rows):
    for n in sizes:
        m = n
        key = jax.random.PRNGKey(1)
        a = jax.random.normal(key, (m, n), dtype=jnp.float32)
        x, y = _vecs(n, 2, seed=2)
        y = y[:m] if m <= n else jnp.pad(y, (0, m - n))
        ker = jax.jit(lambda a, x, y: ops.gemv(1.0, a, x, 0.5, y))
        rows.append(("gemv_kernel_pl", n, _timeit(ker, a, x, y)))
        cpu = jax.jit(lambda a, x, y: ref.gemv(1.0, a, x, 0.5, y))
        rows.append(("gemv_cpu_ref", n, _timeit(cpu, a, x, y)))


def bench_axpydot(sizes, rows):
    prog_df = axpydot_program(mode="dataflow")
    prog_nodf = axpydot_program(mode="nodataflow")
    run_df = prog_df.jitted()
    run_nodf = prog_nodf.jitted()
    for n in sizes:
        w, v, u = _vecs(n, 3, seed=3)
        na = jnp.float32(-0.7)
        t_df = _timeit(lambda: run_df(neg_alpha=na, w=w, v=v, u=u))
        t_nodf = _timeit(lambda: run_nodf(neg_alpha=na, w=w, v=v, u=u))
        cpu = jax.jit(lambda a, w, v, u: ref.axpydot(a, w, v, u))
        t_cpu = _timeit(cpu, jnp.float32(0.7), w, v, u)
        rows.append(("axpydot_df", n, t_df))
        rows.append(("axpydot_nodf", n, t_nodf))
        rows.append(("axpydot_cpu_ref", n, t_cpu))
        rows.append(("axpydot_df_speedup_vs_nodf", n, t_nodf / t_df))


def main(sizes=(2 ** 12, 2 ** 14, 2 ** 16, 2 ** 18)):
    rows = []
    bench_axpy(sizes, rows)
    bench_gemv((256, 1024, 2048), rows)
    bench_axpydot(sizes, rows)
    for name, n, us in rows:
        print(f"{name},{n},{us:.1f}")
    return rows


if __name__ == "__main__":
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])
    main()
