"""Solver benchmarks: iterations/s for the dataflow-composed solvers
and the dataflow-vs-nodataflow speedup of the on-device iteration loop.

Covers both solver styles: the class-based SolverPrograms AND the
JSON-described loop programs (cg_spec / jacobi_spec / bicgstab_spec /
gmres_spec rows), so a regression in the spec-level path shows up next
to its hand-written reference. A gmres_spec "iteration" is one
restart of GMRES_BENCH_RESTART Arnoldi steps (three nested count
loops over stacked Krylov state).

CSV: solver,mode,n,iters,us_per_iter[,df_speedup]

Timing excludes compilation (one warm-up solve per configuration). On
CPU the Pallas kernels run in interpret mode, so absolute numbers are
not hardware numbers — the interesting figure is the relative cost of
fused vs per-routine iteration bodies, the same comparison as the
paper's w/DF vs w/o-DF bars.

A second section reports the *modeled* per-iteration HBM bytes of the
JSON loop-spec bodies (registry cost models via
`Executable.cost_report`), fused vs unfused — the level-2 anchored
fusion groups show up here as per-iteration byte savings.

**Compile-once gate**: every solve records the driver's trace_count;
the script exits non-zero if any loop-spec row (GMRES's nested
while-loops included) traces its body more than once — the
per-iteration-retrace regression CI must never re-admit.

`--smoke` runs tiny sizes with few iterations — the CI drift check.
`--json out.json` persists all rows (the BENCH_solvers.json artifact).
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.solvers import (CG, BiCGStab, Jacobi, LoopProgram,
                           PowerIteration, specs)
from repro.solvers.iterative import jacobi_dinv
from repro.tune.config import current_device_kind

try:                              # under benchmarks/run.py
    from benchmarks import fused_l2_bench
except ImportError:               # run directly as a script
    import fused_l2_bench

DEFAULT_SIZES = (256, 1024, 4096)
SMOKE_SIZES = (64, 128)
GMRES_BENCH_RESTART = 8
# one gmres "iteration" is a whole m-step restart cycle: cap the
# restart count so the row costs roughly what the others do
GMRES_MAX_RESTARTS = 5


def _spd(n, seed=0):
    k = jax.random.PRNGKey(seed)
    m = jax.random.normal(k, (n, n), jnp.float32)
    return m @ m.T / n + jnp.eye(n, dtype=jnp.float32)


def _diag_dominant(n, seed=0):
    a = _spd(n, seed)
    return a + 2.0 * jnp.diag(jnp.sum(jnp.abs(a), axis=1))


def _rhs(n):
    return jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)


def _ops_linear(make_A, n):
    A = make_A(n)
    return {"A": A, "b": _rhs(n)}


def _ops_power(make_A, n):
    return {"A": make_A(n)}


def _ops_cg_loop(make_A, n):
    A = make_A(n)
    return {"A": A, "b": _rhs(n), "x0": jnp.zeros(n, jnp.float32)}


def _ops_jacobi_loop(make_A, n):
    A = make_A(n)
    return {"A": A, "b": _rhs(n), "x0": jnp.zeros(n, jnp.float32),
            "dinv": jacobi_dinv(A), "omega": jnp.float32(1.0)}


# name, solver factory (mode, max_iters) -> solver, matrix maker,
# operand packer
CONFIGS = (
    ("cg", lambda m, i: CG(mode=m, max_iters=i), _spd, _ops_linear),
    ("cg_spec",
     lambda m, i: LoopProgram(specs.CG_LOOP, mode=m, max_iters=i),
     _spd, _ops_cg_loop),
    ("bicgstab", lambda m, i: BiCGStab(mode=m, max_iters=i), _spd,
     _ops_linear),
    ("bicgstab_spec",
     lambda m, i: LoopProgram(specs.BICGSTAB_LOOP, mode=m,
                              max_iters=i),
     _spd, _ops_cg_loop),
    ("gmres_spec",
     lambda m, i: LoopProgram(
         specs.gmres_loop(m=GMRES_BENCH_RESTART), mode=m,
         max_iters=max(2, min(i, GMRES_MAX_RESTARTS))),
     _spd, _ops_cg_loop),
    ("jacobi", lambda m, i: Jacobi(mode=m, max_iters=i),
     _diag_dominant, _ops_linear),
    ("jacobi_spec",
     lambda m, i: LoopProgram(specs.JACOBI_LOOP, mode=m, max_iters=i),
     _diag_dominant, _ops_jacobi_loop),
    ("power", lambda m, i: PowerIteration(mode=m, max_iters=i), _spd,
     _ops_power),
)


def _time_solve(solver, operands, iters=3):
    run = lambda: solver.solve(**operands, tol=0.0)  # noqa: E731
    res = run()                       # warm-up: compile + first solve
    jax.block_until_ready(res.x)
    t0 = time.perf_counter()
    for _ in range(iters):
        res = run()
    jax.block_until_ready(res.x)
    us = (time.perf_counter() - t0) / iters * 1e6
    return us, int(res.iterations)


def bench_one(name, make_solver, make_A, make_ops, n, max_iters):
    """Times a full max_iters solve (tol=0 so no early exit) in both
    modes; returns rows of (solver, mode, n, iters, us_per_iter,
    trace_count)."""
    operands = make_ops(make_A, n)
    rows = []
    per_iter = {}
    for mode in ("dataflow", "nodataflow"):
        solver = make_solver(mode, max_iters)
        us, iters = _time_solve(solver, operands)
        per_iter[mode] = us / max(iters, 1)
        rows.append((name, mode, n, iters, per_iter[mode],
                     solver.trace_count))
    speedup = per_iter["nodataflow"] / per_iter["dataflow"]
    return rows, (name, n, speedup)


def modeled_bytes_rows(sizes):
    """Per-iteration modeled HBM bytes for the JSON loop-spec bodies,
    fused (dataflow, incl. level-2 anchored groups) vs unfused —
    delegated to fused_l2_bench so the numbers in BENCH_solvers.json
    and BENCH_fused_l2.json come from one implementation. (The
    bicgstab row charges the cond's full-step branch; the gmres row
    charges one whole restart — inner count loops times their trip
    counts.) cg/jacobi/bicgstab rows also carry the
    `Executable.profile` drift columns at timing-tractable sizes;
    gmres is excluded — its body is mostly nested inner loops, which
    the top-level-stage drift join does not cover."""
    rows = []
    for name, loop_spec, profiled in (
            ("cg_spec", specs.CG_LOOP, True),
            ("jacobi_spec", specs.JACOBI_LOOP, True),
            ("bicgstab_spec", specs.BICGSTAB_LOOP, True),
            ("gmres_spec", specs.gmres_loop(m=GMRES_BENCH_RESTART),
             False)):
        for n in sizes:
            e = fused_l2_bench.bench_loop_body(name, loop_spec, n,
                                               profiled=profiled)
            row = {
                "solver": name, "n": n,
                "bytes_per_iter_fused": e["bytes_fused"],
                "bytes_per_iter_unfused": e["bytes_unfused"],
                "vector_reduction": e["vector_reduction"],
            }
            for k in ("modeled_us_fused", "profile_us_fused",
                      "drift_fused", "modeled_us_unfused",
                      "profile_us_unfused", "drift_unfused"):
                if k in e:
                    row[k] = e[k]
            rows.append(row)
    return rows


def main(sizes=DEFAULT_SIZES, max_iters=20, json_path=None):
    print("solver,mode,n,iters,us_per_iter")
    timing_rows, speedups, trace_violations = [], [], []
    for name, make_solver, make_A, make_ops in CONFIGS:
        for n in sizes:
            rows, sp = bench_one(name, make_solver, make_A, make_ops,
                                 n, max_iters)
            for rname, mode, nn, iters, us, tc in rows:
                print(f"{rname},{mode},{nn},{iters},{us:.1f}")
                # machine context so BENCH_solvers.json trajectories
                # are comparable across hosts; `tiles` records the
                # tile policy the solve compiled under ("auto" =
                # whatever the persisted tuning table held)
                timing_rows.append({"solver": rname, "mode": mode,
                                    "n": nn, "iters": iters,
                                    "us_per_iter": us,
                                    "trace_count": tc,
                                    "device_kind": current_device_kind(),
                                    "interpret": default_interpret(),
                                    "tiles": "auto"})
                if tc > 1:
                    trace_violations.append(
                        f"{rname} mode={mode} n={nn}: iteration body "
                        f"traced {tc}x (must compile once)")
            speedups.append(sp)
    print()
    print("solver,n,df_speedup")
    for name, n, sp in speedups:
        print(f"{name},{n},{sp:.2f}")
    print()
    print("solver,n,bytes_per_iter_fused,bytes_per_iter_unfused,"
          "vector_reduction")
    byte_rows = modeled_bytes_rows(sizes)
    for r in byte_rows:
        print(f"{r['solver']},{r['n']},{r['bytes_per_iter_fused']},"
              f"{r['bytes_per_iter_unfused']},"
              f"{r['vector_reduction']:.3f}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({
                "bench": "solvers",
                "backend": jax.default_backend(),
                "timing": timing_rows,
                "df_speedups": [
                    {"solver": s, "n": n, "df_speedup": sp}
                    for s, n, sp in speedups],
                "modeled_bytes_per_iter": byte_rows,
            }, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path}")
    if trace_violations:
        print("\nTRACE-COUNT GATE FAILED (compile-once regression):",
              file=sys.stderr)
        for v in trace_violations:
            print(f"  {v}", file=sys.stderr)
        sys.exit(1)
    return speedups


if __name__ == "__main__":
    import argparse
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=list(DEFAULT_SIZES))
    ap.add_argument("--max-iters", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + few iterations (CI drift check)")
    ap.add_argument("--json", metavar="PATH",
                    help="persist results (BENCH_solvers.json artifact)")
    args = ap.parse_args()
    if args.smoke:
        main(sizes=SMOKE_SIZES, max_iters=5, json_path=args.json)
    else:
        main(sizes=tuple(args.sizes), max_iters=args.max_iters,
             json_path=args.json)
