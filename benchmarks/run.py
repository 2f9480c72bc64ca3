"""Benchmark runner: one section per paper table/figure.

  fig3      — paper Fig. 3 (axpy/gemv/axpydot, DF vs no-DF, PL vs
              on-chip, CPU baseline)           [the paper's only figure]
  kernels   — per-kernel microbenchmarks
  solvers   — iterative-solver iteration throughput, DF vs no-DF
  fused_l2  — level-2 anchored fusion: HBM bytes + wall clock,
              fused vs unfused (the BENCH_fused_l2.json gate)
  api       — repro.blas front-door dispatch overhead vs raw jitted
              kernels (the public-API tax must stay negligible)
  roofline  — the (arch x shape) roofline table from the dry-run
              artifacts (run `python -m repro.launch.dryrun --all`
              first; skipped gracefully if absent)

Prints ``name,n,us_per_call`` CSV per row. `--json out.json` persists
every section's CSV text (plus structured solver speedups) so CI can
upload the run as a BENCH_*.json artifact and the perf trajectory
accretes run over run.
"""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

from benchmarks import (api_overhead, fig3_routines, fused_l2_bench,
                        kernel_bench, roofline_table, solver_bench)


def _section(captured, name, fn):
    """Run one section, echoing its output and keeping the CSV text
    for the --json artifact. Echo happens in a finally so a failing
    benchmark still surfaces whatever it printed before raising."""
    print(f"== {name} ==")
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            result = fn()
    finally:
        text = buf.getvalue()
        print(text, end="")
        print()
        captured[name] = text
    return result


def main(json_path=None) -> int:
    captured: dict = {}
    _section(captured, "fig3: routine benchmarks (paper Fig. 3)",
             lambda: fig3_routines.main(sizes=(2 ** 12, 2 ** 14,
                                               2 ** 16)))
    _section(captured, "kernel microbenchmarks", kernel_bench.main)
    speedups = _section(
        captured, "solver benchmarks (dataflow-composed iteration loops)",
        lambda: solver_bench.main(sizes=(256, 1024), max_iters=10))
    gate_rc = _section(
        captured, "level-2 anchored fusion (fused vs unfused)",
        lambda: fused_l2_bench.main(sizes=(256, 1024)))
    _section(captured, "public-API dispatch overhead (repro.blas)",
             api_overhead.main)
    if roofline_table.RESULTS.exists():
        _section(captured, "roofline table (from dry-run artifacts)",
                 roofline_table.main)
    else:
        print("== roofline table (from dry-run artifacts) ==")
        print("(no dry-run results yet — run "
              "`python -m repro.launch.dryrun --all`)")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({
                "bench": "run_all",
                "sections": captured,
                "solver_df_speedups": [
                    {"solver": s, "n": n, "df_speedup": sp}
                    for s, n, sp in (speedups or [])],
                "fused_l2_gate_ok": gate_rc == 0,
            }, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path}")
    return int(gate_rc or 0)


if __name__ == "__main__":
    import argparse

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH",
                    help="persist all sections as a BENCH_*.json "
                         "artifact")
    args = ap.parse_args()
    sys.exit(main(json_path=args.json))
