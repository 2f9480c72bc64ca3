"""Level-3 blocked-solver benchmark: block-CG vs s-fold vmapped CG,
persisted as BENCH_blocked.json.

The comparison the level-3 anchored-fusion work exists for: solving
``A X = B`` with s right-hand sides either as

* **cg_vmapped** — the shipped CG loop spec, vmapped over the s
  columns via ``Executable.batched()`` (the multi-RHS convention:
  vectors batch on axis 0, the matrix broadcasts); every lane streams
  the full n x n matrix through its own gemv per iteration, or
* **block_cg** — the ``BLOCK_CG_LOOP`` spec, whose gemm-anchored
  fused body streams the matrix ONCE per iteration against the whole
  (n, s) direction panel.

Block-CG's iterates are column-for-column identical to per-column CG
(the s recurrences are independent; they only share the matvec), so
both sides run a FIXED iteration budget (``tol=0.0``,
``max_iters=BENCH_ITERS``) and the wall clock measures per-iteration
throughput, not convergence luck.

Per row we record the *modeled* per-iteration HBM bytes from
``Executable.cost_report`` — the vmapped side charges s independent
body iterations, so its matrix stream is s times block-CG's — plus
interpret-mode wall clock and the **autotuned** block-CG column:
``Executable.tune`` sweeps every distinct body stage program at its
true shapes (the direction panel is loop *state*, resolved through
the cost walk's shape environment), persists winners to the on-disk
tuning table, and the recompiled ``tiles="auto"`` executable is
timed as ``us_block_tuned``.

The perf gate: on every timed row with ``n >= GATE_MIN_N`` and
``s >= GATE_MIN_S`` the autotuned block-CG wall clock must be at
least ``GATE_WALLCLOCK - GATE_NOISE`` times the vmapped-CG wall
clock — the regime the blocked formulation exists for. Below that
the panel is too skinny for the gemm to amortize (dispatch overhead
dominates), so small rows are reported but not gated. The modeled
gate (block-CG per-iteration bytes strictly below vmapped) applies
to every row. This script **exits non-zero** on any violation; CI's
bench-smoke job runs ``--smoke``.

``--json out.json`` persists the results (the committed
BENCH_blocked.json at the repo root is this script's full-size
output).
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.blas as blas
from repro.kernels.common import default_interpret
from repro.solvers import specs
from repro.tune.config import current_device_kind

# (n, s) rows: n the system size, s the right-hand-side count
DEFAULT_CASES = ((256, 4), (512, 4), (512, 8), (1024, 8))
SMOKE_CASES = ((64, 4), (128, 4))
BENCH_ITERS = 10        # fixed budget; iterates identical either way
GATE_WALLCLOCK = 1.0    # tuned block-CG must match/beat vmapped CG
GATE_NOISE = 0.03       # interpret-mode CPU jitter allowance
GATE_MIN_N = 512        # gate regime: big enough that the schedule,
GATE_MIN_S = 4          # not dispatch overhead, is what's measured
TUNE_BUDGET = 10
# extra timing rounds (both sides, floors kept) before declaring a
# sub-gate row a real regression rather than a noisy sample
REMEASURE_ROUNDS = 2


def _system(n, s, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n)).astype(np.float32)
    a = jnp.asarray(m @ m.T + n * np.eye(n, dtype=np.float32))
    B = jnp.asarray(rng.standard_normal((n, s)).astype(np.float32))
    return a, B


def _floor(call, res_field="x", iters=None):
    """Wall-clock floor (min over repeats), the robust estimator for
    one-sided interpret-mode noise (GC pauses, preemption)."""
    res = call()
    jax.block_until_ready(getattr(res, res_field))
    t0 = time.perf_counter()
    res = call()
    jax.block_until_ready(getattr(res, res_field))
    once = time.perf_counter() - t0
    if iters is None:
        # ~0.5s total, between 2 and 15 samples
        iters = max(2, min(15, int(0.5 / max(once, 1e-3))))
    best = once
    for _ in range(iters):
        t0 = time.perf_counter()
        res = call()
        jax.block_until_ready(getattr(res, res_field))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_case(n, s, *, budget=TUNE_BUDGET):
    a, B = _system(n, s)
    x0 = jnp.zeros_like(B)
    bt = jnp.transpose(B)
    x0t = jnp.zeros((s, n), jnp.float32)

    exe_block = blas.compile(specs.BLOCK_CG_LOOP,
                             max_iters=BENCH_ITERS)
    exe_cg = blas.compile(specs.CG_LOOP, max_iters=BENCH_ITERS)

    shapes = {"A": (n, n), "B": (n, s), "x0": (n, s)}
    rep_block = exe_block.cost_report(shapes)
    rep_cg = exe_cg.cost_report({"A": (n, n), "b": n, "x0": n})

    run_block = lambda e: (lambda: e.run(A=a, B=B, x0=x0, tol=0.0))
    run_vmapped = lambda: exe_cg.batched(A=a, b=bt, x0=x0t, tol=0.0)

    us_block = _floor(run_block(exe_block))
    us_vmapped = _floor(run_vmapped)

    # autotuned column: sweep the body stage programs (persisting
    # winners to the on-disk table), recompile with tiles="auto"
    tuned = exe_block.tune(shapes, budget=budget)
    us_tuned = _floor(run_block(tuned))
    for _ in range(REMEASURE_ROUNDS):
        if us_tuned <= us_vmapped * (GATE_WALLCLOCK + GATE_NOISE):
            break
        # keep floors from extra rounds on BOTH sides before calling
        # a near-parity row a regression
        us_tuned = min(us_tuned, _floor(run_block(tuned)))
        us_vmapped = min(us_vmapped, _floor(run_vmapped))

    reports = tuned.tune_report
    if not isinstance(reports, list):
        reports = [reports]
    tiles = {}
    for rep in reports:
        tiles.update({f"{rep.program}:{site}": c.key()
                      for site, c in rep.winners.items()})

    return {
        "name": "block_cg_vs_vmapped_cg", "n": n, "s": s,
        "iters": BENCH_ITERS,
        # modeled per-iteration bytes: the vmapped schedule charges s
        # independent CG body iterations (each lane streams A)
        "bytes_block": int(rep_block.bytes),
        "bytes_vmapped": int(rep_cg.bytes) * s,
        "matrix_bytes_block": int(rep_block.matrix_bytes),
        "matrix_bytes_vmapped": int(rep_cg.matrix_bytes) * s,
        "bytes_reduction": (1.0 - rep_block.bytes
                            / (rep_cg.bytes * s)
                            if rep_cg.bytes else 0.0),
        "us_block": us_block,
        "us_block_tuned": us_tuned,
        "us_cg_vmapped": us_vmapped,
        "wallclock_speedup": us_vmapped / max(us_block, 1e-9),
        "wallclock_speedup_tuned": us_vmapped / max(us_tuned, 1e-9),
        "tiles": tiles or "default",
        "tune_sweeps": sum(rep.sweeps for rep in reports),
        "device_kind": current_device_kind(),
        "interpret": default_interpret(),
    }


def check_gates(entries):
    """The perf-trajectory gates. Returns a list of violations."""
    bad = []
    for e in entries:
        if e["bytes_block"] >= e["bytes_vmapped"]:
            bad.append(
                f"n={e['n']} s={e['s']}: block-CG modeled bytes "
                f"{e['bytes_block']:,} >= vmapped "
                f"{e['bytes_vmapped']:,}")
        sp = e.get("wallclock_speedup_tuned")
        if sp is not None and e["n"] >= GATE_MIN_N \
                and e["s"] >= GATE_MIN_S \
                and sp < GATE_WALLCLOCK - GATE_NOISE:
            bad.append(
                f"n={e['n']} s={e['s']}: autotuned block-CG "
                f"{e['us_block_tuned']:.1f}us is {sp:.3f}x vmapped "
                f"CG {e['us_cg_vmapped']:.1f}us "
                f"(gate {GATE_WALLCLOCK} - noise {GATE_NOISE})")
    return bad


def main(cases=DEFAULT_CASES, json_path=None):
    entries = []
    print("n,s,bytes_block,bytes_vmapped,bytes_reduction,"
          "us_block,us_block_tuned,us_cg_vmapped,speedup_tuned")
    for n, s in cases:
        e = bench_case(n, s)
        entries.append(e)
        print(f"{e['n']},{e['s']},{e['bytes_block']},"
              f"{e['bytes_vmapped']},{e['bytes_reduction']:.3f},"
              f"{e['us_block']:.1f},{e['us_block_tuned']:.1f},"
              f"{e['us_cg_vmapped']:.1f},"
              f"{e['wallclock_speedup_tuned']:.2f}")

    violations = check_gates(entries)
    result = {
        "bench": "blocked",
        "backend": jax.default_backend(),
        "device_kind": current_device_kind(),
        "interpret": default_interpret(),
        "bench_iters": BENCH_ITERS,
        "gates": {
            "wallclock_min_speedup": GATE_WALLCLOCK - GATE_NOISE,
            "gate_min_n": GATE_MIN_N, "gate_min_s": GATE_MIN_S,
            "pass": not violations,
            "violations": violations,
        },
        "entries": entries,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path}")
    if violations:
        print("PERF GATE FAILED:", file=sys.stderr)
        for v in violations:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"# gates OK (block-CG modeled bytes < vmapped on every "
          f"row; autotuned block-CG >= "
          f"{GATE_WALLCLOCK - GATE_NOISE:.2f}x vmapped CG at "
          f"n>={GATE_MIN_N}, s>={GATE_MIN_S})")
    return 0


__all__ = ["main", "bench_case", "check_gates"]


if __name__ == "__main__":
    import argparse
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])

    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", type=int, nargs="+", metavar="N S",
                    help="flat (n, s) pairs, e.g. --cases 512 4 512 8")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI drift + perf-gate check)")
    ap.add_argument("--json", metavar="PATH",
                    help="persist results (BENCH_blocked.json)")
    args = ap.parse_args()
    cases = SMOKE_CASES if args.smoke else DEFAULT_CASES
    if args.cases:
        if len(args.cases) % 2:
            ap.error("--cases takes flat (n, s) pairs")
        cases = tuple(zip(args.cases[::2], args.cases[1::2]))
    sys.exit(main(cases=cases, json_path=args.json))
