"""Level-2 anchored fusion benchmark: HBM bytes + wall clock, fused
(dataflow) vs unfused (nodataflow), persisted as BENCH_fused_l2.json.

Two benchmark families:

* **chains** — the canonical anchored shapes (`symv -> dot`,
  `gemv -> axpy -> nrm2`) as standalone programs;
* **loop bodies** — the CG and Jacobi iteration bodies from
  `solvers.specs`, whose stage programs pick up anchored groups for
  free.

For each entry we record the *modeled* per-call (or per-iteration)
HBM bytes from `Executable.cost_report` — total and the avoidable
vector-handoff share (`vector_bytes`; the matrix stream is identical
in both schedules, see docs/spec.md) — in BOTH conventions the report
carries: `vector_reduction` counts handoff round-trips kept on-chip
(write + read per internal edge), `vector_reduction_exact` counts
only bytes physically not moved (a public intermediate still pays its
one write). Interpret-mode wall clock rides along where the size is
tractable, as do the `Executable.profile` drift columns
(`modeled_us_* / profile_us_* / drift_*`): the roofline time of the
modeled bytes joined per kernel group against instrumented eager wall
clock. On CPU the drift ratio is astronomically large by design — the
model describes the accelerator, the measurement interpret-mode
python — so the number to *watch* across commits is its trajectory,
not its magnitude (see docs/observability.md). The modeled numbers are the stable regression surface:
this script **exits non-zero** when fused byte modeling regresses to
(or above) the unfused baseline, or when the CG body's
vector-traffic round-trip reduction drops below the 25% gate, so
CI's bench-smoke job doubles as the perf-trajectory guard.

Each timed chain row additionally carries the **autotuned** fused
wall clock: the `repro.tune` sweep runs on the chain (persisting its
winners to the on-disk tuning table), the chain is recompiled with
`tiles="auto"`, and `us_fused_tuned` / `wallclock_speedup_tuned`
record the result plus the winning tile keys per site. The wall-clock
gate enforces `wallclock_speedup_tuned >= 1.0` (minus a documented
measurement-noise allowance, `GATE_NOISE`) on every timed row where
fusion is enabled — the rows that used to *lose* wall clock while
winning modeled bytes are now a tracked, enforced number. Every row
also records `device_kind` / `interpret` / `tiles` so BENCH_*
trajectories are comparable across machines.

`--json out.json` persists the results (the committed
BENCH_fused_l2.json at the repo root is this script's full-size
output); `--smoke` runs tiny sizes for CI.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

import repro.blas as blas
from repro.kernels.common import default_interpret
from repro.solvers import specs
from repro.tune import autotuner
from repro.tune.config import current_device_kind

DEFAULT_SIZES = (256, 1024, 4096)
SMOKE_SIZES = (64, 128)
CG_VECTOR_REDUCTION_MIN = 0.25
# wall-clock timing in interpret mode is python-speed; skip huge grids
MAX_TIMED_N = 1024
# autotuned fused must match or beat unfused wall clock; the noise
# allowance covers interpret-mode CPU jitter on rows where the two
# schedules are genuinely at parity (small-n chains are ~75us of
# identical math — a strict 1.0 would coin-flip there). On a real
# device set GATE_NOISE to 0.
GATE_WALLCLOCK = 1.0
GATE_NOISE = 0.03
# the wall-clock gate only applies from this size up: below it every
# candidate tile clamps to the full problem (nothing to tune) and
# per-op dispatch overhead dwarfs the HBM traffic fusion saves, so
# fused-vs-unfused at n=64 measures XLA op count, not the schedule
GATE_MIN_N = 128
TUNE_BUDGET = 10
# extra timing rounds (both sides, floors kept) before declaring a
# sub-1.0 tuned row a real regression rather than a noisy sample
REMEASURE_ROUNDS = 2

SYMV_DOT = {
    "name": "symv_dot",
    "routines": [
        {"blas": "symv", "name": "mv",
         "scalars": {"alpha": 1.0, "beta": 0.0},
         "inputs": {"A": "A", "x": "x", "y": "x"},
         "connections": {"out": "d.x"}},
        {"blas": "dot", "name": "d", "inputs": {"y": "x"},
         "outputs": {"out": "q"}},
    ],
}

GEMV_AXPY_NRM2 = {
    "name": "gemv_axpy_nrm2",
    "routines": [
        {"blas": "gemv", "name": "mv",
         "scalars": {"alpha": 1.0, "beta": 0.0},
         "inputs": {"A": "A", "x": "p", "y": "y0"},
         "connections": {"out": "up.x"}, "outputs": {"out": "q"}},
        {"blas": "axpy", "name": "up",
         "scalars": {"alpha": {"input": "neg_alpha"}},
         "inputs": {"y": "r"},
         "connections": {"out": "rn.x"}, "outputs": {"out": "r_next"}},
        {"blas": "nrm2", "name": "rn", "outputs": {"out": "rnorm"}},
    ],
}


def _sym(n, seed=0):
    a = jax.random.normal(jax.random.PRNGKey(seed), (n, n), jnp.float32)
    return (a + a.T) / 2


def _vec(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)


def _chain_inputs(name, n):
    if name == "symv_dot":
        return {"A": _sym(n, 0), "x": _vec(n, 1)}
    return {"A": jax.random.normal(jax.random.PRNGKey(2), (n, n),
                                   jnp.float32),
            "p": _vec(n, 3), "r": _vec(n, 4),
            "y0": jnp.zeros(n, jnp.float32), "neg_alpha": -0.5}


def _chain_shapes(name, n):
    if name == "symv_dot":
        return {"A": (n, n), "x": n}
    return {"A": (n, n), "p": n, "r": n, "y0": n}


def _time_call(exe, inputs, iters=None):
    """Wall-clock floor (min over repeats) of one eager `exe.run`.
    A floor is the robust estimator here: interpret-mode timings have
    a one-sided noise distribution (GC pauses, scheduler preemption),
    and the gate compares two floors. Repeats adapt to the per-call
    cost so small chains get enough samples to converge."""
    out = exe.run(**inputs)
    jax.block_until_ready(list(out.values()))
    t0 = time.perf_counter()
    out = exe.run(**inputs)
    jax.block_until_ready(list(out.values()))
    once = time.perf_counter() - t0
    if iters is None:
        # ~0.25s total, between 3 and 25 samples
        iters = max(3, min(25, int(0.25 / max(once, 1e-4))))
    best = once
    for _ in range(iters):
        t0 = time.perf_counter()
        out = exe.run(**inputs)
        jax.block_until_ready(list(out.values()))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


PROFILE_ITERS = 2


def _drift_columns(entry, drifts):
    """Flatten per-mode DriftReports into entry columns. `profile_us`
    is the instrumented eager wall clock of the generated kernels —
    bigger than the jitted `us_*` columns (per-call retrace, span
    overhead) but attributable per kernel group, which the jitted
    number is not."""
    for mode, rep in drifts.items():
        tag = "fused" if mode == "dataflow" else "unfused"
        entry[f"modeled_us_{tag}"] = 1e6 * rep.modeled_time_s
        entry[f"profile_us_{tag}"] = 1e6 * rep.measured_s
        entry[f"drift_{tag}"] = rep.drift
    return entry


def _cost_entry(name, kind, n, reports, times=None):
    fused, unfused = reports["dataflow"], reports["nodataflow"]
    entry = {
        "name": name, "kind": kind, "n": n,
        "bytes_fused": int(fused.bytes),
        "bytes_unfused": int(unfused.bytes),
        "bytes_reduction": (1.0 - fused.bytes / unfused.bytes
                            if unfused.bytes else 0.0),
        # physical view: public intermediates still pay their write
        "bytes_fused_exact": int(fused.bytes_exact),
        "vector_bytes_fused": int(fused.vector_bytes),
        "vector_bytes_unfused": int(unfused.vector_bytes),
        "vector_reduction": float(fused.vector_reduction),
        "vector_reduction_exact": float(fused.vector_reduction_exact),
        "matrix_bytes": int(fused.matrix_bytes),
    }
    # machine context: BENCH_* trajectories are only comparable when
    # the device and execution mode match
    entry["device_kind"] = current_device_kind()
    entry["interpret"] = default_interpret()
    entry["tiles"] = "default"
    if times is not None:
        entry["us_fused"] = times["dataflow"]
        entry["us_unfused"] = times["nodataflow"]
        entry["wallclock_speedup"] = (times["nodataflow"]
                                      / max(times["dataflow"], 1e-9))
    return entry


def bench_chain(name, spec, n, *, timed=True, budget=TUNE_BUDGET):
    reports, times, drifts = {}, {}, {}
    exes = {}
    shapes = _chain_shapes(name, n)
    for mode in ("dataflow", "nodataflow"):
        exe = blas.compile(spec, mode=mode)
        exes[mode] = exe
        reports[mode] = exe.cost_report(shapes)
        if timed and n <= MAX_TIMED_N:
            times[mode] = _time_call(exe, _chain_inputs(name, n))
            drifts[mode] = exe.profile(shapes, iters=PROFILE_ITERS)
    entry = _cost_entry(name, "chain", n, reports,
                        times if times else None)
    entry = _drift_columns(entry, drifts)

    if timed and n <= MAX_TIMED_N:
        # autotuned column: sweep (persisting winners to the on-disk
        # table), recompile with tiles="auto", time the result
        tuned = exes["dataflow"].tune(shapes, budget=budget)
        rep = tuned.tune_report
        inputs = _chain_inputs(name, n)
        us_tuned = _time_call(tuned, inputs)
        us_unfused = entry["us_unfused"]
        for _ in range(REMEASURE_ROUNDS):
            if us_tuned <= us_unfused * (GATE_WALLCLOCK + GATE_NOISE):
                break
            # keep floors from extra rounds on BOTH sides before
            # calling a near-parity row a regression
            us_tuned = min(us_tuned, _time_call(tuned, inputs))
            us_unfused = min(us_unfused,
                             _time_call(exes["nodataflow"], inputs))
        entry["us_unfused"] = us_unfused
        entry["wallclock_speedup"] = (us_unfused
                                      / max(entry["us_fused"], 1e-9))
        entry["us_fused_tuned"] = us_tuned
        entry["wallclock_speedup_tuned"] = (us_unfused
                                            / max(us_tuned, 1e-9))
        entry["tiles"] = {s: c.key() for s, c in rep.winners.items()} \
            or "default"
        entry["tune_sweeps"] = rep.sweeps
    return entry


def bench_loop_body(name, loop_spec, n, *, profiled=True):
    """Per-iteration modeled bytes for a loop spec's body, fused vs
    unfused. Window shapes come from the spec's declared operands, so
    any loop spec works (solver_bench reuses this for its
    modeled-bytes section). `profiled` adds the drift columns at
    timing-tractable sizes; callers whose bodies are mostly nested
    inner loops (gmres: the drift join covers top-level stages only,
    so the columns would misrepresent the restart) turn it off."""
    shapes = {op: ((n, n) if kind == "matrix" else n)
              for op, kind in loop_spec["operands"].items()
              if kind != "scalar"}
    reports, drifts = {}, {}
    for mode in ("dataflow", "nodataflow"):
        exe = blas.compile(loop_spec, mode=mode)
        reports[mode] = exe.cost_report(shapes)
        if profiled and n <= MAX_TIMED_N:
            drifts[mode] = exe.profile(shapes, iters=PROFILE_ITERS)
    entry = _cost_entry(name, "loop_body", n, reports)
    return _drift_columns(entry, drifts)


def check_gates(entries):
    """The perf-trajectory gates. Returns a list of violations."""
    bad = []
    for e in entries:
        if e["bytes_fused"] >= e["bytes_unfused"]:
            bad.append(
                f"{e['name']} n={e['n']}: fused bytes "
                f"{e['bytes_fused']:,} >= unfused "
                f"{e['bytes_unfused']:,}")
        if e["name"] == "cg_body" and \
                e["vector_reduction"] < CG_VECTOR_REDUCTION_MIN:
            bad.append(
                f"cg_body n={e['n']}: vector-traffic reduction "
                f"{e['vector_reduction']:.3f} < "
                f"{CG_VECTOR_REDUCTION_MIN}")
        # wall-clock gate: on every timed row where fusion is enabled
        # (and large enough that the schedule, not dispatch overhead,
        # is what's measured) the autotuned fused schedule must not
        # lose to unfused
        sp = e.get("wallclock_speedup_tuned")
        if sp is not None and e["n"] >= GATE_MIN_N and \
                sp < GATE_WALLCLOCK - GATE_NOISE:
            bad.append(
                f"{e['name']} n={e['n']}: autotuned fused wall clock "
                f"{e['us_fused_tuned']:.1f}us is "
                f"{sp:.3f}x unfused {e['us_unfused']:.1f}us "
                f"(gate {GATE_WALLCLOCK} - noise {GATE_NOISE})")
    return bad


def main(sizes=DEFAULT_SIZES, json_path=None, timed=True):
    entries = []
    cols = ("name,kind,n,bytes_fused,bytes_unfused,"
            "vector_reduction,us_fused,us_fused_tuned,us_unfused,"
            "speedup_tuned,drift_fused")
    print(cols)
    for n in sizes:
        rows = [
            bench_chain("symv_dot", SYMV_DOT, n, timed=timed),
            bench_chain("gemv_axpy_nrm2", GEMV_AXPY_NRM2, n,
                        timed=timed),
            bench_loop_body("cg_body", specs.CG_LOOP, n),
            bench_loop_body("jacobi_body", specs.JACOBI_LOOP, n),
        ]
        for e in rows:
            uf = e.get("us_fused")
            ut = e.get("us_fused_tuned")
            uu = e.get("us_unfused")
            sp = e.get("wallclock_speedup_tuned")
            df = e.get("drift_fused")
            print(f"{e['name']},{e['kind']},{e['n']},"
                  f"{e['bytes_fused']},{e['bytes_unfused']},"
                  f"{e['vector_reduction']:.3f},"
                  f"{'' if uf is None else f'{uf:.1f}'},"
                  f"{'' if ut is None else f'{ut:.1f}'},"
                  f"{'' if uu is None else f'{uu:.1f}'},"
                  f"{'' if sp is None else f'{sp:.2f}'},"
                  f"{'' if df is None else f'{df:.3g}'}")
        entries.extend(rows)

    violations = check_gates(entries)
    result = {
        "bench": "fused_l2",
        "backend": jax.default_backend(),
        "device_kind": current_device_kind(),
        "interpret": default_interpret(),
        "gates": {
            "cg_vector_reduction_min": CG_VECTOR_REDUCTION_MIN,
            "wallclock_min_speedup": GATE_WALLCLOCK - GATE_NOISE,
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
    print(f"# gates OK (cg vector-traffic reduction >= "
          f"{CG_VECTOR_REDUCTION_MIN:.0%}; autotuned fused >= "
          f"{GATE_WALLCLOCK - GATE_NOISE:.2f}x unfused on every "
          f"timed fused row)")
    return 0


__all__ = ["main", "bench_chain", "bench_loop_body", "check_gates"]


if __name__ == "__main__":
    import argparse
    import pathlib

    from repro.device import use_compile_cache

    use_compile_cache(pathlib.Path(__file__).parents[1])

    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=list(DEFAULT_SIZES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes (CI drift + perf-gate check)")
    ap.add_argument("--json", metavar="PATH",
                    help="persist results (BENCH_fused_l2.json)")
    ap.add_argument("--no-time", action="store_true",
                    help="skip wall-clock timing (model-only run)")
    args = ap.parse_args()
    sizes = SMOKE_SIZES if args.smoke else tuple(args.sizes)
    sys.exit(main(sizes=sizes, json_path=args.json,
                  timed=not args.no_time))
