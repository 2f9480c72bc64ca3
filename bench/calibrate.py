#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, on the
chip, in one process (so the program compiles once):

    python3 bench/calibrate.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --seconds 3

For each of `--seeds` seeds the library runs the cell's traffic for a
short window and every number the check compares is read. Then the
control (the configuration's plain reference in the library's place,
at the `"high"` precision below the configuration's) and the reference
at the configuration's own precision each run on `--control-seeds`
other seeds. One JSON line per run goes to standard output, and a
summary last: the largest reading of the library and the smallest of
the control, for each number. The benchmark's runs never do this.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    args = ap.parse_args(argv)

    here = str(ROOT / "bench")
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    c = harness.cell(args.workload)

    runs = [(None, args.first_seed + i) for i in range(args.seeds)]
    base = args.first_seed + args.seeds
    for precision in ("high", "highest"):
        runs += [(precision, base + i) for i in range(args.control_seeds)]
        base += args.control_seeds
    readings: dict = {}
    for precision, seed in runs:
        kind = harness.prepare(c, seed, precision=precision)
        m = harness.measure(kind, args.seconds, trace=False, setup_s=0.0,
                            log=lambda s: None)
        del kind
        row = {"workload": c.name, "seed": seed,
               "entry": precision or "library",
               "requests": m.rec["requests"],
               "checks": {n: v for n, (v, _) in m.checks.items()},
               "limits": {n: lim for n, (_, lim) in m.checks.items()}}
        print(json.dumps(row), flush=True)
        readings.setdefault(row["entry"], []).append(row["checks"])
    summary = {
        "workload": c.name,
        "library_max": {n: max(r[n] for r in readings["library"])
                        for n in readings["library"][0]},
        "control_min": {n: min(r[n] for r in readings["high"])
                        for n in readings["high"][0]},
        "reference_max": {n: max(r[n] for r in readings["highest"])
                          for n in readings["highest"][0]}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
