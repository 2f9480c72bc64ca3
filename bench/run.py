#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from `BENCHMARK.json` (see `bench/harness.py`). Set-up makes
the cell's data on the device from `--seed`, compiles the library's
entry (JAX's persistent compilation cache sits in `.jax_cache/` of the
checkout, or in `$JAX_COMPILATION_CACHE_DIR`) and warms it. Then one
client sends requests for `--seconds`, with as many in flight as
the traffic mix allows (`bench/loop.py`). With `--trace 1`
a second window of the same length follows under the profiler, and
the per-layer metrics are reported in place of the end-to-end ones:
the device's from the traced window, the host clock's from the first.

Once the windows have closed, what they produced is compared with the
configuration's plain reference. Each number compared is printed with
its limit as the last lines of standard error, and the last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device`, with `--trace 1` `breakdown`, and last `checks`.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the library (no `src/repro`), it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _environment() -> None:
    # libtpu writes its logs under /tmp unless told otherwise; a run
    # writes nothing outside its checkout and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _paths() -> None:
    # run as a script, the first entry of sys.path is bench/ itself,
    # where `trace.py` would shadow the standard library's `trace`
    here = str(ROOT / "bench")
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        _err(f"bench: {ROOT} is not a checkout of the library "
             f"(no src/repro)")
        return 2
    _environment()
    _paths()
    from bench import harness
    c = harness.cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        _err(f"bench: {args.workload} needs {c.chips} TPU chip(s); JAX "
             f"found {len(devices)} {devices[0].platform!r} device(s)")
        return 3
    _err(f"bench: compile cache {harness.use_compile_cache()}")

    kind = harness.prepare(c, args.seed)
    setup_s = time.perf_counter() - T0
    _err(f"bench: set-up {setup_s} s")
    m = harness.measure(kind, args.seconds, bool(args.trace), setup_s,
                        log=lambda s: _err(f"bench: {s}"))
    line = harness.result_line(c, m, bool(args.trace))
    for name, chk in line["checks"].items():
        _err(f"check {name}: {chk['value']!r} limit {chk['limit']!r} "
             f"{'ok' if chk['value'] <= chk['limit'] else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
