#!/usr/bin/env python3
"""Read one cell's layers from inside the library: the front door, the
host's garbage collector, operand padding and compiling.

    python3 bench/layers.py --workload <cell> --seed <n> \\
        --seconds <s> --record <0|1>

The run is a `bench/run.py --trace 1` run: the same set-up, then the
measured window with the profiler off, then a traced window under it.
With `--record 1` the library's recording (`repro.obs`) is turned on
before set-up, so compiles are spans and every collection of the
garbage collector is counted and, under the profiler, annotated.
`repro.obs`'s aggregates are snapshotted around the measured window.
This probe stands in until the benchmark's harness takes those
snapshots itself; then it goes.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the library, it exits non-zero and prints no result. The
last line of standard output is one JSON object:

    front_door_us.call   host µs inside `Executable.run` per call of
                         the measured window (the `blas.run` aggregate)
    dispatch_us          the benchmark's clock around `send` per call
                         of the same window (`bench/metrics/`)
    host_gc_us.call      the collector's pauses in the measured window
                         per call (`--record 1` only)
    pad_us.call          the pads' self time among the traced window's
                         `device_ops`, per traced call, as the
                         benchmark reads it (`bench/metrics/`)
    pad_scope_us.call    union of the device intervals of the ops under
                         a `pad` name scope in the traced window, per
                         traced call (`bench.scopes`): its cross-check
    compile_s.setup      union of the `jax.compile` and `lowering.*`
                         spans of set-up (`--record 1` only)
    calls_per_s, device_idle.call, setup_s, device_ops, device
    gaps                 the traced window's idle gaps of 50 ms or
                         more: [label, seconds], as `bench.trace`
                         labels them

A metric with nothing to read is left out.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
GAP_S = 0.05       # the shortest idle gap listed


def _paths() -> None:
    here = str(ROOT / "bench")      # `trace.py` would shadow `trace`
    sys.path[:] = [p for p in sys.path
                   if str(pathlib.Path(p or ".").resolve()) != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device(chips: int) -> Optional[dict]:
    """JAX's devices (platform, kind, count) where they are at least
    `chips` TPU chips, else None."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _delta(before: dict, after: dict, prefix: str) -> tuple:
    """(count, seconds) added between two `obs.aggregates()`
    snapshots to the aggregates whose name starts with `prefix`."""
    count = seconds = 0.0
    for name, a in after.items():
        if name.startswith(prefix):
            b = before.get(name, {"count": 0, "total_s": 0.0})
            count += a["count"] - b["count"]
            seconds += a["total_s"] - b["total_s"]
    return count, seconds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _err(f"layers: {ROOT} is not a checkout of the library")
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # as bench/run.py
    _paths()
    from repro import obs
    if args.record:
        obs.enable()
    from jax import profiler

    from bench import harness, loop, scopes
    from bench import trace as tr

    c = harness.cell(args.workload)
    dev = device(c.chips)
    if dev is None:
        _err(f"layers: {args.workload} needs {c.chips} TPU chip(s)")
        return 3
    harness.use_compile_cache()
    kind = harness.prepare(c, args.seed)
    setup_s = time.perf_counter() - T0
    setup_end = obs.get_registry().now()

    def window():
        return loop.run(kind.send, kind.wait, kind.ready, kind.keep,
                        args.seconds, kind.ahead)

    s0 = obs.aggregates()
    win = window()
    s1 = obs.aggregates()
    trace_dir = tempfile.mkdtemp(prefix="layers-trace-")
    try:
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            traced = window()
        finally:
            profiler.stop_trace()
        pd = tr.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    red = tr.reduce(pd)
    t0, t1 = next((ev.start_ns, ev.end_ns) for ev in tr.bench_thread(pd)
                  if ev.name == tr.WINDOW)
    exe = kind.entry.__self__       # the bound `Executable.run`
    paths = scopes.op_paths([exe.hlo_text(**kind.fixed, **kind.pool[0])])
    times = scopes.scope_times(pd, paths, t0, t1)

    out = {"workload": args.workload, "seed": args.seed,
           "record": args.record, "setup_s": setup_s,
           "calls_per_s": win.requests / win.seconds,
           "dispatch_us": 1e6 * win.dispatch_seconds / win.requests}
    n, s = _delta(s0, s1, "blas.run")
    if n:
        out["front_door_us.call"] = 1e6 * s / n
    if args.record:
        pauses, gc_s = _delta(s0, s1, "host.gc.")
        out["host_gc_us.call"] = 1e6 * gc_s / win.requests
        out["host_gc.pauses"] = pauses
        out["compile_s.setup"] = obs.compile_seconds(obs.records(),
                                                     t1=setup_end)
    out["pad_us.call"] = (1e6 * scopes.pad_seconds(red.device_ops)
                          / traced.requests)
    if times is not None:
        out["pad_scope_us.call"] = (1e6 * times.get("pad", 0.0)
                                    / traced.requests)
    out["device_idle.call"] = 100.0 * (1.0 - red.busy_s / red.window_s)
    out["traced_calls"] = traced.requests
    out["device_ops"] = red.device_ops
    out["gaps"] = [g for g in red.idle_gaps if g[1] >= GAP_S]
    worst, _ = kind.check()
    out["correct"] = all(v <= kind.limits[k] for k, v in worst.items())
    out["device"] = dev
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
