"""One run of one cell: find its parts by name, set up, measure, check,
and report.

Everything a cell is made of is found from `BENCHMARK.json` by name:

    bench/configs/<config>.json    the configuration as it is run
    bench/configs/<config>.py      its data, plain reference, work
    bench/traffic/<traffic>.json   the request kind and its parameters
    bench/metrics/<metric>.py      `read(rec)`: one metric from the
                                   window's record, or None

so a later cell, mix or metric is new files and a new entry, never an
edit here. `bench.kinds` holds the request kinds, `bench.loop` the
timing loop, `bench.trace` the trace reduction, `bench.scopes` the
device time per name scope.

A new configuration's module gives what its request kind names
(`bench.kinds`): `program`, `inputs`, `reference`, `work`,
`reference_program`, `LIMITS`, and `SMALL`, the sizes at which the
tests run its cells on the CPU. It may give `iteration_work` (a
`solves` cell's least work per loop iteration) and `scope_work` (the
least work under each fusion-group scope); `measure` puts what they
give in `rec`, and `bench/reading.py` turns it into roofline shares.
The tests take a new cell with no edit: each of them runs on every
cell of `BENCHMARK.json` at its configuration's `SMALL`.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Optional

import jax
import jax.monitoring
from jax import profiler

from bench import kinds, loop, peaks, scopes
from bench import trace as tr

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path:
    `$JAX_COMPILATION_CACHE_DIR` where set, else `.jax_cache/` in the
    checkout. Every program is cached, however quick its compile, so
    that only a checkout's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path; its name may hold
    characters (`-`, `.`) that an import statement cannot."""
    name = "bench._loaded." + "".join(
        c if c.isalnum() else "_" for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    module: object           # bench/configs/<config>.py
    traffic: dict            # bench/traffic/<traffic>.json
    end_to_end: list         # the entries of BENCHMARK.json it reports
    per_layer: list
    root: pathlib.Path = ROOT    # the checkout its files are found in


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, entry: Optional[dict] = None,
         root: pathlib.Path = ROOT) -> Cell:
    """The cell `name` of `BENCHMARK.json`, or the one that `entry`
    (a workload entry of the same form) describes, with every file
    found under `root`."""
    bm = load_json(root / "BENCHMARK.json")
    if entry is None:
        entry = next((w for w in bm["workloads"] if w["name"] == name),
                     None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in bm['workloads']]}")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    cfg_path = root / conf["file"]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=load_json(cfg_path),
        module=load_module(cfg_path.with_suffix(".py")),
        traffic=load_json(root / "bench" / "traffic"
                          / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, name)],
        root=root)


def prepare(c: Cell, seed: int, precision: Optional[str] = None):
    """The cell's request kind with its data on the device and its
    entry compiled and warmed: two requests, the first of which
    compiles (or loads from the persistent cache) every program the
    window runs."""
    kind = kinds.KINDS[c.traffic["kind"]](c.config, c.module, c.traffic,
                                          seed, precision)
    for i in range(2):
        kind.wait(kind.send(i))
    return kind


class _CompileCounter:
    """Counts XLA compilations, so that one inside the window shows."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


@functools.cache
def _compile_counter() -> _CompileCounter:
    return _CompileCounter()    # one listener per process


@dataclasses.dataclass
class Measured:
    rec: dict                # what the metric readers read
    checks: dict             # {number: (value, limit)}
    failed: int
    device: dict
    breakdown: Optional[dict]


def _window(kind, seconds: float, counter: _CompileCounter, log,
            what: str) -> loop.Window:
    compiles = counter.count
    win = loop.run(kind.send, kind.wait, kind.ready, kind.keep, seconds,
                   kind.ahead)
    log(f"{what} window: {win.requests} {kind.unit}s in {win.seconds} s "
        f"({kind.ahead} ahead), {counter.count - compiles} compilations "
        f"inside it")
    return win


def _since(now: Optional[int], before: Optional[int]) -> Optional[int]:
    return None if before is None else now - before


def measure(kind, seconds: float, trace: bool, setup_s: float,
            log=print) -> Measured:
    """Run the window, then with `trace` a second window under the
    profiler, then check what both produced.

    Every host-clock number (the end-to-end metrics, and `dispatch_us`)
    comes from the first window, which runs with the profiler off: its
    host tracer records every annotation and dispatch, and so adds time
    to the very calls it watches. Only the device's numbers come from
    the traced window.

    `rec["iterations"]` and `rec["traced_iterations"]` are the loop
    iterations that the solves of the measured and of the traced
    window ran (the kind's `iterations`, read around each window),
    and None for a kind that counts none, as `calls`.

    After both windows have closed, the trace is loaded once and read
    twice: by `bench.trace.reduce`, and by the join of its ops to the
    entry's compiled text (`bench.scopes.scope_times`), which gives
    `rec["scope_s"]`. `bench/reading.py` lists every key of `rec`."""
    counter = _compile_counter()
    before = kind.iterations
    win = _window(kind, seconds, counter, log, "measured")
    iterations = _since(kind.iterations, before)
    traced = traced_iterations = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        before = kind.iterations
        profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            traced = _window(kind, seconds, counter, log, "traced")
        finally:
            profiler.stop_trace()
        traced_iterations = _since(kind.iterations, before)
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    reduced = breakdown = scope_s = None
    if trace:
        t = time.perf_counter()
        pd = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ops = tr.device_ops(pd)
        reduced = tr.reduce(pd, ops=ops)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.device_ops,
                     "idle_gaps": reduced.idle_gaps}
        log(f"trace reduced in {time.perf_counter() - t} s: "
            f"{reduced.kernel_events} kernel events")
        t = time.perf_counter()
        scope_s = _scope_seconds(kind, pd, ops)
        del pd, ops
        log(f"device seconds by name scope, in "
            f"{time.perf_counter() - t} s: {scope_s}")
    nbytes, flops = kind.work()
    iteration_bytes, iteration_flops = kind.iteration_work() or (None,
                                                                 None)
    rec = {"unit": kind.unit, "requests": win.requests,
           "window_s": win.seconds, "setup_s": setup_s,
           "dispatch_s": win.dispatch_seconds,
           "traced_requests": traced.requests if traced else None,
           "iterations": iterations,
           "traced_iterations": traced_iterations,
           "work_bytes": nbytes, "work_flops": flops,
           "iteration_bytes": iteration_bytes,
           "iteration_flops": iteration_flops,
           "peaks": peaks.peaks(device["kind"])
           if device["platform"] == "tpu" else None,
           "trace": reduced, "scope_s": scope_s,
           "scope_work": kind.scope_work()}
    t = time.perf_counter()
    worst, failed = kind.check()
    log(f"check took {time.perf_counter() - t} s")
    checks = {n: (v, kind.limits[n]) for n, v in worst.items()}
    return Measured(rec=rec, checks=checks, failed=failed, device=device,
                    breakdown=breakdown)


def _scope_seconds(kind, pd, ops: dict) -> Optional[dict]:
    """{fusion-group scope or `pad`: device seconds} in the traced
    window, or None where the entry gives no compiled text or no op
    joins to it."""
    text = kind.hlo_text()
    if text is None:
        return None
    t0, t1 = tr.window_ns(tr.bench_thread(pd))
    return scopes.scope_times(pd, scopes.op_paths([text]), t0, t1, ops)


def read_metrics(c: Cell, rec: dict, trace: bool) -> dict:
    out = {}
    for m in (c.per_layer if trace else c.end_to_end):
        reader = load_module(c.root / "bench" / "metrics"
                             / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(c: Cell, m: Measured, trace: bool) -> dict:
    correct = all(v <= lim for v, lim in m.checks.values())
    attempted = m.rec["requests"] + (m.rec["traced_requests"] or 0)
    line = {"correct": correct, "attempted": attempted,
            "failed": m.failed,
            "metrics": read_metrics(c, m.rec, trace),
            "device": m.device}
    if m.breakdown is not None:
        line["breakdown"] = m.breakdown
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, (v, lim) in m.checks.items()}
    return line
