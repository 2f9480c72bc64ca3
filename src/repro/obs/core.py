"""Process-local observability registry: spans, counters, events.

The whole compile/run pipeline reports here — lowering passes,
program-cache hits, fusion decisions, generated-kernel executions,
solver loop traces and convergence results — as flat, structured
records that export to JSONL (`python -m repro.obs` summarizes,
traces and diffs the files).

Design constraints, in priority order:

1. **Zero overhead when disabled** (the default). Every recording
   entrypoint starts with one attribute check against the process
   registry; `span()` returns a shared no-op object without touching
   the clock. Nothing is allocated, nothing is written, and the
   instrumented code paths trace/jit exactly as before. The one
   exception is the always-on `blas.run` aggregate: two clock reads
   a call.
2. **Trace-safe when enabled.** Instrumented sites live inside code
   that JAX may be tracing; recording plain-python metadata during a
   trace is harmless, but *timing* a traced region measures trace
   time, not run time. Kernel-level timing sites therefore guard on
   concreteness (`concrete()`), so spans around generated kernels only
   time real executions.
3. **Stdlib only.** The registry, the JSONL schema, and the CLI have
   no dependency on jax — a JSONL file is readable anywhere; jax is
   imported lazily, for the profiler bridge and the compile listener.
4. **On the profiler's clock.** While recording is on, every span
   also enters a `jax.profiler.TraceAnnotation` of its name and
   attributes, so under a profiler session it sits on the host line of
   the thread that ran it, nested by its parent, on the same clock as
   the device's ops.

Sites that fire on every call keep an `Aggregate` (count, total and
largest seconds) in place of one record per call; `blas.run` keeps one
always, recording or not. `enable()` also installs a `gc.callbacks`
hook (`host.gc.gen<g>` aggregates, and a `host.gc` annotation around
each collection) and a `jax.monitoring` listener that turns JAX's
trace, lowering and compile durations into `jax.compile` spans;
`disable()` removes both.

Record schema (one JSON object per line):

    {"kind": "span",    "name": ..., "path": "a/b", "t": t0_s,
     "dur_s": ..., "attrs": {...}}
    {"kind": "counter", "name": ..., "n": 1, "attrs": {...}}
    {"kind": "event",   "name": ..., "t": t_s, "attrs": {...}}
    {"kind": "aggregate", "name": ..., "count": n, "total_s": ...,
     "max_s": ...}     (module-level `export()` only, one per aggregate)

Timestamps are seconds relative to the registry's creation
(perf_counter based — ordering and duration, not wall-clock dates).
"""
from __future__ import annotations

import atexit
import contextlib
import gc
import json
import os
import pathlib
import sys
import threading
import time
from typing import Dict, Iterable, List, Mapping, Optional


class Registry:
    """One process-local sink for observability records."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.records: List[dict] = []
        self.counters: dict = {}
        self._lock = threading.Lock()
        self._stack: List[str] = []          # active span names
        self._epoch = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._epoch

    def add(self, rec: dict) -> None:
        with self._lock:
            self.records.append(rec)

    def bump(self, name: str, n: int) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def clear(self) -> None:
        with self._lock:
            self.records.clear()
            self.counters.clear()
            self._stack.clear()

    def export_jsonl(self, path, extra: Iterable[dict] = ()
                     ) -> pathlib.Path:
        """Write every record, then `extra`, as one JSON line each;
        returns the path."""
        path = pathlib.Path(path)
        with self._lock:
            lines = [json.dumps(r, default=repr)
                     for r in [*self.records, *extra]]
        path.write_text("\n".join(lines) + ("\n" if lines else ""))
        return path


_REGISTRY = Registry()
_EXPORT_PATH: Optional[str] = None


def get_registry() -> Registry:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY.enabled


def enable(jsonl: Optional[str] = None) -> Registry:
    """Turn recording on, with the garbage collector's hook and the
    compile listener. `jsonl` remembers a default export path for
    `export()` (and the atexit flush when activated via the
    REPRO_OBS_JSONL environment variable)."""
    global _EXPORT_PATH
    _REGISTRY.enabled = True
    if jsonl is not None:
        _EXPORT_PATH = str(jsonl)
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    _compile_listener(install=True)
    return _REGISTRY


def disable() -> None:
    _REGISTRY.enabled = False
    if _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
    _compile_listener(install=False)


def reset() -> None:
    """Drop all accumulated records and counters and zero every
    aggregate (keeps enabled state)."""
    _REGISTRY.clear()
    for a in list(_AGGREGATES.values()):
        a.count, a.total_s, a.max_s = 0, 0.0, 0.0


def export(path: Optional[str] = None) -> pathlib.Path:
    """Export accumulated records as JSONL to `path` (or the path given
    to `enable()`), then one summary record per aggregate that has
    counted anything."""
    target = path if path is not None else _EXPORT_PATH
    if target is None:
        raise ValueError(
            "no export path: pass one to export() or enable(jsonl=...)")
    summary = [{"kind": "aggregate", "name": name, **snap}
               for name, snap in aggregates().items() if snap["count"]]
    return _REGISTRY.export_jsonl(target, extra=summary)


@contextlib.contextmanager
def capture():
    """Scoped recording into a fresh registry (the previous one — and
    its enabled state — is restored on exit). `Executable.profile` uses
    this so profiling runs never mix records into user instrumentation."""
    global _REGISTRY
    prev = _REGISTRY
    _REGISTRY = Registry(enabled=True)
    try:
        yield _REGISTRY
    finally:
        _REGISTRY = prev


# ---------------------------------------------------------------------------
# Recording entrypoints
# ---------------------------------------------------------------------------


class _NullSpan:
    """Shared no-op span: what `span()` hands out when disabled."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def null_span() -> _NullSpan:
    return NULL_SPAN


def _trace_annotation():
    """`jax.profiler.TraceAnnotation` once jax has been imported, else
    None (no profiler can run then). Never imports jax itself: the
    registry needs none, and a collection can start while jax is half
    imported."""
    return getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                   None)


class _Span:
    __slots__ = ("_reg", "name", "attrs", "_t0", "_path", "_ann")

    def __init__(self, reg: Registry, name: str, attrs: dict):
        self._reg = reg
        self.name = name
        self.attrs = attrs
        self._ann = None

    def __enter__(self):
        reg = self._reg
        reg._stack.append(self.name)
        self._path = "/".join(reg._stack)
        ann = _trace_annotation()
        if ann is not None:
            self._ann = ann(self.name, **self.attrs)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        reg = self._reg
        if reg._stack and reg._stack[-1] == self.name:
            reg._stack.pop()
        reg.add({"kind": "span", "name": self.name, "path": self._path,
                 "t": self._t0 - reg._epoch, "dur_s": t1 - self._t0,
                 "attrs": self.attrs})
        return False


def span(name: str, **attrs):
    """Context manager timing one region, and while recording is on a
    profiler annotation of the same name and attributes. Disabled ->
    shared no-op."""
    reg = _REGISTRY
    if not reg.enabled:
        return NULL_SPAN
    return _Span(reg, name, attrs)


def annotate(name: str, **attrs):
    """A profiler annotation alone, with no record: for sites that fire
    on every call and keep an `Aggregate` instead. Disabled (or no
    jax) -> shared no-op."""
    ann = _trace_annotation() if _REGISTRY.enabled else None
    if ann is None:
        return NULL_SPAN
    return ann(name, **attrs)


class Aggregate:
    """Count, total and largest seconds of one site that fires on every
    call. Adding costs no allocation and appends no record, so a long
    run grows nothing for the garbage collector to walk. Not locked:
    sites that several threads share may lose a rare count."""
    __slots__ = ("name", "count", "total_s", "max_s")

    def __init__(self, name: str):
        self.name = name
        self.count, self.total_s, self.max_s = 0, 0.0, 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        if seconds > self.max_s:
            self.max_s = seconds

    def snapshot(self) -> dict:
        return {"count": self.count, "total_s": self.total_s,
                "max_s": self.max_s}


_AGGREGATES: Dict[str, Aggregate] = {}


def aggregate(name: str) -> Aggregate:
    """The process's aggregate `name`, made on first use. Aggregates
    count whether or not recording is on; `reset()` zeroes them."""
    agg = _AGGREGATES.get(name)
    if agg is None:
        agg = _AGGREGATES.setdefault(name, Aggregate(name))
    return agg


def aggregates() -> Dict[str, dict]:
    """{name: {"count", "total_s", "max_s"}} of every aggregate. The
    difference of two snapshots is what happened between them."""
    return {name: a.snapshot() for name, a in list(_AGGREGATES.items())}


class _GcHook:
    """`gc.callbacks` entry installed by `enable()`: each collection
    adds its pause to the `host.gc.gen<generation>` aggregate, and
    while the profiler runs it sits inside a `host.gc` annotation."""

    def __init__(self):
        self._t0 = 0.0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = _trace_annotation()
            if ann is not None:
                self._ann = ann("host.gc", generation=info["generation"])
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        aggregate(f"host.gc.gen{info['generation']}").add(dt)


_gc_hook = _GcHook()

# JAX's compile-path durations, by the `stage` a `jax.compile` span
# gets. `compile` also covers a load from the persistent cache.
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _on_compile(event: str, start: float, end: float, **kw) -> None:
    """`jax.monitoring` time-span listener: one `jax.compile` span per
    compile-path event, moved from the wall clock JAX reports onto the
    registry's."""
    stage = _COMPILE_EVENTS.get(event)
    reg = _REGISTRY
    if stage is None or not reg.enabled:
        return
    shift = time.perf_counter() - time.time()
    reg.add({"kind": "span", "name": "jax.compile",
             "path": "/".join([*reg._stack, "jax.compile"]),
             "t": start + shift - reg._epoch, "dur_s": end - start,
             "attrs": {"stage": stage, "fun": kw.get("fun_name")}})


_listening = False


def _compile_listener(install: bool) -> None:
    global _listening
    if install == _listening:
        return
    try:
        from jax import monitoring
    except ImportError:
        return
    if install:
        monitoring.register_event_time_span_listener(_on_compile)
    else:
        monitoring.unregister_event_time_span_listener(_on_compile)
    _listening = install


def counter(name: str, n: int = 1, **attrs) -> None:
    """Bump a named counter (aggregated in the registry AND appended as
    a record, so JSONL files stay self-contained)."""
    reg = _REGISTRY
    if not reg.enabled:
        return
    reg.bump(name, n)
    rec = {"kind": "counter", "name": name, "n": n}
    if attrs:
        rec["attrs"] = attrs
    reg.add(rec)


def event(name: str, **attrs) -> None:
    """Record one structured event."""
    reg = _REGISTRY
    if not reg.enabled:
        return
    reg.add({"kind": "event", "name": name, "t": reg.now(),
             "attrs": attrs})


def counters() -> Mapping[str, int]:
    """Snapshot of the aggregated counters."""
    return dict(_REGISTRY.counters)


def records() -> List[dict]:
    """Snapshot of the raw records."""
    with _REGISTRY._lock:
        return list(_REGISTRY.records)


def concrete(values: Iterable) -> bool:
    """True when none of `values` is a JAX tracer — the guard timing
    sites use so spans never time a trace instead of an execution.
    Import-lazy so the obs core stays importable without jax."""
    try:
        from jax.core import Tracer
    except ImportError:       # no jax: everything is a host value
        return True
    return not any(isinstance(v, Tracer) for v in values)


def block(values: Iterable) -> None:
    """Wait for async jax computations so span timings measure the
    work, not the dispatch."""
    for v in values:
        wait = getattr(v, "block_until_ready", None)
        if wait is not None:
            wait()


# REPRO_OBS_JSONL=trace.jsonl activates recording for the whole
# process and flushes to the file at exit — the no-code-change way to
# instrument an existing script (CI's obs-smoke uses the explicit API
# instead).
_env_path = os.environ.get("REPRO_OBS_JSONL")
if _env_path:
    enable(jsonl=_env_path)
    atexit.register(lambda: export(_env_path))
del _env_path
