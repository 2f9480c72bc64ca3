"""`repro.obs` — observability for the compile/run pipeline.

Structured spans, counters and event records with a process-local
registry, zero overhead when disabled (the default), and JSONL export.
Instrumented sites across the stack:

* `core.lowering` — one span per compiler pass (parse -> graph ->
  infer -> fuse -> place -> emit) and `lowering.cache.hit/miss`
  counters for the digest-keyed program cache;
* `core.fusion` — one `fusion.absorb` / `fusion.reject` decision event
  per level-2 anchor candidate, with the planner's reason (convexity,
  cyclic-quotient, x-side producer rule, ...);
* `core.codegen` — `codegen.group` tags for every generated kernel and
  `kernel.group` timing spans around concrete executions;
* `solvers.driver` — `solver.solve` spans, `loop.trace` events (the
  compile-once counter) and `solver.result` convergence telemetry
  (iterations, final residual, converged — never the NaN tail);
* `blas.executable` — the always-on `blas.run` aggregate (calls and
  host seconds inside `Executable.run`), and with recording on a
  `blas.run` profiler annotation per call;
* the host runtime — `host.gc.gen<g>` aggregates and `host.gc`
  annotations from the garbage collector's hook, and `jax.compile`
  spans from JAX's compile-path durations (both while enabled).

While recording is on, every span is also a profiler annotation, and
the generated program's ops run under one name scope per fusion group
(`<program>.g<i>`) with pads under `pad`, so a profiler trace
attributes device time to both.

Typical use:

    from repro import obs
    obs.enable()
    x = blas.cg(A=A, b=b)          # instrumented end to end
    obs.export("solve.jsonl")      # python -m repro.obs summarize ...

or `REPRO_OBS_JSONL=trace.jsonl python my_script.py` with no code
changes. `Executable.profile(shapes)` builds on the same records to
produce a modeled-vs-measured `DriftReport` per fused group.
"""
from .core import (NULL_SPAN, Aggregate, Registry,  # noqa: F401
                   aggregate, aggregates, annotate, block, capture,
                   concrete, counter, counters, disable, enable,
                   enabled, event, export, get_registry, null_span,
                   records, reset, span)
from .report import (DriftReport, DriftRow, compile_seconds,  # noqa: F401
                     diff_summaries, format_summary, join_drift,
                     load_jsonl, summarize_records)

__all__ = [
    "Aggregate", "DriftReport", "DriftRow",
    "NULL_SPAN", "Registry", "aggregate", "aggregates", "annotate",
    "block", "capture", "compile_seconds", "concrete", "counter",
    "counters", "diff_summaries", "disable", "enable", "enabled",
    "event", "export", "format_summary", "get_registry", "join_drift",
    "load_jsonl", "null_span", "records", "reset", "span",
    "summarize_records",
]
