"""Arithmetic shared by the metric readers in `bench/metrics/`.

A reader gets the run's record (`bench.harness.measure`), which holds:

    unit               "call", for calls and solves alike
    requests           requests of the measured window (profiler off)
    window_s           its seconds, from the first send to the last
                       completion
    setup_s            process start to window start
    dispatch_s         host seconds inside `send`, summed over the
                       measured window
    traced_requests    requests of the traced window; None without
                       `--trace 1`
    iterations         loop iterations that the solves of the measured
                       window ran; None for calls
    traced_iterations  the same of the traced window; None for calls
                       and without `--trace 1`
    work_bytes,        the least one request needs, from the
    work_flops         configuration's `work`
    iteration_bytes,   the least one loop iteration needs, from the
    iteration_flops    configuration's optional `iteration_work`; None
                       for calls and where it gives none
    peaks              `bench.peaks` of the device; None off a TPU
    trace              `bench.trace.Reduced` of the traced window; None
                       without `--trace 1`
    scope_s            {fusion-group scope `<program>.g<i>` or `pad`:
                       device seconds in the traced window}
                       (`bench.scopes.scope_times`); None without
                       `--trace 1`, and where the entry gives no
                       compiled text to join the trace to (the plain
                       reference, and today a loop program)
    scope_work         {scope: (bytes, flops)} that one request, or
                       in a solve one iteration, needs under the
                       scope, from the configuration's optional
                       `scope_work`; None where it gives none

A configuration's module also gives `SMALL`, the sizes at which the
tests run its cells on the CPU; the record does not hold it.

Each function returns None where the record has nothing to read, and
the harness then leaves the metric out.
"""
from __future__ import annotations

from typing import Optional


def _traced(rec, unit: str) -> bool:
    return (rec["unit"] == unit and rec["trace"] is not None
            and rec["traced_requests"])


def _least_s(peaks, nbytes: float, flops: float) -> float:
    """The least time the chip could take: the larger of bytes over HBM
    bandwidth and FLOPs over peak."""
    return max(nbytes / peaks.hbm_bw, flops / peaks.flops)


def roofline_pct(rec, unit: str) -> Optional[float]:
    """The least time the chip could take for the traced window's
    requests (the larger of bytes over HBM bandwidth and FLOPs over
    peak) as a share of the device's busy time in that window."""
    if not _traced(rec, unit) or rec["peaks"] is None:
        return None
    busy = rec["trace"].busy_s
    if busy <= 0:
        return None
    least = rec["traced_requests"] * _least_s(
        rec["peaks"], rec["work_bytes"], rec["work_flops"])
    return 100.0 * least / busy


def idle_pct(rec, unit: str) -> Optional[float]:
    """100 × (1 − busy union ÷ traced window)."""
    if not _traced(rec, unit):
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def kernels_per_request(rec, unit: str) -> Optional[float]:
    """Pallas kernel events in the traced window per request."""
    if not _traced(rec, unit):
        return None
    return rec["trace"].kernel_events / rec["traced_requests"]


def iterations_per_request(rec) -> Optional[float]:
    """Loop iterations per solve of the measured window."""
    if rec["iterations"] is None or not rec["requests"]:
        return None
    return rec["iterations"] / rec["requests"]


def _iteration_busy_s(rec) -> Optional[float]:
    """The traced window's device busy seconds, where it ran loop
    iterations."""
    if rec["trace"] is None or not rec["traced_iterations"]:
        return None
    return rec["trace"].busy_s


def iteration_us(rec) -> Optional[float]:
    """Device busy microseconds per loop iteration of the traced
    window."""
    busy = _iteration_busy_s(rec)
    if busy is None:
        return None
    return 1e6 * busy / rec["traced_iterations"]


def iteration_roofline_pct(rec) -> Optional[float]:
    """The least time of the traced window's loop iterations (each the
    larger of `iteration_bytes` over HBM bandwidth and
    `iteration_flops` over peak) as a share of the device's busy time
    in that window."""
    busy = _iteration_busy_s(rec)
    if (busy is None or busy <= 0 or rec["peaks"] is None
            or rec["iteration_bytes"] is None):
        return None
    least = rec["traced_iterations"] * _least_s(
        rec["peaks"], rec["iteration_bytes"], rec["iteration_flops"])
    return 100.0 * least / busy


def scope_roofline_pct(rec, scope: str) -> Optional[float]:
    """The least time of the work under name scope `scope` in the
    traced window (`scope_work` per request, or per loop iteration in
    a solve, times the traced window's requests or iterations) as a
    share of the device seconds its ops took there (`scope_s`)."""
    if (rec["peaks"] is None or not rec["scope_s"]
            or not rec["scope_work"] or scope not in rec["scope_work"]):
        return None
    seconds = rec["scope_s"].get(scope)
    count = (rec["traced_requests"] if rec["iterations"] is None
             else rec["traced_iterations"])
    if not seconds or not count:
        return None
    nbytes, flops = rec["scope_work"][scope]
    return 100.0 * count * _least_s(rec["peaks"], nbytes, flops) / seconds
