"""Arithmetic shared by the metric readers in `bench/metrics/`.

A reader gets the run's record (`bench.harness.measure`): `unit`
("call", for calls and solves alike), `requests`, `window_s`,
`setup_s` and `dispatch_s` of the measured window (profiler off),
`traced_requests` of the traced window (None without `--trace 1`),
`iterations` and `traced_iterations` (the loop iterations that the
solves of each window ran; None for calls, and the traced one without
`--trace 1`), `work_bytes` and `work_flops` (the least one request
needs, from the configuration's work function), `peaks` (`bench.peaks`,
None off a TPU) and `trace` (`bench.trace.Reduced` of the traced
window, else None). Each function returns None where the
record has nothing to read, and the harness then leaves the metric
out.
"""
from __future__ import annotations

from typing import Optional


def _traced(rec, unit: str) -> bool:
    return (rec["unit"] == unit and rec["trace"] is not None
            and rec["traced_requests"])


def roofline_pct(rec, unit: str) -> Optional[float]:
    """The least time the chip could take for the traced window's
    requests (the larger of bytes over HBM bandwidth and FLOPs over
    peak) as a share of the device's busy time in that window."""
    if not _traced(rec, unit) or rec["peaks"] is None:
        return None
    busy = rec["trace"].busy_s
    if busy <= 0:
        return None
    p = rec["peaks"]
    least = rec["traced_requests"] * max(rec["work_bytes"] / p.hbm_bw,
                                         rec["work_flops"] / p.flops)
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
