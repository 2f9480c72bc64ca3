"""Reduction of one profiler trace (`.xplane.pb`) to the numbers the
benchmark reports from the device.

    red = reduce(load(log_dir))
    red.busy_s, red.window_s, red.kernel_events, red.device_ops,
    red.idle_gaps

- The window is the benchmark's own host annotation `bench.window`,
  which spans the measured loop. Everything is clipped to it.
- Device operations are the events of each device's `XLA Ops` line
  (TPU planes `/device:TPU:<n>`). On the CPU backend, which the tests
  use, they are the events of its executor threads that carry an
  `hlo_op` stat.
- Busy time is the union of the operation intervals, averaged over the
  devices that ran any operation. Idle gaps are the holes in that
  union inside the window.
- Each idle gap is labelled by what the benchmark's thread was doing at
  its midpoint: the innermost `bench.*` annotation, and under it the
  innermost other host event (a jitted dispatch, a transfer).
- Kernel events are the operations that are Pallas kernels: custom
  calls to `tpu_custom_call` (`is_pallas_kernel`).
- `device_ops` ranks operation names by self time (an operation's
  duration less that of the operations nested in it, such as a
  `while` around its body).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import warnings
from typing import Callable, Iterable, Mapping, Optional, Sequence

WINDOW = "bench.window"
TOP = 10
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_CPU_OPS_LINES = "tf_XLA"       # the CPU backend's executor threads


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Mapping[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Reduced:
    window_s: float
    busy_s: float                # mean over the devices that ran ops
    devices: int
    kernel_events: int           # summed over devices
    device_ops: list             # [[name, self seconds], ...] top 10
    idle_gaps: list              # [[label, seconds], ...] top 10


def load(log_dir: str):
    """The ProfileData of the one `.xplane.pb` under `log_dir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {paths}")
    return ProfileData.from_file(paths[0])


def _events(line, stats: bool) -> list:
    """The line's events; reading their stats is most of the cost of a
    reduction, so only device operations have them read."""
    with warnings.catch_warnings():
        # the profiler's stats type lacks __module__, which iterating
        # over it reports as a DeprecationWarning
        warnings.simplefilter("ignore", DeprecationWarning)
        return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats) if stats else {})
                for e in line.events]


def device_ops(pd) -> dict:
    """{device plane name: [Event]} of the operations each device ran."""
    out = {}
    for plane in pd.planes:
        if _TPU_PLANE.match(plane.name):
            ops = [ev for line in plane.lines if line.name == _OPS_LINE
                   for ev in _events(line, stats=True)]
            if ops:
                out[plane.name] = ops
    if out:
        return out
    for plane in pd.planes:      # the CPU backend runs ops on host threads
        if plane.name == _HOST_PLANE:
            ops = [ev for line in plane.lines
                   if line.name.startswith(_CPU_OPS_LINES)
                   for ev in _events(line, stats=True)
                   if "hlo_op" in ev.stats]
            if ops:
                out[plane.name] = ops
    return out


def bench_thread(pd) -> list:
    """The events of the host thread that ran the `bench.window`
    annotation, in start order."""
    for plane in pd.planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            if any(e.name == WINDOW for e in line.events):
                return sorted(_events(line, stats=False),
                              key=lambda ev: ev.start_ns)
    raise ValueError(f"no host thread carries a {WINDOW!r} annotation")


def union(intervals: Iterable[tuple]) -> list:
    """Sorted, merged (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def clip(intervals: Iterable[tuple], t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def holes(merged: Sequence[tuple], t0: float, t1: float) -> list:
    """The gaps of merged intervals inside [t0, t1]."""
    out, t = [], t0
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return out


def self_times(events: Sequence[Event]) -> dict:
    """{name: self nanoseconds}: each event's duration less that of the
    events directly nested in it."""
    totals: dict = collections.defaultdict(float)
    stack: list = []            # [event, child nanoseconds]
    for ev in sorted(events, key=lambda ev: (ev.start_ns, -ev.end_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            done, child = stack.pop()
            totals[done.name] += (done.end_ns - done.start_ns) - child
        if stack:
            stack[-1][1] += ev.end_ns - ev.start_ns
        stack.append([ev, 0.0])
    for done, child in stack:
        totals[done.name] += (done.end_ns - done.start_ns) - child
    return dict(totals)


def is_pallas_kernel(ev: Event) -> bool:
    """A Pallas TPU kernel: an XLA custom call to `tpu_custom_call`,
    named so by its HLO text (the event's name on a TPU's `XLA Ops`
    line, or a stat such as `long_name`) or by a stat naming its
    target. Only where the event carries no HLO text at all does its
    instruction name decide, so that XLA's own custom calls, whose text
    names another target, are not counted."""
    text = [ev.name] + [str(v) for v in ev.stats.values()]
    if any("tpu_custom_call" in t for t in text):
        return True
    if any(" = " in t for t in text):      # HLO text of another op
        return False
    return ev.name.startswith("custom-call")


def label_at(t_ns: float, host: Sequence[Event]) -> str:
    """What the benchmark's thread was doing at `t_ns`: the innermost
    `bench.*` annotation and the innermost other event under it."""
    covering = [ev for ev in host if ev.start_ns <= t_ns <= ev.end_ns]
    marks = [ev for ev in covering if ev.name.startswith("bench.")]
    if not marks:
        return "outside the benchmark's annotations"
    mark = max(marks, key=lambda ev: (ev.start_ns, -ev.end_ns))
    under = [ev for ev in covering if not ev.name.startswith("bench.")
             and ev.start_ns >= mark.start_ns]
    if not under:
        return mark.name
    inner = max(under, key=lambda ev: (ev.start_ns, -ev.end_ns))
    return f"{mark.name} / {inner.name}"


def window_ns(host: Sequence[Event]) -> tuple:
    """(start, end) of the `bench.window` annotation among the bench
    thread's events (`bench_thread`)."""
    window = next(ev for ev in host if ev.name == WINDOW)
    return window.start_ns, window.end_ns


def reduce(pd, is_kernel: Callable[[Event], bool] = is_pallas_kernel,
           ops: Optional[dict] = None) -> Reduced:
    """The reduction of a loaded trace; `ops` is `device_ops(pd)` where
    the caller has it already (reading the ops' stats is most of the
    cost)."""
    host = bench_thread(pd)
    t0, t1 = window_ns(host)
    per_device = device_ops(pd) if ops is None else ops
    if not per_device:
        raise ValueError("the trace holds no device operations")
    busy, kernels, self_ns = [], 0, collections.Counter()
    gaps: list = []
    for ops in per_device.values():
        inside = [ev for ev in ops if ev.end_ns > t0 and ev.start_ns < t1]
        merged = union(clip(((ev.start_ns, ev.end_ns) for ev in inside),
                            t0, t1))
        busy.append(sum(e - s for s, e in merged))
        kernels += sum(1 for ev in inside if is_kernel(ev))
        self_ns.update(self_times(inside))
        gaps += holes(merged, t0, t1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window_s=(t1 - t0) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        devices=len(busy),
        kernel_events=kernels,
        device_ops=[[name, ns * 1e-9]
                    for name, ns in self_ns.most_common(TOP)],
        idle_gaps=[[label_at(0.5 * (s + e), host), (e - s) * 1e-9]
                   for s, e in gaps[:TOP]])
