"""Device time by name scope in a profiler trace.

The library runs each fusion group's ops under the name scope
`<program>.g<i>` and each operand pad under `pad`, so the compiled
program's HLO text gives every op an `op_name` such as
`jit(program)/mvt.g0/jit(gemv)/pad/jit(_pad)/pad`. The trace does not
carry that path: on a TPU an `XLA Ops` event is named by the op's HLO
text, which leaves metadata out, and its stats hold only device
offsets; on the CPU backend an op event carries its instruction
(`hlo_op`) and module (`hlo_module`). So an op's scopes come from
joining its instruction name, within the HLO module running it, to the
compiled text (`repro.blas.Executable.hlo_text`).

    paths = op_paths([exe.hlo_text(**inputs)])
    times = scope_times(pd, paths, t0_ns, t1_ns)  # {"mvt.g0": s, "pad": s}

`scope_times` gives every fusion-group scope and `pad` the union of
its ops' intervals, so ops that overlap count once (where
`bench.trace.self_times` would take one op's time out of another's);
a pad runs inside its group, so it counts in both. `pad_seconds`
needs no raw trace: it sums the self time of the `pad` ops that
`bench.trace.reduce` keeps, which is what the benchmark's `pad_us.call`
reads. The pads do not overlap one another, so the two agree where
every pad is among the ten ops kept.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, Optional

from bench import trace as tr

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"',
                 re.M)
_NAME = re.compile(r"^%([\w.\-]+) = ")
_RUN_ID = re.compile(r"\(\d+\)$")
_MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r"^%?([\w.\-]+)(?: = |$)")
# a pad, or a fusion of nothing but pads and bitcasts (XLA names a
# fusion after its ops, producers first: `pad_dot_fusion` is a dot)
_PAD = re.compile(r"^pad(?:_(?:pad|bitcast))*(?:_fusion)?(?:\.\d+)?$")
# a fusion group's name scope (`repro.core.codegen.group_key`)
_GROUP = re.compile(r"^.+\.g\d+$")


def op_paths(hlo_texts: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """{HLO module: {instruction: op_name}} of compiled HLO texts."""
    out: Dict[str, Dict[str, str]] = {}
    for text in hlo_texts:
        m = _MODULE.search(text)
        if m is not None:
            out.setdefault(m.group(1), {}).update(_OP.findall(text))
    return out


def is_pad_op(name: str) -> bool:
    """An op that pads and does nothing else, by its name in a trace:
    its HLO text on a TPU (`%pad.19 = f32[...] pad(...)`), its
    instruction name on the CPU (`pad.19`, `pad_pad_fusion`)."""
    m = _INSTR.match(name)
    return m is not None and _PAD.match(m.group(1)) is not None


def pad_seconds(device_ops) -> float:
    """Self seconds of the `pad` ops among `bench.trace.Reduced`'s
    `device_ops` ([[name, self seconds], ...], the ten ops with the
    most self time)."""
    return sum(s for name, s in device_ops if is_pad_op(name))


def _modules(pd) -> dict:
    """{TPU plane: [(start, end, module)]} from its `XLA Modules`
    line, the run id dropped from each name."""
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            if line.name == _MODULES_LINE:
                out[plane.name] = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     _RUN_ID.sub("", ev.name)) for ev in line.events)
    return out


def _module_at(runs: list, starts: list, t: float) -> Optional[str]:
    """The module whose run covers `t`; runs do not overlap."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= runs[i][1]:
        return runs[i][2]
    return None


def op_path(ev: tr.Event, paths: dict, module: Optional[str]
            ) -> Optional[str]:
    """The `op_name` of a device op, or None where the join finds
    none: its instruction is the `hlo_op` stat or the head of its HLO
    text, its module the `hlo_module` stat or `module`."""
    instr = ev.stats.get("hlo_op")
    if instr is None:
        m = _NAME.match(ev.name)
        instr = m.group(1) if m else None
    mod = ev.stats.get("hlo_module", module)
    if instr is None or mod is None:
        return None
    return paths.get(str(mod), {}).get(str(instr))


def _layer_scopes(path: str) -> list:
    """The fusion-group scopes and `pad` among an op path's names."""
    return [n for n in set(path.split("/")) if n == "pad" or _GROUP.match(n)]


def scope_times(pd, paths: dict, t0: float, t1: float,
                device_ops: Optional[dict] = None
                ) -> Optional[Dict[str, float]]:
    """{scope: seconds} in [t0, t1] (ns) during which an op under each
    fusion-group scope (`<program>.g<i>`) or `pad` ran: the union of
    their intervals, mean over the devices that ran ops. None where no
    op of the window joins to a path. `device_ops` is
    `bench.trace.device_ops(pd)` where the caller has it already
    (reading the ops' stats is most of the cost)."""
    modules = _modules(pd)
    if device_ops is None:
        device_ops = tr.device_ops(pd)
    total: Dict[str, float] = collections.defaultdict(float)
    scopes_of: Dict[str, list] = {}      # a window repeats few paths
    joined = False
    for plane, ops in device_ops.items():
        runs = modules.get(plane, [])
        starts = [r[0] for r in runs]
        spans = collections.defaultdict(list)
        for ev in ops:
            if ev.end_ns <= t0 or ev.start_ns >= t1:
                continue
            path = op_path(ev, paths, _module_at(runs, starts, ev.start_ns))
            if path is None:
                continue
            joined = True
            if path not in scopes_of:
                scopes_of[path] = _layer_scopes(path)
            for scope in scopes_of[path]:
                spans[scope].append((ev.start_ns, ev.end_ns))
        for scope, intervals in spans.items():
            total[scope] += sum(e - s for s, e in
                                tr.union(tr.clip(intervals, t0, t1)))
    if not joined:
        return None
    return {scope: ns / len(device_ops) * 1e-9
            for scope, ns in total.items()}

