"""The block-shape autotuner: sweep, measure, persist.

`tune_program` lowers one dataflow spec per candidate `TilePlan`,
times whole jitted calls (min-of-k wall clock over synthetic
operands), and keeps a candidate only when it beats the incumbent by
a noise margin. Winners land in the persistent store twice over:

* as **entries** keyed by (pattern, shape bucket, mode, fuse, anchor,
  device kind) — so any *other* spec containing the same routine or
  fused-group shape picks the tiles up via `tiles="auto"` resolution;
* as the spec's **artifact** (digest-keyed spec JSON + resolved plan)
  — so recompiling this exact program, in this or any later process,
  resolves without re-deriving anything.

Measurements are wall clock on whatever `jax.devices()[0]` is — in
CI that is interpret-mode CPU, where block shapes mostly trade python
grid-step overhead; on a real TPU the same sweep keys its results
under that device kind. The two never contaminate each other.

Sites are swept coordinate-descent style (largest modeled-cost group
first), so a `budget` cap spends measurements where they matter.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Mapping, Optional, Tuple

import jax

from repro import obs
from repro.core import lowering

from . import config as C
from . import store as S

DEFAULT_BUDGET = 32
DEFAULT_ITERS = 3
# a candidate must beat the incumbent by this factor to dethrone it —
# interpret-mode timings are noisy and ties should keep defaults
IMPROVEMENT_MARGIN = 0.97


@dataclasses.dataclass(frozen=True)
class SiteInfo:
    site: str               # plan site key ("g0" / "g1:mv")
    pattern: str            # store pattern ("symv+dot" / "gemv")
    family: str             # candidate family ("symv"/"gemv"/"gemm"/"l1")
    dims: Tuple[int, ...]   # operand dims for bucketing/clamping
    bucket: str
    cost: int               # modeled flops, for sweep ordering


@dataclasses.dataclass
class Measurement:
    site: str
    tiles: str              # TileConfig.key()
    us: float


@dataclasses.dataclass
class TuneReport:
    program: str
    digest: str
    mode: str
    fuse: bool
    anchor: bool
    device_kind: str
    baseline_us: float
    tuned_us: float
    sweeps: int
    winners: Dict[str, C.TileConfig]
    measurements: List[Measurement]

    @property
    def speedup(self) -> float:
        return self.baseline_us / max(self.tuned_us, 1e-9)

    def __str__(self):
        lines = [f"tune report: {self.program!r} mode={self.mode} "
                 f"device={self.device_kind} ({self.sweeps} sweeps)"]
        lines.append(f"  default {self.baseline_us:10.1f} us")
        lines.append(f"  tuned   {self.tuned_us:10.1f} us  "
                     f"({self.speedup:.2f}x)")
        for site, cfg in sorted(self.winners.items()):
            lines.append(f"  {site:<12} -> {cfg.key()}")
        if not self.winners:
            lines.append("  (defaults win everywhere)")
        return "\n".join(lines)


def _squarish(rdef) -> bool:
    from repro.core import routines as R
    return any(k == R.MAT for k in rdef.inputs.values())


def _site_family(rspec) -> str:
    rdef = rspec.rdef
    if rdef.level == 1 or not _squarish(rdef):
        return "l1"
    if rspec.blas == "gemm":
        return "gemm"
    if rspec.blas == "symv":
        return "symv"
    return "gemv"


def _input_shapes(ir, shapes: Mapping) -> Dict[tuple, Tuple[int, ...]]:
    """(routine, port) -> shape for every non-scalar public input."""
    out = {}
    for pi in ir.io.inputs:
        if pi.kind == "scalar":
            continue
        if pi.name not in shapes:
            raise ValueError(
                f"tune: missing shape for program input {pi.name!r} "
                f"(a {pi.kind})")
        sh = shapes[pi.name]
        out[(pi.routine, pi.port)] = \
            (int(sh),) if isinstance(sh, int) else tuple(
                int(d) for d in sh)
    return out


def _discover_sites(ir, shapes: Mapping) -> List[SiteInfo]:
    """One sweepable site per fused group / standalone routine, with
    the dims the candidates are clamped and bucketed against."""
    from repro.core import routines as R
    port_shapes = _input_shapes(ir, shapes)
    vec_lens = [sh[0] for sh in port_shapes.values() if len(sh) == 1]
    fallback_n = max(vec_lens) if vec_lens else 128

    def matrix_dims(name):
        rspec = ir.graph.nodes[name]
        for port, kind in rspec.rdef.inputs.items():
            if kind == R.MAT and (name, port) in port_shapes:
                return port_shapes[(name, port)]
        return None

    def cost_of(names):
        total = 0
        for name in names:
            rdef = ir.graph.nodes[name].rdef
            if rdef.cost is None:
                continue
            sh = {}
            for port in rdef.inputs:
                sh[port] = port_shapes.get(
                    (name, port),
                    matrix_dims(name) or (fallback_n,))
            try:
                fl, _ = rdef.cost(sh)
                total += int(fl)
            except Exception:
                continue
        return total

    def gemm_dims(name):
        """(m, n, k) for a gemm site — A.m, B.n, A.k — matching the
        `tile_resolve(m, n, k)` lookup `make_tiled_callable.run` and
        the standalone gemm dispatch perform at call time."""
        ports = ir.graph.nodes[name].rdef.anchor_ports or {}
        a = port_shapes.get((name, ports.get("mat", "A")))
        b = port_shapes.get((name, ports.get("cols", "B")))
        m = a[0] if a else fallback_n
        k = a[1] if a is not None and len(a) > 1 else m
        n = b[1] if b is not None and len(b) > 1 else k
        return (m, n, k)

    sites = []
    for gi, g in enumerate(ir.groups or ()):
        if g.fused and len(g.nodes) >= 2:
            pattern = "+".join(ir.graph.nodes[n].blas for n in g.nodes)
            if g.anchor:
                family = _site_family(ir.graph.nodes[g.anchor])
                if family == "gemm":
                    dims = gemm_dims(g.anchor)
                else:
                    dims = matrix_dims(g.anchor) or (fallback_n,
                                                     fallback_n)
            else:
                dims, family = (fallback_n,), "l1"
            sites.append(SiteInfo(
                site=f"g{gi}", pattern=pattern, family=family,
                dims=dims, bucket=C.shape_bucket(*dims),
                cost=cost_of(g.nodes)))
            continue
        for name in g.nodes:
            rspec = ir.graph.nodes[name]
            if rspec.rdef.kernel is None:
                continue                    # reference-only routine
            family = _site_family(rspec)
            if family == "l1":
                dims = (fallback_n,)
            elif rspec.blas == "gemm":
                dims = gemm_dims(name)
            else:
                dims = matrix_dims(name) or (fallback_n, fallback_n)
            sites.append(SiteInfo(
                site=f"g{gi}:{name}", pattern=rspec.blas,
                family=family, dims=dims,
                bucket=C.shape_bucket(*dims), cost=cost_of([name])))
    sites.sort(key=lambda s: -s.cost)
    return sites


def _synthesize(ir, shapes: Mapping):
    from repro.core.runtime import Program
    prog = Program.from_ir(ir)
    sizes = {}
    for pi in ir.io.inputs:
        if pi.kind == "scalar":
            sizes[pi.name] = ()
        else:
            sh = shapes[pi.name]
            sizes[pi.name] = (sh,) if isinstance(sh, int) else tuple(sh)
    inputs = prog.synthetic_inputs(sizes)
    return {k: jax.block_until_ready(v) for k, v in inputs.items()}


def _time_ir(ir, inputs, iters: int) -> float:
    """Min-of-k wall clock (us) of the jitted program — min, not mean,
    because scheduler noise only ever adds time."""
    fn = getattr(ir, "_jit_fn", None)
    if fn is None:
        fn = jax.jit(ir.fn)
        ir._jit_fn = fn
    out = fn(dict(inputs))               # compile + warm cache
    jax.block_until_ready(list(out.values()))
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        out = fn(dict(inputs))
        jax.block_until_ready(list(out.values()))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def tune_program(raw, shapes: Mapping, *, mode: str = "dataflow",
                 fuse: Optional[bool] = None,
                 anchor: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 budget: Optional[int] = None,
                 iters: int = DEFAULT_ITERS,
                 store: Optional[S.TuningTable] = None,
                 persist: bool = True) -> TuneReport:
    """Sweep tile candidates for every site of one dataflow spec and
    persist the winners (entries + digest-keyed artifact). `budget`
    caps the number of timed candidate measurements (baseline timing
    is free); `persist=False` runs a dry sweep for tests/reports."""
    raw = lowering._canonical_raw(raw)
    digest = lowering.spec_digest(raw)
    if fuse is None:
        fuse = mode == "dataflow"
    if anchor is None:
        anchor = fuse
    budget = DEFAULT_BUDGET if budget is None else int(budget)
    store = store if store is not None else S.get_store()
    dk = C.current_device_kind()

    def lower_with(plan):
        # candidate sweeps re-lower an already-validated spec; skip
        # re-running the static analyzer per plan
        return lowering.lower(raw, mode=mode, fuse=fuse, anchor=anchor,
                              interpret=interpret, tiles=plan,
                              verify=False)

    ir0 = lower_with(C.EMPTY_PLAN)
    inputs = _synthesize(ir0, shapes)
    sites = _discover_sites(ir0, shapes)
    baseline_us = _time_ir(ir0, inputs, iters)

    plan_sites: Dict[str, Dict[str, C.TileConfig]] = {}
    winners: Dict[str, C.TileConfig] = {}
    measurements: List[Measurement] = []
    site_best: Dict[str, float] = {}
    sweeps = 0
    current_us = baseline_us

    for info in sites:
        seen = {C.clamp(C.TileConfig(), info.dims).key()}
        best_us, best_cfg = current_us, None
        for cand in C.candidates_for(info.family):
            eff = C.clamp(cand, info.dims)
            if eff.key() in seen:
                continue                 # clamps to an already-timed shape
            seen.add(eff.key())
            if sweeps >= budget:
                break
            trial = dict(plan_sites)
            trial[info.site] = {info.bucket: cand}
            ir = lower_with(C.TilePlan.from_dict(trial))
            us = _time_ir(ir, inputs, iters)
            sweeps += 1
            measurements.append(Measurement(info.site, cand.key(), us))
            obs.event("tune.measure", site=info.site, tiles=cand.key(),
                      us=us, baseline_us=current_us)
            if us < best_us:
                best_us, best_cfg = us, cand
        if best_cfg is not None and \
                best_us < current_us * IMPROVEMENT_MARGIN:
            plan_sites[info.site] = {info.bucket: best_cfg}
            winners[info.site] = best_cfg
            site_best[info.site] = best_us
            current_us = best_us
        if sweeps >= budget:
            break

    final_plan = C.TilePlan.from_dict(plan_sites)
    tuned_us = current_us

    if persist:
        for info in sites:
            cfg = winners.get(info.site)
            store.record_entry(
                info.pattern, info.bucket, mode, fuse, anchor, dk,
                tiles=cfg if cfg is not None
                else C.clamp(C.TileConfig(), info.dims),
                us=site_best.get(info.site, baseline_us),
                default_us=baseline_us, sweeps=sweeps)
        store.put_artifact(digest, mode, fuse, anchor, dk, spec=raw,
                           plan=final_plan, tuned=True)

    return TuneReport(
        program=ir0.spec.name, digest=digest, mode=mode, fuse=fuse,
        anchor=anchor, device_kind=dk, baseline_us=baseline_us,
        tuned_us=tuned_us, sweeps=sweeps, winners=winners,
        measurements=measurements)


def tune_routine(name: str, n: int = 256, *, mode: str = "dataflow",
                 **kw) -> TuneReport:
    """Tune one registry routine as a single-routine program at size
    n (matrices are (n, n)). The winning tiles land under the routine
    name's pattern, so every program containing that routine benefits."""
    from repro.blas.functional import routine_spec
    from repro.core import routines as R
    spec = routine_spec(name)
    rdef = R.get(name)
    shapes = {}
    for port, kind in rdef.inputs.items():
        if kind == R.MAT:
            shapes[port] = (n, n)
        elif kind == R.VEC:
            shapes[port] = (n,)
    return tune_program(spec, shapes, mode=mode, **kw)
