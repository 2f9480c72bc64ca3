"""Code generation: fusion groups -> executable JAX/Pallas callables.

This is the TPU analogue of AIEBLAS's template-based generators
(Fig. 1): from a fusion group it *generates a Pallas kernel body* by
splicing each routine's `emitter` trace function together, with
internal edges becoming VMEM/VREG values (never HBM). Standalone
level-2/3 routines dispatch to their hand-tiled kernels in
repro.kernels.

Three generated-kernel shapes:

* level-1 groups — one (block_rows, 128) window walk over the vectors
  (`make_group_callable`);
* level-2 **anchored** groups (`make_anchored_callable`) — the matrix
  is streamed through VMEM in (bm, bn) windows exactly like the
  standalone `kernels.gemv`/`symv`/`gemvt` tilings (whose block bodies
  are reused verbatim), the anchor's output block accumulates in a
  VMEM scratch, and the absorbed level-1 routines run in-register on
  that block: producers of the accumulator operand in the row phase
  (j == 0), consumers in the finish phase (j == last), with
  reductions accumulating across output blocks. The intermediate
  vector never touches HBM. For `gemvt` the output axis runs over A's
  columns and the reduction over A's row blocks — the same roles,
  transposed;
* level-3 **tiled** groups (`make_tiled_callable`) — a `gemm` anchor
  finishes (bm, bn) output tiles in a 2-D VMEM accumulator over a
  (bk,) contraction walk (the standalone `kernels.gemm` schedule, same
  `gemm_block` body), and absorbed columnwise panel routines splice
  against the finished tile: element-wise panel epilogues rewrite it
  in-register, columnwise reductions (`coldot`) fold it into (1, bn)
  partials accumulated across row blocks. The panel intermediates of a
  blocked multi-RHS step never touch HBM.

Three modes mirror the paper's evaluation matrix:
  dataflow     — fused groups, on-chip intermediates   ("w/ DF")
  nodataflow   — one kernel per routine, HBM handoffs  ("w/o DF")
  reference    — pure-jnp oracle path                  (the CPU baseline)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import gemm as gemm_mod, gemv as gemv_mod, ops, \
    symv as symv_mod
from repro.kernels.common import (ACC_SHAPE, LANES, acc_add, as_2d, cdiv,
                                  default_interpret, pad_to, pl, pltpu,
                                  smem_scalar_spec)
from repro.kernels.dot import iamax_block, iamax_update
from repro.kernels.gemm import gemm_block
from repro.kernels.gemv import gemv_block, gemvt_block
from repro.kernels.symv import symv_block
from repro.tune import config as tile_config

from . import routines as R
from .fusion import FusionGroup
from .graph import DataflowGraph


def group_key(program: str, gi: int) -> str:
    """The stable name of fusion group `gi` of `program`: the name
    scope of the group's ops (so their `op_name` reads
    `.../mvt.g0/...`) and its generated kernel's name. The drift
    report labels the group's row the same way."""
    return f"{program}.g{gi}"


# ---------------------------------------------------------------------------
# Standalone dispatch (non-fused nodes)
# ---------------------------------------------------------------------------

_KERNEL_CALL: Dict[str, Callable] = {
    "axpy": lambda s, i, kw: ops.axpy(s["alpha"], i["x"], i["y"], **kw),
    "scal": lambda s, i, kw: ops.scal(s["alpha"], i["x"], **kw),
    "waxpby": lambda s, i, kw: ops.waxpby(s["alpha"], i["x"], s["beta"],
                                          i["y"], **kw),
    "vsub": lambda s, i, kw: ops.axpy(-1.0, i["y"], i["x"], **kw),
    "vmul": lambda s, i, kw: ops.vmul(i["x"], i["y"], **kw),
    "copy": lambda s, i, kw: ops.copy(i["x"], **kw),
    "rot": lambda s, i, kw: ops.rot(s["c"], s["s"], i["x"], i["y"], **kw),
    "dot": lambda s, i, kw: ops.dot(i["x"], i["y"], **kw),
    "asum": lambda s, i, kw: ops.asum(i["x"], **kw),
    "nrm2": lambda s, i, kw: ops.nrm2(i["x"], **kw),
    "iamax": lambda s, i, kw: ops.iamax(i["x"], **kw),
    "gemv": lambda s, i, kw: ops.gemv(s["alpha"], i["A"], i["x"],
                                      s["beta"], i["y"], **kw),
    "gemvt": lambda s, i, kw: ops.gemvt(s["alpha"], i["A"], i["x"],
                                        s["beta"], i["y"], **kw),
    "transpose": lambda s, i, kw: ops.transpose(i["A"], **kw),
    "symv": lambda s, i, kw: ops.symv(s["alpha"], i["A"], i["x"],
                                      s["beta"], i["y"], **kw),
    "ger": lambda s, i, kw: ops.ger(s["alpha"], i["x"], i["y"], i["A"],
                                    **kw),
    "gemm": lambda s, i, kw: ops.gemm(s["alpha"], i["A"], i["B"],
                                      s["beta"], i["C"], **kw),
}

# level-2/3 kernels taking block-shape kwargs (symv's square window is
# a single `block=`)
_L2_BLOCK = {"gemv", "gemvt", "symv", "ger", "transpose", "gemm"}

# Per-core VMEM capacity the verify analyzer lints fused-group window
# footprints against (RV401). 16 MiB matches current TPU cores; a
# group whose live windows approach it will spill or fail to lower.
# Overridable per-part via the REPRO_VMEM_BUDGET env var (bytes).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _call_standalone(rspec, scalars, inputs, mode, interpret,
                     tile_cfg=None):
    rdef = rspec.rdef
    if mode == "reference" or rdef.kernel is None or \
            rspec.blas not in _KERNEL_CALL:
        args = [inputs[p] for p in rdef.inputs]
        return rdef.reference(scalars, *args)
    kw = {}
    if rdef.level == 1:
        br = rspec.window_size
        if tile_cfg is not None and tile_cfg.block_rows is not None:
            br = tile_cfg.block_rows
        kw = dict(block_rows=br, interpret=interpret)
    elif rspec.blas in _L2_BLOCK:
        kw = dict(interpret=interpret)
        if tile_cfg is not None:
            if rspec.blas == "symv":
                if tile_cfg.block_m is not None:
                    kw["block"] = tile_cfg.block_m
            else:
                if tile_cfg.block_m is not None:
                    kw["block_m"] = tile_cfg.block_m
                if tile_cfg.block_n is not None:
                    kw["block_n"] = tile_cfg.block_n
                if rspec.blas == "gemm" and \
                        tile_cfg.block_k is not None:
                    kw["block_k"] = tile_cfg.block_k
    return _KERNEL_CALL[rspec.blas](scalars, inputs, kw)


def _standalone_dims(rspec, ins):
    """The dims a standalone node's tile config is bucketed against —
    must mirror the autotuner's `_discover_sites` convention: matrix
    shape for level-2 (gemm appends its contraction dim), vector
    length otherwise."""
    rdef = rspec.rdef
    for port, kind in rdef.inputs.items():
        if kind == R.MAT:
            sh = tuple(int(d) for d in ins[port].shape)
            if rspec.blas == "gemm" and len(sh) == 2:
                b = ins.get("B")
                n = (int(b.shape[1]) if getattr(b, "ndim", 0) == 2
                     else sh[1])
                sh = (sh[0], n, sh[1])
            return sh
    for port in rdef.inputs:
        v = ins[port]
        if getattr(v, "ndim", 0) >= 1:
            return (int(v.shape[0]),)
    return ()


# ---------------------------------------------------------------------------
# Fused-group kernel generation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GroupSignature:
    scalar_keys: List[tuple]   # (routine, scalar_name)
    vec_in_keys: List[tuple]   # (routine, port)
    elt_out_keys: List[tuple]  # (routine, port) eltwise window outputs
    red_out_keys: List[tuple]  # (routine, port) reduction outputs


def _group_signature(graph: DataflowGraph, group: FusionGroup
                     ) -> GroupSignature:
    members = set(group.nodes)
    scalar_keys, vec_in, elt_out, red_out = [], [], [], []
    for name in group.nodes:
        rspec = graph.nodes[name]
        rdef = rspec.rdef
        for sname in rdef.scalars:
            scalar_keys.append((name, sname))
        for port in rdef.inputs:
            e = graph.producer_of(name, port)
            if e is None or e.src not in members:
                vec_in.append((name, port))
        for port, kind in rdef.outputs.items():
            if kind == R.OUT_SCALAR:
                red_out.append((name, port))
                continue
            consumers = graph.consumers_of(name, port)
            external = [e for e in consumers if e.dst not in members]
            is_pub = (not consumers) or bool(external) or \
                port in rspec.output_aliases
            if is_pub:
                elt_out.append((name, port))
    return GroupSignature(scalar_keys, vec_in, elt_out, red_out)


def _splice_routine(graph, members, name, scal_env, env, *, idx_step):
    """Run one member routine's emitter on the current block env and
    propagate its value(s) along internal edges (the on-chip
    handoff). `idx_step` is the sequential block position feeding an
    index-carrying reduction's global offset."""
    rdef = graph.nodes[name].rdef
    s = {sn: scal_env[(name, sn)] for sn in rdef.scalars}
    args = [env[(name, p)] for p in rdef.inputs]
    if rdef.index_reduction:
        vals = (iamax_block(args[0], idx_step),)
    else:
        val = rdef.emitter(s, *args)
        vals = val if isinstance(val, tuple) else (val,)
    assert len(vals) == len(rdef.outputs), rdef.name
    for port, v in zip(rdef.outputs, vals):
        for e in graph.consumers_of(name, port):
            if e.dst in members:
                env[(e.dst, e.dst_port)] = v
        env[(name, port)] = v


def _red_ref_map(sig, r_refs, is_idx):
    """Map reduction output keys to their accumulator refs: an
    (f32 max, int32 index) pair for index-carrying reductions, a
    single f32 accumulator for plain sums."""
    red_refs, cursor = {}, 0
    for key in sig.red_out_keys:
        if is_idx(key):
            red_refs[key] = (r_refs[cursor], r_refs[cursor + 1])
            cursor += 2
        else:
            red_refs[key] = (r_refs[cursor],)
            cursor += 1
    return red_refs


def _red_out_specs(graph, sig, index_map):
    """(out_specs, out_shapes) for a signature's reduction outputs:
    index-carrying reductions accumulate into an (f32 max, int32
    index) tile pair, plain sums into one f32 tile. Every tile is a
    lane-dense `ACC_SHAPE` block holding the running value in each
    element (the chip stores no scalars into VMEM)."""
    red_specs, red_shapes = [], []
    for k in sig.red_out_keys:
        dtypes = ((jnp.float32, jnp.int32)
                  if graph.nodes[k[0]].rdef.index_reduction
                  else (jnp.float32,))
        for dt in dtypes:
            red_specs.append(pl.BlockSpec(ACC_SHAPE, index_map))
            red_shapes.append(jax.ShapeDtypeStruct(ACC_SHAPE, dt))
    return red_specs, red_shapes


def _collect_results(graph, sig, outs, length, width=None):
    """Unpack a fused kernel's pallas outputs into a {(routine, port):
    value} map: window outputs are un-padded back to `length` (or
    `(length, width)` tiles for a 2-D tiled group), columnwise
    reduction outputs un-pad to `width` columns, plain reductions get
    their `post` hook (nrm2's sqrt) applied, and index-carrying
    reductions return the int32 index. A reduction tile holds its
    value in every element; element [0, 0] is read."""
    results = {}
    for key, o in zip(sig.elt_out_keys, outs[:len(sig.elt_out_keys)]):
        if width is not None:
            results[key] = o[:length, :width]
        else:
            results[key] = o.reshape(-1)[:length]
    cursor = len(sig.elt_out_keys)
    for key in getattr(sig, "colred_out_keys", ()):
        rdef = graph.nodes[key[0]].rdef
        val = outs[cursor].reshape(-1)[:width]
        cursor += 1
        post = rdef.post
        results[key] = post(val) if post is not None else val
    for key in sig.red_out_keys:
        rdef = graph.nodes[key[0]].rdef
        if rdef.index_reduction:
            results[key] = outs[cursor + 1][0, 0]
            cursor += 2
            continue
        val = outs[cursor][0, 0]
        cursor += 1
        post = rdef.post
        results[key] = post(val) if post is not None else val
    return results


def _build_fused_kernel(graph: DataflowGraph, group: FusionGroup,
                        sig: GroupSignature, out_dtype):
    """Generate the Pallas kernel body for a level-1 fused group."""
    members = set(group.nodes)
    ns, nv = len(sig.scalar_keys), len(sig.vec_in_keys)
    ne = len(sig.elt_out_keys)

    def _is_idx(key):
        return graph.nodes[key[0]].rdef.index_reduction

    def kernel(*refs):
        s_refs = refs[:ns]
        v_refs = refs[ns:ns + nv]
        e_refs = refs[ns + nv:ns + nv + ne]
        r_refs = refs[ns + nv + ne:]
        step = pl.program_id(0)

        red_refs = _red_ref_map(sig, r_refs, _is_idx)

        env = {}
        for key, ref_ in zip(sig.vec_in_keys, v_refs):
            env[key] = ref_[...].astype(jnp.float32)
        scal_env = {key: s_refs[i][0]
                    for i, key in enumerate(sig.scalar_keys)}

        for name in group.nodes:   # topo order inside the group
            _splice_routine(graph, members, name, scal_env, env,
                            idx_step=step)

        for key, ref_ in zip(sig.elt_out_keys, e_refs):
            ref_[...] = env[key].astype(out_dtype)
        for key in sig.red_out_keys:
            if _is_idx(key):
                iamax_update(*red_refs[key], *env[key], step == 0)
            else:
                acc_add(*red_refs[key], env[key], step == 0)

    return kernel


def make_group_callable(graph: DataflowGraph, group: FusionGroup,
                        dtype, *, interpret=None, tile_resolve=None,
                        name=None):
    """Returns fn(scalars: {(r,s): val}, vec_ins: {(r,p): 1-D array})
    -> {(r,p): value} for a fused group. `tile_resolve` is a
    `TilePlan.lookup` resolver overriding the group's block_rows per
    shape bucket; `name` names the kernel (`group_key`)."""
    interpret = default_interpret() if interpret is None else interpret
    sig = _group_signature(graph, group)
    default_rows = max(graph.nodes[n].window_size for n in group.nodes)
    kernel = _build_fused_kernel(graph, group, sig, dtype)
    # one jitted pallas_call per (padded rows, block) — built once and
    # reused, so eager re-execution (obs profiling) hits the jax
    # dispatch cache instead of re-tracing the kernel every call
    calls: Dict[tuple, Callable] = {}

    def _call_for(rows, br):
        fn = calls.get((rows, br))
        if fn is not None:
            return fn
        vec_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
        red_specs, red_shapes = _red_out_specs(graph, sig,
                                               lambda i: (0, 0))
        out_shapes = (
            [jax.ShapeDtypeStruct((rows, LANES), dtype)
             for _ in sig.elt_out_keys]
            + red_shapes)
        fn = jax.jit(pl.pallas_call(
            kernel,
            grid=(cdiv(rows, br),),
            in_specs=[smem_scalar_spec()] * len(sig.scalar_keys)
            + [vec_spec] * len(sig.vec_in_keys),
            out_specs=[vec_spec] * len(sig.elt_out_keys) + red_specs,
            out_shape=out_shapes,
            interpret=interpret,
            name=name,
        ))
        calls[(rows, br)] = fn
        return fn

    def run(scalars, vec_ins):
        vecs = [vec_ins[k] for k in sig.vec_in_keys]
        n = vecs[0].shape[0]
        for k, v in zip(sig.vec_in_keys, vecs):
            if v.shape[0] != n:
                raise ValueError(
                    f"fused group vectors disagree on length: "
                    f"{sig.vec_in_keys[0]}={n}, {k}={v.shape[0]}")
        v2ds = []
        for v in vecs:
            v2d, _ = as_2d(v)
            v2ds.append(v2d)
        rows = v2ds[0].shape[0]
        block_rows = default_rows
        if tile_resolve is not None:
            cfg = tile_resolve(n)
            if cfg is not None and cfg.block_rows is not None:
                block_rows = cfg.block_rows
        br = min(block_rows, rows)
        v2ds = [pad_to(v, br, axis=0) for v in v2ds]
        rows = v2ds[0].shape[0]
        outs = _call_for(rows, br)(
            *[jnp.reshape(scalars[k], (1,)).astype(jnp.float32)
              for k in sig.scalar_keys], *v2ds)
        return _collect_results(graph, sig, outs, n)

    run.signature = sig
    return run


# ---------------------------------------------------------------------------
# Level-2 anchored group kernel generation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AnchoredSignature:
    """Operand layout of a level-2 anchored fused kernel. vec_in_keys
    is the driver-facing set (it includes the matrix operand, so
    emit_program's plumbing is identical to level-1 groups);
    win_in_keys are the streamed *vector* operands in kernel order."""
    anchor: str
    scalar_keys: List[tuple]
    vec_in_keys: List[tuple]        # all external ins, incl. the matrix
    win_in_keys: List[tuple]        # vector ins only, kernel order
    elt_out_keys: List[tuple]
    red_out_keys: List[tuple]
    mat_key: tuple                  # (anchor, A)
    cols_key: tuple                 # (anchor, x): (bn, 1) windows over j
    rows_key: tuple                 # (anchor, y): (bm, 1) windows over i
    pre: Tuple[str, ...]            # members emitted in the row phase
    post: Tuple[str, ...]           # members emitted in the finish phase


def _anchored_signature(graph: DataflowGraph, group: FusionGroup
                        ) -> AnchoredSignature:
    base = _group_signature(graph, group)
    anchor = group.anchor
    ports = graph.nodes[anchor].rdef.anchor_ports
    mat_key = (anchor, ports["mat"])
    cols_key = (anchor, ports["cols"])
    rows_key = (anchor, ports["rows"])
    win_in = [k for k in base.vec_in_keys if k != mat_key]
    # members feeding the anchor run in the row phase. Group convexity
    # guarantees member-to-member paths stay inside the group, so a
    # walk back over in-group producer edges finds exactly the
    # anchor's in-group ancestors — no whole-graph sweep needed.
    members = set(group.nodes)
    pre_set, stack = set(), [anchor]
    while stack:
        node = stack.pop()
        for port in graph.nodes[node].rdef.inputs:
            e = graph.producer_of(node, port)
            if e is not None and e.src in members and \
                    e.src != anchor and e.src not in pre_set:
                pre_set.add(e.src)
                stack.append(e.src)
    pre = tuple(m for m in group.nodes if m in pre_set)
    post = tuple(m for m in group.nodes
                 if m != anchor and m not in pre_set)
    return AnchoredSignature(
        anchor=anchor, scalar_keys=base.scalar_keys,
        vec_in_keys=base.vec_in_keys, win_in_keys=win_in,
        elt_out_keys=base.elt_out_keys, red_out_keys=base.red_out_keys,
        mat_key=mat_key, cols_key=cols_key, rows_key=rows_key,
        pre=pre, post=post)


def _build_anchored_kernel(graph: DataflowGraph, group: FusionGroup,
                           sig: AnchoredSignature, out_dtype,
                           ni: int, nj: int):
    """Generate the Pallas kernel body for an anchored group.

    Grid is (ni row blocks, nj col blocks), col axis innermost — the
    same schedule as the standalone gemv/symv kernels. Per step: the
    absorbed producer chain runs on the resident (bm, 1) row windows
    (values stay in trace scope for both phases; the recompute is a
    few VPU ops on VMEM-resident data), the accumulator scratch picks
    up one (bm, bn) matrix window's contribution, and at the last col
    block the finished output window feeds the spliced consumer
    emitters: element-wise outputs are written back, reductions
    accumulate across row blocks. The anchor's output vector exists
    only in the VMEM scratch unless it is itself a program output.

    The grid shape is static here, so a single-step grid (1, 1) —
    every problem whose dims clamp below the block shape, i.e. the
    whole small-n regime — compiles to straight-line code: no
    `pl.when` phases, no cross-step accumulator staging, and (symv)
    no second mirror-window operand, since the lone block's mirror is
    its own transpose. In interpret mode those conds and the extra
    window load were costing more than the absorbed level-1 work."""
    members = set(group.nodes)
    blas = graph.nodes[sig.anchor].blas
    ns, nv = len(sig.scalar_keys), len(sig.win_in_keys)
    ne = len(sig.elt_out_keys)
    single = ni == 1 and nj == 1
    nm = 2 if blas == "symv" and not single else 1

    def _is_idx(key):
        return graph.nodes[key[0]].rdef.index_reduction

    def kernel(*refs):
        s_refs = refs[:ns]
        mat_refs = refs[ns:ns + nm]
        v_refs = refs[ns + nm:ns + nm + nv]
        e_refs = refs[ns + nm + nv:ns + nm + nv + ne]
        r_refs = refs[ns + nm + nv + ne:len(refs) - (0 if single else 1)]
        # (output_block, 1) f32 VMEM scratch: bm rows for gemv/symv,
        # bn columns of A for gemvt
        acc = None if single else refs[-1]
        if single:
            i = j = jnp.int32(0)
        else:
            i, j = pl.program_id(0), pl.program_id(1)

        red_refs = _red_ref_map(sig, r_refs, _is_idx)
        scal_env = {key: s_refs[k][0]
                    for k, key in enumerate(sig.scalar_keys)}
        env = {}
        for key, ref_ in zip(sig.win_in_keys, v_refs):
            env[key] = ref_[...].astype(jnp.float32)

        # row phase: absorbed producers of the accumulator operand
        for name in sig.pre:
            _splice_routine(graph, members, name, scal_env, env,
                            idx_step=i)

        alpha = scal_env[(sig.anchor, "alpha")]
        beta = scal_env[(sig.anchor, "beta")]
        rows_val = env[sig.rows_key]

        if blas == "symv":
            mirror = mat_refs[0] if single else mat_refs[1]
            contrib = symv_block(mat_refs[0][...], mirror[...],
                                 env[sig.cols_key], i, j)
        elif blas == "gemvt":
            # (bm, bn) A window transposed in-register against its
            # (bm, 1) x window: output tiles run over A's columns
            contrib = gemvt_block(mat_refs[0][...], env[sig.cols_key])
        else:
            contrib = gemv_block(mat_refs[0][...], env[sig.cols_key])

        if not single:
            @pl.when(j == 0)
            def _init_row():
                acc[...] = beta * rows_val

            acc[...] += alpha * contrib

        def _finish_body():
            fenv = dict(env)
            out_port = next(iter(graph.nodes[sig.anchor].rdef.outputs))
            block = (beta * rows_val + alpha * contrib) if single \
                else acc[...]
            for e in graph.consumers_of(sig.anchor, out_port):
                if e.dst in members:
                    fenv[(e.dst, e.dst_port)] = block
            fenv[(sig.anchor, out_port)] = block
            for name in sig.post:
                _splice_routine(graph, members, name, scal_env, fenv,
                                idx_step=i)
            for key, ref_ in zip(sig.elt_out_keys, e_refs):
                ref_[...] = fenv[key].astype(out_dtype)
            # reductions accumulate once per row block; the i == 0
            # select seeds them without a separate init step (the
            # single-step kernel just writes)
            first = True if single else i == 0
            for key in sig.red_out_keys:
                if _is_idx(key):
                    iamax_update(*red_refs[key], *fenv[key], first)
                else:
                    acc_add(*red_refs[key], fenv[key], first)

        if single:
            _finish_body()
        else:
            pl.when(j == nj - 1)(_finish_body)

    kernel.single = single
    kernel.nm = nm
    return kernel


def make_anchored_callable(graph: DataflowGraph, group: FusionGroup,
                           dtype, *, interpret=None, tile_resolve=None,
                           name=None):
    """Returns fn(scalars: {(r,s): val}, vec_ins: {(r,p): array}) ->
    {(r,p): value} for a level-2 anchored group. vec_ins carries the
    matrix operand under (anchor, A) alongside the vectors.
    `tile_resolve` is a `TilePlan.lookup` resolver overriding the
    (bm, bn) matrix window per shape bucket; `name` names the kernel
    (`group_key`)."""
    interpret = default_interpret() if interpret is None else interpret
    sig = _anchored_signature(graph, group)
    blas = graph.nodes[sig.anchor].blas
    # one generated kernel + jitted pallas_call per (m, n, bm, bn).
    # Building these inside every run() call used to force a fresh
    # trace/compile per eager execution — the 500x profile-vs-bench
    # wall-clock drift the obs report flagged.
    calls: Dict[tuple, Callable] = {}

    def _call_for(m, n, bm, bn):
        key = (m, n, bm, bn)
        fn = calls.get(key)
        if fn is not None:
            return fn
        mp, np_ = cdiv(m, bm) * bm, cdiv(n, bn) * bn
        # grid axis 0 walks output blocks, axis 1 (innermost) the
        # reduction axis: rows/cols of A for gemv+symv, transposed
        # for gemvt (output over A's columns, reduction over rows)
        if blas == "gemvt":
            ob, rb = bn, bm
            grid = (cdiv(np_, bn), cdiv(mp, bm))
            mat_specs = [pl.BlockSpec((bm, bn), lambda i, j: (j, i))]
        else:
            ob, rb = bm, bn
            grid = (cdiv(mp, bm), cdiv(np_, bn))
            mat_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j))]

        win_specs = []
        for key_ in sig.win_in_keys:
            if key_ == sig.cols_key:
                win_specs.append(
                    pl.BlockSpec((rb, 1), lambda i, j: (j, 0)))
            else:
                win_specs.append(
                    pl.BlockSpec((ob, 1), lambda i, j: (i, 0)))

        kernel = _build_anchored_kernel(graph, group, sig, dtype,
                                        grid[0], grid[1])

        if kernel.nm == 2:
            # mirror window (j, i), transposed
            mat_specs.append(
                pl.BlockSpec((bn, bm), lambda i, j: (j, i)))

        elt_spec = pl.BlockSpec((ob, 1), lambda i, j: (i, 0))
        red_specs, red_shapes = _red_out_specs(graph, sig,
                                               lambda i, j: (0, 0))
        out_rows = np_ if blas == "gemvt" else mp
        out_shapes = (
            [jax.ShapeDtypeStruct((out_rows, 1), dtype)
             for _ in sig.elt_out_keys]
            + red_shapes)

        fn = jax.jit(pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[smem_scalar_spec()] * len(sig.scalar_keys)
            + mat_specs + win_specs,
            out_specs=[elt_spec] * len(sig.elt_out_keys) + red_specs,
            out_shape=out_shapes,
            scratch_shapes=[] if kernel.single
            else [pltpu.VMEM((ob, 1), jnp.float32)],
            interpret=interpret,
            name=name,
        ))
        calls[key] = (fn, kernel.nm)
        return calls[key]

    def run(scalars, vec_ins):
        a = vec_ins[sig.mat_key]
        if a.ndim != 2:
            raise ValueError(
                f"anchored group {sig.anchor!r}: matrix operand must "
                f"be 2-D, got shape {a.shape}")
        m, n = a.shape
        if blas == "symv" and m != n:
            raise ValueError(
                f"symv needs a square matrix, got {a.shape}")
        cfg = tile_resolve(m, n) if tile_resolve is not None else None
        if blas == "symv":
            bm = bn = min(
                cfg.block_m if cfg is not None and
                cfg.block_m is not None else symv_mod.DEFAULT_BLOCK,
                max(n, 1))
        else:
            bm = min(
                cfg.block_m if cfg is not None and
                cfg.block_m is not None else gemv_mod.DEFAULT_BLOCK_M,
                max(m, 1))
            bn = min(
                cfg.block_n if cfg is not None and
                cfg.block_n is not None else gemv_mod.DEFAULT_BLOCK_N,
                max(n, 1))
        ap = pad_to(pad_to(a, bm, axis=0), bn, axis=1)

        # gemvt transposes the roles: its output (and every output-
        # aligned vector) runs over A's columns, its reduction-axis
        # operand x over A's rows
        out_len, red_len = (n, m) if blas == "gemvt" else (m, n)
        out_blk, red_blk = (bn, bm) if blas == "gemvt" else (bm, bn)
        win_args = []
        for key in sig.win_in_keys:
            v = vec_ins[key]
            want = red_len if key == sig.cols_key else out_len
            if v.shape[0] != want:
                raise ValueError(
                    f"anchored group vectors disagree on length: "
                    f"{key} has {v.shape[0]}, the {blas} anchor "
                    f"wants {want}")
            bv = red_blk if key == sig.cols_key else out_blk
            win_args.append(pad_to(v, bv, axis=0).reshape(-1, 1))

        fn, nm = _call_for(m, n, bm, bn)
        outs = fn(
            *[jnp.reshape(scalars[k], (1,)).astype(jnp.float32)
              for k in sig.scalar_keys], *([ap] * nm), *win_args)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        return _collect_results(graph, sig, outs, out_len)

    run.signature = sig
    return run


# ---------------------------------------------------------------------------
# Level-3 tiled (gemm-anchored) group kernel generation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TiledSignature:
    """Operand layout of a level-3 gemm-anchored fused kernel.
    vec_in_keys is the driver-facing set (matrices included, so
    emit_program's plumbing is identical to the other group shapes);
    the rest partitions it by window shape."""
    anchor: str
    scalar_keys: List[tuple]
    vec_in_keys: List[tuple]      # all external ins (driver-facing)
    mat_in_keys: List[tuple]      # member panel ins, (bm, bn) @ (i, jo)
    col_in_keys: List[tuple]      # member vector ins, (bn, 1) over jo
    elt_out_keys: List[tuple]     # (bm, bn) output tiles @ (i, jo)
    colred_out_keys: List[tuple]  # columnwise reductions, (1, bn) @ jo
    red_out_keys: List[tuple]     # scalar reductions
    mat_key: tuple                # (anchor, A): (bm, bk) @ (i, k)
    cols_key: tuple               # (anchor, B): (bk, bn) @ (k, jo)
    rows_key: tuple               # (anchor, C): (bm, bn) @ (i, jo)
    post: Tuple[str, ...]         # members spliced at the tile flush


def _tiled_signature(graph: DataflowGraph, group: FusionGroup
                     ) -> TiledSignature:
    base = _group_signature(graph, group)
    anchor = group.anchor
    ports = graph.nodes[anchor].rdef.anchor_ports
    mat_key = (anchor, ports["mat"])
    cols_key = (anchor, ports["cols"])
    rows_key = (anchor, ports["rows"])
    anchor_keys = {mat_key, cols_key, rows_key}
    mat_in, col_in = [], []
    for k in base.vec_in_keys:
        if k in anchor_keys:
            continue
        kind = graph.nodes[k[0]].rdef.inputs[k[1]]
        (mat_in if kind == R.MAT else col_in).append(k)
    # columnwise reductions (coldot) have OUT_VEC outputs, which the
    # base signature files under elt_out; re-split by classification
    elt_out, colred_out = [], []
    for k in base.elt_out_keys:
        if graph.nodes[k[0]].rdef.reduction:
            colred_out.append(k)
        else:
            elt_out.append(k)
    post = tuple(m for m in group.nodes if m != anchor)
    return TiledSignature(
        anchor=anchor, scalar_keys=base.scalar_keys,
        vec_in_keys=base.vec_in_keys, mat_in_keys=mat_in,
        col_in_keys=col_in, elt_out_keys=elt_out,
        colred_out_keys=colred_out, red_out_keys=base.red_out_keys,
        mat_key=mat_key, cols_key=cols_key, rows_key=rows_key,
        post=post)


def _build_tiled_kernel(graph: DataflowGraph, group: FusionGroup,
                        sig: TiledSignature, out_dtype,
                        ni: int, njo: int, nk: int):
    """Generate the Pallas kernel body for a gemm-anchored group.

    Grid is (ni row tiles, njo col tiles, nk contraction blocks), the
    contraction axis innermost — the standalone `kernels.gemm`
    schedule. Per step the (bm, bn) f32 accumulator scratch picks up
    one `gemm_block` contribution; at the last contraction block the
    finished tile (alpha·acc + beta·C) feeds the spliced panel
    emitters: element-wise panel outputs write (bm, bn) tiles back,
    columnwise reductions fold the tile into (1, bn) partials
    accumulated across row tiles (seeded at i == 0 by a select, like
    the 1-D anchored kernel), scalar reductions seed at the first
    output tile. Member vector operands arrive as (bn, 1) column
    windows and are presented to the emitters transposed, (1, bn), so
    the panel broadcast rule (`a * x + y`) matches the reference
    layout. A single-step (1, 1, 1) grid compiles to straight-line
    code with no scratch, exactly like the 1-D anchored kernel."""
    members = set(group.nodes)
    ns = len(sig.scalar_keys)
    nmat, ncol = len(sig.mat_in_keys), len(sig.col_in_keys)
    ne, ncr = len(sig.elt_out_keys), len(sig.colred_out_keys)
    single = ni == 1 and njo == 1 and nk == 1

    def _is_idx(key):
        return graph.nodes[key[0]].rdef.index_reduction

    def kernel(*refs):
        s_refs = refs[:ns]
        a_ref, b_ref, c_ref = refs[ns], refs[ns + 1], refs[ns + 2]
        base = ns + 3
        m_refs = refs[base:base + nmat]
        v_refs = refs[base + nmat:base + nmat + ncol]
        base += nmat + ncol
        e_refs = refs[base:base + ne]
        cr_refs = refs[base + ne:base + ne + ncr]
        r_refs = refs[base + ne + ncr:len(refs) - (0 if single else 1)]
        acc = None if single else refs[-1]  # (bm, bn) f32 VMEM scratch
        if single:
            i = jo = k = jnp.int32(0)
        else:
            i, jo, k = (pl.program_id(0), pl.program_id(1),
                        pl.program_id(2))

        red_refs = _red_ref_map(sig, r_refs, _is_idx)
        scal_env = {key: s_refs[idx][0]
                    for idx, key in enumerate(sig.scalar_keys)}

        if not single:
            @pl.when(k == 0)
            def _init_tile():
                acc[...] = jnp.zeros_like(acc)

            acc[...] += gemm_block(a_ref[...], b_ref[...])

        def _finish_body():
            alpha = scal_env[(sig.anchor, "alpha")]
            beta = scal_env[(sig.anchor, "beta")]
            contrib = gemm_block(a_ref[...], b_ref[...]) if single \
                else acc[...]
            tile = alpha * contrib + beta * c_ref[...].astype(jnp.float32)

            fenv = {}
            for key, ref_ in zip(sig.mat_in_keys, m_refs):
                fenv[key] = ref_[...].astype(jnp.float32)
            for key, ref_ in zip(sig.col_in_keys, v_refs):
                # (bn, 1) column window presented (1, bn): broadcasts
                # along the tile's column axis like the (s,) reference
                fenv[key] = ref_[...].astype(jnp.float32).reshape(1, -1)
            out_port = next(iter(graph.nodes[sig.anchor].rdef.outputs))
            for e in graph.consumers_of(sig.anchor, out_port):
                if e.dst in members:
                    fenv[(e.dst, e.dst_port)] = tile
            fenv[(sig.anchor, out_port)] = tile
            for name in sig.post:
                _splice_routine(graph, members, name, scal_env, fenv,
                                idx_step=i)

            for key, ref_ in zip(sig.elt_out_keys, e_refs):
                ref_[...] = fenv[key].astype(out_dtype)
            # columnwise reductions accumulate their (1, bn) partial
            # once per row tile; the i == 0 select seeds each jo block
            for key, ref_ in zip(sig.colred_out_keys, cr_refs):
                val = fenv[key].astype(jnp.float32)
                if single:
                    ref_[...] = val
                    continue
                prev = jnp.where(i == 0, jnp.zeros_like(val), ref_[...])
                ref_[...] = prev + val
            first = True if single else (i == 0) & (jo == 0)
            for key in sig.red_out_keys:
                if _is_idx(key):
                    raise NotImplementedError(
                        "index reductions cannot ride a tiled group")
                acc_add(*red_refs[key], fenv[key], first)

        if single:
            _finish_body()
        else:
            pl.when(k == nk - 1)(_finish_body)

    kernel.single = single
    return kernel


def make_tiled_callable(graph: DataflowGraph, group: FusionGroup,
                        dtype, *, interpret=None, tile_resolve=None,
                        name=None):
    """Returns fn(scalars: {(r,s): val}, vec_ins: {(r,p): array}) ->
    {(r,p): value} for a level-3 gemm-anchored group. vec_ins carries
    the three anchor matrices under (anchor, A/B/C) alongside the
    member panels and vectors. `tile_resolve` is a `TilePlan.lookup`
    resolver overriding the (bm, bn, bk) tile per (m, n, k) bucket;
    `name` names the kernel (`group_key`)."""
    interpret = default_interpret() if interpret is None else interpret
    sig = _tiled_signature(graph, group)
    calls: Dict[tuple, Callable] = {}

    def _call_for(m, n, k, bm, bn, bk):
        key = (m, n, k, bm, bn, bk)
        fn = calls.get(key)
        if fn is not None:
            return fn
        mp, np_ = cdiv(m, bm) * bm, cdiv(n, bn) * bn
        kp = cdiv(k, bk) * bk
        grid = (cdiv(mp, bm), cdiv(np_, bn), cdiv(kp, bk))
        kernel = _build_tiled_kernel(graph, group, sig, dtype,
                                     grid[0], grid[1], grid[2])

        tile_spec = pl.BlockSpec((bm, bn), lambda i, jo, kk: (i, jo))
        in_specs = (
            [smem_scalar_spec()] * len(sig.scalar_keys)
            + [pl.BlockSpec((bm, bk), lambda i, jo, kk: (i, kk)),
               pl.BlockSpec((bk, bn), lambda i, jo, kk: (kk, jo)),
               tile_spec]
            + [tile_spec] * len(sig.mat_in_keys)
            + [pl.BlockSpec((bn, 1), lambda i, jo, kk: (jo, 0))]
            * len(sig.col_in_keys))
        colred_spec = pl.BlockSpec((1, bn), lambda i, jo, kk: (0, jo))
        red_specs, red_shapes = _red_out_specs(graph, sig,
                                               lambda i, jo, kk: (0, 0))
        out_shapes = (
            [jax.ShapeDtypeStruct((mp, np_), dtype)
             for _ in sig.elt_out_keys]
            + [jax.ShapeDtypeStruct((1, np_), jnp.float32)
               for _ in sig.colred_out_keys]
            + red_shapes)

        fn = jax.jit(pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[tile_spec] * len(sig.elt_out_keys)
            + [colred_spec] * len(sig.colred_out_keys) + red_specs,
            out_shape=out_shapes,
            scratch_shapes=[] if kernel.single
            else [pltpu.VMEM((bm, bn), jnp.float32)],
            name=name,
            interpret=interpret,
        ))
        calls[key] = fn
        return fn

    def run(scalars, vec_ins):
        a = vec_ins[sig.mat_key]
        b = vec_ins[sig.cols_key]
        c = vec_ins[sig.rows_key]
        if a.ndim != 2 or b.ndim != 2 or c.ndim != 2:
            raise ValueError(
                f"tiled group {sig.anchor!r}: A/B/C must be 2-D, got "
                f"{a.shape}, {b.shape}, {c.shape}")
        m, kdim = a.shape
        n = b.shape[1]
        if b.shape[0] != kdim or c.shape != (m, n):
            raise ValueError(
                f"tiled group {sig.anchor!r}: inconsistent gemm "
                f"operands A{a.shape} B{b.shape} C{c.shape}")
        cfg = tile_resolve(m, n, kdim) if tile_resolve is not None \
            else None
        bm = min(cfg.block_m if cfg is not None and
                 cfg.block_m is not None else gemm_mod.DEFAULT_BLOCK_M,
                 max(m, 1))
        bn = min(cfg.block_n if cfg is not None and
                 cfg.block_n is not None else gemm_mod.DEFAULT_BLOCK_N,
                 max(n, 1))
        bk = min(cfg.block_k if cfg is not None and
                 cfg.block_k is not None else gemm_mod.DEFAULT_BLOCK_K,
                 max(kdim, 1))
        ap = pad_to(pad_to(a, bm, axis=0), bk, axis=1)
        bp = pad_to(pad_to(b, bk, axis=0), bn, axis=1)
        cp = pad_to(pad_to(c, bm, axis=0), bn, axis=1)

        panel_args = []
        for key in sig.mat_in_keys:
            v = vec_ins[key]
            if v.shape != (m, n):
                raise ValueError(
                    f"tiled group panels disagree on shape: {key} has "
                    f"{v.shape}, the {sig.anchor} anchor tiles (m, n)="
                    f"({m}, {n})")
            panel_args.append(pad_to(pad_to(v, bm, axis=0), bn, axis=1))
        col_args = []
        for key in sig.col_in_keys:
            v = vec_ins[key]
            if v.shape[0] != n:
                raise ValueError(
                    f"tiled group column vectors disagree on length: "
                    f"{key} has {v.shape[0]}, want n={n}")
            col_args.append(pad_to(v, bn, axis=0).reshape(-1, 1))

        outs = _call_for(m, n, kdim, bm, bn, bk)(
            *[jnp.reshape(scalars[key], (1,)).astype(jnp.float32)
              for key in sig.scalar_keys],
            ap, bp, cp, *panel_args, *col_args)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        return _collect_results(graph, sig, outs, m, width=n)

    run.signature = sig
    return run


# ---------------------------------------------------------------------------
# Whole-program emission
# ---------------------------------------------------------------------------


def emit_program(graph: DataflowGraph, groups: List[FusionGroup],
                 mode: str, *, interpret=None, tiles=None):
    """Lower (graph, fusion plan) to one python callable over a dict of
    program inputs, returning a dict of program outputs. `tiles` is
    the resolved `TilePlan` (sites `g{i}` for fused groups,
    `g{i}:{routine}` for standalone nodes); None/empty keeps kernel
    defaults everywhere."""
    if mode not in ("dataflow", "nodataflow", "reference"):
        raise ValueError(f"unknown mode {mode!r}")
    interpret = default_interpret() if interpret is None else interpret
    dtype = graph.spec.dtype
    if tiles is None:
        tiles = tile_config.EMPTY_PLAN

    # public-input bindings: name -> list[(routine, port)]
    input_bindings: Dict[str, list] = {}
    for pi in graph.inputs:
        input_bindings.setdefault(pi.name, []).append((pi.routine, pi.port))

    fused_callables = {}
    if mode == "dataflow":
        for gi, g in enumerate(groups):
            if not g.fused:
                continue
            if g.anchor is None:
                make = make_group_callable
            elif R.OUT_MAT in set(
                    graph.nodes[g.anchor].rdef.outputs.values()):
                make = make_tiled_callable
            else:
                make = make_anchored_callable
            fused_callables[gi] = make(
                graph, g, dtype, interpret=interpret,
                tile_resolve=tiles.lookup(f"g{gi}") if tiles else None,
                name=group_key(graph.spec.name, gi))

    # call-time tile resolvers for standalone dispatches
    standalone_resolvers = {}
    if tiles and mode != "reference":
        for gi, g in enumerate(groups):
            if gi in fused_callables:
                continue
            for name in g.nodes:
                standalone_resolvers[(gi, name)] = \
                    tiles.lookup(f"g{gi}:{name}")

    if obs.enabled():
        # one tag per generated kernel / standalone dispatch so JSONL
        # traces carry the whole emitted-kernel inventory
        for gi, g in enumerate(groups):
            kind = ("anchored" if g.anchor else
                    "fused" if gi in fused_callables else "standalone")
            obs.event("codegen.group", program=graph.spec.name,
                      mode=mode, group=gi, kind=kind,
                      anchor=g.anchor, routines=list(g.nodes))

    def _group_span(gi, g, timed):
        """Timing hook around one group execution: a `kernel.group`
        span when recording is on AND the operands are concrete (a
        span during jit tracing would time the trace, not the
        kernel)."""
        if not timed:
            return obs.NULL_SPAN
        return obs.span(
            "kernel.group", program=graph.spec.name, mode=mode,
            group=gi, anchor=g.anchor, fused=g.fused,
            routines="+".join(g.nodes))

    def program(inputs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        missing = [n for n in graph.input_names() if n not in inputs]
        if missing:
            raise ValueError(f"missing program inputs: {missing}")
        # values produced so far, keyed by (routine, port)
        env: Dict[tuple, jax.Array] = {}
        for pub, bindings in input_bindings.items():
            for key in bindings:
                env[key] = inputs[pub]

        timed = obs.enabled() and obs.concrete(inputs.values())

        def scalar_value(rspec, sname):
            b = rspec.scalars[sname]
            if b.kind == "value":
                return jnp.asarray(b.value, jnp.float32)
            return jnp.asarray(inputs[b.input_name], jnp.float32)

        for gi, g in enumerate(groups):
            with jax.named_scope(group_key(graph.spec.name, gi)), \
                    _group_span(gi, g, timed):
                if gi in fused_callables:
                    run = fused_callables[gi]
                    sig = run.signature
                    scalars = {
                        (rn, sn): scalar_value(graph.nodes[rn], sn)
                        for (rn, sn) in sig.scalar_keys}
                    vec_ins = {k: env[k] for k in sig.vec_in_keys}
                    out = run(scalars, vec_ins)
                    if timed:
                        obs.block(out.values())
                    env.update(out)
                else:
                    for name in g.nodes:
                        rspec = graph.nodes[name]
                        rdef = rspec.rdef
                        s = {sn: scalar_value(rspec, sn)
                             for sn in rdef.scalars}
                        ins = {p: env[(name, p)] for p in rdef.inputs}
                        resolve = standalone_resolvers.get((gi, name))
                        cfg = None
                        if resolve is not None:
                            cfg = resolve(*_standalone_dims(rspec, ins))
                        out = _call_standalone(rspec, s, ins, mode,
                                               interpret, tile_cfg=cfg)
                        out_ports = list(rdef.outputs)
                        outs = out if isinstance(out, tuple) else (out,)
                        for port, val in zip(out_ports, outs):
                            env[(name, port)] = val
                        if timed:
                            obs.block(outs)
            # propagate along edges leaving this group
            for name in g.nodes:
                for port in graph.nodes[name].rdef.outputs:
                    for e in graph.consumers_of(name, port):
                        if (e.src, e.src_port) in env:
                            env[(e.dst, e.dst_port)] = env[
                                (e.src, e.src_port)]

        return {o.name: env[(o.routine, o.port)] for o in graph.outputs}

    return program
