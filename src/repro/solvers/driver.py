"""Drivers that run dataflow-composed iteration bodies fully on-device.

Two ways to describe an iteration, one driver underneath:

* `SolverProgram` — subclass hooks written in Python
  (`_init_state` / `_step` / `_solution`) built from compiled
  `core.runtime.Program` bodies. BiCGStab and power iteration use this.
* `LoopProgram` — the iteration itself is *described in the JSON
  spec* (`iterate` section: state fields, feedback edges for vectors
  AND scalars, scalar update expressions, stop rule) and executed
  generically. CG and Jacobi run this way — zero per-solver Python.

Either way the driver wraps the iteration in a single
`jax.lax.while_loop` under one `jax.jit`, so the entire solve —
matvecs, vector updates, scalar feedback, and the convergence test —
compiles once and never leaves the device. The loop stops when
`res <= tol * scale` or after `max_iters` iterations, and a
per-iteration residual history rides along in the carry for telemetry
(NaN past the stopping point).

`trace_count` counts how many times the loop body is *traced* (not
executed): it must be 1 after a solve, which is how the tests pin down
"the iteration body compiles once, no per-iteration retracing".

`batched()` (LoopProgram) / `solve_batched()` vmap the same jitted
solve over a leading right-hand-side axis: one compiled loop serves a
whole block of systems, with per-lane stopping handled by JAX's
while-loop batching rule.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import lowering
from repro.core.expr import sdiv as _sdiv  # noqa: F401  (re-export)
from repro.core.runtime import Program
from repro.core.spec import CountRule, SpecError
from repro.guard import chaos as _chaos
from repro.guard import status as ST

_TINY = 1e-30


@dataclasses.dataclass
class SolverResult:
    """Outcome of one on-device solve (batched fields carry a leading
    right-hand-side axis when produced by a batched solve)."""
    x: jax.Array            # solution (eigvec for eigen-solvers)
    iterations: jax.Array   # int32 — iterations actually run
    residual: jax.Array     # final convergence metric
    history: jax.Array      # (max_iters + 1,) f32; NaN past the stop
    converged: jax.Array    # bool
    # int8 repro.guard.status code (CONVERGED/MAX_ITERS/BREAKDOWN/
    # NONFINITE/DIVERGED/STAGNATED), per lane for batched solves
    status: Optional[jax.Array] = None
    aux: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # escalation-driver attempt log (guard.escalate.Attempt records);
    # None for plain solves
    attempts: Optional[list] = None

    def __repr__(self):
        it = jnp.asarray(self.iterations)
        if it.ndim:   # batched result
            return (f"SolverResult(batch={it.shape[0]}, "
                    f"iterations={it.tolist()}, "
                    f"status={self.status_names()})")
        return (f"SolverResult(iterations={int(self.iterations)}, "
                f"residual={float(self.residual):.3e}, "
                f"status={self.status_names()})")

    def status_names(self):
        """Status code(s) as name strings: one string, or a per-lane
        list for batched results."""
        st = jnp.asarray(self.status)
        if st.ndim:
            return [ST.status_name(s) for s in st]
        return ST.status_name(st)

    def history_trimmed(self):
        """Residual history without the NaN tail past the stopping
        point: a (iterations + 1,) numpy array, or a per-lane list of
        such arrays for batched results (lanes stop at different
        iterations, so the trimmed histories are ragged)."""
        import numpy as np
        hist = np.asarray(self.history)
        its = np.asarray(self.iterations)
        if its.ndim:
            return [hist[lane, :int(k) + 1]
                    for lane, k in enumerate(its)]
        return hist[:int(its) + 1]


class SolverProgram:
    """Base driver for iterative solvers over AIEBLAS dataflow programs."""

    name = "solver"

    def __init__(self, *, mode: str = "dataflow", max_iters: int = 200,
                 interpret: Optional[bool] = None):
        if mode not in ("dataflow", "nodataflow", "reference"):
            raise ValueError(f"unknown mode {mode!r}")
        self.mode = mode
        self.max_iters = int(max_iters)
        self.interpret = interpret
        self.trace_count = 0
        self._solve_fn = None
        self._batched_fns = {}

    # -- subclass hooks -------------------------------------------------

    def _init_state(self, operands):
        raise NotImplementedError

    def _step(self, operands, state, threshold):
        raise NotImplementedError

    def _solution(self, state):
        raise NotImplementedError

    # -- plumbing -------------------------------------------------------

    def _program(self, spec) -> Program:
        """Compile one iteration-body piece through the full lowering
        pipeline (parse -> graph -> infer -> fuse -> place -> emit);
        repeated bodies hit the program cache and compile once."""
        return Program.from_spec(spec, mode=self.mode,
                                 interpret=self.interpret)

    def _guards(self):
        """The GuardSpec driving the guarded while-loop, or None for
        the classic ungated loop (class-based solvers, loop specs
        without a guards section). With None the solve closure is
        byte-identical to the pre-guard driver."""
        return None

    def _step_guarded(self, operands, state, threshold, k):
        """Guarded-path step hook: like `_step` but also returns an
        int8 in-body fault code (RUNNING when clean). `k` is the
        traced iteration counter, published to `repro.guard.chaos` so
        iteration-targeted fault plans can gate on it."""
        st, res = self._step(operands, state, threshold)
        return st, res, jnp.int8(ST.RUNNING)

    def _build_raw(self):
        """The solve closure, before jit — also the vmap target for
        batched solves."""
        if self._guards() is not None:
            return self._build_raw_guarded(self._guards())
        max_iters = self.max_iters

        def solve(operands, tol):
            state, res0, scale = self._init_state(operands)
            res0 = jnp.asarray(res0, jnp.float32)
            threshold = tol * jnp.maximum(
                jnp.asarray(scale, jnp.float32), _TINY)
            hist = jnp.full((max_iters + 1,), jnp.nan, jnp.float32)
            hist = hist.at[0].set(res0)

            def cond(carry):
                k, res, _, _ = carry
                return jnp.logical_and(k < max_iters, res > threshold)

            def body(carry):
                self.trace_count += 1  # python side effect: counts traces
                obs.event("loop.trace", program=self.name,
                          mode=self.mode, trace=self.trace_count)
                k, _, st, h = carry
                st, res = self._step(operands, st, threshold)
                res = jnp.asarray(res, jnp.float32)
                h = h.at[k + 1].set(res)
                return (k + 1, res, st, h)

            k, res, state, hist = jax.lax.while_loop(
                cond, body, (jnp.int32(0), res0, state, hist))
            return dict(state=state, iterations=k, residual=res,
                        history=hist, converged=res <= threshold)

        return solve

    def _build_raw_guarded(self, guards):
        """The guarded solve closure: same single `lax.while_loop`,
        but the carry holds an int8 status and the cond is simply
        `status == RUNNING`. Each iteration the body classifies the
        new metric (and any in-body fault from `_step_guarded`) into a
        `repro.guard.status` code, so a poisoned solve exits in O(1)
        iterations after the fault instead of running all max_iters.
        Under vmap each lane carries its own status: JAX's while-loop
        batching freezes a lane's carry once its cond goes False, so
        statuses are per-lane exact."""
        max_iters = self.max_iters
        window = guards.stagnation
        keep = jnp.float32(1.0 - guards.min_drop)

        def classify(k1, stall):
            """Lowest-priority codes; the caller layers DIVERGED,
            CONVERGED, NONFINITE, and the in-body fault on top (later
            writes win)."""
            status = jnp.int8(ST.RUNNING)
            status = jnp.where(k1 >= max_iters,
                               jnp.int8(ST.MAX_ITERS), status)
            if window is not None:
                status = jnp.where(stall >= window,
                                   jnp.int8(ST.STAGNATED), status)
            return status

        def solve(operands, tol):
            state, res0, scale = self._init_state(operands)
            res0 = jnp.asarray(res0, jnp.float32)
            threshold = tol * jnp.maximum(
                jnp.asarray(scale, jnp.float32), _TINY)
            hist = jnp.full((max_iters + 1,), jnp.nan, jnp.float32)
            hist = hist.at[0].set(res0)
            div_limit = None
            if guards.divergence is not None:
                div_limit = jnp.float32(guards.divergence) * \
                    jnp.maximum(res0, jnp.float32(_TINY))

            status0 = jnp.where(res0 <= threshold,
                                jnp.int8(ST.CONVERGED),
                                jnp.int8(ST.RUNNING))
            status0 = jnp.where(jnp.isfinite(res0), status0,
                                jnp.int8(ST.NONFINITE))
            if max_iters <= 0:    # degenerate budget: never iterate
                status0 = jnp.where(status0 == jnp.int8(ST.RUNNING),
                                    jnp.int8(ST.MAX_ITERS), status0)

            def cond(carry):
                return carry[2] == jnp.int8(ST.RUNNING)

            def body(carry):
                self.trace_count += 1  # python side effect: trace count
                obs.event("loop.trace", program=self.name,
                          mode=self.mode, trace=self.trace_count)
                k, _, _, st, h, best, stall = carry
                st, res, fault = self._step_guarded(
                    operands, st, threshold, k)
                res = jnp.asarray(res, jnp.float32)
                h = h.at[k + 1].set(res)
                k1 = k + 1
                improved = res < best * keep
                stall1 = jnp.where(improved, jnp.int32(0), stall + 1)
                best1 = jnp.minimum(best, res)
                status = classify(k1, stall1)
                if div_limit is not None:
                    status = jnp.where(res > div_limit,
                                       jnp.int8(ST.DIVERGED), status)
                status = jnp.where(res <= threshold,
                                   jnp.int8(ST.CONVERGED), status)
                status = jnp.where(jnp.isfinite(res), status,
                                   jnp.int8(ST.NONFINITE))
                status = jnp.where(fault != jnp.int8(ST.RUNNING),
                                   fault, status)
                return (k1, res, status, st, h, best1, stall1)

            k, res, status, state, hist, _, _ = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), res0, status0, state, hist, res0,
                 jnp.int32(0)))
            return dict(state=state, iterations=k, residual=res,
                        history=hist,
                        converged=status == jnp.int8(ST.CONVERGED),
                        status=status)

        return solve

    def _build(self):
        return jax.jit(self._build_raw())

    def _package(self, out) -> SolverResult:
        sol = dict(self._solution(out["state"]))
        status = out.get("status")
        if status is None:
            # ungated loop: the only outcomes are converged or budget
            # exhausted (derived host-side, the loop jaxpr unchanged)
            status = jnp.where(out["converged"],
                               jnp.int8(ST.CONVERGED),
                               jnp.int8(ST.MAX_ITERS))
        return SolverResult(
            x=sol.pop("x"),
            iterations=out["iterations"],
            residual=out["residual"],
            history=out["history"],
            converged=out["converged"],
            status=status,
            aux=sol,
        )

    def _export_result(self, res: SolverResult, *, batched: bool
                       ) -> None:
        """Convergence telemetry: one `solver.result` event per solve
        with (iterations, final_residual, converged) — per lane for
        batched solves, never the NaN-padded raw history."""
        if not obs.enabled():
            return
        import numpy as np
        its = np.asarray(res.iterations)
        resid = np.asarray(res.residual)
        conv = np.asarray(res.converged)
        if batched:
            obs.event("solver.result", program=self.name,
                      mode=self.mode, batch=int(its.shape[0]),
                      iterations=[int(k) for k in its],
                      final_residual=[float(r) for r in resid],
                      converged=[bool(c) for c in conv],
                      status=res.status_names())
        else:
            obs.event("solver.result", program=self.name,
                      mode=self.mode, iterations=int(its),
                      final_residual=float(resid),
                      converged=bool(conv),
                      status=res.status_names())

    def _run(self, operands: Dict[str, jax.Array],
             tol: float) -> SolverResult:
        if self._solve_fn is None:
            self._solve_fn = self._build()
        with obs.span("solver.solve", program=self.name,
                      mode=self.mode):
            out = self._solve_fn(operands, jnp.float32(tol))
            if obs.enabled():
                obs.block(jax.tree_util.tree_leaves(out))
        res = self._package(out)
        self._export_result(res, batched=False)
        return res

    def _run_batched(self, operands: Dict[str, jax.Array], tol: float,
                     in_axes: Mapping[str, Optional[int]]) -> SolverResult:
        """vmap the jitted solve over the given per-operand axes; the
        vmapped program is cached per axes signature."""
        key = tuple(sorted(in_axes.items()))
        fn = self._batched_fns.get(key)
        if fn is None:
            fn = jax.jit(jax.vmap(self._build_raw(),
                                  in_axes=(dict(in_axes), None)))
            self._batched_fns[key] = fn
        with obs.span("solver.solve", program=self.name,
                      mode=self.mode, batched=True):
            out = fn(operands, jnp.float32(tol))
            if obs.enabled():
                obs.block(jax.tree_util.tree_leaves(out))
        res = self._package(out)
        self._export_result(res, batched=True)
        return res

    def describe(self) -> str:
        """Fusion-plan report for every compiled iteration-body piece."""
        lines = [f"solver {self.name!r} mode={self.mode} "
                 f"max_iters={self.max_iters}"]
        for attr in sorted(vars(self)):
            prog = getattr(self, attr)
            if isinstance(prog, Program):
                lines.append(prog.describe())
        return "\n".join(lines)


class LoopProgram(SolverProgram):
    """Generic executor for JSON-described loop programs.

    The spec's `iterate` section IS the solver: state init, the staged
    dataflow body, scalar update expressions, vector/scalar feedback
    edges, and the stop rule all come from JSON (`core.spec.parse_loop`
    + `core.lowering.lower_loop`); this class only threads values
    between compiled stage programs inside the shared while-loop
    driver. Stage programs are compiled through the digest-keyed
    program cache, so bodies shared between loop specs (or with the
    class-based solvers) compile once per mode.
    """

    def __init__(self, spec, *, mode: Optional[str] = None,
                 max_iters: Optional[int] = None,
                 interpret: Optional[bool] = None, tiles="auto",
                 verify: bool = True, fault=None):
        if isinstance(spec, lowering.LoopIR):
            # a pre-lowered IR fixes mode/interpret: its stage kernels
            # are already compiled for that configuration
            lir = spec
            if mode is not None and mode != lir.mode:
                raise ValueError(
                    f"LoopIR was lowered for mode={lir.mode!r}; "
                    f"cannot run it as mode={mode!r}")
            if interpret is not None and interpret != lir.interpret:
                raise ValueError(
                    f"LoopIR was lowered with "
                    f"interpret={lir.interpret!r}; cannot run it with "
                    f"interpret={interpret!r}")
            if fault is not None:
                raise ValueError(
                    "fault plans must be threaded through lowering; "
                    "pass the raw spec (not a pre-lowered LoopIR) "
                    "together with fault=")
            mode, interpret = lir.mode, lir.interpret
        else:
            mode = "dataflow" if mode is None else mode
            lir = lowering.lower_loop(spec, mode=mode,
                                      interpret=interpret, tiles=tiles,
                                      verify=verify, fault=fault)
        self.lir = lir
        self.name = lir.lspec.name
        if "x" not in lir.lspec.solution:
            raise SpecError(
                f"loop {self.name!r}: iterate.solution must bind 'x' "
                f"(the primary solution the driver reports)")
        super().__init__(
            mode=mode,
            max_iters=(lir.lspec.stop.max_iters
                       if max_iters is None else max_iters),
            interpret=interpret)
        self._setup_env = None

    # -- spec-driven driver hooks ---------------------------------------

    def _run_stages(self, stages, env):
        for cs in stages:
            if cs.tag == "let":
                for name, expr in cs.stage.bindings:
                    env[name] = expr.evaluate(env)
            elif cs.tag == "program":
                ins = {pub: env[src] for pub, src in cs.inputs.items()}
                out = cs.ir.fn(ins)
                for pub, dst in cs.outputs.items():
                    env[dst] = out[pub]
            elif cs.tag == "read":
                st = cs.stage
                idx = jnp.asarray(st.slot.evaluate(env), jnp.int32)
                env[st.name] = jax.lax.dynamic_index_in_dim(
                    env[st.source], idx, axis=0, keepdims=False)
            elif cs.tag == "store":
                st = cs.stage
                idx = jnp.asarray(st.slot.evaluate(env), jnp.int32)
                buf, val = env[st.into], env[st.value]
                if st.at is not None:
                    at = jnp.asarray(st.at.evaluate(env), jnp.int32)
                    env[st.into] = buf.at[idx, at].set(
                        jnp.asarray(val, buf.dtype))
                else:
                    env[st.into] = buf.at[idx].set(
                        jnp.asarray(val, buf.dtype))
            elif cs.tag == "cond":
                self._run_cond(cs, env)
            else:                     # "loop": nested iterate
                self._run_inner(cs, env)
        return env

    def _run_cond(self, cs, env):
        """One `lax.cond` stage: both branches return the names they
        have in common (the lowered `produced` tuple); everything else
        stays branch-local."""
        pred = cs.stage.pred.evaluate(env)

        def branch(stages):
            def fn(_):
                benv = self._run_stages(stages, dict(env))
                return tuple(benv[n] for n in cs.produced)
            return fn

        vals = jax.lax.cond(pred, branch(cs.then), branch(cs.orelse),
                            None)
        env.update(zip(cs.produced, vals))

    def _run_inner(self, cs, env):
        """One nested iterate: its own `lax.while_loop` inside the
        enclosing loop's body trace. Inner state initializes from the
        enclosing environment; yields export final inner state. Its
        body runs under lax control flow, where per-kernel spans stay
        silent."""
        ispec = cs.stage
        state = self._init_fields(ispec.state, env)
        stop = ispec.stop

        def step(k, st):
            benv = dict(env)
            benv.update(st)
            if ispec.counter is not None:
                benv[ispec.counter] = k
            benv = self._run_stages(cs.body, benv)
            return benv, self._next_state(ispec, st, benv)

        if isinstance(stop, CountRule):
            count = jnp.asarray(stop.count.evaluate(env), jnp.int32)

            def cond_fn(carry):
                k, _ = carry
                return k < count

            def body_fn(carry):
                k, st = carry
                _, st = step(k, st)
                return (k + 1, st)

            _, state = jax.lax.while_loop(cond_fn, body_fn,
                                          (jnp.int32(0), state))
        else:
            scale = (env[stop.scale] if isinstance(stop.scale, str)
                     else jnp.float32(stop.scale))
            thr = jnp.float32(stop.rtol) * jnp.maximum(
                jnp.asarray(scale, jnp.float32), _TINY)
            res0 = jnp.asarray(env[stop.init_metric], jnp.float32)

            def cond_fn(carry):
                k, res, _ = carry
                return jnp.logical_and(k < stop.max_iters, res > thr)

            def body_fn(carry):
                k, _, st = carry
                benv, st = step(k, st)
                return (k + 1,
                        jnp.asarray(benv[stop.metric], jnp.float32),
                        st)

            _, _, state = jax.lax.while_loop(
                cond_fn, body_fn, (jnp.int32(0), res0, state))

        for outer_name, field in ispec.yields.items():
            env[outer_name] = state[field]

    def _make_stack(self, f, env):
        """Preallocate one stack buffer: zeros (optionally slot 0
        seeded), or a whole buffer adopted from the environment."""
        dtype = self.lir.lspec.dtype
        if f.source is not None:
            buf = jnp.asarray(env[f.source], dtype)
            if buf.shape[0] != f.slots:
                raise ValueError(
                    f"loop {self.name!r}: stack {f.name!r} adopts "
                    f"{f.source!r} with leading dim {buf.shape[0]}, "
                    f"but declares {f.slots} slots")
            return buf
        if f.of == "scalar":
            buf = jnp.zeros((f.slots,), dtype)
        elif f.length is not None:
            buf = jnp.zeros((f.slots, f.length), dtype)
        else:
            # element shape adopted from the prototype: (n,) for a
            # vector stack, (n, s) for a matrix stack
            proto = f.like if f.like is not None else f.slot0
            buf = jnp.zeros((f.slots,) + tuple(env[proto].shape),
                            dtype)
        if f.slot0 is not None:
            buf = buf.at[0].set(jnp.asarray(env[f.slot0], dtype))
        return buf

    def _init_fields(self, fields, env):
        state = {}
        for f in fields:
            if f.is_stack:
                state[f.name] = self._make_stack(f, env)
            else:
                bare = f.init.bare_name
                state[f.name] = (env[bare] if bare is not None
                                 else f.init.evaluate(env))
        return state

    @staticmethod
    def _next_state(it, state, env):
        """Next loop carry: explicit feedback edges, automatic
        feedback for stacks (the buffer as mutated by the iteration's
        stores), carry-over for the rest. `it` is anything with
        `.state` fields and a `.feedback` map (LoopSpec or
        InnerLoopStage)."""
        out = {}
        for f in it.state:
            if f.is_stack:
                out[f.name] = env[f.name]
            elif f.name in it.feedback:
                out[f.name] = env[it.feedback[f.name]]
            else:
                out[f.name] = state[f.name]
        return out

    def _init_state(self, operands):
        env = self._run_stages(self.lir.setup, dict(operands))
        # loop-invariant setup values are closed over by the body trace
        # (they become implicit while_loop operands, not carry entries)
        self._setup_env = env
        state = self._init_fields(self.lir.lspec.state, env)
        stop = self.lir.lspec.stop
        scale = (env[stop.scale] if isinstance(stop.scale, str)
                 else jnp.float32(stop.scale))
        return state, env[stop.init_metric], scale

    def _step(self, operands, state, threshold):
        env = dict(self._setup_env)
        env.update(state)
        # reserved name: cond predicates can express early exits
        # against the driver's stop threshold (tol * scale)
        env["threshold"] = threshold
        env = self._run_stages(self.lir.body, env)
        lspec = self.lir.lspec
        return (self._next_state(lspec, state, env),
                env[lspec.stop.metric])

    def _guards(self):
        return self.lir.lspec.guards

    def _step_guarded(self, operands, state, threshold, k):
        """One guarded iteration: run the staged body with the loop
        counter published (so iteration-targeted FaultPlans can
        fire), then evaluate the spec's breakdown/nonfinite guard
        predicates over the fresh body environment."""
        env = dict(self._setup_env)
        env.update(state)
        env["threshold"] = threshold
        with _chaos.loop_iteration(k):
            env = self._run_stages(self.lir.body, env)
        lspec = self.lir.lspec
        g = lspec.guards
        fault = jnp.int8(ST.RUNNING)
        for name in g.nonfinite:
            ok = jnp.all(jnp.isfinite(
                jnp.asarray(env[name], jnp.float32)))
            fault = jnp.where(ok, fault, jnp.int8(ST.NONFINITE))
        for bg in g.breakdown:
            # vector sentinels (one entry per right-hand side, as in
            # block-CG's Gram diagonal) trip if ANY entry collapses.
            # Checked last so BREAKDOWN (the root cause) outranks
            # NONFINITE (its downstream symptom) when a collapsed
            # denominator has already poisoned the iterate.
            trip = jnp.any(jnp.abs(jnp.asarray(env[bg.value],
                                               jnp.float32)) < bg.below)
            fault = jnp.where(trip, jnp.int8(ST.BREAKDOWN), fault)
        return (self._next_state(lspec, state, env),
                env[lspec.stop.metric], fault)

    def _solution(self, state):
        return {pub: state[src]
                for pub, src in self.lir.lspec.solution.items()}

    # -- public API -----------------------------------------------------

    def _check_operands(self, operands):
        want = set(self.lir.lspec.operands)
        missing = sorted(want - set(operands))
        extra = sorted(set(operands) - want)
        if missing or extra:
            raise ValueError(
                f"loop {self.name!r}: operand mismatch "
                f"(missing {missing}, unexpected {extra}); declared "
                f"operands: {sorted(want)}")

    def solve(self, *, tol: Optional[float] = None,
              **operands) -> SolverResult:
        """One on-device solve; operands are the spec's declared
        operand names. `tol` overrides the spec's `while.rtol`."""
        self._check_operands(operands)
        rtol = self.lir.lspec.stop.rtol if tol is None else tol
        return self._run(operands, rtol)

    def batched(self, *, tol: Optional[float] = None,
                axes: Optional[Mapping[str, Optional[int]]] = None,
                **operands) -> SolverResult:
        """Multi-RHS solve: vmap over the jitted solve. By default
        vector operands batch over a leading axis and matrix/scalar
        operands broadcast (the multi-right-hand-side convention);
        `axes` overrides per operand. Every result field gains a
        leading batch axis."""
        self._check_operands(operands)
        kinds = self.lir.lspec.operands
        in_axes = {n: (0 if kinds[n] == "vector" else None)
                   for n in kinds}
        if axes:
            unknown = sorted(set(axes) - set(in_axes))
            if unknown:
                raise ValueError(
                    f"loop {self.name!r}: axes for unknown operands "
                    f"{unknown}")
            in_axes.update(axes)
        rtol = self.lir.lspec.stop.rtol if tol is None else tol
        return self._run_batched(operands, rtol, in_axes)

    def _describe_stages(self, stages, label, lines, indent="  "):
        for cs in stages:
            if cs.tag == "let":
                exprs = ", ".join(f"{n} = {e.src}"
                                  for n, e in cs.stage.bindings)
                lines.append(f"{indent}{label} let: {exprs}")
            elif cs.tag == "program":
                desc = Program.from_ir(cs.ir).describe()
                lines.append(indent + desc.replace("\n", "\n" + indent))
            elif cs.tag == "read":
                st = cs.stage
                lines.append(f"{indent}{label} read: {st.name} = "
                             f"{st.source}[{st.slot.src}]")
            elif cs.tag == "store":
                st = cs.stage
                at = f", {st.at.src}" if st.at is not None else ""
                lines.append(f"{indent}{label} store: "
                             f"{st.into}[{st.slot.src}{at}] = "
                             f"{st.value}")
            elif cs.tag == "cond":
                lines.append(f"{indent}{label} cond: "
                             f"if {cs.stage.pred.src}")
                self._describe_stages(cs.then, "then", lines,
                                      indent + "  ")
                self._describe_stages(cs.orelse, "else", lines,
                                      indent + "  ")
            else:                     # nested iterate
                st = cs.stage
                stop = st.stop
                if isinstance(stop, CountRule):
                    src = stop.count.src
                    if stop.count.ast[0] == "num" and \
                            float(stop.count.ast[1]).is_integer():
                        src = str(int(stop.count.ast[1]))
                    rule = f"count {src}"
                else:
                    rule = (f"{stop.metric} <= rtol * {stop.scale!r} "
                            f"(max {stop.max_iters})")
                stacks = ", ".join(
                    f"{f.name}[{f.slots}]" for f in st.state
                    if f.is_stack)
                lines.append(
                    f"{indent}{label} inner loop"
                    + (f" (counter {st.counter})" if st.counter
                       else "")
                    + f": {rule}"
                    + (f" stacks: {stacks}" if stacks else ""))
                self._describe_stages(cs.body, "inner", lines,
                                      indent + "  ")

    def describe(self) -> str:
        """Stage-by-stage report: fusion plans of every compiled stage
        program, scalar-expression stages, conditionals, stack
        reads/stores, and nested loops."""
        lspec = self.lir.lspec
        lines = [f"loop program {self.name!r} mode={self.mode} "
                 f"max_iters={self.max_iters} "
                 f"stop: {lspec.stop.metric} <= rtol * "
                 f"{lspec.stop.scale!r}"]
        self._describe_stages(self.lir.setup, "setup", lines)
        self._describe_stages(self.lir.body, "body", lines)
        feedback = ", ".join(f"{k} <- {v}"
                             for k, v in lspec.feedback.items())
        if feedback:
            lines.append(f"  feedback: {feedback}")
        stacks = ", ".join(f"{f.name}[{f.slots}]"
                           for f in lspec.state if f.is_stack)
        if stacks:
            lines.append(f"  stacks (auto-feedback): {stacks}")
        return "\n".join(lines)
