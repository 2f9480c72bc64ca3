#!/usr/bin/env python3
"""Smoke run of the BLAS library's main path on one TPU chip.

    python3 chip_smoke.py [--seed N]

Four phases run in this one process. Each makes its data on the device
from `--seed`, goes through the public front door (`repro.blas`), and
checks its result against a plain reference: XLA's own matrix product
at `precision=HIGHEST` on the device (a 2-D array is slow to copy to
the host), everything else in float64 NumPy on the host:

  cg        `specs.CG_LOOP` on a dense, symmetric, diagonally dominant
            A with n = 16384 f32 (1 GiB)
  block_cg  `specs.BLOCK_CG_LOOP` on the same A with s = 8
            right-hand sides
  fused_l1  the README's axpydot builder on vectors of 2**26 f32
            (256 MiB each)
  fused_l2  an anchored gemv -> axpy -> nrm2 chain at n = 10000, which
            is not a multiple of the kernels' block shape

Programs are compiled with `tiles="default"`, so no tuning table
outside the checkout changes what runs. The solvers are compiled
directly rather than through `blas.solve`, whose last rung is a host
solve. Each phase compiles its program ahead of time, requires
`tpu_custom_call` in it (a kernel that fell back to the interpreter or
to a jnp reference has none), then runs that program twice.

Each phase prints one line: compile seconds, wall seconds of the
second call (timed to `block_until_ready`), iterations, its error
beside its limit, and the number of `tpu_custom_call`s in the compiled
program. These are smoke numbers, not benchmark numbers. The last line
of standard output is `{"ok": true, "device": {...}}`, printed only when
every phase passed. Without a TPU, or outside a checkout of this
repository, the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

CG_N = 16384
BLOCK_CG_S = 8
CG_TOL = 1e-5            # f32 reaches it: the stop rule's relative residual
CG_MAX_ITERS = 200
RESID_LIMIT = 1e-4       # true ‖b - A x‖ / ‖b‖ in float64, per column
L1_N = 1 << 26
L1_NEG_ALPHA = -0.7
# |beta - ref| / sum_i |z_i u_i|: f32 accumulation over 2**26 terms
# (2048 blocks) is expected near 1e-6
L1_LIMIT = 1e-5
L2_N = 10000
L2_NEG_ALPHA = -0.5
L2_LIMIT = 1e-4          # relative error of q, r_next and rnorm


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    import jax
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


def _compile_and_run(fn, *args):
    """AOT-compile `fn(*args)`, then run the compiled program twice.
    Returns (outputs, compile_s, wall_s of the second call, number of
    tpu_custom_calls)."""
    import jax
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    _timed(lambda: compiled(*args))
    out, wall_s = _timed(lambda: compiled(*args))
    return out, compile_s, wall_s, _custom_calls(compiled)


def make_spd(key, n):
    """Dense symmetric A with |a_ij| <= 1 off the diagonal and a
    diagonal that exceeds each row's absolute sum by 1."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        u = jax.random.uniform(key, (n, n), jnp.float32, -1.0, 1.0)
        a = (u + u.T) * 0.5
        d = jnp.sum(jnp.abs(a), axis=1) + 1.0
        idx = jnp.arange(n)
        return a.at[idx, idx].add(d)

    return build(key)


def _matvec_ref(a, x):
    """A @ x by XLA at full f32 precision, independent of the Pallas
    kernels (XLA's default f32 matmul on a TPU is a bf16 pass)."""
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST)


def _rel_resid(a, b, x):
    """Column-wise ‖b - A x‖ / ‖b‖: the residual vector on the device,
    its norms in float64 on the host."""
    import numpy as np
    r = np.asarray(b - _matvec_ref(a, x), np.float64)
    b64 = np.asarray(b, np.float64)
    return np.linalg.norm(r, axis=0) / np.linalg.norm(b64, axis=0)


def _solver_phase(spec, key, a, rhs_shape):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import blas
    from repro.guard import status as ST

    exe = blas.compile(spec, tiles="default", max_iters=CG_MAX_ITERS)
    b = jax.random.normal(key, rhs_shape, jnp.float32)
    rhs_name = "b" if len(rhs_shape) == 1 else "B"

    def solve(a, b, x0):
        res = exe.run(A=a, x0=x0, tol=CG_TOL, **{rhs_name: b})
        return res.x, res.iterations, res.status

    (x, iters, status), compile_s, wall_s, calls = _compile_and_run(
        solve, a, b, jnp.zeros_like(b))
    resid = _rel_resid(a, b, x)
    worst = float(np.max(resid))
    status = ST.status_name(status)
    ok = (status == "CONVERGED" and worst <= RESID_LIMIT and calls > 0)
    return dict(ok=ok, compile_s=compile_s, wall_s=wall_s,
                iterations=int(iters), status=status, err=worst,
                limit=RESID_LIMIT, tpu_custom_calls=calls)


def phase_cg(key, a):
    from repro.solvers import specs
    return _solver_phase(specs.CG_LOOP, key, a, (a.shape[0],))


def phase_block_cg(key, a, s=BLOCK_CG_S):
    from repro.solvers import specs
    return _solver_phase(specs.BLOCK_CG_LOOP, key, a, (a.shape[0], s))


def phase_fused_l1(key, n=L1_N):
    """The README's axpydot: z = w + neg_alpha * v (on-chip), beta = z.u"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import blas

    b = blas.program("axpydot")
    z = b.axpy(alpha=b.input("neg_alpha"), x="v", y="w")
    b.dot(x=z, y="u", out="beta")
    exe = blas.compile(b, tiles="default")

    # positive data: a dropped or repeated block moves beta by ~1/2048
    kv, kw, ku = jax.random.split(key, 3)
    v, w, u = (jax.random.uniform(k, (n,), jnp.float32) for k in (kv, kw, ku))
    neg_alpha = jnp.float32(L1_NEG_ALPHA)

    def run(neg_alpha, v, w, u):
        return exe.one(neg_alpha=neg_alpha, v=v, w=w, u=u)

    beta, compile_s, wall_s, calls = _compile_and_run(run, neg_alpha, v, w, u)
    z64 = (np.asarray(w, np.float64)
           + L1_NEG_ALPHA * np.asarray(v, np.float64))
    zu = z64 * np.asarray(u, np.float64)
    err = abs(float(beta) - float(np.sum(zu))) / float(np.sum(np.abs(zu)))
    return dict(ok=err <= L1_LIMIT and calls > 0, compile_s=compile_s,
                wall_s=wall_s, iterations=1, err=err, limit=L1_LIMIT,
                tpu_custom_calls=calls)


def phase_fused_l2(key, n=L2_N):
    """q = A p ; r_next = r + neg_alpha * q ; rnorm = ‖r_next‖, one
    gemv-anchored fused kernel with q public."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import blas

    b = blas.program("gemv_axpy_nrm2")
    q = b.gemv(alpha=1.0, beta=0.0, A="A", x="p", y="y0", out="q")
    rn = b.axpy(alpha=b.input("neg_alpha"), x=q, y="r", out="r_next")
    b.nrm2(x=rn, out="rnorm")
    exe = blas.compile(b, tiles="default")

    ka, kp, kr = jax.random.split(key, 3)
    a = jax.random.normal(ka, (n, n), jnp.float32) / np.sqrt(n)
    p = jax.random.normal(kp, (n,), jnp.float32)
    r = jax.random.normal(kr, (n,), jnp.float32)
    y0 = jnp.zeros((n,), jnp.float32)
    neg_alpha = jnp.float32(L2_NEG_ALPHA)

    def run(neg_alpha, a, p, y0, r):
        out = exe.run(neg_alpha=neg_alpha, A=a, p=p, y0=y0, r=r)
        return out["q"], out["r_next"], out["rnorm"]

    (q, r_next, rnorm), compile_s, wall_s, calls = _compile_and_run(
        run, neg_alpha, a, p, y0, r)
    q64 = np.asarray(_matvec_ref(a, p), np.float64)
    r64 = np.asarray(r, np.float64) + L2_NEG_ALPHA * q64

    def rel(got, want):
        return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                     / np.linalg.norm(want))

    rnorm64 = np.linalg.norm(r64)
    err = max(rel(q, q64), rel(r_next, r64),
              abs(float(rnorm) - rnorm64) / rnorm64)
    return dict(ok=err <= L2_LIMIT and calls > 0, compile_s=compile_s,
                wall_s=wall_s, iterations=1, err=err, limit=L2_LIMIT,
                tpu_custom_calls=calls)


def _report(name, res):
    fields = " ".join(f"{k}={v}" for k, v in res.items() if k != "ok")
    print(f"phase {name}: {'ok' if res['ok'] else 'FAILED'} {fields}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of this repository "
              f"(no src/repro)", file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.device import use_compile_cache
    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)
    print(f"device: {dev.device_kind} x{len(jax.devices())}", flush=True)

    key = jax.random.PRNGKey(args.seed)
    k_a, k_cg, k_bcg, k_l1, k_l2 = jax.random.split(key, 5)
    failed = []

    def run(name, fn, *fn_args):
        try:
            res = fn(*fn_args)
        except Exception:  # noqa: BLE001 — reported, then fails the run
            traceback.print_exc()
            print(f"phase {name}: FAILED with an exception", flush=True)
            failed.append(name)
            return
        _report(name, res)
        if not res["ok"]:
            failed.append(name)

    try:
        t = time.perf_counter()
        a = jax.block_until_ready(make_spd(k_a, CG_N))
        print(f"setup: A {CG_N}x{CG_N} f32 made on the device in "
              f"{time.perf_counter() - t} s", flush=True)
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        failed += ["cg", "block_cg"]
    else:
        run("cg", phase_cg, k_cg, a)
        run("block_cg", phase_block_cg, k_bcg, a)
        del a
    run("fused_l1", phase_fused_l1, k_l1)
    run("fused_l2", phase_fused_l2, k_l2)

    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
