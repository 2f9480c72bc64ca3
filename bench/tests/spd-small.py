"""A test-only configuration for the `solves` request kind: a small
seeded SPD system, solved by the library's `CG_LOOP` (`cg`) and
`BLOCK_CG_LOOP` (`block_cg`) with the configuration's `rtol` and
`max_iters` baked into the spec.

    A = M Mᵀ / n + shift I        M (n x n) hashed from the seed
    b, or B (n x s)               hashed from the seed, x0 = 0

The device makes A in one jitted call; the plain reference makes M
again in float64 NumPy and solves with it, so no matrix is copied
back.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, numerics

# ‖x_j − x*_j‖ / ‖x*_j‖ of the worst column, between the library's
# 0.8e-6–1.7e-6 and the three-pass bf16 control's 2.5e-5–3.4e-5 on the
# CPU; and the solves that did not end in the traffic's status
LIMITS = {"err": 6e-6, "unconverged": 0}

# the configuration is at a size a test run holds already
SMALL: dict = {}

M_STREAM, B_STREAM = 1, 2


def program(cfg, name):
    from repro.solvers import specs

    spec = copy.deepcopy({"cg": specs.CG_LOOP,
                          "block_cg": specs.BLOCK_CG_LOOP}[name])
    spec["iterate"]["while"].update(rtol=cfg["rtol"],
                                    max_iters=cfg["max_iters"])
    return spec


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _make(n, s, pool, shift, keys):
    (m0, m1), (b0, b1) = keys
    m = gen.uniform_from_key(jnp, m0, m1, jnp.arange(
        n * n, dtype=jnp.uint32).reshape(n, n))
    a = jnp.matmul(m, m.T, precision=jax.lax.Precision.HIGHEST) / n \
        + shift * jnp.eye(n, dtype=jnp.float32)
    b = gen.uniform_from_key(jnp, b0, b1, jnp.arange(
        pool * n * s, dtype=jnp.uint32).reshape(pool, n, s))
    return a, b


def _keys(seed):
    return tuple(tuple(jnp.uint32(w) for w in gen.key(seed, k))
                 for k in (M_STREAM, B_STREAM))


def inputs(cfg, name, seed: int, pool: int):
    """({"A": A}, [{"b" or "B": panel, "x0": zeros} per pool entry])."""
    a, b = _make(cfg["n"], cfg["s"][name], pool, cfg["shift"], _keys(seed))
    if name == "cg":
        return {"A": a}, [{"b": v[:, 0], "x0": jnp.zeros_like(v[:, 0])}
                          for v in b]
    return {"A": a}, [{"B": v, "x0": jnp.zeros_like(v)} for v in b]


def reference(cfg, name, seed: int, pool_indices) -> dict:
    """{"x": (len(pool_indices), n, s) float64}: A⁻¹ of each panel."""
    n = cfg["n"]
    m = gen.uniform(np, seed, M_STREAM, (n, n)).astype(np.float64)
    a = m @ m.T / n + cfg["shift"] * np.eye(n)
    rows = np.asarray(pool_indices, dtype=np.int64)
    pool = int(rows.max()) + 1 if len(rows) else 1
    b = gen.uniform(np, seed, B_STREAM, (pool, n, cfg["s"][name]))[rows]
    return {"x": np.stack([np.linalg.solve(a, v.astype(np.float64))
                           for v in b])}


def reference_program(cfg, name, precision: str):
    """The plain CG of `bench.numerics` at `precision`, with the
    keywords of `Executable.run` of `program(cfg, name)`."""
    rhs = "b" if name == "cg" else "B"

    @jax.jit
    def run(A, b, x0):
        return numerics.cg(A, b, x0, cfg["rtol"], cfg["max_iters"],
                           precision)

    return lambda A, x0, **kw: run(A, kw[rhs], x0)


def work(cfg, name) -> tuple:
    """(bytes, flops) one solve cannot do without: A read once, the
    panel read and x written, one product with A."""
    n, s = cfg["n"], cfg["s"][name]
    return n * n * 4 + 2 * n * s * 4, 2 * n * n * s


def iteration_work(cfg, name) -> tuple:
    """(bytes, flops) one CG iteration cannot do without, for each of
    the s columns: A read once for the panel's product q = A p; x, r
    and p each read and written once (q and the step lengths stay on
    the chip); 2n² flops for the product, and 2n each for p·q, x + αp,
    r − αq, r·r and r + βp."""
    n, s = cfg["n"], cfg["s"][name]
    return n * n * 4 + 6 * n * s * 4, 2 * n * n * s + 10 * n * s
