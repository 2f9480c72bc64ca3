"""PolyBench's level-2 kernels on one chip: programs, data, plain
reference and work function of the configuration in
`polybench-xl.json`.

    mvt      x1 += A y1 ; x2 += Aᵀ y2              (A is N x N)
    gesummv  y = alpha A x + beta B x              (A, B are N x N)

Each program is written with the `repro.blas` builder. The data is
hashed from the seed (`bench.gen`): the device makes it in one jitted
call, and the plain reference makes it again in float64 NumPy on the
host, so no matrix is copied back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, numerics

# Limit of ‖out − ref‖ / ‖ref‖ over every output of a checked call;
# PERF.md gives the readings it was set from.
LIMITS = {"err": 1.5e-6}

# sizes a test run on the CPU can hold, merged over the configuration
SMALL = {"mvt": {"N": 300}, "gesummv": {"N": 200, "alpha": 1.5, "beta": 1.2}}

# one hash stream per operand
MATRICES = {"mvt": {"A": 1}, "gesummv": {"A": 2, "B": 3}}
VECTORS = {"mvt": {"y1": 11, "y2": 12, "x1": 13, "x2": 14},
           "gesummv": {"x": 21}}


def _n(cfg, name) -> int:
    return int(cfg[name]["N"])


def program(cfg, name):
    """The kernel written with the `repro.blas` builder."""
    from repro import blas

    b = blas.program(name)
    if name == "mvt":
        b.gemv(alpha=1.0, beta=1.0, A="A", x="y1", y="x1", out="x1_out")
        b.gemvt(alpha=1.0, beta=1.0, A="A", x="y2", y="x2", out="x2_out")
    elif name == "gesummv":
        p = cfg[name]
        tmp = b.gemv(alpha=p["alpha"], beta=0.0, A="A", x="x", y="x")
        b.gemv(alpha=p["beta"], beta=1.0, A="B", x="x", y=tmp, out="y")
    else:
        raise KeyError(f"polybench-xl has no program {name!r}")
    return b


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(n, pool, keys):
    """Every operand from its key words, on the device."""
    mats = {k: gen.uniform_from_key(
        jnp, k0, k1, jnp.arange(n * n, dtype=jnp.uint32).reshape(n, n))
        for k, (k0, k1) in keys["mat"].items()}
    vecs = {k: gen.uniform_from_key(
        jnp, k0, k1, jnp.arange(pool * n, dtype=jnp.uint32).reshape(pool, n))
        for k, (k0, k1) in keys["vec"].items()}
    return mats, vecs


def _keys(name, seed, xp):
    def words(stream):
        return tuple(xp.uint32(w) for w in gen.key(seed, stream))
    return {"mat": {k: words(s) for k, s in MATRICES[name].items()},
            "vec": {k: words(s) for k, s in VECTORS[name].items()}}


def inputs(cfg, name, seed: int, pool: int):
    """(matrices, [vectors of each call in the pool]) on the device."""
    mats, vecs = _make(_n(cfg, name), pool, _keys(name, seed, jnp))
    return mats, [{k: v[i] for k, v in vecs.items()} for i in range(pool)]


def reference(cfg, name, seed: int, pool_indices) -> dict:
    """{output: (len(pool_indices), N) float64} of the calls that took
    those pool entries."""
    n = _n(cfg, name)
    rows = np.asarray(pool_indices, dtype=np.int64)
    pool = int(rows.max()) + 1 if len(rows) else 1

    def mat(k):
        return gen.uniform(np, seed, MATRICES[name][k], (n, n)).astype(
            np.float64)

    def vec(k):
        return gen.uniform(np, seed, VECTORS[name][k], (pool, n))[rows]\
            .astype(np.float64)

    if name == "mvt":
        a = mat("A")
        return {"x1_out": vec("x1") + vec("y1") @ a.T,
                "x2_out": vec("x2") + vec("y2") @ a}
    p = cfg[name]
    x = vec("x")
    return {"y": p["alpha"] * (x @ mat("A").T) + p["beta"] * (x @ mat("B").T)}


def reference_program(cfg, name, precision: str):
    """The kernel in plain jax.numpy at `precision`, with the keywords
    and outputs of `Executable.run` of `program(cfg, name)`."""
    if name == "mvt":
        @jax.jit
        def run(A, y1, y2, x1, x2):
            return {"x1_out": x1 + numerics.matmul(A, y1, precision),
                    "x2_out": x2 + numerics.matmul(A.T, y2, precision)}
    else:
        p = cfg[name]

        @jax.jit
        def run(A, B, x):
            return {"y": p["alpha"] * numerics.matmul(A, x, precision)
                    + p["beta"] * numerics.matmul(B, x, precision)}
    return lambda **kw: run(**kw)


def work(cfg, name) -> tuple:
    """(bytes, flops) one call cannot do without: each matrix read once,
    each vector read or written once."""
    n = _n(cfg, name)
    if name == "mvt":
        return n * n * 4 + 6 * n * 4, 4 * n * n + 2 * n
    return 2 * n * n * 4 + 2 * n * 4, 4 * n * n + 3 * n
