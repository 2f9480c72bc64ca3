"""BLAS level-2 `symv` (y' = alpha A x + beta y, A symmetric) as a
Pallas TPU kernel.

Only the lower triangle of A is referenced — the upper triangle is
reconstructed on the fly by streaming each (i, j) window together with
its mirror window (j, i) and selecting per element on the global
row/column ids. This is the window-mirroring trick an AIE symv kernel
uses to halve the matrix traffic: the same A operand serves both
triangles, so a tile is never fetched twice for its transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (cdiv, default_interpret, mxu_dot, pad_to, pl,
                     smem_scalar_spec)

DEFAULT_BLOCK = 256


def symv_block(a_block, mirror_block, x_block, i, j):
    """f32 contribution of the (i, j) symv window: the stored
    lower-triangle block and its mirrored transpose block, selected
    per element on global row/column ids, against the (bn, 1) x
    window. Factored out so the standalone kernel below and the
    anchored fused-kernel generator (core.codegen) splice the exact
    same block body."""
    a = a_block.astype(jnp.float32)             # A[i-block, j-block]
    mirror = mirror_block.astype(jnp.float32).T   # = A[j-block, i-block]ᵀ
    bm, bn = a.shape
    r_ids = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    c_ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    a_sym = jnp.where(r_ids >= c_ids, a, mirror)
    return mxu_dot(a_sym, x_block)


def _symv_kernel(alpha_ref, beta_ref, a_ref, am_ref, x_ref, y_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = beta_ref[0] * y_ref[...].astype(jnp.float32)

    o_ref[...] += alpha_ref[0] * symv_block(
        a_ref[...], am_ref[...], x_ref[...], i, j)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def symv(alpha, a, x, beta, y, *, block=DEFAULT_BLOCK, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    n = a.shape[0]
    if a.shape[0] != a.shape[1]:
        raise ValueError(f"symv needs a square matrix, got {a.shape}")
    block = min(block, max(n, 1))
    ap = pad_to(pad_to(a, block, axis=0), block, axis=1)
    xp = pad_to(x, block, axis=0).reshape(-1, 1)
    yp = pad_to(y, block, axis=0).reshape(-1, 1)
    np_ = ap.shape[0]
    grid = (cdiv(np_, block), cdiv(np_, block))
    out = pl.pallas_call(
        _symv_kernel,
        grid=grid,
        in_specs=[
            smem_scalar_spec(),
            smem_scalar_spec(),
            pl.BlockSpec((block, block), lambda i, j: (i, j)),
            pl.BlockSpec((block, block), lambda i, j: (j, i)),
            pl.BlockSpec((block, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)).astype(jnp.float32),
      jnp.reshape(beta, (1,)).astype(jnp.float32), ap, ap, xp, yp)
    return out[:n, 0].astype(a.dtype)
