"""BLAS level-2 `gemv` (y' = alpha A x + beta y) as a Pallas TPU kernel,
plus its transposed sibling `gemvt` (y' = alpha Aᵀ x + beta y).

A is streamed through VMEM in (block_m, block_n) windows; x is staged
as (block_n, 1) column windows so the inner product runs on the MXU.
The grid is (M/bm, N/bn) with the N axis innermost: each output block
accumulates across its row of A windows — the same
window-at-a-time schedule an AIE gemv kernel uses in the paper.

`gemvt` walks the same (block_m, block_n) A windows but with the
output tiled over A's columns and the reduction running over A's row
blocks — the block is transposed in-register, so Aᵀ never
materializes in HBM. It exists for algorithms that project against a
stored basis (GMRES's Gram-Schmidt correction w − Vᵀh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (cdiv, default_interpret, mxu_dot, pad_to, pl,
                     smem_scalar_spec)

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 512


def gemv_block(a_block, x_block):
    """f32 contribution of one (bm, bn) A window against its (bn, 1) x
    window — the MXU inner product. Factored out so the standalone
    kernel below and the anchored fused-kernel generator
    (core.codegen) splice the exact same block body."""
    return mxu_dot(a_block, x_block)


def _gemv_kernel(alpha_ref, beta_ref, a_ref, x_ref, y_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = beta_ref[0] * y_ref[...].astype(jnp.float32)

    o_ref[...] += alpha_ref[0] * gemv_block(a_ref[...], x_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def gemv(alpha, a, x, beta, y, *, block_m=DEFAULT_BLOCK_M,
         block_n=DEFAULT_BLOCK_N, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    m, n = a.shape
    ap = pad_to(pad_to(a, block_m, axis=0), block_n, axis=1)
    xp = pad_to(x, block_n, axis=0).reshape(-1, 1)
    yp = pad_to(y, block_m, axis=0).reshape(-1, 1)
    mp, np_ = ap.shape
    grid = (cdiv(mp, block_m), cdiv(np_, block_n))
    out = pl.pallas_call(
        _gemv_kernel,
        grid=grid,
        in_specs=[
            smem_scalar_spec(),
            smem_scalar_spec(),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)).astype(jnp.float32),
      jnp.reshape(beta, (1,)).astype(jnp.float32), ap, xp, yp)
    return out[:m, 0].astype(a.dtype)


def gemvt_block(a_block, x_block):
    """f32 contribution of one (bm, bn) A window, transposed
    in-register, against its (bm, 1) x window — one MXU inner product
    per A-row block, accumulating into a (bn, 1) output. Factored out
    for the same reason as `gemv_block`: the anchored fused-kernel
    generator splices this exact block body."""
    return mxu_dot(a_block.T, x_block)


def _gemvt_kernel(alpha_ref, beta_ref, a_ref, x_ref, y_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = beta_ref[0] * y_ref[...].astype(jnp.float32)

    o_ref[...] += alpha_ref[0] * gemvt_block(a_ref[...], x_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def gemvt(alpha, a, x, beta, y, *, block_m=DEFAULT_BLOCK_M,
          block_n=DEFAULT_BLOCK_N, interpret=None):
    """y' = alpha Aᵀ x + beta y for A (m, n), x (m,), y (n,)."""
    interpret = default_interpret() if interpret is None else interpret
    m, n = a.shape
    ap = pad_to(pad_to(a, block_m, axis=0), block_n, axis=1)
    xp = pad_to(x, block_m, axis=0).reshape(-1, 1)
    yp = pad_to(y, block_n, axis=0).reshape(-1, 1)
    mp, np_ = ap.shape
    # output tiles over A's columns (i), reduction over row blocks (j)
    grid = (cdiv(np_, block_n), cdiv(mp, block_m))
    out = pl.pallas_call(
        _gemvt_kernel,
        grid=grid,
        in_specs=[
            smem_scalar_spec(),
            smem_scalar_spec(),
            pl.BlockSpec((block_m, block_n), lambda i, j: (j, i)),
            pl.BlockSpec((block_m, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)).astype(jnp.float32),
      jnp.reshape(beta, (1,)).astype(jnp.float32), ap, xp, yp)
    return out[:n, 0].astype(a.dtype)
