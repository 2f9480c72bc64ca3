"""BLAS level-3 `gemm` (C' = alpha A B + beta C) as a Pallas TPU kernel.

Classic MXU-tiled matmul: grid (M/bm, N/bn, K/bk), K innermost, an f32
VMEM scratch accumulator per (i, j) output window. Block shapes default
to 128-multiples so every matmul maps 1:1 onto 128x128 MXU passes; they
are the JSON spec's window-size knob for level-3 routines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (cdiv, default_interpret, mxu_dot, pad_to, pl, pltpu,
                     smem_scalar_spec)

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 256


def gemm_block(a_block, b_block):
    """f32 contribution of one (bm, bk) A window against its (bk, bn) B
    window on the MXU (`mxu_dot`). Factored out so the standalone kernel below
    and the tiled anchored-kernel generator (core.codegen) splice the
    exact same block body."""
    return mxu_dot(a_block, b_block)


def _gemm_kernel(alpha_ref, beta_ref, a_ref, b_ref, c_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += gemm_block(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = (
            alpha_ref[0] * acc_ref[...]
            + beta_ref[0] * c_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def gemm(alpha, a, b, beta, c, *, block_m=DEFAULT_BLOCK_M,
         block_n=DEFAULT_BLOCK_N, block_k=DEFAULT_BLOCK_K, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(128, n))
    block_k = min(block_k, max(128, k))
    ap = pad_to(pad_to(a, block_m, 0), block_k, 1)
    bp = pad_to(pad_to(b, block_k, 0), block_n, 1)
    cp = pad_to(pad_to(c, block_m, 0), block_n, 1)
    mp, kp = ap.shape
    _, np_ = bp.shape
    grid = (cdiv(mp, block_m), cdiv(np_, block_n), cdiv(kp, block_k))
    out = pl.pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[
            smem_scalar_spec(),
            smem_scalar_spec(),
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), c.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)).astype(jnp.float32),
      jnp.reshape(beta, (1,)).astype(jnp.float32), ap, bp, cp)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"))
def matmul(a, b, *, block_m=DEFAULT_BLOCK_M, block_n=DEFAULT_BLOCK_N,
           block_k=DEFAULT_BLOCK_K, interpret=None):
    """C = A @ B via the gemm kernel (alpha=1, beta=0)."""
    m, n = a.shape[0], b.shape[1]
    c = jnp.zeros((m, n), dtype=a.dtype)
    return gemm(1.0, a, b, 0.0, c, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret)
