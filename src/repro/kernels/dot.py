"""BLAS level-1 reductions (dot, asum, nrm2) as Pallas TPU kernels.

Reductions accumulate across sequential grid steps into a single VMEM
output block — the TPU grid is guaranteed sequential, which is what an
AIE kernel iterating over incoming windows does on the paper's device.
Accumulation is always f32 regardless of input dtype. The block is an
`ACC_SHAPE` tile with the running value in every element (the chip
stores no scalars into VMEM); the caller reads element [0, 0].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (ACC_SHAPE, LANES, acc_add, as_2d, cdiv,
                     default_interpret, pad_to, pl)

DEFAULT_BLOCK_ROWS = 256


def _dot_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    acc_add(o_ref, jnp.sum(x * y), pl.program_id(0) == 0)


def _asum_kernel(x_ref, o_ref):
    acc_add(o_ref, jnp.sum(jnp.abs(x_ref[...].astype(jnp.float32))),
            pl.program_id(0) == 0)


def _sumsq_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    acc_add(o_ref, jnp.sum(x * x), pl.program_id(0) == 0)


def iamax_block(x, step):
    """Block-local (max |x|, global flat index) pair for an
    index-carrying reduction. Shared by the standalone kernel below and
    the fused-kernel generator (core.codegen), so the dataflow and
    nodataflow paths cannot diverge. Ties keep the first occurrence
    (BLAS isamax semantics) via the min-index select; `step` is the
    sequential grid position supplying the block's global offset. The
    index rides in int32 (exact through the full int32 range — the old
    f32 lane carry was exact only to 2^24).
    """
    absx = jnp.abs(x.astype(jnp.float32))
    rows, lanes = absx.shape
    local_max = jnp.max(absx)
    flat = (jax.lax.broadcasted_iota(jnp.int32, absx.shape, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, absx.shape, 1))
    sentinel = jnp.int32(jnp.iinfo(jnp.int32).max)
    local_idx = jnp.min(jnp.where(absx == local_max, flat, sentinel))
    return local_max, step * (rows * lanes) + local_idx


def iamax_update(m_ref, i_ref, local_max, gidx, first):
    """Fold one block's (max |x|, index) pair into the running f32/int32
    accumulator tiles. `first` (a traced bool, or Python `True` for a
    single-step grid) seeds them instead; cross-block ties keep the
    first occurrence via the strictly-greater compare."""
    if first is True:
        m_ref[...] = jnp.full(m_ref.shape, local_max, jnp.float32)
        i_ref[...] = jnp.full(i_ref.shape, gidx, jnp.int32)
        return
    # any |x| >= 0 beats the -1 seed
    prev_m = jnp.where(first, jnp.float32(-1.0), m_ref[...])
    prev_i = jnp.where(first, jnp.int32(0), i_ref[...])
    better = local_max > prev_m
    i_ref[...] = jnp.where(better, gidx, prev_i)
    m_ref[...] = jnp.where(better, local_max, prev_m)


def _iamax_kernel(x_ref, m_ref, i_ref):
    """m = running max |x|, i = its flat index (separate f32/int32
    accumulator tiles)."""
    step = pl.program_id(0)
    local_max, gidx = iamax_block(x_ref[...], step)
    iamax_update(m_ref, i_ref, local_max, gidx, step == 0)


def _reduce_call(kernel, vectors, *, block_rows, interpret,
                 out_shape=None):
    x2ds = []
    for v in vectors:
        v2d, _ = as_2d(v)
        x2ds.append(v2d)
    rows = x2ds[0].shape[0]
    block_rows = min(block_rows, rows)
    # pad rows to a full block multiple: OOB blocks read NaN in interpret
    # mode and garbage on HW, which a reduction would sum.
    x2ds = [pad_to(v, block_rows, axis=0) for v in x2ds]
    rows = x2ds[0].shape[0]
    grid = (cdiv(rows, block_rows),)
    vec_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    single = out_shape is None
    if single:
        out_shape = [jax.ShapeDtypeStruct(ACC_SHAPE, jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec] * len(x2ds),
        # every grid step maps to the same accumulator block(s)
        out_specs=[pl.BlockSpec(s.shape, lambda i: (0, 0))
                   for s in out_shape],
        out_shape=out_shape,
        interpret=interpret,
    )(*x2ds)
    return out[0][0, 0] if single else out


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dot(x, y, *, block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _reduce_call(_dot_kernel, [x, y], block_rows=block_rows,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def asum(x, *, block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _reduce_call(_asum_kernel, [x], block_rows=block_rows,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def nrm2(x, *, block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    ss = _reduce_call(_sumsq_kernel, [x], block_rows=block_rows,
                      interpret=interpret)
    return jnp.sqrt(ss)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def iamax(x, *, block_rows=DEFAULT_BLOCK_ROWS, interpret=None):
    """Index of the first element with maximal |x_i| (BLAS isamax).
    The index accumulates in a dedicated int32 ref, exact for any
    int32-addressable vector (no 2^24 f32-mantissa cap)."""
    interpret = default_interpret() if interpret is None else interpret
    _, idx = _reduce_call(
        _iamax_kernel, [x], block_rows=block_rows, interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct(ACC_SHAPE, jnp.float32),
                   jax.ShapeDtypeStruct(ACC_SHAPE, jnp.int32)])
    return idx[0, 0]
