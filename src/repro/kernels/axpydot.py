"""Fused `axpydot` — the paper's flagship dataflow composition.

    z = w - alpha * v        (axpy)
    beta = zᵀ u              (dot)

In the paper, the two routines run on two AIE tiles and `z` flows over
the NoC, never touching DRAM. On TPU the idiomatic equivalent is a
single Pallas kernel: each (block_rows, 128) window of z is produced in
VMEM/VREGs and immediately consumed by the dot accumulation — z is
never materialized in HBM. The separate, non-dataflow version (two
pallas_calls with an HBM round-trip for z) lives in ops.py as
`axpydot_nodf` and is what Fig. 3's "w/o DF" bars measure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (ACC_SHAPE, LANES, acc_add, as_2d, cdiv,
                     default_interpret, pad_to, pl, smem_scalar_spec)

DEFAULT_BLOCK_ROWS = 256


def _axpydot_kernel(alpha_ref, w_ref, v_ref, u_ref, o_ref):
    # axpy stage: produce the z window in registers/VMEM (on-chip edge)
    z = w_ref[...].astype(jnp.float32) - alpha_ref[0] * v_ref[...].astype(
        jnp.float32)
    # dot stage: consume it immediately
    acc_add(o_ref, jnp.sum(z * u_ref[...].astype(jnp.float32)),
            pl.program_id(0) == 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def axpydot(alpha, w, v, u, *, block_rows=DEFAULT_BLOCK_ROWS,
            interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    w2d, _ = as_2d(w)
    v2d, _ = as_2d(v)
    u2d, _ = as_2d(u)
    rows = w2d.shape[0]
    block_rows = min(block_rows, rows)
    w2d, v2d, u2d = (pad_to(t, block_rows, axis=0) for t in (w2d, v2d, u2d))
    rows = w2d.shape[0]
    vec_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _axpydot_kernel,
        grid=(cdiv(rows, block_rows),),
        in_specs=[smem_scalar_spec(), vec_spec, vec_spec, vec_spec],
        out_specs=pl.BlockSpec(ACC_SHAPE, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(ACC_SHAPE, jnp.float32),
        interpret=interpret,
    )(jnp.reshape(alpha, (1,)).astype(jnp.float32), w2d, v2d, u2d)
    return out[0, 0]
