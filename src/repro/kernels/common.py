"""Shared helpers for the Pallas kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU in interpret mode. `default_interpret()` picks the mode
from the backend so the same call sites work in both worlds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pl", "pltpu", "default_interpret", "pad_to", "cdiv",
    "as_2d", "LANES", "SUBLANES", "ACC_SHAPE", "acc_add", "mxu_dot",
    "smem_scalar_spec",
]

# TPU vector-register geometry: the VPU operates on (8, 128) f32 tiles,
# the MXU on 128x128 systolic tiles. These play the role of the AIE's
# 512-bit vector width in the paper: block shapes must be multiples.
LANES = 128
SUBLANES = 8

# Reductions accumulate into one lane-dense (8, 128) VMEM tile that
# holds the running value in every element: the chip's compiler stores
# vectors into VMEM, never scalars. Callers read element [0, 0] of the
# finished tile outside the kernel.
ACC_SHAPE = (SUBLANES, LANES)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def default_interpret() -> bool:
    """Compile natively on a TPU and interpret on the CPU. Any other
    backend raises: the kernels are written for the TPU only, and a
    silent fall-back to the interpreter would hide the device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on backend {backend!r}; use a "
        f"TPU, or JAX_PLATFORMS=cpu for interpret mode")


def acc_add(ref, value, first):
    """Add a 0-d kernel value to every element of an `ACC_SHAPE`
    accumulator tile. `first` (a traced bool, or Python `True` for a
    single-step grid) seeds the tile with `value` instead."""
    if first is True:
        ref[...] = jnp.full(ref.shape, value, ref.dtype)
        return
    ref[...] = jnp.where(first, jnp.zeros_like(ref), ref[...]) + value


def mxu_dot(a, b):
    """`a @ b` on the MXU, accumulated in f32. Two bf16 operands
    multiply exactly in one pass; anything else is taken in f32 at
    full f32 precision. Mosaic's default for an f32 matmul is a single
    bf16 pass, which leaves a relative error near 2e-3: a CG solve on
    a v5e then stalls there however small its recurrence residual."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def pad_to(x: jax.Array, multiple: int, axis: int = 0, value=0):
    """Zero-pad `axis` of x up to the next multiple. The pad runs under
    the name scope `pad`, so its device ops carry `/pad/` in their
    `op_name` and a profiler trace can attribute their time."""
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    with jax.named_scope("pad"):
        return jnp.pad(x, widths, constant_values=value)


def as_2d(x: jax.Array, lanes: int = LANES):
    """View a 1-D vector as a zero-padded (rows, lanes) window matrix.

    This is the TPU equivalent of staging an AIE *window*: the lane dim
    matches the vector unit, the row dim is what the grid strides over.
    Returns (x2d, original_length).
    """
    n = x.shape[0]
    xp = pad_to(x, lanes, axis=0)
    return xp.reshape(-1, lanes), n


def smem_scalar_spec():
    """BlockSpec placing a small scalar operand in SMEM (an AIE 'stream')."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def jit_kernel(fn=None, **static):
    """functools.partial(jax.jit, static_argnames=...) convenience."""
    if fn is None:
        return functools.partial(jit_kernel, **static)
    return jax.jit(fn, **static)
