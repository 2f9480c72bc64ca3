"""Seeded data that the device and the host make alike.

`uniform(xp, seed, stream, index)` maps a 64-bit seed, a stream number
and an array of uint32 element indices to float32 values in [-1, 1).
It is a counter-based hash (two rounds of the `lowbias32` mixer), so
the device makes a matrix in one jitted call from its indices, and the
plain reference makes the same values again on the host in NumPy from
the seed alone, without copying anything back. Every value is a 24-bit
integer times 2**-23, minus 1, so float32 and float64 hold it exactly.

The seed enters as two uint32 scalars, traced arguments of the jitted
maker, so one compiled maker serves every seed.
"""
from __future__ import annotations

import numpy as np

_M32 = 0xFFFFFFFF


def _mix_int(h: int) -> int:
    """lowbias32 on a Python int (the key schedule)."""
    h &= _M32
    h ^= h >> 16
    h = (h * 0x7FEB352D) & _M32
    h ^= h >> 15
    h = (h * 0x846CA68B) & _M32
    h ^= h >> 16
    return h


def key(seed: int, stream: int) -> tuple[int, int]:
    """Two uint32 words for (seed, stream); any seed below 2**64."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    lo, hi = seed & _M32, seed >> 32
    k0 = _mix_int(lo ^ _mix_int(hi + 0x9E3779B9 * (stream + 1)))
    k1 = _mix_int(k0 + 0x85EBCA6B + stream)
    return k0, k1


def _mix(xp, h):
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * xp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def uniform_from_key(xp, k0, k1, index):
    """float32 values in [-1, 1) for uint32 `index` under key words
    (k0, k1); `xp` is `numpy` or `jax.numpy`."""
    h = _mix(xp, index ^ k0)
    h = _mix(xp, h + k1)
    return (h >> 8).astype(xp.float32) * xp.float32(2.0 ** -23) - \
        xp.float32(1.0)


def uniform(xp, seed: int, stream: int, shape) -> "np.ndarray":
    """A whole array of `shape`, indexed in row-major order."""
    k0, k1 = key(seed, stream)
    size = int(np.prod(shape))
    if size > 1 << 32:
        raise ValueError(f"{shape} has more elements than uint32 indexes")
    index = xp.arange(size, dtype=xp.uint32).reshape(shape)
    return uniform_from_key(xp, xp.uint32(k0), xp.uint32(k1), index)
