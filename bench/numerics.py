"""Matrix products of the plain references, at a stated precision.

`"highest"` is a float32 product at full float32 precision. `"high"`
is the three-pass bfloat16 product below it: each operand is split
into a high part and a low part, each rounded to bfloat16's 8-bit
significand, and the product keeps hi·hi + hi·lo + lo·hi. Each of
those products is exact in float32 and is taken at full precision,
accumulated in float32.

The parts are rounded with `lax.reduce_precision`, which XLA keeps,
and not by a cast to bfloat16 and back: XLA on a TPU may fold such a
pair of casts away as excess precision, and the product then comes out
at full float32 precision, which no control may. So the control
computes the same thing on the CPU and on the TPU.
"""
from __future__ import annotations

PRECISIONS = ("highest", "high")


def _to_bf16(a):
    """`a` rounded to bfloat16's significand, kept in float32."""
    import jax
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _to_bf16(a)
    return hi, _to_bf16(a - hi)


def matmul(a, b, precision: str):
    import jax
    import jax.numpy as jnp

    def mm(x, y):
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)

    if precision == "highest":
        return mm(a, b)
    if precision != "high":
        raise ValueError(f"precision {precision!r} is not one of "
                         f"{PRECISIONS}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
