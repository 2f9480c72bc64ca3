"""Matrix products of the plain references, at a stated precision,
and the plain conjugate gradient that a solve's control runs on them.

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


# the codes of `repro.guard.status` that a plain solve can end in
CONVERGED, MAX_ITERS = 0, 1


def cg(a, b, x0, rtol: float, max_iters: int, precision: str) -> dict:
    """Conjugate gradient on each column of `b` ((n,) or (n, s)) in
    plain jax.numpy, every product with `a` taken by `matmul` at
    `precision`. The stop rule is the library's loop specs': the
    worst column's residual norm at most `rtol` times the largest
    column norm of `b`, or `max_iters` iterations. Gives `x` (shaped
    as `b`), `iterations` and `status` (CONVERGED or MAX_ITERS), as
    device arrays."""
    import jax
    import jax.numpy as jnp

    shape = b.shape
    b, x0 = b.reshape(shape[0], -1), x0.reshape(shape[0], -1)
    r = b - matmul(a, x0, precision)
    rz = jnp.sum(r * r, axis=0)
    threshold = rtol * jnp.sqrt(jnp.max(jnp.sum(b * b, axis=0)))

    def running(carry):
        k, _, _, _, rz = carry
        return jnp.logical_and(k < max_iters,
                               jnp.sqrt(jnp.max(rz)) > threshold)

    def step(carry):
        k, x, r, p, rz = carry
        q = matmul(a, p, precision)
        alpha = rz / jnp.sum(p * q, axis=0)
        x, r = x + p * alpha, r - q * alpha
        rz_next = jnp.sum(r * r, axis=0)
        return k + 1, x, r, r + p * (rz_next / rz), rz_next

    k, x, _, _, rz = jax.lax.while_loop(
        running, step, (jnp.int32(0), x0, r, r, rz))
    status = jnp.where(jnp.sqrt(jnp.max(rz)) <= threshold,
                       jnp.int8(CONVERGED), jnp.int8(MAX_ITERS))
    return {"x": x.reshape(shape), "iterations": k, "status": status}
