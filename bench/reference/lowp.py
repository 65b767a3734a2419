"""The lower-precision control's rounding: float8 (e4m3) with one scale
per tensor, the largest magnitude mapped to the format's largest finite
value, as fp8 serving stores weights and matmul inputs.  Values come
back in float32, so the products are exact and the sums float32: only
the operands' rounding differs from the reference.  The configurations
state bfloat16, so fp8 is the step below."""

import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fp8(x):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / FP8_MAX
    return (xf / scale).astype(FP8).astype(jnp.float32) * scale


def einsum(spec, a, b, *, precision, low=False):
    """``jnp.einsum`` at ``precision``; with ``low``, both operands
    first rounded to fp8."""
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=precision)
