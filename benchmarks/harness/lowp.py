"""Matrix products of the plain references, at a stated precision.

``f32``   float32 operands, ``jax.default_matmul_precision("highest")`` —
          the reference proper.
``bf16``  operands rounded to bfloat16, f32 accumulation — what the
          configurations state; used in tests to show the gap to it is small.
``fp8``   operands scaled per tensor to the e4m3 range and rounded to
          float8_e4m3fn, f32 accumulation — the nearest precision below
          bfloat16: the CONTROL that has to come out as not correct.
``int8``  operands scaled per tensor to [-127, 127] and rounded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8", "int8")


def round_to(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "fp8":
        s = 448.0 / amax
        return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    if precision == "int8":
        s = 127.0 / amax
        return jnp.round(x * s) / s
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum`` with both operands at *precision* and f32 accumulation.
    Differentiable: the rounding is a straight-through estimator, as in
    low-precision training."""
    if precision != "f32":
        a = a + jax.lax.stop_gradient(round_to(a, precision) - a)
        b = b + jax.lax.stop_gradient(round_to(b, precision) - b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
