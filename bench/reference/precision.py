"""Matmul precision of the plain references and of their controls.

``f32``  -- float32 operands, products at ``HIGHEST`` (full float32 on a TPU,
            where a float32 dot otherwise takes one bfloat16 pass).
``high`` -- the control for a float32 configuration whose products are
            stated at ``HIGHEST``: what ``HIGH`` does on a TPU, spelt out so
            that it reads the same on any device.  Each operand is split
            into a bfloat16 head and a bfloat16 tail, and the product is
            head x head + head x tail + tail x head (three bfloat16 passes,
            the tail x tail term left out), accumulated in float32.
``bf16`` -- the control for another float32 configuration: operands and results of
            every product rounded to bfloat16, and the state kept in
            bfloat16 (``state_dtype``).
``fp8``  -- the control for a bfloat16 configuration: each operand scaled per
            tensor into float8_e4m3fn (largest magnitude to 448) and back,
            as fp8 training does; the state keeps the configured dtype.
            The rounding passes gradients straight through (an unscaled
            fp8 cotangent would underflow to zero, which no fp8 recipe
            does), so the backward products read the rounded operands.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0


def _fp8(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = jnp.clip(x / scale, -_E4M3_MAX, _E4M3_MAX)
    q = q.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def operand(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    if precision == "fp8":
        return _fp8(x)
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x.astype(jnp.float32)


def _split(x: jnp.ndarray):
    x = x.astype(jnp.float32)
    head = x.astype(jnp.bfloat16).astype(jnp.float32)
    return head, (x - head).astype(jnp.bfloat16).astype(jnp.float32)


def einsum(spec: str, a: jnp.ndarray, b: jnp.ndarray,
           precision: str) -> jnp.ndarray:
    """``jnp.einsum`` of two operands at the given precision (float32 out)."""
    full = jax.lax.Precision.HIGHEST
    if precision == "high":
        (ah, at), (bh, bt) = _split(a), _split(b)
        return (jnp.einsum(spec, ah, bh, precision=full)
                + jnp.einsum(spec, ah, bt, precision=full)
                + jnp.einsum(spec, at, bh, precision=full))
    out = jnp.einsum(spec, operand(a, precision), operand(b, precision),
                     precision=full)
    if precision == "bf16":
        out = out.astype(jnp.bfloat16).astype(jnp.float32)
    return out


def state_dtype(configured, precision: str):
    """The dtype the reference keeps its parameters in."""
    return jnp.bfloat16 if precision == "bf16" else configured
