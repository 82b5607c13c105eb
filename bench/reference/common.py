"""Helpers shared by the plain references: exact float32 matmuls and the
float8 rounding the control computes in.

Every matmul runs at ``Precision.HIGHEST`` (on a TPU a float32 matmul
otherwise rounds its operands to bfloat16).  With ``quant=True`` both
operands of every linear layer are first rounded to float8 e4m3 with a
scale per output channel (weights) or per token (activations), the
nearest precision below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                     # largest finite float8_e4m3fn


def fp8(x: jax.Array, axis) -> jax.Array:
    """``x`` rounded to float8 e4m3 with absmax scaling over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x: jax.Array, w: jax.Array, n_in: int, quant: bool) -> jax.Array:
    """``x (..., *in) @ w (*in, *out)`` in float32; ``n_in`` trailing axes
    of ``x`` contract with the leading ``n_in`` axes of ``w``."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = fp8(x, tuple(range(x.ndim - n_in, x.ndim)))
        w = fp8(w, tuple(range(n_in)))
    return jnp.tensordot(x, w, axes=n_in, precision=HI)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def layer_norm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)
