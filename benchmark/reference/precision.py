"""The precision a reference computes its matrix products in.

Every product of a reference goes through :func:`matmul`/:func:`einsum`,
which round the OPERANDS to the stated precision and then multiply in
float32 at ``highest`` (on a TPU a float32 product otherwise runs as one
bfloat16 pass). ``float32`` rounds nothing: that is the reference.
``bfloat16`` and ``fp8`` are the controls: the same mathematics in the
precision a later change might be tempted to take. ``fp8`` is e4m3 with a
per-tensor scale (the favourable way to use it); gradients pass straight
through the rounding, so the backward products use rounded saved operands
and unrounded cotangents.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")
_E4M3_MAX = 448.0


def _round(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def operand(x, precision):
    """``x`` as a matmul operand at ``precision`` (float32 out)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")
    x = x.astype(jnp.float32)
    if precision == "float32":
        return x
    return x + jax.lax.stop_gradient(_round(x, precision) - x)


def einsum(spec, a, b, precision):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def dense(x, w, b, precision):
    """``x @ w.T + b`` with ``w`` stored (out, in), as the papers' code does."""
    return einsum("...i,oi->...o", x, w, precision) + b


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta
