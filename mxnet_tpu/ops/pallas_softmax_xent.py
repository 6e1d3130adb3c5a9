"""Fused softmax-cross-entropy Pallas kernel (sparse labels, custom VJP).

Reference analog: ``src/operator/nn/softmax-inl.h`` +
``SoftmaxCrossEntropyLoss`` — the training loss of every LM head in the
model zoo. The unfused gluon composition (``log_softmax`` → ``pick``)
materializes the full (N, C) log-probability tensor just to read one
column per row; at LM-head widths (C = vocab) that is the largest
activation in the backward residual set. The kernel computes the per-row
loss ``logsumexp(x) - x[label]`` in one VMEM-resident pass over the
logits — the (N, C) intermediate never exists — and the custom VJP
recomputes ``softmax(x) - onehot`` from the saved *logits* (f32-stable,
fusion-friendly jnp, mirroring the flash-attention/layernorm design
split: Pallas forward, analytic jnp backward).

Gating mirrors ``pallas_layernorm``: opt-in knob (``fused_softmax_xent``
/ ``MXNET_TPU_FUSED_SOFTMAX_XENT``), TPU backend, lane-aligned class dim.
CPU CI exercises the same kernel (forward AND vjp) under
``interpret=True`` in the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..registry import register
from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

_BLOCK_ROWS = 128
# class-dim cap: one (rows, C) f32 block + its exp copy must sit in VMEM
_MAX_C = 65536


def xent_kernel_supported(pred, axis=-1) -> bool:
    """Opt-in (``MXNET_TPU_FUSED_SOFTMAX_XENT=1``), hardware-only, and the
    class axis must be last, lane-aligned, and VMEM-bounded; the gluon
    loss falls back to the ``log_softmax``→``pick`` composition
    otherwise."""
    from .. import config as _config

    if not _config.get("fused_softmax_xent"):
        return False
    ax = axis % pred.ndim if pred.ndim else 0
    return (_on_tpu() and pred.ndim >= 2
            and ax == pred.ndim - 1
            and pred.shape[-1] % _LANES == 0 and pred.shape[-1] <= _MAX_C
            and pred.dtype in (jnp.float32, jnp.bfloat16))


def _xent_kernel(x_ref, l_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # (rows, C) in VMEM once
    lbl = l_ref[...]                                 # (rows, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1))
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(col == lbl, x, 0.0), axis=-1)
    o_ref[...] = (lse - picked)[:, None]


def _xent_forward(x2, labels, interpret=False):
    n, c = x2.shape
    # scale the row block down as C grows: the block's f32 copy, its exp
    # and the iota/select temporaries share Mosaic's 16 MB of scoped VMEM
    # (v5e: (32, 32768) compiles, (64, 32768) is refused)
    rows = max(8, min(_BLOCK_ROWS, (2 ** 20) // c // 8 * 8, n))
    n_pad = -(-n // rows) * rows
    if n_pad != n:
        # padded rows pick class 0 of zero logits -> finite garbage, sliced off
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
        labels = jnp.pad(labels, (0, n_pad - n))
    out = pl.pallas_call(
        _xent_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        grid=(n_pad // rows,),
        in_specs=[
            pl.BlockSpec((rows, c), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, labels.reshape(-1, 1).astype(jnp.int32))
    return out[:n, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xent(x2, labels, interpret):
    return _xent_forward(x2, labels, interpret)


def _xent_vjp_fwd(x2, labels, interpret):
    # residuals are the raw logits — the (N, C) log-softmax intermediate of
    # the unfused composition is never materialized in either direction
    return _xent_forward(x2, labels, interpret), (x2, labels)


def _xent_vjp_bwd(interpret, res, g):
    x2, labels = res
    xf = x2.astype(jnp.float32)
    p = jax.nn.softmax(xf, axis=-1)
    onehot = jax.nn.one_hot(labels, x2.shape[-1], dtype=jnp.float32)
    dx = (p - onehot) * g[:, None].astype(jnp.float32)
    return dx.astype(x2.dtype), None


_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


@register("softmax_cross_entropy_fused")
def softmax_cross_entropy_fused(pred, label, interpret=None):
    """Per-row sparse-label cross entropy ``logsumexp(pred) - pred[label]``
    over the last axis; leading shape preserved (f32 output, the dtype the
    unfused f32 ``log_softmax`` path produces)."""
    c = pred.shape[-1]
    lead = pred.shape[:-1]
    x2 = pred.reshape(-1, c)
    lbl = jnp.asarray(label, jnp.int32).reshape(-1)
    return _xent(x2, lbl, _resolve_interpret(interpret)).reshape(lead)
