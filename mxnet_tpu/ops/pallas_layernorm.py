"""Fused LayerNorm Pallas kernel (SURVEY §7 M6: the second marquee kernel
after flash attention).

Reference analog: ``src/operator/nn/layer_norm.cc``'s fused CUDA kernel
(one pass: mean/var + normalize + affine). XLA already fuses the naive
composition well; the kernel's wins are (a) a single VMEM-resident pass —
the row is loaded once for mean, variance AND normalize (Welford-free
two-moment accumulation in f32), and (b) no intermediate f32 materialization
of the whole activation when the input is bf16.

Forward is the kernel; backward is the analytic LN VJP expressed in jnp
(fusion-friendly, matches the flash-attention design split). Gated like the
flash kernel: TPU backend + feature dim a 128-lane multiple; callers fall
back to the jnp composition otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

_BLOCK_ROWS = 256
# feature-dim cap: a (rows, d) f32 block must fit VMEM with room for the
# output block and the in-kernel f32 copy (~16MB total per core)
_MAX_D = 8192


def ln_kernel_supported(x, axis=-1) -> bool:
    # opt-in on hardware (MXNET_TPU_FUSED_LAYERNORM=1). On a v5e under jax
    # 0.9.0 (tools/kernelbench.py, PR 21) the kernel compiles and agrees
    # with the jnp composition to one bf16 step at (8k-32k rows, d
    # 1024-4096); its speed against XLA's own fusion of this pattern is not
    # measured, so the default stays the composition
    from .. import config as _config

    if not _config.get("fused_layernorm"):
        return False
    ax = axis % x.ndim
    return (_on_tpu() and ax == x.ndim - 1
            and x.shape[-1] % _LANES == 0 and x.shape[-1] <= _MAX_D
            and x.dtype in (jnp.float32, jnp.bfloat16))


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # (rows, d) resident in VMEM once
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_forward(x2, gamma, beta, eps, interpret=False):
    n, d = x2.shape
    # scale the row block down as d grows: keep in+out+f32-copy well under
    # VMEM (2^21 f32 elements ~ 8MB for the input block)
    rows = max(8, min(_BLOCK_ROWS, (2 ** 21) // max(d, 1), n))
    # pad rows so the grid divides evenly (padded rows normalize garbage,
    # sliced off below — cheap, keeps BlockSpecs static)
    n_pad = -(-n // rows) * rows
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x2.dtype),
        grid=(n_pad // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        interpret=interpret,
    )(x2, gamma, beta)
    return out[:n] if n_pad != n else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2, gamma, beta, eps, interpret):
    return _ln_forward(x2, gamma, beta, eps, interpret)


def _ln_fwd(x2, gamma, beta, eps, interpret):
    return _ln_forward(x2, gamma, beta, eps, interpret), (x2, gamma)


def _ln_bwd(eps, interpret, res, g):
    # analytic LN backward in f32 (reference layer_norm.cc backward math)
    x2, gamma = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    d = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = xc * rstd
    dy = gf * gamma.astype(jnp.float32)
    dx = rstd * (dy - jnp.mean(dy, axis=-1, keepdims=True)
                 - xhat * jnp.mean(dy * xhat, axis=-1, keepdims=True))
    dgamma = jnp.sum(gf * xhat, axis=0)
    dbeta = jnp.sum(gf, axis=0)
    return (dx.astype(x2.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_fused(data, gamma, beta, eps=1e-5, interpret=None):
    """Fused LN over the last axis; any leading shape (flattened to rows)."""
    d = data.shape[-1]
    x2 = data.reshape(-1, d)
    out = _ln(x2, gamma, beta, float(eps), _resolve_interpret(interpret))
    return out.reshape(data.shape)
