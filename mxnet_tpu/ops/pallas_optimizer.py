"""Fused Adam / master-weight update Pallas kernel.

Reference analog: the hand-rolled multi-tensor ``adam_update`` /
``mp_*_update`` kernels in ``src/operator/optimizer_op.cc`` — one kernel
pass per parameter instead of the unfused elementwise chain. XLA fuses the
chain decently, but the multi-precision path
(``Optimizer.update_multi_precision``) still runs *two* passes over the
weight bytes: the f32 master update, then a separate cast back into the
bf16/f16 model copy. The fused kernel emits both in one pass over
grad/m/v/master — each operand is read once from HBM, the low-precision
model copy is written as a second kernel output.

Math contract: the exact op order of
``mxnet_tpu.ops.optimizer_ops.adam_update`` (rescale → clip → +wd·w →
moment EMAs → ``w - lr·m/(sqrt(v)+eps)``, all f32), with the bias-corrected
``lr_t`` computed by the caller exactly as ``Adam.update_raw`` does.
Results agree with the XLA chain to a few f32 ulp (XLA may reassociate
fused multiply-adds differently), which the parity tests pin.

Gating mirrors ``pallas_layernorm``: opt-in knob (``fused_adam`` /
``MXNET_TPU_FUSED_ADAM``), TPU backend only — the imperative
Trainer/Updater path picks it up per-parameter; the mesh-compiled
``TrainStep`` path never routes through it because GSPMD cannot partition
a ``pallas_call`` (see docs/PERFORMANCE.md "Custom kernels"). CPU CI runs
the same kernel under ``interpret=True`` in the parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

_BLOCK_ROWS = 256  # (rows, 128) f32 blocks: 5 operands in + 4 out ≈ 1.2MB


def fused_adam_supported(w, g, mean) -> bool:
    """Opt-in (``MXNET_TPU_FUSED_ADAM=1``), hardware-only, f32 states.

    The imperative update path (Trainer / KVStore Updater /
    ``update_multi``) qualifies; weights of any rank — operands are
    flattened to lane-padded (rows, 128) blocks, so there is no shape
    divisibility requirement, only the dtype contract (f32 master/moments,
    f32 or bf16 gradient).
    """
    from .. import config as _config

    if not _config.get("fused_adam"):
        return False
    if not _on_tpu():
        return False
    return (w.dtype == jnp.float32 and mean.dtype == jnp.float32
            and g.dtype in (jnp.float32, jnp.bfloat16)
            and w.size >= _LANES)


def _adam_kernel(lr_ref, wd_ref, w_ref, g_ref, m_ref, v_ref, *out_refs,
                 beta1, beta2, epsilon, rescale_grad, clip_gradient):
    # out_refs = (new_w, new_m, new_v[, new_w_lowp]) — the optional 4th
    # output is the one-pass master-weight cast of the mp path
    lr = lr_ref[0, 0]
    wd = wd_ref[0, 0]
    wf = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    g = g + wd * wf
    m = beta1 * m_ref[...].astype(jnp.float32) + (1 - beta1) * g
    v = beta2 * v_ref[...].astype(jnp.float32) + (1 - beta2) * jnp.square(g)
    w = wf - lr * m / (jnp.sqrt(v) + epsilon)
    out_refs[0][...] = w.astype(out_refs[0].dtype)
    out_refs[1][...] = m.astype(out_refs[1].dtype)
    out_refs[2][...] = v.astype(out_refs[2].dtype)
    if len(out_refs) == 4:
        out_refs[3][...] = w.astype(out_refs[3].dtype)


def _pad_rows(x, n_pad):
    flat = x.reshape(-1)
    if n_pad != flat.shape[0]:
        flat = jnp.pad(flat, (0, n_pad - flat.shape[0]))
    return flat.reshape(-1, _LANES)


def adam_update_fused(w, g, mean, var, lr_t, *, beta1, beta2, epsilon,
                      wd, rescale_grad=1.0, clip_gradient=-1.0,
                      out_dtype=None, interpret=None):
    """One-pass Adam step; ``lr_t`` is the bias-corrected learning rate.

    Returns ``(new_w, new_m, new_v)`` — plus a 4th array ``new_w_lowp``
    (``out_dtype``) when ``out_dtype`` is given and differs from the
    weight dtype: the fused master-weight variant, where the low-precision
    model copy costs no extra read pass. ``lr_t``/``wd`` may be traced
    scalars (they ride in SMEM), so hyperparameter schedules never
    retrigger compilation.
    """
    interpret = _resolve_interpret(interpret)
    shape, dtype = w.shape, w.dtype
    n = w.size
    rows = max(8, min(_BLOCK_ROWS, -(-n // _LANES)))
    n_pad = -(-n // (rows * _LANES)) * rows * _LANES
    ops2d = [_pad_rows(x, n_pad) for x in (w, g, mean, var)]
    nrows = n_pad // _LANES

    emit_lp = out_dtype is not None and jnp.dtype(out_dtype) != dtype
    out_shapes = [jax.ShapeDtypeStruct((nrows, _LANES), dtype),
                  jax.ShapeDtypeStruct((nrows, _LANES), mean.dtype),
                  jax.ShapeDtypeStruct((nrows, _LANES), var.dtype)]
    if emit_lp:
        out_shapes.append(jax.ShapeDtypeStruct((nrows, _LANES), out_dtype))

    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    block = lambda: pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                          epsilon=epsilon, rescale_grad=rescale_grad,
                          clip_gradient=clip_gradient),
        out_shape=out_shapes,
        grid=(nrows // rows,),
        in_specs=[scalar_spec, scalar_spec] + [block() for _ in range(4)],
        out_specs=[block() for _ in out_shapes],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(lr_t, jnp.float32).reshape(1, 1),
      jnp.asarray(wd, jnp.float32).reshape(1, 1), *ops2d)

    unpad = lambda x: x.reshape(-1)[:n].reshape(shape)
    outs = [unpad(o) for o in outs]
    return tuple(outs)
