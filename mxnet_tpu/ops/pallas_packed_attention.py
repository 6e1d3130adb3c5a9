"""Short-sequence self-attention over the packed q/k/v projection, one
Pallas kernel forward and one backward (docs/PERFORMANCE.md "Custom
kernels").

``flash_attention`` is built for long sequences: ``(B, H, T, D)`` blocks, an
online softmax, head size padded to 128, a crossover at sequence 2048. BERT
pretraining is the opposite shape (sequence 128-512, head size 64, a
key-padding mask), and there the einsum path pays for layout, not
arithmetic: the ``(B, T, 3C)`` projection is transposed to ``(3, B, H, T,
D)``, the ``(B, H, T, T)`` scores are written in float32, the probabilities
of every layer are kept for the backward pass and the context is transposed
back, all with 64 or 128 in the lane dimension.

Here a whole batch row fits VMEM at once, so there is no online softmax and
no layout change:

  - *layout*: the kernel reads ``qkv`` of shape ``(B, T, 3C)`` with columns
    ordered ``[3][H][D]``, as the ``qkv`` Dense writes it, and writes the
    context as ``(B, T, C)``, as the ``proj`` Dense reads it. The grid runs
    over batch rows; one block is a row's ``(T, 3C)``.
  - *heads*: columns are taken 128 at a time (a lane tile: two heads of 64,
    or one of 128). For two heads in a tile the other head's lanes are
    zeroed in the contraction's left operand, which leaves the scores exact
    and lets the MXU contract over its native 128, and each head's lanes of
    the 128-wide result are selected. No 64-lane slice is ever taken.
  - *mask*: keys only, ``(B, 1, 1, T)``, handed in as a float32 bias of 0 or
    ``-inf`` added to the scores: a masked key gets weight exactly 0.0, as
    ``attention._reference_mha`` gives, and gradient exactly 0 in ``dk`` and
    ``dv``. A row with every key masked is NaN, there as here.
  - *dtype policy*: ``multi_head_attention``'s. Only the products run in the
    input dtype (float32 accumulation); scores, the softmax and its
    normaliser are float32. The scores stay float32 from the accumulator,
    where the einsum path rounds them to the input dtype first.
  - *backward*: the residuals are ``qkv`` and the bias. One kernel over the
    same grid recomputes the scores and probabilities per head, then
    ``dv = p^T do``, ``dp = do v^T``, ``ds = p (dp - rowsum(dp p))``,
    ``dq = ds k``, ``dk = ds^T q``, written into a ``(T, 3C)`` block of
    ``dqkv``: the cotangent the ``qkv`` Dense's weight gradient wants. No
    probabilities are kept across the step. It holds the scores transposed,
    ``(keys, queries)``, with the heads of a tile side by side: the softmax
    and ``rowsum`` then reduce over sublanes, ``dv`` and ``dk`` need no
    transposed operand (only ``dq`` does), and each product streams the rows
    of both heads past one stationary operand. On a v5e that took the
    backward kernel from 0.51 to 0.25 ms a layer at BERT-large's shape
    (PERF.md, PR 25).
  - *names*: under jax 0.9 XLA names a Pallas call's instruction after the
    innermost scope around it, which is the kernel's ``name`` (or the
    calling block's scope when there is none, or when the name starts with
    ``tpu_custom_call``), and a device trace knows the operation by that
    name. Both kernels' names start with ``custom_call``: that is what they
    are, and it is how the benchmark's ``custom_call_share_pct`` (and a
    reader of a trace) tells a Pallas kernel from a fusion.

The gate (:func:`packed_attention_refusal`) reads only what it can observe
in its operands and the process: backend, dtype, shapes, the mask's form and
the active mesh. ``ops.attention.self_attention_packed`` is the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mesh_state import current_mesh
from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

# What one grid step of the backward kernel may hold: its double-buffered
# blocks (qkv, dqkv, do) and the float32 score-shaped temporaries of one
# tile, (T, T) for each of its heads. A v5e core has 128 MiB of VMEM, of
# which a kernel gets 16 MiB unless it asks for more; the kernels ask for
# twice what _vmem_bytes counts
_MAX_VMEM_BYTES = 48 * 1024 * 1024
_SCORE_TEMPS = 8  # float32 score-shaped values live at once in the backward


def _vmem_bytes(t, c, d, itemsize):
    blocks = 2 * (2 * t * 3 * c + t * c) * itemsize
    return blocks + _SCORE_TEMPS * (_LANES // d) * t * t * 4


def _keys_only(mask, b, t):
    """Whether ``mask`` has the form the kernel takes, ``(B or 1, 1, 1, T)``:
    it may depend on the key, not on the query or the head."""
    return (mask.ndim == 4 and mask.shape[1:] == (1, 1, t)
            and mask.shape[0] in (1, b))


def packed_attention_refusal(qkv, mask, heads):
    """Why the packed kernel does NOT run for these operands (anything with
    ``.shape``/``.dtype``), or None when it does. The first condition that
    fails is the one named."""
    if not _on_tpu():
        return "the backend is not a TPU"
    if qkv.ndim != 3 or qkv.shape[2] % (3 * heads):
        return f"qkv of shape {tuple(qkv.shape)} is not (B, T, 3 x {heads} x D)"
    b, t, c3 = qkv.shape
    c = c3 // 3
    d = c // heads
    if qkv.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {jnp.dtype(qkv.dtype).name} is not float32 or bfloat16"
    if d not in (64, 128) or c % _LANES:
        return (f"head size {d} is not 64 or 128, or the {c} columns are "
                f"not whole {_LANES}-lane tiles")
    if t % _LANES:
        return f"sequence length {t} is not a multiple of {_LANES}"
    need = _vmem_bytes(t, c, d, jnp.dtype(qkv.dtype).itemsize)
    if need > _MAX_VMEM_BYTES:
        return (f"a batch row of sequence {t} needs {need} bytes of VMEM "
                f"(budget {_MAX_VMEM_BYTES})")
    if mask is not None and not _keys_only(mask, b, t):
        return (f"mask of shape {tuple(mask.shape)} is not keys-only "
                f"(B, 1, 1, T)")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# Past this sequence length the tiles of a batch row go through a rolled loop.
# On a v5e (PR 25) unrolled code is twice as fast at sequence 128 (0.37
# against 0.72 ms a layer, forward + backward) and no faster at 512, where
# Mosaic takes 10 s to compile it and 1 s for the loop; 256 is not measured
_UNROLL_UP_TO = 256


def _for_each_tile(qkv_ref, c, body):
    """``body(lo, q, k, v)`` for each 128-lane tile of the head columns,
    ``lo`` its column offset and q, k, v ``(T, 128)``."""
    def tile(i, carry):
        lo = pl.multiple_of(i * _LANES, _LANES)
        at = lambda base: pl.ds(pl.multiple_of(base + lo, _LANES), _LANES)  # noqa: E731
        body(lo, qkv_ref[0, :, at(0)], qkv_ref[0, :, at(c)],
             qkv_ref[0, :, at(2 * c)])
        return carry

    t = qkv_ref.shape[1]
    lax.fori_loop(0, c // _LANES, tile, 0, unroll=t <= _UNROLL_UP_TO)


def _head_lanes(t, d):
    """One boolean ``(T, 128)`` lane selector per head of a tile; [None]
    when a head is the whole tile."""
    if d == _LANES:
        return [None]
    head = lax.broadcasted_iota(jnp.int32, (t, _LANES), 1) // d
    return [head == j for j in range(_LANES // d)]


def _only(sel, x):
    """``x`` with the lanes outside ``sel`` zeroed."""
    return x if sel is None else jnp.where(sel, x, jnp.zeros_like(x))


def _merge(sel, new, acc):
    """``new`` on the lanes of ``sel``, ``acc`` elsewhere."""
    return new if acc is None or sel is None else jnp.where(sel, new, acc)


def _softmax(s, axis):
    e = jnp.exp(s - jnp.max(s, axis=axis, keepdims=True))
    return e * (1.0 / jnp.sum(e, axis=axis, keepdims=True))


def _fwd_kernel(qkv_ref, bias_ref, o_ref, *, d, scale):
    t, c = o_ref.shape[1], o_ref.shape[2]
    bias = bias_ref[0]  # (1, T): one per key
    lanes = _head_lanes(t, d)

    def tile(lo, q, k, v):
        out = None
        for sel in lanes:
            p = _softmax(_dot(_only(sel, q), k, _NT) * scale + bias, -1)
            out = _merge(sel, _dot(p.astype(v.dtype), v), out)
        o_ref[0, :, pl.ds(lo, _LANES)] = out.astype(o_ref.dtype)

    _for_each_tile(qkv_ref, c, tile)


def _bwd_kernel(qkv_ref, bias_ref, do_ref, dqkv_ref, *, d, scale):
    t, c = do_ref.shape[1], do_ref.shape[2]
    bias = bias_ref[0]  # (T, 1): one per key
    lanes = _head_lanes(t, d)
    # the heads of a tile stacked over rows, and back
    stack = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, 0)  # noqa: E731
    heads_of = lambda x: [x[:, j * t:(j + 1) * t] for j in range(len(lanes))]  # noqa: E731

    def unstack(x):
        out = None
        for j, sel in enumerate(lanes):
            out = _merge(sel, x[j * t:(j + 1) * t], out)
        return out

    def tile(lo, q, k, v):
        do = do_ref[0, :, pl.ds(lo, _LANES)]
        qs = stack([_only(sel, q) for sel in lanes])     # (gT, 128)
        dos = stack([_only(sel, do) for sel in lanes])
        # everything score-shaped is (keys, g x queries)
        p = _softmax(_dot(k, qs, _NT) * scale + bias, 0)
        dp = _dot(v, dos, _NT)
        ds = p * (dp - jnp.sum(dp * p, axis=0, keepdims=True)) * scale
        ds = ds.astype(q.dtype)
        dv = unstack(_dot(stack(heads_of(p.astype(do.dtype))), do))
        dk = unstack(_dot(stack(heads_of(ds)), q))
        dq = unstack(_dot(ds, k, _TN))
        for base, g in ((0, dq), (c, dk), (2 * c, dv)):
            at = pl.ds(pl.multiple_of(base + lo, _LANES), _LANES)
            dqkv_ref[0, :, at] = g.astype(dqkv_ref.dtype)

    _for_each_tile(qkv_ref, c, tile)


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6, 7))
def _call(kernel, name, qkv, bias, others, out_width, heads, interpret):
    """Run ``kernel`` over batch rows: ``qkv`` and every one of ``others``,
    ``(B, T, width)``, is handed over a row at a time, and so is ``bias``,
    ``(B, 1, T)`` or ``(B, T, 1)``. Jitted, so that the 24 layers of a model
    trace and lower each kernel once and not 24 times (4 s of every process
    start of BERT-large, compile cache warm or not)."""
    b, t, c3 = qkv.shape
    c = c3 // 3
    d = c // heads
    row = lambda x: pl.BlockSpec((1,) + x.shape[1:], lambda i: (i, 0, 0))  # noqa: E731
    need = _vmem_bytes(t, c, d, jnp.dtype(qkv.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(kernel, d=d, scale=1.0 / (d ** 0.5)),
        out_shape=jax.ShapeDtypeStruct((b, t, out_width), qkv.dtype),
        grid=(b,),
        in_specs=[row(x) for x in (qkv, bias, *others)],
        out_specs=pl.BlockSpec((1, t, out_width), lambda i: (i, 0, 0)),
        name=name,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(2 * need, 16 * 1024 * 1024)),
    )(qkv, bias, *others)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _packed(qkv, bias, heads, interpret):
    return _call(_fwd_kernel, "custom_call_packed_attention_fwd", qkv,
                 bias, (), qkv.shape[2] // 3, heads, interpret)


def _packed_vjp_fwd(qkv, bias, heads, interpret):
    return _packed(qkv, bias, heads, interpret), (qkv, bias)


def _packed_vjp_bwd(heads, interpret, res, do):
    qkv, bias = res
    b, t, c3 = qkv.shape
    dqkv = _call(_bwd_kernel, "custom_call_packed_attention_bwd", qkv,
                 bias.reshape(b, t, 1), (do,), c3, heads, interpret)
    return dqkv, jnp.zeros_like(bias)


_packed.defvjp(_packed_vjp_fwd, _packed_vjp_bwd)


def packed_attention(qkv, mask=None, heads=1, interpret=None):
    """Self-attention of every head over ``qkv`` ``(B, T, 3C)``, columns
    ordered ``[3][H][D]``; ``mask`` None or keys-only ``(B or 1, 1, 1, T)``
    (true = attend). Returns the context ``(B, T, C)``. Callers gate via
    :func:`packed_attention_refusal`."""
    b, t, _ = qkv.shape
    if mask is None:
        bias = jnp.zeros((b, 1, t), jnp.float32)
    else:
        if not _keys_only(mask, b, t):
            raise ValueError(f"packed_attention takes a keys-only mask "
                             f"(B, 1, 1, T), not {tuple(mask.shape)}")
        bias = jnp.where(mask.astype(bool), 0.0, -jnp.inf).astype(jnp.float32)
        bias = jnp.broadcast_to(bias.reshape(-1, 1, t), (b, 1, t))
    return _packed(qkv, bias, int(heads), _resolve_interpret(interpret))
