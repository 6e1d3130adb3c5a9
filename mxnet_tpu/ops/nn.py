"""Neural-network operators.

Covers the reference's ``src/operator/nn/`` family — FullyConnected,
Convolution (cuDNN autotuned in the reference), BatchNorm, LayerNorm,
Pooling, Activation, softmax, Dropout, RNN — as lax/jnp compositions that XLA
maps onto the MXU. Layout: the public contract is NCHW (the reference's
cuDNN-native layout) and ``convolution`` passes NCHW/OIHW
``dimension_numbers`` AS WRITTEN — no Python-level transposes. XLA's layout
assignment picks the physical tiling for TPU itself (logical dims !=
physical layout on TPU; hand-transposing to NHWC in the graph would just
add ops the compiler has to cancel). Hardware A/B pending: the
NCHW-as-written vs explicit-NHWC comparison on a ResNet-50 stage-3 shape
is implemented (tools/kernelbench.py conv_layout rows) but no committed
KERNELBENCH artifact contains those rows yet — the claim above rests on
the XLA layout-assignment design, not a measurement.

RNN replaces the cuDNN fused descriptor machinery (``src/operator/rnn.cc``,
``cudnn_rnn-inl.h``) with a ``lax.scan`` over fused-gate cells — the
compiler-friendly TPU formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..registry import register
from .. import random as _random


# --------------------------------------------------------------------------
# FullyConnected (reference: fully_connected.cc → cuBLAS gemm)
# --------------------------------------------------------------------------
def _amp_compute_dtype():
    from ..contrib.amp import compute_dtype

    return compute_dtype()


@register("FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    adt = _amp_compute_dtype()
    if adt is not None and data.dtype == jnp.float32:
        # AMP: MXU compute in bf16/f16, f32 accumulate, f32 out
        out = jnp.matmul(data.astype(adt), weight.astype(adt).T,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.matmul(data, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# --------------------------------------------------------------------------
# Convolution / Deconvolution (reference: convolution.cc + cudnn autotune)
# --------------------------------------------------------------------------
def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@register("Convolution", aliases=("convolution",))
def convolution(data, weight, bias=None, kernel=None, stride=(1, 1), dilate=(1, 1),
                pad=(0, 0), num_filter=None, num_group=1, no_bias=False, layout="NCHW"):
    """2D (or 1D) convolution, NCHW public layout, MXU-friendly inside."""
    conv_1d = data.ndim == 3
    if conv_1d:  # NCW -> NCHW with H=1
        data = data[:, :, None, :]
        weight = weight[:, :, None, :]
        stride, dilate, pad = (1, _pair(stride, 1)[0]), (1, _pair(dilate, 1)[0]), (0, _pair(pad, 1)[0])
    stride, dilate, pad = _pair(stride), _pair(dilate), _pair(pad)
    orig_dtype = data.dtype
    adt = _amp_compute_dtype()
    # NOTE: no preferred_element_type here — jax's conv transpose rule can't
    # mix the upcast f32 cotangent with low-precision operands (TypeError at
    # grad time; round-3 finding). bf16 is safe without it: its exponent
    # range equals f32's (no overflow) and the MXU accumulates partial
    # products in f32 internally. f16's 65504 max IS overflowable across a
    # large fan-in, and cuDNN accumulates f32 there — so f16 convs stay in
    # f32 (AMP-f16 skips the downcast; f16-cast nets upcast).
    if adt == jnp.bfloat16 and orig_dtype == jnp.float32:
        data, weight = data.astype(adt), weight.astype(adt)
    elif data.dtype == jnp.float16:
        data, weight = data.astype(jnp.float32), weight.astype(jnp.float32)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        rhs_dilation=dilate,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=int(num_group),
    )
    out = out.astype(orig_dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    if conv_1d:
        out = out[:, :, 0, :]
    return out


@register("Deconvolution", aliases=("deconvolution",))
def deconvolution(data, weight, bias=None, kernel=None, stride=(1, 1), dilate=(1, 1),
                  pad=(0, 0), adj=(0, 0), num_filter=None, num_group=1, no_bias=False):
    stride, pad = _pair(stride), _pair(pad)
    kh, kw = weight.shape[-2], weight.shape[-1]
    orig_dtype = data.dtype
    adt = _amp_compute_dtype()
    # transposed conv = lhs-dilated conv with flipped kernel (IOHW).
    # No preferred_element_type — see convolution() above (conv transpose
    # rule breaks on mixed-dtype cotangents; f16 stays f32 for overflow
    # safety, AMP-bf16 computes natively).
    if adt == jnp.bfloat16 and orig_dtype == jnp.float32:
        data, weight = data.astype(adt), weight.astype(adt)
    elif data.dtype == jnp.float16:
        data, weight = data.astype(jnp.float32), weight.astype(jnp.float32)
    out = lax.conv_general_dilated(
        data, jnp.flip(weight, (-1, -2)).swapaxes(0, 1),
        window_strides=(1, 1),
        padding=[(kh - 1 - pad[0], kh - 1 - pad[0] + adj[0]), (kw - 1 - pad[1], kw - 1 - pad[1] + adj[1])],
        lhs_dilation=stride,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=int(num_group),
    )
    out = out.astype(orig_dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


# --------------------------------------------------------------------------
# Pooling (reference: pooling.cc / cudnn_pooling)
# --------------------------------------------------------------------------
@register("Pooling", aliases=("pooling",))
def pooling(data, kernel=(2, 2), pool_type="max", stride=None, pad=(0, 0),
            global_pool=False, count_include_pad=True, pooling_convention="valid"):
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=(-2, -1), keepdims=True)
        return jnp.mean(data, axis=(-2, -1), keepdims=True)
    kernel = _pair(kernel)
    stride = _pair(stride) if stride is not None else kernel
    pad = _pair(pad)
    dims = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1]))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, dims, strides, padding)
    s = lax.reduce_window(data, 0.0, lax.add, dims, strides, padding)
    if count_include_pad or pad == (0, 0):
        return s / (kernel[0] * kernel[1])
    ones = jnp.ones(data.shape[-2:], data.dtype)[None, None]
    cnt = lax.reduce_window(jnp.broadcast_to(ones, (1, 1) + data.shape[-2:]), 0.0, lax.add, dims, strides, padding)
    return s / cnt


@register("_contrib_AdaptiveAvgPooling2D")
def adaptive_avg_pooling(data, output_size=1):
    oh, ow = _pair(output_size)
    n, c, h, w = data.shape
    x = data.reshape(n, c, oh, h // oh, ow, w // ow)
    return x.mean(axis=(3, 5))


# --------------------------------------------------------------------------
# Activation (reference: activation.cc + leaky_relu.cc)
# --------------------------------------------------------------------------
_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "erf_gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "tanh_gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
}


@register("Activation", aliases=("activation",))
def activation(data, act_type="relu"):
    return _ACTS[act_type](data)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2
        return jnp.where(data >= 0, data, mid * data)
    raise ValueError(f"unknown LeakyReLU act_type {act_type!r}")


# --------------------------------------------------------------------------
# softmax family (reference: softmax.cc, softmax_output; fused on TPU by XLA)
# --------------------------------------------------------------------------
@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    if length is not None:
        steps = jnp.arange(data.shape[axis])
        mask = steps[None, :] < length[:, None].astype(jnp.int32)
        shape = [1] * data.ndim
        shape[0], shape[axis] = mask.shape[0], mask.shape[1]
        data = jnp.where(mask.reshape(shape), data, -jnp.inf)
    # dtype-aware f32 softmax: softmax is an _F32_OPS member of the AMP
    # policy — low-precision scores (bf16/f16 under the compiled policy)
    # normalize in f32 and return in the caller's dtype, matching the f32
    # accumulation the fused attention paths already do internally
    if data.dtype in (jnp.float16, jnp.bfloat16):
        return jax.nn.softmax(data.astype(jnp.float32),
                              axis=int(axis)).astype(data.dtype)
    return jax.nn.softmax(data, axis=int(axis))


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    # same f32 policy as softmax: log_softmax feeds cross-entropy losses,
    # where bf16 log-probabilities would visibly bias the loss trajectory
    if data.dtype in (jnp.float16, jnp.bfloat16):
        return jax.nn.log_softmax(data.astype(jnp.float32),
                                  axis=int(axis)).astype(data.dtype)
    return jax.nn.log_softmax(data, axis=int(axis))


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return jnp.sum(nll)


@functools.lru_cache(maxsize=None)
def _softmax_output_fn(grad_scale, ignore_label, use_ignore, normalization,
                       out_grad, smooth_alpha):
    """The reference op's FUSED gradient (softmax_output-inl.h): backward
    w.r.t. data is ``(softmax - smoothed_one_hot(label)) * grad_scale`` —
    independent of the incoming cotangent unless ``out_grad=True`` (then the
    cotangent scales it elementwise, reference semantics). This is what lets
    classic symbols train with SoftmaxOutput as the graph head
    (Module.backward seeds ones)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _so(data, label):
        return jax.nn.softmax(data, axis=-1)

    def _fwd(data, label):
        p = jax.nn.softmax(data, axis=-1)
        return p, (p, label)

    def _bwd(res, g):
        p, label = res
        idx = label.astype(jnp.int32)
        k = p.shape[-1]
        onehot = jax.nn.one_hot(idx, k, dtype=p.dtype)
        if smooth_alpha:
            # reference label smoothing: 1-a on the target class, a/(k-1)
            # spread over the others
            onehot = onehot * (1.0 - smooth_alpha) \
                + (1.0 - onehot) * (smooth_alpha / max(k - 1, 1))
        ds = (p - onehot) * grad_scale
        if out_grad:
            ds = ds * g.astype(p.dtype)
        if use_ignore:
            keep = (idx != int(ignore_label)).astype(p.dtype)[..., None]
            ds = ds * keep
        if normalization == "batch":
            ds = ds / p.shape[0]
        elif normalization == "valid" and use_ignore:
            n = jnp.maximum(jnp.sum(
                (idx != int(ignore_label)).astype(jnp.float32)), 1.0)
            ds = ds / n
        elif normalization == "valid":
            ds = ds / p.shape[0]
        # integer labels need float0 cotangents (jax custom_vjp contract)
        if jnp.issubdtype(label.dtype, jnp.integer):
            import numpy as _onp

            dlabel = _onp.zeros(label.shape, jax.dtypes.float0)
        else:
            dlabel = jnp.zeros_like(label)
        return ds.astype(p.dtype), dlabel

    _so.defvjp(_fwd, _bwd)
    return _so


@register("SoftmaxOutput", aliases=("softmax_output",))
def softmax_output(data, label=None, grad_scale=1.0, ignore_label=-1, use_ignore=False,
                   multi_output=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0):
    """Forward = softmax over the last axis. With a label, the backward is
    the reference's fused ``p - smoothed_one_hot(label)`` (see
    _softmax_output_fn); label-free calls are plain differentiable softmax."""
    if label is None:
        return jax.nn.softmax(data, axis=-1)
    if multi_output:
        raise NotImplementedError(
            "SoftmaxOutput(multi_output=True) (the (n, c, d...) layout) is "
            "not supported; reshape to (n*d, c) instead")
    fn = _softmax_output_fn(float(grad_scale), int(ignore_label),
                            bool(use_ignore), str(normalization),
                            bool(out_grad), float(smooth_alpha))
    return fn(data, label)


# --------------------------------------------------------------------------
# regression heads (reference: regression_output-inl.h — Linear/Logistic/MAE
# RegressionOutput: forward applies the link, backward is the FUSED
# (link(data) - label) * grad_scale / num_output, independent of the
# incoming cotangent — what lets classic symbols train with a regression
# head and Module.backward's ones seed)
# --------------------------------------------------------------------------
def _regression_output_fn(link, dlink, grad_scale):
    @jax.custom_vjp
    def _ro(data, label):
        return link(data)

    def _fwd(data, label):
        out = link(data)
        return out, (out, label)

    def _bwd(res, g):
        out, label = res
        num_out = max(out.size // out.shape[0], 1) if out.ndim else 1
        ds = dlink(out, label.reshape(out.shape)) * (grad_scale / num_out)
        return ds.astype(out.dtype), jnp.zeros_like(label)

    _ro.defvjp(_fwd, _bwd)
    return _ro


def _make_regression_head(reg_name, aliases, link, dlink, doc):
    @register(reg_name, aliases=aliases)
    def head(data, label=None, grad_scale=1.0):
        if label is None:
            return link(data)
        return _regression_output_fn(link, dlink, float(grad_scale))(
            data, label)

    head.__doc__ = doc
    return head


_make_regression_head(
    "LinearRegressionOutput", ("linear_regression_output",),
    lambda x: x, lambda out, lbl: out - lbl,
    "Identity link; backward (out - label) * grad_scale / num_output.")
_make_regression_head(
    "LogisticRegressionOutput", ("logistic_regression_output",),
    lambda x: jax.nn.sigmoid(x), lambda out, lbl: out - lbl,
    "Sigmoid link; the (p - label) gradient is exact for the implied "
    "cross-entropy loss (reference logistic_regression_output).")
_make_regression_head(
    "MAERegressionOutput", ("mae_regression_output",),
    lambda x: x, lambda out, lbl: jnp.sign(out - lbl),
    "Identity link; backward sign(out - label) * grad_scale / num_output.")


# --------------------------------------------------------------------------
# normalization (reference: batch_norm.cc, layer_norm.cc, l2_normalization)
# --------------------------------------------------------------------------
@register("BatchNorm", aliases=("batch_norm",), nout=3)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5, momentum=0.9,
               fix_gamma=False, use_global_stats=False, axis=1, training=False):
    """Returns (out, batch_mean, batch_var); moving-stat update happens in the
    Gluon layer (functional state threading, unlike the reference's in-kernel
    mutation of aux states)."""
    axis = int(axis) % data.ndim
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    xf = data.astype(jnp.float32)
    if training and not use_global_stats:
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
    else:
        mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    inv = lax.rsqrt(var + eps)
    out = (xf - mean.reshape(shape)) * inv.reshape(shape)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype), mean, var


@register("LayerNorm", aliases=("layer_norm",))
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    ax = int(axis)
    from . import pallas_layernorm as _pln

    if _pln.ln_kernel_supported(data, ax):
        # fused single-pass VMEM kernel on TPU (see pallas_layernorm.py);
        # the jnp composition below is the fallback XLA fuses itself
        return _pln.layer_norm_fused(data, gamma, beta, eps)
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=ax, keepdims=True)
    var = jnp.var(xf, axis=ax, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
    elif mode == "channel":
        red = (1,)
    else:  # spatial
        red = tuple(range(2, data.ndim))
    n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / n


@register("RMSNorm", aliases=("_contrib_rms_norm",))
def rms_norm(data, gamma, axis=-1, eps=1e-6):
    xf = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
    out = xf * lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
    return out.astype(data.dtype)


def _held_expert_ffn(data, weights, router_data, **how):
    from ..parallel.moe import held_expert_ffn as ffn

    router_h = None if router_data is None else \
        router_data.reshape(-1, router_data.shape[-1])
    out, stats = ffn(data.reshape(-1, data.shape[-1]), *weights,
                     router_h=router_h, **how)
    return (out.reshape(data.shape), *stats)


@register("held_expert_ffn", nout=4)
def held_expert_ffn(data, router_weight, gate_weight, up_weight, down_weight,
                    held_experts=(), n_group=1, topk_group=1, top_k=1,
                    scale=1.0, norm_topk_prob=False, scoring="softmax",
                    router_bias=None, router_data=None, activation="silu"):
    """The held experts' part of a top-k expert layer over ``data`` (..., d)
    (group-limited softmax, or sigmoid scores with a selection bias; the
    router reads ``router_data`` where given, else ``data``; the gate's
    ``activation`` ``silu`` or ``relu``):
    :func:`mxnet_tpu.parallel.moe.held_expert_ffn`.
    Returns (the part, pairs routed to held experts, largest load of one,
    [1]: 1 where the call walked every sorted pair and not their prefix)."""
    return _held_expert_ffn(
        data, (router_weight, gate_weight, up_weight, down_weight),
        router_data, count_route=True, held_experts=held_experts, n_group=n_group,
        topk_group=topk_group, top_k=top_k, scale=scale,
        norm_topk_prob=norm_topk_prob, scoring=scoring,
        router_bias=router_bias, activation=activation)


@register("held_expert_ffn_hit", nout=4)
def held_expert_ffn_hit(data, router_weight, gate_weight, up_weight,
                        down_weight, **how):
    """:func:`held_expert_ffn` with a fourth output: the held experts that
    drew a pair at all (how near the layer stands to every expert's weights
    being read), and no count of the route: a layer that holds every expert
    has one."""
    router_data = how.pop("router_data", None)
    return _held_expert_ffn(
        data, (router_weight, gate_weight, up_weight, down_weight),
        router_data, count_hit=True, **how)


# --------------------------------------------------------------------------
# Dropout (reference: dropout-inl.h w/ cuDNN dropout descriptors)
# --------------------------------------------------------------------------
@register("Dropout", aliases=("dropout",), stochastic=True)
def dropout(data, p=0.5, mode="training", axes=(), training=False, key=None):
    if not training or p <= 0.0:
        return data
    if key is None:
        key = _random.next_key()
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype)).astype(data.dtype)


# --------------------------------------------------------------------------
# RNN (reference: rnn.cc fused cuDNN op) → lax.scan formulation
# --------------------------------------------------------------------------
def _lstm_cell(carry, x_t, wx, wh, b):
    h, c = carry
    gates = x_t @ wx.T + h @ wh.T + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h


def _gru_cell(carry, x_t, wx, wh, b):
    (h,) = carry
    xz = x_t @ wx.T + b
    hz = h @ wh.T
    xr, xu, xn = jnp.split(xz, 3, axis=-1)
    hr, hu, hn = jnp.split(hz, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    u = jax.nn.sigmoid(xu + hu)
    n = jnp.tanh(xn + r * hn)
    h = (1 - u) * n + u * h
    return (h,), h


def _tanh_cell(carry, x_t, wx, wh, b):
    (h,) = carry
    h = jnp.tanh(x_t @ wx.T + h @ wh.T + b)
    return (h,), h


def _relu_cell(carry, x_t, wx, wh, b):
    (h,) = carry
    h = jnp.maximum(x_t @ wx.T + h @ wh.T + b, 0)
    return (h,), h


_RNN_CELLS = {"lstm": (_lstm_cell, 4, 2), "gru": (_gru_cell, 3, 1),
              "rnn_tanh": (_tanh_cell, 1, 1), "rnn_relu": (_relu_cell, 1, 1)}


def rnn_layer_scan(x_tbc, h0, c0, wx, wh, b, mode):
    """One direction, one layer: x (T,B,C) -> (T,B,H). Weights pre-split."""
    cell, ngates, nstate = _RNN_CELLS[mode]
    carry = (h0, c0)[:nstate]

    def step(carry, x_t):
        return cell(carry, x_t, wx, wh, b)

    carry, ys = lax.scan(step, carry, x_tbc)
    return ys, carry


@register("RNN", nout=3, stochastic=True)
def rnn(data, params, state, state_cell=None, state_size=None, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, projection_size=None,
        training=False, key=None):
    """Fused multi-layer RNN with cuDNN-compatible flat param layout.

    data: (T, B, C); params: flat vector in cuDNN order (per layer, per
    direction: W_x then W_h, then biases b_x, b_h); state: (L*D, B, H).
    Returns (output, h_n, c_n) like the reference op with state_outputs=True.
    """
    cell, ngates, nstate = _RNN_CELLS[mode]
    T, B, C = data.shape
    H = int(state_size)
    D = 2 if bidirectional else 1
    L = int(num_layers)

    # unflatten params
    off = 0

    def take(n, shape):
        nonlocal off
        w = lax.dynamic_slice(params, (off,), (n,)).reshape(shape)
        off += n
        return w

    layer_ws = []
    for layer in range(L):
        in_dim = C if layer == 0 else H * D
        dirs = []
        for d in range(D):
            wx = take(ngates * H * in_dim, (ngates * H, in_dim))
            wh = take(ngates * H * H, (ngates * H, H))
            dirs.append((wx, wh))
        layer_ws.append(dirs)
    layer_bs = []
    for layer in range(L):
        dirs = []
        for d in range(D):
            bx = take(ngates * H, (ngates * H,))
            bh = take(ngates * H, (ngates * H,))
            dirs.append(bx + bh)
        layer_bs.append(dirs)

    h_n, c_n = [], []
    x = data
    for layer in range(L):
        outs = []
        for d in range(D):
            idx = layer * D + d
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else jnp.zeros_like(h0)
            wx, wh = layer_ws[layer][d]
            b = layer_bs[layer][d]
            xs = jnp.flip(x, 0) if d == 1 else x
            ys, carry = rnn_layer_scan(xs, h0, c0, wx, wh, b, mode)
            if d == 1:
                ys = jnp.flip(ys, 0)
            outs.append(ys)
            h_n.append(carry[0])
            c_n.append(carry[1] if nstate == 2 else jnp.zeros_like(carry[0]))
        x = jnp.concatenate(outs, axis=-1) if D == 2 else outs[0]
        if training and p > 0 and layer < L - 1:
            k = key if key is not None else _random.next_key()
            mask = jax.random.bernoulli(jax.random.fold_in(k, layer), 1 - p, x.shape)
            x = jnp.where(mask, x / (1 - p), 0).astype(x.dtype)
    return x, jnp.stack(h_n), jnp.stack(c_n)


# --------------------------------------------------------------------------
# misc image ops used by the vision zoo
# --------------------------------------------------------------------------
@register("UpSampling")
def upsampling(data, scale=2, sample_type="nearest", num_args=1):
    s = int(scale)
    return jnp.repeat(jnp.repeat(data, s, axis=-2), s, axis=-1)


@register("BilinearResize2D", aliases=("_contrib_BilinearResize2D",))
def bilinear_resize(data, height=None, width=None, scale_height=None, scale_width=None):
    n, c, h, w = data.shape
    oh = int(height) if height else int(h * scale_height)
    ow = int(width) if width else int(w * scale_width)
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


# --------------------------------------------------------------------------
# loss ops (reference: src/operator/loss_binary_op.cc smooth_l1 in
# elemwise_unary_op, src/operator/nn/ctc_loss.cc)
# --------------------------------------------------------------------------
@register("smooth_l1")
def smooth_l1(data, scalar=1.0):
    """Huber-style smooth L1 with transition at 1/scalar^2 (the SSD/Faster-
    RCNN bbox regression loss; reference: smooth_l1 in elemwise ops)."""
    sigma2 = float(scalar) ** 2
    a = jnp.abs(data)
    return jnp.where(a < 1.0 / sigma2, 0.5 * sigma2 * data * data, a - 0.5 / sigma2)


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """Connectionist temporal classification loss.

    data: (T, B, C) activations (softmax applied internally, like the
    reference); label: (B, L) class ids, 0-padded when label_lengths absent
    (blank_label='first': blank id 0, labels are 1-based).
    Alpha recursion in the log semiring via ``lax.scan`` over time — the
    lax formulation of the reference's warp-ctc kernel.
    """
    T, B, C = data.shape
    L = label.shape[1]
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)  # [T,B,C]
    label = label.astype(jnp.int32)
    blank = 0 if blank_label == "first" else C - 1
    if label_lengths is not None and use_label_lengths:
        lab_len = label_lengths.astype(jnp.int32)
    else:
        # padding value: 0 for blank_label='first' (labels are 1-based),
        # -1 for blank_label='last' (0 is a valid class) — reference semantics
        pad = 0 if blank_label == "first" else -1
        lab_len = jnp.sum((label != pad).astype(jnp.int32), axis=1)
    if data_lengths is not None and use_data_lengths:
        seq_len = data_lengths.astype(jnp.int32)
    else:
        seq_len = jnp.full((B,), T, jnp.int32)

    S = 2 * L + 1
    pos = jnp.arange(S)
    # ext[b, s]: blank on even s, label[(s-1)//2] on odd s
    ext = jnp.where(pos[None, :] % 2 == 1,
                    jnp.take_along_axis(label, jnp.clip((pos[None, :] - 1) // 2, 0, L - 1),
                                        axis=1),
                    blank)                                    # [B, S]
    ext = jnp.clip(ext, 0, C - 1)  # -1 padding is masked by valid_s; keep indices in range
    neg_inf = jnp.float32(-1e30)
    # can skip from s-2 when ext[s] != blank and ext[s] != ext[s-2]
    ext_m2 = jnp.concatenate([jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    can_skip = (ext != blank) & (ext != ext_m2)               # [B, S]
    valid_s = pos[None, :] < (2 * lab_len[:, None] + 1)       # [B, S]

    emit0 = jnp.take_along_axis(logp[0], ext, axis=1)         # [B, S]
    alpha0 = jnp.where((pos[None, :] < 2) & valid_s, emit0, neg_inf)

    def step(alpha, t):
        a1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        a2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        a2 = jnp.where(can_skip, a2, neg_inf)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, a1), a2)
        emit = jnp.take_along_axis(logp[t], ext, axis=1)
        new = jnp.where(valid_s, merged + emit, neg_inf)
        # past the sequence end the lattice freezes
        new = jnp.where((t < seq_len)[:, None], new, alpha)
        return new, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    # terminal states: S-1 and S-2 for each batch's actual label length
    send = 2 * lab_len                                        # even terminal (blank)
    last_blank = jnp.take_along_axis(alpha, send[:, None], axis=1)[:, 0]
    last_label = jnp.take_along_axis(alpha, jnp.maximum(send - 1, 0)[:, None], axis=1)[:, 0]
    ll = jnp.logaddexp(last_blank, jnp.where(lab_len > 0, last_label, neg_inf))
    return -ll
