"""Olmo-Hybrid-7B (``allenai/Olmo-Hybrid-7B`` ``config.json``): Gated
DeltaNet layers (arXiv:2412.06464; negative eigenvalues as arXiv:2411.12537)
three to one with full softmax attention, in OLMo 2's block
(arXiv:2501.00656): no biases, RMSNorm on each sublayer's OUTPUT inside the
residual, an untied head. With ``x`` a layer's input ::

    x1 = x  + RMSNorm(mixer(x))
    x2 = x1 + RMSNorm(Wdown(silu(Wgate x1) * (Wup x1)))

A ``linear_attention`` mixer, 30 heads h of key width 96 and value width 192 ::

    z   = [Wq x ; Wk x ; Wv x]                    # 2,880 + 2,880 + 5,760
    c_t = silu(sum_j conv[:, j] * z_(t-3+j))      # causal, depth-wise, width 4
    q_t, k_t, v_t = heads of c_t;  q, k L2-normalised a head, q * 96^-1/2
    beta_t  = 2 sigmoid(w_b . x_t)                # (0, 2): linear_allow_neg_eigval
    alpha_t = exp(-exp(A_log) * softplus(w_a . x_t + dt_bias))
    S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T     # 96 x 192
    o_t = S_t^T q_t
    y_t = Wo [ RMSNorm_192(o_t) * silu(Wg x_t)_h ]_h

A ``full_attention`` mixer: causal softmax over 30 heads of 128 (30
key-value heads), q and k RMS-normed over the whole projection, no
positions of any kind.

A full forward over one whole sequence in float32 at ``highest``: the
recurrence position by position (a ``lax.scan`` over t: no chunk, no kernel,
no cache, no batching), the full layers as a plain masked softmax with the
queries in blocks so that 4,096 positions fit.

``precision`` is ``float32`` (the reference), ``bfloat16``/``fp8`` (the same
mathematics with rounded matmul operands), or a control of the MATHEMATICS
in float32: ``state_bf16`` (the state kept in bfloat16 between positions),
``no_decay`` (alpha = 1), ``beta_le_1`` (no factor 2 on the write strength),
``no_conv`` (the convolution left out: c_t = silu(z_t)) and
``pad_writes_state`` (what a prefill that lets its bucket's padding into the
recurrence computes: the pad tokens between the prompt and the answer pass
through every layer, hidden from the softmax's keys as a mask hides them,
and write into the state).

What the configuration's file assumes is in its ``assumed``; the decays'
band is :func:`decay_leaf`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .deepseek_v2 import linear, rms_norm, swiglu
from .precision import einsum

MATH_CONTROLS = ("state_bf16", "no_decay", "beta_le_1", "no_conv",
                 "pad_writes_state")


def split_precision(precision):
    """(the products' precision, the control of the mathematics or None)."""
    if precision in MATH_CONTROLS:
        return "float32", precision
    return precision, None


# -- shapes -----------------------------------------------------------------
def layer_types(cfg):
    return tuple(cfg["layer_types"][:cfg["n_layer"]])


def gdn_sizes(cfg):
    """(heads, key width, value width, convolution's channels)."""
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    if cfg["linear_num_key_heads"] != h:
        raise ValueError("key heads and value heads of a linear layer differ: "
                         "not this reference's layer")
    return h, dk, dv, h * (2 * dk + dv)


def layer_specs(cfg, i):
    """(name, shape, init) of layer ``i``'s leaves; weights stored (out, in).
    ``A_log`` and ``dt_bias`` are drawn around 0: :func:`decay_leaf` adds
    the configuration's offsets."""
    hd, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    std = ("normal", cfg["initializer_range"])
    p = f"layer{i}."
    if cfg["layer_types"][i] == "linear_attention":
        h, dk, dv, channels = gdn_sizes(cfg)
        d = cfg["decay_init"]
        mixer = [(p + "gdn.q.w", (h * dk, hd), std),
                 (p + "gdn.k.w", (h * dk, hd), std),
                 (p + "gdn.v.w", (h * dv, hd), std),
                 (p + "gdn.g.w", (h * dv, hd), std),
                 (p + "gdn.o.w", (hd, h * dv), std),
                 (p + "gdn.a.w", (h, hd), ("normal", d["a_proj_std"])),
                 (p + "gdn.b.w", (h, hd), std),
                 (p + "gdn.conv.w", (channels, cfg["linear_conv_kernel_dim"]),
                  ("normal", d["conv_std"])),
                 (p + "gdn.A_log", (h,), ("normal", d["A_log_std"])),
                 (p + "gdn.dt_bias", (h,), ("normal", d["dt_bias_std"])),
                 (p + "gdn.o_norm.gamma", (dv,), "ones")]
    else:
        width = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        mixer = [(p + "attn.q.w", (width, hd), std),
                 (p + "attn.k.w", (kv, hd), std),
                 (p + "attn.v.w", (kv, hd), std),
                 (p + "attn.o.w", (hd, width), std),
                 (p + "attn.q_norm.gamma", (width,), "ones"),
                 (p + "attn.k_norm.gamma", (kv,), "ones")]
    return mixer + [(p + "mixer_norm.gamma", (hd,), "ones"),
                    (p + "ffn.gate.w", (ffn, hd), std),
                    (p + "ffn.up.w", (ffn, hd), std),
                    (p + "ffn.down.w", (hd, ffn), std),
                    (p + "ffn_norm.gamma", (hd,), "ones")]


def param_specs(cfg):
    hd, v = cfg["hidden_size"], cfg["n_vocab"]
    std = ("normal", cfg["initializer_range"])
    out = [("embed.word", (v, hd), std)]
    for i in range(cfg["n_layer"]):
        out += layer_specs(cfg, i)
    return out + [("norm.gamma", (hd,), "ones"), ("head.w", (v, hd), std)]


def decay_leaf(cfg, name, leaf):
    """``A_log`` or ``dt_bias`` (by the end of ``name``) as the model holds
    it: the drawn leaf (a normal about 0, all ``make_weights`` can draw)
    moved by the configuration's ``decay_init`` mean, which puts A =
    exp(A_log) and the step softplus(dt_bias) in the band of the published
    initialisation (A uniform in (0, 16), the step log-uniform in (0.001,
    0.1)). Any other leaf as it is. The program's adaptor hands its model
    the same sums."""
    for moved in ("A_log", "dt_bias"):
        if name.endswith(moved):
            return leaf + cfg["decay_init"][moved + "_mean"]
    return leaf


# -- the linear layer -------------------------------------------------------
def short_conv(z, w, control=None):
    """silu of the causal depth-wise convolution of ``z`` (T, C) over time
    with ``w`` (C, width): position t reads z_(t-width+1) .. z_t, zeros
    before the sequence."""
    if control == "no_conv":
        return jax.nn.silu(z)
    width = w.shape[1]
    padded = jnp.pad(z, ((width - 1, 0), (0, 0)))
    out = sum(padded[j:j + z.shape[0]] * w[:, j] for j in range(width))
    return jax.nn.silu(out)


def l2_normalise(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gates(params, p, cfg, x, control=None):
    """(alpha (T, H), beta (T, H)) of the sublayer's input ``x`` (T, hidden):
    float32 whatever the products' precision, as the program keeps them."""
    a_log, dt_bias = (decay_leaf(cfg, n, params[p + "gdn." + n])
                      for n in ("A_log", "dt_bias"))
    step = jax.nn.softplus(linear(x, params[p + "gdn.a.w"], "float32")
                           + dt_bias)
    alpha = jnp.exp(-jnp.exp(a_log) * step)
    beta = jax.nn.sigmoid(linear(x, params[p + "gdn.b.w"], "float32"))
    if control == "no_decay":
        alpha = jnp.ones_like(alpha)
    if control != "beta_le_1":
        beta = 2.0 * beta
    return alpha, beta


def delta_rule(q, k, v, alpha, beta, control=None):
    """The gated delta rule position by position: ``q``, ``k`` (T, H, dk),
    ``v`` (T, H, dv), ``alpha``, ``beta`` (T, H); returns o (T, H, dv). The
    state (H, dk, dv) starts at zero."""
    hi = jax.lax.Precision.HIGHEST

    def step(s, at):
        q_t, k_t, v_t, a_t, b_t = at
        s = s * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", s, k_t, precision=hi)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - read),
                           precision=hi)
        if control == "state_bf16":  # what a bfloat16 state would carry on
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=hi)

    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, out = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                          (q, k, v, alpha, beta))
    return out


def gated_delta_net(params, p, cfg, x, precision, control=None, alphas=None):
    """One linear-attention sublayer over the whole sequence ``x`` (T, H);
    its decays (T, H) appended to ``alphas`` where a list is given."""
    t = x.shape[0]
    h, dk, dv, _ = gdn_sizes(cfg)
    z = jnp.concatenate([linear(x, params[p + f"gdn.{n}.w"], precision)
                         for n in "qkv"], axis=-1)
    c = short_conv(z, params[p + "gdn.conv.w"], control)
    q = l2_normalise(c[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5
    k = l2_normalise(c[:, h * dk:2 * h * dk].reshape(t, h, dk))
    v = c[:, 2 * h * dk:].reshape(t, h, dv)
    alpha, beta = gates(params, p, cfg, x, control)
    if alphas is not None:
        alphas.append(alpha)
    o = delta_rule(q, k, v, alpha, beta, control)
    gate = jax.nn.silu(linear(x, params[p + "gdn.g.w"], precision))
    o = rms_norm(o, params[p + "gdn.o_norm.gamma"], cfg["rms_norm_eps"]) \
        * gate.reshape(t, h, dv)
    return linear(o.reshape(t, h * dv), params[p + "gdn.o.w"], precision)


# -- the full layer ---------------------------------------------------------
def attention(params, p, cfg, x, precision, keys=None, query_block=512):
    """Causal softmax attention over the whole sequence ``x`` (T, H), no
    positions: queries in blocks, a head at a time. ``keys`` (T,) bool hides
    positions from every query (None: none hidden)."""
    t, ch = x.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if heads != kv:
        raise ValueError("grouped key-value heads: not this reference's layer")
    eps = cfg["rms_norm_eps"]
    q = rms_norm(linear(x, params[p + "attn.q.w"], precision),
                 params[p + "attn.q_norm.gamma"], eps).reshape(t, heads, ch)
    k = rms_norm(linear(x, params[p + "attn.k.w"], precision),
                 params[p + "attn.k_norm.gamma"], eps).reshape(t, kv, ch)
    v = linear(x, params[p + "attn.v.w"], precision).reshape(t, kv, ch)
    qb = math.gcd(t, query_block)
    shown = jnp.ones((t,), bool) if keys is None else keys

    def head_of(args):
        qs, ks, vs = args                                   # (T, ch) each

        def queries_of(start):
            s = einsum("qd,kd->qk",
                       jax.lax.dynamic_slice_in_dim(qs, start, qb, 0), ks,
                       precision) * ch ** -0.5
            seen = (jnp.arange(t)[None, :]
                    <= (start + jnp.arange(qb))[:, None]) & shown[None, :]
            att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return einsum("qk,kd->qd", att, vs, precision)

        return jax.lax.map(queries_of, jnp.arange(0, t, qb)).reshape(t, ch)

    ctx = jax.lax.map(head_of, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                v.transpose(1, 0, 2)))      # (heads, T, ch)
    return linear(ctx.transpose(1, 0, 2).reshape(t, heads * ch),
                  params[p + "attn.o.w"], precision)


def hidden(params, cfg, tokens, precision="float32", keys=None, alphas=None):
    """Final hidden states (T, H), normed, of one sequence ``tokens`` (T,);
    every linear layer's decays appended to ``alphas`` where given."""
    precision, control = split_precision(precision)
    eps = cfg["rms_norm_eps"]
    x = params["embed.word"][tokens]
    for i, kind in enumerate(layer_types(cfg)):
        p = f"layer{i}."
        y = gated_delta_net(params, p, cfg, x, precision, control, alphas) \
            if kind == "linear_attention" \
            else attention(params, p, cfg, x, precision, keys)
        x = x + rms_norm(y, params[p + "mixer_norm.gamma"], eps)
        y = swiglu(x, params[p + "ffn.gate.w"], params[p + "ffn.up.w"],
                   params[p + "ffn.down.w"], precision)
        x = x + rms_norm(y, params[p + "ffn_norm.gamma"], eps)
    return rms_norm(x, params["norm.gamma"], eps)


_SHAPE_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "n_layer",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim")


def config_key(cfg):
    """What the forward reads of the configuration, hashable (a static
    argument of the jitted forward)."""
    return (tuple((k, cfg[k]) for k in _SHAPE_KEYS)
            + (("layer_types", layer_types(cfg)),
               ("decay_init", tuple(sorted(cfg["decay_init"].items())))))


def _cfg_of(cfg_key):
    cfg = dict(cfg_key)
    cfg["decay_init"] = dict(cfg["decay_init"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _logits(params, tokens, rows, keys, cfg_key, precision):
    with jax.default_matmul_precision("highest"):
        x = hidden(params, _cfg_of(cfg_key), tokens, precision, keys)
        return einsum("th,vh->tv", x[rows], params["head.w"],
                      split_precision(precision)[0])


def next_token_logits(params, cfg, tokens, first, count, precision="float32",
                      pad_to=128, out_pad=32):
    """Logits (count, V), on the host, that follow positions ``first ..
    first+count-1`` of ``tokens``; the sequence padded to a multiple of
    ``pad_to`` (a causal model is blind to what follows), so few shapes
    compile. Under ``pad_writes_state`` the engine's pad tokens stand between
    the prompt (``tokens[:first + 1]``) and what follows it, up to the
    prompt's prefill bucket."""
    tokens = list(tokens)
    n_out = -(-count // out_pad) * out_pad
    rows = first + np.arange(n_out)
    pads = 0
    if split_precision(precision)[1] == "pad_writes_state":
        prompt = first + 1
        pads = min(b for b in cfg["engine"]["prefill_buckets"]
                   if b >= prompt) - prompt
        tokens = tokens[:prompt] + [cfg["engine"].get("pad_id", 0)] * pads \
            + tokens[prompt:]
        rows = np.where(rows > first, rows + pads, rows)
    n = len(tokens)
    length = -(-max(n, first + pads + n_out) // pad_to) * pad_to
    buf = np.zeros((length,), np.int32)
    buf[:n] = tokens
    keys = np.ones((length,), bool)
    keys[first + 1:first + 1 + pads] = False
    return np.asarray(_logits(params, buf, rows, keys, config_key(cfg),
                              precision))[:count]


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _alphas(params, tokens, cfg_key):
    alphas = []
    with jax.default_matmul_precision("highest"):
        hidden(params, _cfg_of(cfg_key), tokens, alphas=alphas)
    return jnp.stack(alphas)


def decay_quantiles(params, cfg, tokens, qs=(1, 10, 50, 90, 99)):
    """{q: alpha's q-th percentile} over the positions of ``tokens`` and the
    heads of every linear layer, and the same a layer: how long the seeded
    weights' state lives (1 / (1 - alpha) positions)."""
    alphas = np.asarray(_alphas(params, np.asarray(tokens, np.int32),
                                config_key(cfg)))
    return {"all": {q: float(np.percentile(alphas, q)) for q in qs},
            "by_layer": [{q: float(np.percentile(a, q)) for q in qs}
                         for a in alphas]}


# -- bytes ------------------------------------------------------------------
def gdn_state_bytes(cfg, rows, state_bytes=4):
    """Bytes the recurrent state of ``rows`` rows moves in one decode step:
    every linear layer's heads x key width x value width matrix read once
    and written once (the convolution's tail, 8% of it, is not the decode
    kernel's and is left out)."""
    h, dk, dv, _ = gdn_sizes(cfg)
    linear_layers = sum(k == "linear_attention" for k in layer_types(cfg))
    return rows * linear_layers * 2 * h * dk * dv * state_bytes


def kv_read_bytes(cfg, positions_read, cache_bytes=2):
    """Bytes the keys and values of ``positions_read`` positions hold, over
    the full layers."""
    full = sum(k == "full_attention" for k in layer_types(cfg))
    return positions_read * full * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * cache_bytes


def decode_step_bytes(cfg, held_positions, rows=None, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to move: every weight held here once (the
    word embedding is read by row, so not counted; the head is), the keys
    and values of the positions the rows hold (every full layer reads them
    all) and the recurrent state of ``rows`` rows, read and written.
    ``rows`` defaults to the engine's slots: the accepted reader
    ``decode_hbm_roofline_pct.serve`` passes held positions alone, and above
    the knee the slots are full."""
    rows = cfg["engine"]["batch_size"] if rows is None else rows
    count = sum(math.prod(shape) for name, shape, _ in param_specs(cfg)
                if name != "embed.word")
    return count * weight_bytes + kv_read_bytes(cfg, held_positions,
                                                cache_bytes) \
        + gdn_state_bytes(cfg, rows)
