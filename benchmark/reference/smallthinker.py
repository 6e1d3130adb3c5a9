"""SmallThinker-21BA3B-Instruct (``PowerInfer/SmallThinker-21BA3B-Instruct``
``config.json``; arXiv:2507.20984): pre-norm decoder blocks with RMSNorm, no
biases, an untied head. With ``x`` the block's input ::

    h  = RMSNorm(x; g1)
    r  = h Wr^T                                   # router logits, float32
    q, k, v = h Wq, h Wk, h Wv                    # 28 heads over 4 of 128
    q, k = rotary(q, k)   where rope_layout[l]            (theta 1.5e6)
    a_t = softmax_s(q_t . k_s / sqrt(128)) v_s over s <= t, and
          s > t - 4096   where sliding_window_layout[l];
          query head i reads key-value head i // 7
    x1 = x + concat(a) Wo
    u  = RMSNorm(x1; g2)
    ids = top-6 of r;  p = softmax(r)[ids] normalised
    x2 = x1 + sum_i p_i Wdown[ids_i](relu(Wgate[ids_i] u) * (Wup[ids_i] u))

A full forward over one whole sequence in float32: no cache, no paging, no
kernel, no batching; queries in blocks and a key-value head at a time so
that 10,240 positions fit; the experts as a plain loop over the ids this
chip holds (``held_experts``; ``None`` is the uncut layer), each applied
to every token and weighted by the token's share of it.

``precision`` is ``float32`` (the reference), ``bfloat16``/``fp8`` (the same
mathematics with rounded operands; the router is never rounded, so a lower
precision routes as the reference does), or a control of the MATHEMATICS in
float32: ``no_window`` (a window layer attends every position),
``rope_everywhere`` (positions on the layers that have none),
``rope_nowhere`` (none on the layers that have them), ``window_minus_1``
(a window layer sees one position fewer) and ``router_reads_u`` (the router
reads the experts' input, after attention).

What the configuration's file assumes is in its ``assumed``: the rotary
layout, the window's count, ReLU as the gate's activation, the router's
input, no attention bias.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .deepseek_v2 import linear, rms_norm, rotate
from .precision import einsum

MATH_CONTROLS = ("no_window", "rope_everywhere", "rope_nowhere",
                 "window_minus_1", "router_reads_u")


def split_precision(precision):
    """(the products' precision, the control of the mathematics or None)."""
    if precision in MATH_CONTROLS:
        return "float32", precision
    return precision, None


# -- shapes -----------------------------------------------------------------
def held_ids(cfg):
    """The routed experts this chip holds (all of them where not stated)."""
    held = cfg.get("held_experts")
    return tuple(range(cfg["moe_num_primary_experts"])) if held is None \
        else tuple(held)


def layer_specs(cfg, i):
    """(name, shape, init) of layer ``i``'s leaves; weights stored (out, in)."""
    h, ch = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, w = len(held_ids(cfg)), cfg["moe_ffn_hidden_size"]
    std = ("normal", cfg["initializer_range"])
    p = f"layer{i}."
    return [(p + "attn_norm.gamma", (h,), "ones"),
            (p + "q.w", (heads * ch, h), std), (p + "k.w", (kv * ch, h), std),
            (p + "v.w", (kv * ch, h), std), (p + "o.w", (h, heads * ch), std),
            (p + "ffn_norm.gamma", (h,), "ones"),
            (p + "router.w", (cfg["moe_num_primary_experts"], h), std),
            (p + "experts.gate.w", (e, w, h), std),
            (p + "experts.up.w", (e, w, h), std),
            (p + "experts.down.w", (e, h, w), std)]


def param_specs(cfg):
    h, v = cfg["hidden_size"], cfg["n_vocab"]
    std = ("normal", cfg["initializer_range"])
    out = [("embed.word", (v, h), std)]
    for i in range(cfg["n_layer"]):
        out += layer_specs(cfg, i)
    return out + [("norm.gamma", (h,), "ones"), ("head.w", (v, h), std)]


# -- attention --------------------------------------------------------------
def rotary_tables(theta, dim, positions):
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def attention(params, p, cfg, x, window, rope, precision, query_block=512):
    """One attention sublayer over the whole sequence ``x`` (T, H), normed:
    ``window`` positions a query sees (None: all before it), ``rope`` whether
    queries and keys are rotated."""
    t, ch = x.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group = heads // kv
    q = linear(x, params[p + "q.w"], precision).reshape(t, heads, ch)
    k = linear(x, params[p + "k.w"], precision).reshape(t, kv, ch)
    v = linear(x, params[p + "v.w"], precision).reshape(t, kv, ch)
    if rope:
        cos, sin = rotary_tables(float(cfg["rope_theta"]), ch, jnp.arange(t))
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    qb = math.gcd(t, query_block)
    # a block of queries reads the ``span`` keys that end with its last
    span = t if window is None else min(t, qb + -(-window // qb) * qb)
    scale = ch ** -0.5

    def head_of(args):
        qs, ks, vs = args             # (group, T, ch), (T, ch), (T, ch)

        def queries_of(start):
            first = jnp.clip(start + qb - span, 0, t - span)
            cut = lambda z: jax.lax.dynamic_slice_in_dim(z, first, span, 0)  # noqa: E731
            s = einsum("gqd,kd->gqk",
                       jax.lax.dynamic_slice_in_dim(qs, start, qb, 1), cut(ks),
                       precision) * scale
            tq = (start + jnp.arange(qb))[:, None]
            at = (first + jnp.arange(span))[None, :]
            seen = at <= tq
            if window is not None:
                seen &= at > tq - window
            att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return einsum("gqk,kd->gqd", att, cut(vs), precision)

        out = jax.lax.map(queries_of, jnp.arange(0, t, qb))  # (T/qb, g, qb, ch)
        return out.transpose(1, 0, 2, 3).reshape(group, t, ch)

    ctx = jax.lax.map(head_of, (
        q.transpose(1, 0, 2).reshape(kv, group, t, ch),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))          # (kv, g, T, ch)
    ctx = ctx.reshape(heads, t, ch).transpose(1, 0, 2).reshape(t, heads * ch)
    return linear(ctx, params[p + "o.w"], precision)


# -- experts ----------------------------------------------------------------
def route(cfg, router_in, router_w):
    """(weights (T, k), expert ids (T, k)): softmax over all routed experts
    in float32, the k largest, normalised over the chosen
    (``norm_topk_prob``). Never rounded."""
    logits = einsum("ti,ei->te", router_in, router_w, "float32")
    probs = jax.nn.softmax(logits, axis=-1) \
        if cfg["moe_primary_router_apply_softmax"] else logits
    w, ids = jax.lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w, ids


def reglu(x, gate, up, down, precision):
    y = jax.nn.relu(linear(x, gate, precision)) * linear(x, up, precision)
    return linear(y, down, precision)


def routed_part(params, p, cfg, u, router_in, precision):
    """What the held experts add: a plain loop over their ids, every held
    expert applied to every token and weighted by the token's share of it
    (0 for most)."""
    w, ids = route(cfg, router_in, params[p + "router.w"])

    def add(out, expert):
        e, gate, up, down = expert
        share = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)       # (T,)
        return out + share[:, None] * reglu(u, gate, up, down, precision), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(u), (
        jnp.asarray(held_ids(cfg), jnp.int32), params[p + "experts.gate.w"],
        params[p + "experts.up.w"], params[p + "experts.down.w"]))
    return out


def layer_kind(cfg, i, control=None):
    """(window or None, whether rotated) of layer ``i`` under ``control``."""
    window = cfg["sliding_window_size"] if cfg["sliding_window_layout"][i] \
        else None
    if window is not None and control == "no_window":
        window = None
    if window is not None and control == "window_minus_1":
        window -= 1
    rope = bool(cfg["rope_layout"][i])
    rope = {"rope_everywhere": True, "rope_nowhere": False}.get(control, rope)
    return window, rope


def hidden(params, cfg, tokens, precision="float32"):
    """Final hidden states (T, H), normed, of one sequence ``tokens`` (T,)."""
    precision, control = split_precision(precision)
    eps = cfg["rms_norm_eps"]
    x = params["embed.word"][tokens]
    for i in range(cfg["n_layer"]):
        p = f"layer{i}."
        window, rope = layer_kind(cfg, i, control)
        h = rms_norm(x, params[p + "attn_norm.gamma"], eps)
        x = x + attention(params, p, cfg, h, window, rope, precision)
        u = rms_norm(x, params[p + "ffn_norm.gamma"], eps)
        x = x + routed_part(params, p, cfg, u,
                            u if control == "router_reads_u" else h, precision)
    return rms_norm(x, params["norm.gamma"], eps)


_SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "rope_theta", "sliding_window_size", "rms_norm_eps", "n_layer",
    "moe_ffn_hidden_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob")


def config_key(cfg):
    """What the forward reads of the configuration, hashable (a static
    argument of the jitted forward)."""
    n = cfg["n_layer"]
    return (tuple((k, cfg[k]) for k in _SHAPE_KEYS)
            + (("rope_layout", tuple(cfg["rope_layout"][:n])),
               ("sliding_window_layout",
                tuple(cfg["sliding_window_layout"][:n])),
               ("held_experts", held_ids(cfg))))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "n_out"))
def _logits(params, tokens, first, cfg_key, precision, n_out):
    x = hidden(params, dict(cfg_key), tokens, precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return einsum("th,vh->tv", x, params["head.w"],
                  split_precision(precision)[0])


def next_token_logits(params, cfg, tokens, first, count, precision="float32",
                      pad_to=128, out_pad=32):
    """Logits (count, V), on the host, that follow positions ``first ..
    first+count-1`` of ``tokens``; the sequence padded to a multiple of
    ``pad_to`` (a causal model is blind to what follows), so few shapes
    compile."""
    n = len(tokens)
    n_out = -(-count // out_pad) * out_pad
    length = -(-max(n, first + n_out) // pad_to) * pad_to
    buf = np.zeros((length,), np.int32)
    buf[:n] = tokens
    return np.asarray(_logits(params, buf, np.int32(first), config_key(cfg),
                              precision, n_out))[:count]


# -- bytes ------------------------------------------------------------------
def gqa_read_bytes(cfg, positions_read, cache_bytes=2):
    """Bytes the keys and values of ``positions_read`` positions hold: a
    position a layer is one key and one value of ``num_key_value_heads x
    head_dim`` values (the positions the layers' softmaxes read, summed
    over rows and layers)."""
    return positions_read * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * cache_bytes


def decode_step_bytes(cfg, held_positions, rows=None, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to read: every weight held here once (the
    word embedding is read by row, so not counted; the head is), and the
    keys and values of the positions each layer READS: all a row holds in a
    full layer, the last ``sliding_window_size`` of them in a window layer.
    ``held_positions`` is the rows' total; they are taken to hold equal
    shares of it."""
    rows = cfg["engine"]["batch_size"] if rows is None else rows
    count = sum(math.prod(shape) for name, shape, _ in param_specs(cfg)
                if name != "embed.word")
    windows = sum(cfg["sliding_window_layout"][:cfg["n_layer"]])
    read = (cfg["n_layer"] - windows) * held_positions + windows * rows * min(
        held_positions / rows, cfg["sliding_window_size"])
    return count * weight_bytes + gqa_read_bytes(cfg, read, cache_bytes)
