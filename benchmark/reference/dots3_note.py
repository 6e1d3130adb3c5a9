"""dots3-note-prev (``dots-studio/dots3-note-prev`` ``config.json``; 288B-A17B):
pre-norm decoder blocks with RMSNorm, no biases, an untied head. Attention is
multi-head latent attention with a headwise output gate (arXiv:2505.06708)
in two kinds of layer (``layer_types``). A *full* layer attends the 2,048
positions its indexer selects (DeepSeek-V3.2-Exp's sparse attention: a small
multi-head scorer with one shared key a position); a *window* layer attends
the last 513 positions through a latent of its own sizes (``swa_*``). The
first layer's feed-forward is a dense SwiGLU, the others' an expert layer:
sigmoid scores over 256 experts, the 8 largest of score plus a learned bias
(``noaux_tc``), weights the unbiased scores normalised, one shared expert.

A full forward over one whole sequence in float32: decompressed attention
only, no cache, no paging, heads and queries in blocks so that 20,480
positions fit, the experts as a plain loop over the ids this chip holds
(``held_experts``; ``None`` is the uncut layer, as in ``deepseek_v2``).

``precision`` is ``float32`` (the reference), ``bfloat16``/``fp8`` (the same
mathematics with rounded operands; the indexer and the router are never
rounded, so a lower precision selects and routes as the reference does),
``bfloat16_index`` (``bfloat16``, and the indexer's queries, keys and head
weights rounded to bfloat16 as well, which is how the program holds them:
what the near-ties at rank ``index_topk`` cost when they fall the other way),
or a control of the MATHEMATICS in float32: ``no_selection`` (a full layer
attends every position) and ``window_512`` (a window layer sees one
position fewer).

What the configuration's file assumes is in its ``assumed``: the latents'
rescale, the window's count, the rotary layout, the indexer's scales.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .deepseek_v2 import (held_ids, linear, rms_norm, rotate, shared_part,
                          swiglu)
from .precision import einsum, layer_norm

MATH_CONTROLS = ("no_selection", "window_512")
INDEX_NORM_EPS = 1e-6


def split_precision(precision):
    """(the products' precision, the control of the mathematics or None,
    whether the indexer's operands are rounded to bfloat16)."""
    if precision in MATH_CONTROLS:
        return "float32", precision, False
    if precision == "bfloat16_index":
        return "bfloat16", None, True
    return precision, None, False


def _held_in_bfloat16(x, rounded):
    """``x`` as a program that keeps it in bfloat16 holds it
    (``reduce_precision``: the TPU's compiler drops a pair of converts whose
    result is carried into a loop)."""
    return jax.lax.reduce_precision(x, 8, 7) if rounded else x


# -- shapes -----------------------------------------------------------------
def layer_kind(cfg, i):
    return "window" if cfg["layer_types"][i] == "sliding_attention" else "full"


def attention_sizes(cfg, kind):
    """heads, nope, rope, value dim, query rank, latent rank, theta, gate."""
    p = "swa_" if kind == "window" else ""
    return dict(
        heads=cfg[p + "num_attention_heads"], nope=cfg[p + "qk_nope_head_dim"],
        rope=cfg[p + "qk_rope_head_dim"], vd=cfg[p + "v_head_dim"],
        ql=cfg[p + "q_lora_rank"], kl=cfg[p + "kv_lora_rank"],
        theta=float(cfg[p + "rope_theta"]),
        gate=cfg[p + "attention_gate_type"] == "headwise")


def layer_specs(cfg, i):
    """(name, shape, init) of layer ``i``'s leaves; weights stored (out, in)."""
    h, kind = cfg["hidden_size"], layer_kind(cfg, i)
    a = attention_sizes(cfg, kind)
    std = ("normal", cfg["initializer_range"])
    p = f"layer{i}."
    out = [(p + "attn_norm.gamma", (h,), "ones"),
           (p + "q_a.w", (a["ql"], h), std),
           (p + "q_norm.gamma", (a["ql"],), "ones"),
           (p + "q_b.w", (a["heads"] * (a["nope"] + a["rope"]), a["ql"]), std),
           (p + "kv_a.w", (a["kl"] + a["rope"], h), std),
           (p + "kv_norm.gamma", (a["kl"],), "ones"),
           (p + "kv_b.w", (a["heads"] * (a["nope"] + a["vd"]), a["kl"]), std),
           (p + "o.w", (h, a["heads"] * a["vd"]), std)]
    if a["gate"]:
        out.append((p + "attn_gate.w", (a["heads"], h), std))
    if kind == "full":
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        out += [(p + "index.q_b.w", (ih * idim, a["ql"]), std),
                (p + "index.k.w", (idim, h), std),
                (p + "index.k_norm.gamma", (idim,), "ones"),
                (p + "index.k_norm.beta", (idim,), "zeros"),
                (p + "index.weights.w", (ih, h), std)]
    out.append((p + "ffn_norm.gamma", (h,), "ones"))
    if i < cfg["first_k_dense_replace"]:
        w = cfg["intermediate_size"]
        return out + [(p + "gate.w", (w, h), std), (p + "up.w", (w, h), std),
                      (p + "down.w", (h, w), std)]
    e, w = len(held_ids(cfg)), cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * w
    return out + [(p + "router.w", (cfg["n_routed_experts"], h), std),
                  (p + "router.bias", (cfg["n_routed_experts"],), std),
                  (p + "experts.gate.w", (e, w, h), std),
                  (p + "experts.up.w", (e, w, h), std),
                  (p + "experts.down.w", (e, h, w), std),
                  (p + "shared.gate.w", (s, h), std),
                  (p + "shared.up.w", (s, h), std),
                  (p + "shared.down.w", (h, s), std)]


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


def _block(t, most):
    return math.gcd(t, most)


def index_keys(params, p, cfg, x, cos, sin, rounded=False):
    """The indexer's key of every position, (T, index_head_dim): LayerNorm of
    a projection of the sublayer's input, its first ``rope`` dims rotated."""
    rope = cfg["qk_rope_head_dim"]
    held = "bfloat16" if rounded else "float32"
    k = layer_norm(linear(x, params[p + "index.k.w"], held),
                   params[p + "index.k_norm.gamma"],
                   params[p + "index.k_norm.beta"], INDEX_NORM_EPS)
    return _held_in_bfloat16(jnp.concatenate(
        [rotate(k[:, :rope], cos, sin), k[:, rope:]], axis=-1), rounded)


def index_scores(params, p, cfg, x, c_q, k, cos, sin, q_rows, rounded=False):
    """``I[t, s]`` (len(q_rows), T) in float32, whatever the precision of the
    rest: ``sum_j w[t,j] relu(q[t,j] . k[s]) * index_head_dim ** -0.5``, the
    queries from the normed query latent ``c_q``, the head weights ``w`` from
    the sublayer's input times ``index_n_heads ** -0.5``. ``rounded``: the
    projections' operands, the queries and the head weights in bfloat16
    (the sums stay float32)."""
    ih, idim, rope = (cfg["index_n_heads"], cfg["index_head_dim"],
                      cfg["qk_rope_head_dim"])
    held = "bfloat16" if rounded else "float32"
    q = linear(c_q[q_rows], params[p + "index.q_b.w"], held).reshape(
        -1, ih, idim)
    q = _held_in_bfloat16(jnp.concatenate(
        [rotate(q[..., :rope], cos[q_rows], sin[q_rows]), q[..., rope:]],
        axis=-1), rounded)
    w = _held_in_bfloat16(linear(x[q_rows], params[p + "index.weights.w"],
                                 held), rounded) * ih ** -0.5
    dots = jax.nn.relu(einsum("tjd,sd->tjs", q, k, "float32"))
    return einsum("tjs,tj->ts", dots, w, "float32") * idim ** -0.5


def selection_mask(params, p, cfg, x, c_q, cos, sin, rounded=False):
    """(T, T) bool: ``s <= t`` and ``I[t, s]`` among the ``index_topk``
    largest of row ``t`` (all of them while the row is shorter; of equal
    scores the earlier position first), queries in blocks."""
    t, top = x.shape[0], cfg["index_topk"]
    qb = _block(t, 128)
    cols = jnp.arange(t)
    k = index_keys(params, p, cfg, x, cos, sin, rounded)

    def rows_of(start):
        rows = start + jnp.arange(qb)
        causal = cols[None, :] <= rows[:, None]
        if top >= t:
            return causal
        scores = jnp.where(causal, index_scores(params, p, cfg, x, c_q, k, cos,
                                                sin, rows, rounded), -jnp.inf)
        # the ``top`` largest, equal scores in the order of their positions
        kth = jax.lax.top_k(scores, top)[0][:, -1:]
        more, same = scores > kth, scores == kth
        room = top - more.sum(axis=-1, keepdims=True)
        return causal & (more | (same & (jnp.cumsum(same, axis=-1) <= room)))

    return jax.lax.map(rows_of, jnp.arange(0, t, qb)).reshape(t, t)


def latent_attention(params, p, cfg, kind, x, precision, math_control=None,
                     index_rounded=False, head_block=8):
    """One attention sublayer over the whole sequence ``x`` (T, H), normed."""
    a = attention_sizes(cfg, kind)
    t, heads, nope, rope, vd, kl = (x.shape[0], a["heads"], a["nope"],
                                    a["rope"], a["vd"], a["kl"])
    eps, h = cfg["rms_norm_eps"], cfg["hidden_size"]
    cos, sin = rotary_tables(a["theta"], rope, jnp.arange(t))
    c_q = rms_norm(linear(x, params[p + "q_a.w"], precision),
                   params[p + "q_norm.gamma"], eps)
    kv = linear(x, params[p + "kv_a.w"], precision)
    c_kv = rms_norm(kv[:, :kl], params[p + "kv_norm.gamma"], eps)
    q_scale = kv_scale = 1.0
    if cfg["apply_mla_qkv_lora_rescale"]:
        q_scale, kv_scale = math.sqrt(h / a["ql"]), math.sqrt(h / kl)
    c_kv = c_kv * kv_scale
    k_rope = rotate(kv[:, kl:], cos, sin)  # one for all heads
    q_b = params[p + "q_b.w"].reshape(heads, nope + rope, a["ql"])
    kv_b = params[p + "kv_b.w"].reshape(heads, nope + vd, kl)
    scale = (nope + rope) ** -0.5
    rows = jnp.arange(t)
    if kind == "full":
        if math_control == "no_selection":
            mask = rows[None, :] <= rows[:, None]
        else:
            mask = selection_mask(params, p, cfg, x, c_q, cos, sin,
                                  index_rounded)
        span, qb = t, _block(t, 512)
    else:
        window = cfg["sliding_window_size"] - (math_control == "window_512")
        # a block of queries reads the ``span`` keys that end with its last
        qb = _block(t, 512)
        span = min(t, qb + -(-window // qb) * qb)
        mask = None
    block = min(head_block, heads)

    def heads_of(args):
        # a block of heads at a time: their queries, keys and values are made
        # here, so nothing head-sized exists for all heads at once
        w_q, w = args     # (block, nope + rope, ql), (block, nope + vd, kl)
        q = einsum("tl,hdl->htd", c_q, w_q, precision) * q_scale
        qn, qr = q[..., :nope], rotate(q[..., nope:].transpose(1, 0, 2), cos,
                                       sin).transpose(1, 0, 2)
        k_v = einsum("tl,hdl->htd", c_kv, w, precision)

        def queries_of(start):
            first = jnp.clip(start + qb - span, 0, t - span)
            at = lambda z, axis: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                z, first, span, axis)
            qs = lambda z: jax.lax.dynamic_slice_in_dim(z, start, qb, 1)  # noqa: E731
            s = (einsum("hqd,hkd->hqk", qs(qn), at(k_v[..., :nope], 1),
                        precision)
                 + einsum("hqd,kd->hqk", qs(qr), at(k_rope, 0), precision)
                 ) * scale
            if mask is None:
                tq = (start + jnp.arange(qb))[:, None]
                ks = (first + jnp.arange(span))[None, :]
                seen = (ks <= tq) & (ks > tq - window)
            else:
                seen = jax.lax.dynamic_slice_in_dim(mask, start, qb, 0)
            att = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return einsum("hqk,hkd->hqd", att, at(k_v[..., nope:], 1),
                          precision)

        out = jax.lax.map(queries_of, jnp.arange(0, t, qb))  # (T/qb, block, qb, vd)
        return out.transpose(1, 0, 2, 3).reshape(block, t, vd)

    ctx = jax.lax.map(heads_of, (
        q_b.reshape(heads // block, block, nope + rope, a["ql"]),
        kv_b.reshape(heads // block, block, nope + vd, kl)))
    ctx = ctx.reshape(heads, t, vd).transpose(1, 0, 2)        # (T, heads, vd)
    if a["gate"]:
        gate = jax.nn.sigmoid(linear(x, params[p + "attn_gate.w"], precision))
        ctx = ctx * gate[:, :, None]
    return linear(ctx.reshape(t, heads * vd), params[p + "o.w"], precision)


# -- experts ----------------------------------------------------------------
def route(cfg, h, router_w, router_bias):
    """(weights (T, k), expert ids (T, k)) of ``noaux_tc`` without groups:
    sigmoid scores over all routed experts in float32; the k largest of
    score + bias are the token's experts; their weights are the UNBIASED
    scores, normalised over the chosen (``norm_topk_prob``), times
    ``routed_scaling_factor``. Never rounded."""
    s = jax.nn.sigmoid(einsum("ti,ei->te", h, router_w, "float32"))
    _, ids = jax.lax.top_k(s + router_bias[None, :].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], ids


def routed_part(params, p, cfg, h, precision):
    """What the held experts add: a plain loop over their ids."""
    w, ids = route(cfg, h, params[p + "router.w"], params[p + "router.bias"])
    out = jnp.zeros_like(h)
    for slot, expert in enumerate(held_ids(cfg)):
        share = jnp.sum(jnp.where(ids == expert, w, 0.0), axis=-1)  # (T,)
        y = swiglu(h, params[p + "experts.gate.w"][slot],
                   params[p + "experts.up.w"][slot],
                   params[p + "experts.down.w"][slot], precision)
        out = out + share[:, None] * y
    return out


def in_token_blocks(f, h, most=4096):
    """``f(h)`` with ``h`` (T, H) walked in blocks of tokens: a feed-forward
    sublayer's 13,824-wide float32 activations of 20,480 tokens at once are
    gigabytes."""
    t = h.shape[0]
    block = _block(t, most)
    if block == t:
        return f(h)
    return jax.lax.map(f, h.reshape(t // block, block, -1)).reshape(t, -1)


def hidden(params, cfg, tokens, precision="float32"):
    """Final hidden states (T, H), normed, of one sequence ``tokens`` (T,)."""
    precision, control, index_rounded = split_precision(precision)
    eps = cfg["rms_norm_eps"]
    x = params["embed.word"][tokens]
    for i in range(cfg["n_layer"]):
        p = f"layer{i}."
        x = x + latent_attention(
            params, p, cfg, layer_kind(cfg, i),
            rms_norm(x, params[p + "attn_norm.gamma"], eps), precision, control,
            index_rounded)
        h = rms_norm(x, params[p + "ffn_norm.gamma"], eps)
        if i < cfg["first_k_dense_replace"]:
            ffn = lambda z: swiglu(z, params[p + "gate.w"],  # noqa: E731
                                   params[p + "up.w"], params[p + "down.w"],
                                   precision)
        else:
            ffn = lambda z: routed_part(params, p, cfg, z, precision) \
                + shared_part(params, p, z, precision)  # noqa: E731
        x = x + in_token_blocks(ffn, h)
    return rms_norm(x, params["norm.gamma"], eps)


_SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
    "rope_theta", "attention_gate_type", "swa_num_attention_heads",
    "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
    "swa_q_lora_rank", "swa_kv_lora_rank", "swa_rope_theta",
    "swa_attention_gate_type", "sliding_window_size", "index_n_heads",
    "index_head_dim", "index_topk", "apply_mla_qkv_lora_rescale",
    "rms_norm_eps", "n_layer", "first_k_dense_replace", "intermediate_size",
    "moe_intermediate_size", "n_shared_experts", "n_routed_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def config_key(cfg):
    """What the forward reads of the configuration, hashable (a static
    argument of the jitted forward)."""
    return (tuple((k, cfg[k]) for k in _SHAPE_KEYS)
            + (("layer_types", tuple(cfg["layer_types"][:cfg["n_layer"]])),
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
def _kinds(cfg):
    return [layer_kind(cfg, i) for i in range(cfg["n_layer"])]


def _rows(cfg, rows):
    return cfg["engine"]["batch_size"] if rows is None else rows


def sparse_read_bytes(cfg, held_positions, rows=None, cache_bytes=2):
    """Bytes the selected latents of one decode step hold: in every full
    layer each row reads ``min(held, index_topk)`` cached vectors of
    ``kv_lora_rank + qk_rope_head_dim`` values. ``held_positions`` is the
    rows' total; they are taken to hold equal shares of it."""
    rows = _rows(cfg, rows)
    width = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes
    read = min(held_positions / rows, cfg["index_topk"]) * rows
    return _kinds(cfg).count("full") * read * width


def index_score_bytes(cfg, held_positions, cache_bytes=2):
    """Bytes the indexer's scoring of one decode step has to read: in every
    full layer the key (``index_head_dim`` values) of every held position."""
    return _kinds(cfg).count("full") * held_positions \
        * cfg["index_head_dim"] * cache_bytes


def decode_step_bytes(cfg, held_positions, rows=None, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to read: every weight held here once (the
    word embedding is read by row, so not counted; the head is); in a full
    layer the indexer's key of every held position (``index_head_dim``
    values) and the selected latents; in a window layer the latents of the
    last ``sliding_window_size`` positions (``swa_kv_lora_rank +
    swa_qk_rope_head_dim`` values)."""
    rows = _rows(cfg, rows)
    count = sum(math.prod(shape) for name, shape, _ in param_specs(cfg)
                if name != "embed.word")
    index = index_score_bytes(cfg, held_positions, cache_bytes)
    window = _kinds(cfg).count("window") * rows * min(
        held_positions / rows, cfg["sliding_window_size"]) * (
            cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]) * cache_bytes
    return count * weight_bytes + index + window \
        + sparse_read_bytes(cfg, held_positions, rows, cache_bytes)
