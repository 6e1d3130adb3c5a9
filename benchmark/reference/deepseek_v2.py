"""DeepSeek-V2 (arXiv:2405.04434; ``deepseek-ai/DeepSeek-V2``
``modeling_deepseek.py``): pre-norm decoder blocks with RMSNorm, multi-head
latent attention with decoupled rotary keys under YaRN, a leading dense
SwiGLU layer, then expert layers (group-limited top-6 of 160 routed experts
plus two shared experts), an untied output head. A full forward over one
whole sequence in float32: decompressed attention only, no cache, no
paging, the experts as a plain loop over the ids this chip holds.

The chip's share (the ``model-configs`` guide, section 4): the router keeps
its published 160 outputs, the reference is given the same ``held_experts``
as the program, and what the absent experts would add is left out; that
partial result goes on to the next layer. ``held_experts`` of ``None`` is
the uncut layer (every expert held): the share test adds the shares up to it.

Departures from the published code: the rotary dimensions of a head are
laid out as two halves (``rotate_half``) where the published code first
permutes interleaved pairs into that layout (a fixed permutation of columns
of random weights); weights are normal(0, ``initializer_range``) from the
seed; the experts' weights are stacked, one leaf a projection.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .precision import einsum


# -- shapes -----------------------------------------------------------------
def held_ids(cfg):
    """The routed experts this chip holds (all of them where not stated)."""
    held = cfg.get("held_experts")
    return tuple(range(cfg["n_routed_experts"])) if held is None else tuple(held)


def layer_specs(cfg, i):
    """(name, shape, init) of layer ``i``'s leaves; weights stored (out, in)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    std = ("normal", cfg["initializer_range"])
    p = f"layer{i}."
    out = [(p + "attn_norm.gamma", (h,), "ones"),
           (p + "q_a.w", (ql, h), std), (p + "q_norm.gamma", (ql,), "ones"),
           (p + "q_b.w", (heads * (nope + rope), ql), std),
           (p + "kv_a.w", (kl + rope, h), std),
           (p + "kv_norm.gamma", (kl,), "ones"),
           (p + "kv_b.w", (heads * (nope + vd), kl), std),
           (p + "o.w", (h, heads * vd), std),
           (p + "ffn_norm.gamma", (h,), "ones")]
    if i < cfg["first_k_dense_replace"]:
        w = cfg["intermediate_size"]
        return out + [(p + "gate.w", (w, h), std), (p + "up.w", (w, h), std),
                      (p + "down.w", (h, w), std)]
    e, w = len(held_ids(cfg)), cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * w
    return out + [(p + "router.w", (cfg["n_routed_experts"], h), std),
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


# -- rotary positions under YaRN ---------------------------------------------
def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """Per-frequency blend of the interpolated (1/factor) and the plain
    frequencies by the linear ramp between the two correction dims."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain / rs["factor"] * ramp + plain * (1.0 - ramp)


def rotary_tables(cfg, positions):
    """cos, sin (T, rope) at ``positions``; the factor on both is
    ``mscale / mscale_all_dim`` (1 as published)."""
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, cos, sin):
    """``x`` (T, ..., rope) rotated; cos/sin (T, rope)."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return x * cos.reshape(shape) + turned * sin.reshape(shape)


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


# -- layers -----------------------------------------------------------------
def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


def linear(x, w, precision):
    return einsum("...i,oi->...o", x, w, precision)


def swiglu(x, gate, up, down, precision):
    y = jax.nn.silu(linear(x, gate, precision)) * linear(x, up, precision)
    return linear(y, down, precision)


def cache_round(x):
    """``x`` as a cache in 8 bits would hold it: four exponent bits, three of
    mantissa, a scale per tensor. By ``lax.reduce_precision``, which a
    compiler has to keep: the pair of converts ``precision.operand`` rounds
    by (float32 -> float8 -> float32) is dropped by the TPU's compiler
    where its result is carried into the loop over heads (one layer on the
    chip: 4e-7 of the attention output moved, 3.7% with this), and the
    control then read 0.0 on every seed (PERF.md, PR 27)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0  # e4m3's largest
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def latent_attention(params, p, cfg, x, cos, sin, precision, round_cache=None,
                     head_block=8):
    """Decompressed latent attention over the whole sequence ``x`` (T, H),
    heads in blocks so that the scores of a long sequence fit.
    ``round_cache`` (the ``kv8`` control) rounds what a cache would hold."""
    t, heads = x.shape[0], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = rms_norm(linear(x, params[p + "q_a.w"], precision),
                   params[p + "q_norm.gamma"], eps)
    q = linear(c_q, params[p + "q_b.w"], precision).reshape(t, heads, -1)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
    kv = linear(x, params[p + "kv_a.w"], precision)
    c_kv = rms_norm(kv[:, :kl], params[p + "kv_norm.gamma"], eps)
    k_rope = rotate(kv[:, kl:], cos, sin)  # one for all heads
    if round_cache is not None:
        c_kv, k_rope = round_cache(c_kv), round_cache(k_rope)
    kv_b = params[p + "kv_b.w"].reshape(heads, nope + vd, kl)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = softmax_scale(cfg)
    block = min(head_block, heads)

    def heads_of(args):
        qn, qr, w = args  # (block, T, nope), (block, T, rope), (block, nope+vd, kl)
        k_v = einsum("tl,hdl->htd", c_kv, w, precision)
        s = (einsum("hqd,hkd->hqk", qn, k_v[..., :nope], precision)
             + einsum("hqd,kd->hqk", qr, k_rope, precision)) * scale
        att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return einsum("hqk,hkd->hqd", att, k_v[..., nope:], precision)

    def blocks(a):  # (T, heads, d) -> (heads / block, block, T, d)
        return a.transpose(1, 0, 2).reshape(heads // block, block, t, -1)

    ctx = jax.lax.map(heads_of, (blocks(q_nope), blocks(q_rope),
                                 kv_b.reshape(heads // block, block, -1, kl)))
    ctx = ctx.reshape(heads, t, vd).transpose(1, 0, 2).reshape(t, heads * vd)
    return linear(ctx, params[p + "o.w"], precision)


def route(cfg, h, router_w):
    """(weights (T, k), expert ids (T, k)) of ``group_limited_greedy``: the
    softmax over all routed experts in float32; a group's score is its
    largest probability; the best ``topk_group`` groups stay; the top k
    probabilities among them, unchanged (``norm_topk_prob`` false), times
    ``routed_scaling_factor``. The router is never rounded: a control in a
    lower precision routes as the reference does."""
    n, groups = cfg["n_routed_experts"], cfg["n_group"]
    p = jax.nn.softmax(einsum("ti,ei->te", h, router_w, "float32"), axis=-1)
    group_best = p.reshape(-1, groups, n // groups).max(axis=-1)
    _, keep = jax.lax.top_k(group_best, cfg["topk_group"])
    open_group = jnp.zeros_like(group_best, bool).at[
        jnp.arange(h.shape[0])[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(open_group, n // groups, axis=1), p, 0.0)
    w, ids = jax.lax.top_k(masked, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], ids


def routed_part(params, p, cfg, h, precision):
    """What the held experts add: a plain loop over their ids."""
    w, ids = route(cfg, h, params[p + "router.w"])
    out = jnp.zeros_like(h)
    for slot, expert in enumerate(held_ids(cfg)):
        share = jnp.sum(jnp.where(ids == expert, w, 0.0), axis=-1)  # (T,)
        y = swiglu(h, params[p + "experts.gate.w"][slot],
                   params[p + "experts.up.w"][slot],
                   params[p + "experts.down.w"][slot], precision)
        out = out + share[:, None] * y
    return out


def shared_part(params, p, h, precision):
    return swiglu(h, params[p + "shared.gate.w"], params[p + "shared.up.w"],
                  params[p + "shared.down.w"], precision)


def hidden(params, cfg, tokens, precision="float32"):
    """Final hidden states (T, H), normed, of one sequence ``tokens`` (T,).
    ``precision`` ``kv8`` is the control for the cache alone: the 576 cached
    values a token a layer rounded to 8 bits, every product in float32."""
    round_cache = cache_round if precision == "kv8" else None
    precision = precision.replace("kv8", "float32")
    eps = cfg["rms_norm_eps"]
    x = params["embed.word"][tokens]
    cos, sin = rotary_tables(cfg, jnp.arange(tokens.shape[0]))
    for i in range(cfg["n_layer"]):
        p = f"layer{i}."
        x = x + latent_attention(
            params, p, cfg, rms_norm(x, params[p + "attn_norm.gamma"], eps),
            cos, sin, precision, round_cache)
        h = rms_norm(x, params[p + "ffn_norm.gamma"], eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu(h, params[p + "gate.w"], params[p + "up.w"],
                           params[p + "down.w"], precision)
        else:
            x = x + routed_part(params, p, cfg, h, precision) \
                + shared_part(params, p, h, precision)
    return rms_norm(x, params["norm.gamma"], eps)


_SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
    "rms_norm_eps", "rope_theta", "n_layer", "first_k_dense_replace",
    "intermediate_size", "moe_intermediate_size", "n_shared_experts",
    "n_routed_experts", "n_group", "topk_group", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor")


def config_key(cfg):
    """What the forward reads of the configuration, hashable (a static
    argument of the jitted forward)."""
    return (tuple((k, cfg[k]) for k in _SHAPE_KEYS)
            + (("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),
               ("held_experts", held_ids(cfg))))


def _thaw(key):
    cfg = dict(key)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "n_out"))
def _logits(params, tokens, first, cfg_key, precision, n_out):
    x = hidden(params, _thaw(cfg_key), tokens, precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return einsum("th,vh->tv", x, params["head.w"],
                  precision.replace("kv8", "float32"))


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
def latent_bytes_per_token(cfg, cache_bytes=2):
    """What the latent cache holds for one token: the normalised latent and
    the rotated key of every layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes \
        * cfg["n_layer"]


def decode_step_bytes(cfg, held_positions, weight_bytes=2, cache_bytes=2):
    """Bytes one decode step has to read: every weight held here once (the
    word embedding is read by row, so not counted; the head is), plus the
    latent cache of the positions the rows hold."""
    count = sum(math.prod(shape) for name, shape, _ in param_specs(cfg)
                if name != "embed.word")
    return count * weight_bytes \
        + latent_bytes_per_token(cfg, cache_bytes) * held_positions
