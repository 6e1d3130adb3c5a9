"""GPT-2 (Radford et al. 2019, ``openai/gpt-2`` ``src/model.py``): pre-LN
decoder blocks, tanh GELU, learned positions, the word embedding as the
output head. A full forward over the whole sequence: no cache, no paging.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .precision import dense, einsum, layer_norm, operand


def param_specs(cfg):
    h, v = cfg["n_embd"], cfg["n_vocab"]
    std = ("normal", cfg["initializer_range"])
    out = [("embed.word", (v, h), std),
           ("embed.position", (cfg["n_ctx"], h),
            ("normal", cfg["position_initializer_range"]))]
    for i in range(cfg["n_layer"]):
        p = f"layer{i}."
        out += [(p + "ln1.gamma", (h,), "ones"), (p + "ln1.beta", (h,), "zeros"),
                (p + "qkv.w", (3 * h, h), std), (p + "qkv.b", (3 * h,), "zeros"),
                (p + "proj.w", (h, h), std), (p + "proj.b", (h,), "zeros"),
                (p + "ln2.gamma", (h,), "ones"), (p + "ln2.beta", (h,), "zeros"),
                (p + "ffn1.w", (4 * h, h), std), (p + "ffn1.b", (4 * h,), "zeros"),
                (p + "ffn2.w", (h, 4 * h), std), (p + "ffn2.b", (h,), "zeros")]
    return out + [("lnf.gamma", (h,), "ones"), ("lnf.beta", (h,), "zeros")]


def hidden(params, cfg, tokens, precision="float32"):
    """Final hidden states (T, H) of one sequence ``tokens`` (T,).
    ``precision`` ``kv8`` is the control for the cache alone: keys and
    values rounded to 8 bits (fp8, a scale per tensor) as a cache would hold
    them, every product in float32."""
    kv8, precision = precision == "kv8", precision.replace("kv8", "float32")
    t = tokens.shape[0]
    heads, eps = cfg["n_head"], cfg["layer_norm_eps"]
    x = params["embed.word"][tokens] + params["embed.position"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["n_layer"]):
        p = f"layer{i}."
        y = layer_norm(x, params[p + "ln1.gamma"], params[p + "ln1.beta"], eps)
        qkv = dense(y, params[p + "qkv.w"], params[p + "qkv.b"], precision)
        q, k, v = jnp.moveaxis(qkv.reshape(t, 3, heads, -1), 1, 0)
        if kv8:
            k, v = operand(k, "fp8"), operand(v, "fp8")
        s = einsum("qhc,khc->hqk", q, k, precision) / math.sqrt(q.shape[-1])
        att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = einsum("hqk,khc->qhc", att, v, precision).reshape(t, -1)
        x = x + dense(ctx, params[p + "proj.w"], params[p + "proj.b"], precision)
        y = layer_norm(x, params[p + "ln2.gamma"], params[p + "ln2.beta"], eps)
        y = jax.nn.gelu(dense(y, params[p + "ffn1.w"], params[p + "ffn1.b"],
                              precision), approximate=True)
        x = x + dense(y, params[p + "ffn2.w"], params[p + "ffn2.b"], precision)
    return layer_norm(x, params["lnf.gamma"], params["lnf.beta"], eps)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "n_out"))
def _logits(params, tokens, first, cfg_key, precision, n_out):
    x = hidden(params, dict(cfg_key), tokens, precision)
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=0)
    return einsum("th,vh->tv", x, params["embed.word"],
                  precision.replace("kv8", "float32"))


def next_token_logits(params, cfg, tokens, first, count, precision="float32",
                      pad_to=128, out_pad=32):
    """Logits (count, V), on the host, that follow positions ``first ..
    first+count-1`` of ``tokens``. The sequence is padded to a multiple of
    ``pad_to`` (causal attention keeps a position blind to what follows it),
    so few shapes compile; everything around the one jitted forward is
    numpy, so nothing else compiles at all."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    n = len(tokens)
    n_out = -(-count // out_pad) * out_pad
    length = -(-max(n, first + n_out) // pad_to) * pad_to
    buf = np.zeros((length,), np.int32)
    buf[:n] = tokens
    return np.asarray(_logits(params, buf, np.int32(first), cfg_key, precision,
                              n_out))[:count]


def decode_step_bytes(cfg, held_positions, weight_bytes=4, cache_bytes=2):
    """Bytes one decode step has to read: every weight once (the word
    embedding is read as the output head; the position table is not), plus
    the keys and values of the positions the rows hold."""
    h, v, layers = cfg["n_embd"], cfg["n_vocab"], cfg["n_layer"]
    per_layer = 12 * h * h + 13 * h
    weights = (v * h + layers * per_layer + 2 * h) * weight_bytes
    return weights + 2 * layers * h * cache_bytes * held_positions
