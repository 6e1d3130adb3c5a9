"""MiniCPM-SALA (``openbmb/MiniCPM-SALA`` ``config.json``): by ``mixer_types``
one ``minicpm4`` layer (InfLLM-v2 block-sparse attention: MiniCPM4,
arXiv:2506.07900; InfLLM-V2, arXiv:2509.24663) to three ``lightning-attn``
layers (Lightning Attention, arXiv:2401.04658), in MiniCPM's block: no bias,
RMSNorm on each sublayer's INPUT, an untied head, and three constants ::

    c   = scale_depth / sqrt(num_hidden_layers)       # 1.4 / sqrt(32), published 32
    h_0 = scale_emb * E[token]                        # 12
    x   = x + c * Mixer(RMSNorm(x))
    x   = x + c * Wdown(silu(Wgate u) * (Wup u)),  u = RMSNorm(x)
    logits = Whead (RMSNorm(x_last) / (hidden_size / dim_model_base))   # / 16

A ``minicpm4`` mixer, 32 query heads of 128 over 2 key-value heads ::

    q = RMSNorm_128(Wq u), k = RMSNorm_128(Wk u), v = Wv u     # no positions
    c_j = mean(k_(16j) .. k_(16j+31))                          # a key-value head
    the query at t, seeing n = t + 1 keys:
      n <= dense_len (8,192): causal softmax over every key
      else: p_h = softmax_j(q_h . c_j / sqrt(128)) over the c_j wholly at or
            before t; s_j = sum of p_h,j over the group's 16 heads; block m
            (64 positions) scores b_m = max of s_j, j in 4m-1 .. 4m+3; block
            0, the query's own block and the 32 before it are taken whatever
            they score (34 forced), the rest of the 64 are the best by b_m;
            causal softmax over the chosen blocks' keys
    y = Wo (a * sigmoid(Wg u))

A ``lightning-attn`` mixer at published layer l, 32 heads of 128 ::

    q = RMSNorm_128(Wq u), k = RMSNorm_128(Wk u), v = Wv u
    q, k rotated over all 128 dims (theta 10,000; two halves); q * 128^-1/2
    lambda_h = exp(-2^(-8h/32) * (1 - l/31 + 1e-5)),  h = 1 .. 32
    S_t = lambda_h S_(t-1) + k_t v_t^T  (128 x 128, float32);  o_t = S_t^T q_t
    y = Wo (RMSNorm_128(o) * sigmoid(Wg u))             # the norm a head

A full forward over one whole sequence in float32 at ``highest``: no kernel,
no cache, no batching. The recurrence runs position by position (a
``lax.scan`` over t); the sparse layer is a masked softmax with the queries
in blocks, each query's choice made anew from the compressed keys of the
whole sequence; the feed-forward walks the tokens in blocks so that 69,632
positions fit beside the weights.

``precision`` is ``float32`` (the reference), ``bfloat16``/``fp8`` (the same
mathematics with rounded matmul operands), or a control of the MATHEMATICS
in float32: ``no_selection`` (every key read past the dense length too),
``forced_only`` (the forced blocks and none by score), ``no_decay`` (lambda =
1), and two that only the CPU tests' toy can tell apart: ``early_key`` (a
compressed key seen one position before its last key) and ``dense_by_call``
(dense or sparse decided by the whole sequence's length and not by each
query's position).

What the configuration's file assumes is in its ``assumed``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .deepseek_v2 import linear, rms_norm
from .precision import einsum, operand

MATH_CONTROLS = ("no_selection", "forced_only", "no_decay", "early_key",
                 "dense_by_call")
_FFN_TOKENS = 2048    # tokens a block of the feed-forward
_QUERY_BLOCK = 256    # queries a block of the sparse layer


def split_precision(precision):
    """(the products' precision, the control of the mathematics or None)."""
    if precision in MATH_CONTROLS:
        return "float32", precision
    return precision, None


# -- shapes -----------------------------------------------------------------
def mixer_types(cfg):
    return tuple(cfg["mixer_types"][:cfg["n_layer"]])


def lightning_sizes(cfg):
    """(heads, head size) of a lightning layer."""
    if cfg["lightning_nh"] != cfg["lightning_nkv"]:
        raise ValueError("query and key-value heads of a lightning layer "
                         "differ: not this reference's layer")
    return cfg["lightning_nh"], cfg["lightning_head_dim"]


def layer_specs(cfg, i):
    """(name, shape, init) of layer ``i``'s leaves; weights stored (out, in)."""
    hd, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    std = ("normal", cfg["initializer_range"])
    p = f"layer{i}."
    if cfg["mixer_types"][i] == "minicpm4":
        ch = cfg["head_dim"]
        width, kv = cfg["num_attention_heads"] * ch, cfg["num_key_value_heads"] * ch
        mixer = [(p + "attn.q.w", (width, hd), std),
                 (p + "attn.k.w", (kv, hd), std),
                 (p + "attn.v.w", (kv, hd), std),
                 (p + "attn.g.w", (width, hd), std),
                 (p + "attn.o.w", (hd, width), std),
                 (p + "attn.q_norm.gamma", (ch,), "ones"),
                 (p + "attn.k_norm.gamma", (ch,), "ones")]
    else:
        h, ch = lightning_sizes(cfg)
        mixer = [(p + f"lin.{n}.w", (h * ch, hd), std) for n in "qkvg"] + [
            (p + "lin.o.w", (hd, h * ch), std),
            (p + "lin.q_norm.gamma", (ch,), "ones"),
            (p + "lin.k_norm.gamma", (ch,), "ones"),
            (p + "lin.o_norm.gamma", (ch,), "ones")]
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


def residual_scale(cfg):
    """c: what every sublayer's output is multiplied by before it joins the
    residual stream, by the PUBLISHED depth."""
    return cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers"])


def decays(cfg, layer):
    """lambda (H,) of the lightning layer at published index ``layer``."""
    h, _ = lightning_sizes(cfg)
    slopes = 2.0 ** (-8.0 * np.arange(1, h + 1) / h)
    factor = 1.0 - layer / (cfg["num_hidden_layers"] - 1) + 1e-5
    return np.exp(-slopes * factor).astype(np.float32)


# -- the sparse layer -------------------------------------------------------
def compressed_keys(k, sparse):
    """c_j (J, Hkv, Ch) of the keys ``k`` (T, Hkv, Ch): the mean of
    ``kernel_size`` keys every ``kernel_stride`` positions, for every j whose
    positions all exist (J = (T - kernel_size) // kernel_stride + 1)."""
    size, stride = sparse["kernel_size"], sparse["kernel_stride"]
    t = k.shape[0]
    if size % stride or t % stride:
        raise ValueError("kernel_size and the length must be multiples of "
                         "kernel_stride")
    strides = k.reshape(t // stride, stride, *k.shape[1:]).sum(axis=1)
    n = size // stride
    j = t // stride - n + 1
    return sum(strides[i:i + j] for i in range(n)) / size


def block_scores(s, valid, sparse, n_blocks):
    """b_m (..., M) of the compressed keys' summed weights ``s`` (..., J):
    the largest over the compressed keys that touch block m (those starting
    in it and the ``kernel_size / kernel_stride - 1`` that reach into it
    from before), ``-inf`` where none of them is ``valid``."""
    per = sparse["block_size"] // sparse["kernel_stride"]       # 4
    back = sparse["kernel_size"] // sparse["kernel_stride"] - 1  # 1
    s = jnp.where(valid, s, -jnp.inf)
    lead = s.shape[:-1]
    total = back + per * n_blocks
    s = jnp.concatenate(
        [jnp.full((*lead, back), -jnp.inf), s,
         jnp.full((*lead, max(total - back - s.shape[-1], 0)), -jnp.inf)],
        axis=-1)[..., :total]
    b = s[..., back:].reshape(*lead, n_blocks, per).max(axis=-1)
    for i in range(back):   # the keys that reach in from the block before
        b = jnp.maximum(b, s[..., i:i + per * n_blocks:per])
    return b


def chosen_blocks(q, ck, at, sparse, n_blocks, precision, control=None,
                  length=None):
    """(Q, M) bool: the blocks the queries ``q`` (Q, G, Ch) of ONE key-value
    head's group, at positions ``at`` (Q,), read of ``n_blocks`` blocks, by
    the compressed keys ``ck`` (J, Ch). ``length``: the sequence's real
    length, which only the control ``dense_by_call`` reads."""
    size, stride = sparse["kernel_size"], sparse["kernel_stride"]
    block, ch = sparse["block_size"], q.shape[-1]
    m = jnp.arange(n_blocks)[None, :]
    own = (at // block)[:, None]
    held = m <= own
    if control == "no_selection":
        return held
    last = jnp.arange(ck.shape[0]) * stride + size - 1     # a c_j's last key
    if control == "early_key":
        last = last - 1
    valid = last[None, :] <= at[:, None]                    # (Q, J)
    dots = einsum("qgc,jc->qgj", q, ck, precision) * ch ** -0.5
    p = jax.nn.softmax(jnp.where(valid[:, None, :], dots, -jnp.inf), axis=-1)
    s = jnp.where(valid[:, None, :], p, 0.0).sum(axis=1)    # (Q, J)
    b = block_scores(s, valid, sparse, n_blocks)
    local = sparse["window_size"] // block
    forced = (m < sparse["init_blocks"]) | ((m >= own - local) & held)
    rank = jnp.where(forced, jnp.inf, jnp.where(held, b, -jnp.inf))
    _, ids = jax.lax.top_k(rank, min(sparse["topk"], n_blocks))
    taken = (ids[:, :, None] == m[:, None, :]).any(axis=1) \
        & (rank > -jnp.inf)
    if control == "forced_only":
        taken = forced
    dense = at + 1 <= sparse["dense_len"]
    if control == "dense_by_call":
        dense = jnp.full_like(dense, length <= sparse["dense_len"])
    return jnp.where(dense[:, None], held, taken)


def sparse_attention(params, p, cfg, u, precision, control=None, length=None):
    """One ``minicpm4`` sublayer over the whole sequence ``u`` (T, hidden)."""
    t, ch = u.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group, sparse, eps = heads // kv, cfg["sparse_config"], cfg["rms_norm_eps"]
    block = sparse["block_size"]
    q = rms_norm(linear(u, params[p + "attn.q.w"], precision)
                 .reshape(t, kv, group, ch), params[p + "attn.q_norm.gamma"], eps)
    k = rms_norm(linear(u, params[p + "attn.k.w"], precision)
                 .reshape(t, kv, ch), params[p + "attn.k_norm.gamma"], eps)
    v = linear(u, params[p + "attn.v.w"], precision).reshape(t, kv, ch)
    ck = compressed_keys(k, sparse)                         # (J, kv, ch)
    n_blocks = -(-t // block)
    qb = math.gcd(t, _QUERY_BLOCK)
    key_block = jnp.arange(t) // block

    def head_of(args):
        qs, ks, vs, cks = args    # (T, G, ch), (T, ch), (T, ch), (J, ch)

        def queries_of(start):
            at = start + jnp.arange(qb)
            q_b = jax.lax.dynamic_slice_in_dim(qs, start, qb, 0)
            taken = chosen_blocks(q_b, cks, at, sparse, n_blocks, precision,
                                  control, length)          # (qb, M)
            seen = taken[:, key_block] \
                & (jnp.arange(t)[None, :] <= at[:, None])
            s = einsum("qgc,kc->qgk", q_b, ks, precision) * ch ** -0.5
            att = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf),
                                 axis=-1)
            return einsum("qgk,kc->qgc", att, vs, precision)

        return jax.lax.map(queries_of, jnp.arange(0, t, qb)).reshape(
            t, group, ch)

    a = jax.lax.map(head_of, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                              v.transpose(1, 0, 2), ck.transpose(1, 0, 2)))
    a = a.transpose(1, 0, 2, 3).reshape(t, heads * ch)      # (T, kv*G*ch)
    gate = jax.nn.sigmoid(linear(u, params[p + "attn.g.w"], precision))
    return linear(a * gate, params[p + "attn.o.w"], precision)


# -- the lightning layer ----------------------------------------------------
def rotate(x, theta):
    """``x`` (T, H, Ch) rotated over all of Ch (two halves) by its position."""
    ch = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(ch // 2) / ch)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., ch // 2:], x[..., :ch // 2]], axis=-1)
    return x * cos + turned * sin


def decayed_state(q, k, v, lam):
    """``o_t = S_t^T q_t`` with ``S_t = lam S_(t-1) + k_t v_t^T`` from zero,
    position by position: ``q``, ``k``, ``v`` (T, H, Ch), ``lam`` (H,)."""
    hi = jax.lax.Precision.HIGHEST

    def step(s, at):
        q_t, k_t, v_t = at
        s = s * lam[:, None, None] + jnp.einsum("hk,hv->hkv", k_t, v_t,
                                                precision=hi)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=hi)

    h, ch = q.shape[1], q.shape[2]
    _, out = jax.lax.scan(step, jnp.zeros((h, ch, ch), jnp.float32), (q, k, v))
    return out


def lightning_attention(params, p, cfg, u, layer, precision, control=None):
    """One ``lightning-attn`` sublayer over the whole sequence ``u``
    (T, hidden), ``layer`` its published index."""
    t, eps = u.shape[0], cfg["rms_norm_eps"]
    h, ch = lightning_sizes(cfg)
    q = rms_norm(linear(u, params[p + "lin.q.w"], precision).reshape(t, h, ch),
                 params[p + "lin.q_norm.gamma"], eps)
    k = rms_norm(linear(u, params[p + "lin.k.w"], precision).reshape(t, h, ch),
                 params[p + "lin.k_norm.gamma"], eps)
    v = linear(u, params[p + "lin.v.w"], precision).reshape(t, h, ch)
    q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    lam = jnp.ones((h,), jnp.float32) if control == "no_decay" \
        else jnp.asarray(decays(cfg, layer))
    o = rms_norm(decayed_state(q * ch ** -0.5, k, v, lam),
                 params[p + "lin.o_norm.gamma"], eps).reshape(t, h * ch)
    gate = jax.nn.sigmoid(linear(u, params[p + "lin.g.w"], precision))
    return linear(o * gate, params[p + "lin.o.w"], precision)


# -- the block --------------------------------------------------------------
def swiglu_blocks(u, gate, up, down, precision):
    """``Wdown(silu(Wgate u) * (Wup u))`` with the tokens in blocks."""
    t = u.shape[0]
    tb = math.gcd(t, _FFN_TOKENS)

    def of(x):
        y = jax.nn.silu(linear(x, gate, precision)) * linear(x, up, precision)
        return linear(y, down, precision)

    return jax.lax.map(of, u.reshape(t // tb, tb, -1)).reshape(t, -1)


def hidden_layer(params, cfg, x, i, precision, control=None, length=None):
    """Layer ``i`` applied to the residual stream ``x`` (T, hidden)."""
    eps, c = cfg["rms_norm_eps"], residual_scale(cfg)
    p = f"layer{i}."
    u = rms_norm(x, params[p + "mixer_norm.gamma"], eps)
    y = sparse_attention(params, p, cfg, u, precision, control, length) \
        if cfg["mixer_types"][i] == "minicpm4" \
        else lightning_attention(params, p, cfg, u, i, precision, control)
    x = x + c * y
    u = rms_norm(x, params[p + "ffn_norm.gamma"], eps)
    return x + c * swiglu_blocks(u, params[p + "ffn.gate.w"],
                                 params[p + "ffn.up.w"],
                                 params[p + "ffn.down.w"], precision)


def hidden(params, cfg, tokens, precision="float32", length=None):
    """What the head reads, (T, hidden), of one sequence ``tokens`` (T,)."""
    precision, control = split_precision(precision)
    x = cfg["scale_emb"] * params["embed.word"][tokens]
    for i in range(cfg["n_layer"]):
        x = hidden_layer(params, cfg, x, i, precision, control, length)
    return rms_norm(x, params["norm.gamma"], cfg["rms_norm_eps"]) \
        / (cfg["hidden_size"] / cfg["dim_model_base"])


_SHAPE_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "n_layer",
    "num_hidden_layers", "lightning_nh", "lightning_nkv",
    "lightning_head_dim", "rope_theta", "scale_emb", "scale_depth",
    "dim_model_base")


def config_key(cfg):
    """What the forward reads of the configuration, hashable (a static
    argument of the jitted forward)."""
    return (tuple((k, cfg[k]) for k in _SHAPE_KEYS)
            + (("mixer_types", mixer_types(cfg)),
               ("sparse_config", tuple(sorted(cfg["sparse_config"].items())))))


def _cfg_of(cfg_key):
    cfg = dict(cfg_key)
    cfg["sparse_config"] = dict(cfg["sparse_config"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "n_out"))
def _logits(params, tokens, first, real, cfg_key, precision, n_out):
    with jax.default_matmul_precision("highest"):
        x = hidden(params, _cfg_of(cfg_key), tokens, precision, real)
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
    return np.asarray(_logits(params, buf, np.int32(first), np.int32(n),
                              config_key(cfg), precision, n_out))[:count]


@functools.partial(jax.jit, static_argnames=("cfg_key", "layer", "precision"))
def _choices(params, tokens, at, cfg_key, layer, precision):
    """(Hkv, Q, M) bool: the choice of the queries at positions ``at`` (Q,)
    in the sparse layer ``layer``, its q and its keys rounded to
    ``precision`` (the layers before it in float32)."""
    cfg = _cfg_of(cfg_key)
    with jax.default_matmul_precision("highest"):
        x = cfg["scale_emb"] * params["embed.word"][tokens]
        for i in range(layer):
            x = hidden_layer(params, cfg, x, i, "float32")
        p = f"layer{layer}."
        eps, sparse = cfg["rms_norm_eps"], cfg["sparse_config"]
        u = rms_norm(x, params[p + "mixer_norm.gamma"], eps)
        t, ch = u.shape[0], cfg["head_dim"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        q = rms_norm(linear(u[at], params[p + "attn.q.w"], precision)
                     .reshape(-1, kv, heads // kv, ch),
                     params[p + "attn.q_norm.gamma"], eps)
        k = rms_norm(linear(u, params[p + "attn.k.w"], precision)
                     .reshape(t, kv, ch), params[p + "attn.k_norm.gamma"], eps)
        # a cache in that precision holds the keys, and their means, rounded
        ck = operand(compressed_keys(operand(k, precision), sparse), precision)
        n_blocks = -(-t // sparse["block_size"])
        return jnp.stack([
            chosen_blocks(q[:, g], ck[:, g], at, sparse, n_blocks, precision)
            for g in range(kv)])


def choice_agreement(params, cfg, tokens, layer=0, precision="bfloat16",
                     every=64):
    """The share of the blocks chosen in float32 that the same selection
    with operands rounded to ``precision`` chooses too, over every
    ``every``-th position of ``tokens`` past the dense length, in the sparse
    layer ``layer`` (forced blocks left out of both counts): how decisive
    the seeded weights make the choice."""
    tokens = np.asarray(tokens, np.int32)
    sparse = cfg["sparse_config"]
    at = np.arange(sparse["dense_len"], len(tokens), every)
    want, got = (np.asarray(_choices(params, tokens, at, config_key(cfg),
                                     layer, p))
                 for p in ("float32", precision))
    block = np.arange(want.shape[-1])[None, :]
    own = (at // sparse["block_size"])[:, None]
    free = (block >= sparse["init_blocks"]) \
        & (block <= own - sparse["window_size"] // sparse["block_size"])
    return float((want & got & free).sum()) / max(int((want & free).sum()), 1)


# -- bytes ------------------------------------------------------------------
def _count(cfg, kind):
    return sum(k == kind for k in mixer_types(cfg))


def lightning_state_bytes(cfg, rows, state_bytes=4):
    """Bytes the recurrent state of ``rows`` rows moves in one decode step:
    every lightning layer's heads x 128 x 128 matrix read once and written
    once."""
    h, ch = lightning_sizes(cfg)
    return rows * _count(cfg, "lightning-attn") * 2 * h * ch * ch * state_bytes


def block_read_bytes(cfg, blocks_read, cache_bytes=2):
    """Bytes the selected pages' read has to move in ONE sparse layer:
    ``blocks_read`` blocks a key-value head's table lists (the program's own
    count, summed over the rows), for every key-value head the key and the
    value of that head's ``head_dim`` lanes of the block's positions. The
    query's own block counts whole: the kernel copies whole pages."""
    return blocks_read * cfg["num_key_value_heads"] \
        * cfg["sparse_config"]["block_size"] * 2 * cfg["head_dim"] * cache_bytes


def block_select_bytes(cfg, blocks_held, cache_bytes=2):
    """Bytes the selection's scoring has to read in ONE sparse layer: the
    compressed keys, every key-value head's, of the ``blocks_held`` blocks
    the rows hold (``block_size / kernel_stride`` a block), once."""
    sparse = cfg["sparse_config"]
    return blocks_held * (sparse["block_size"] // sparse["kernel_stride"]) \
        * cfg["num_key_value_heads"] * cfg["head_dim"] * cache_bytes


def blocks_a_row_reads(cfg, length):
    """Blocks a key-value head's table lists for a row that sees ``length``
    keys: every block it holds up to the dense length, ``topk`` past it."""
    sparse = cfg["sparse_config"]
    held = -(-length // sparse["block_size"])
    return held if length <= sparse["dense_len"] else min(held, sparse["topk"])


def decode_step_bytes(cfg, held_positions, rows=None, weight_bytes=2,
                      cache_bytes=2):
    """Bytes one decode step has to move: every weight held here once (the
    word embedding is read by row, so not counted; the head is), the state
    of ``rows`` rows read and written, the compressed keys of the positions
    held, and the keys and values the sparse layers read: of rows that hold
    ``held_positions`` between them, each as long as the mean, the selected
    blocks or everything under the dense length. ``rows`` defaults to the
    engine's slots: the accepted reader ``decode_hbm_roofline_pct.serve``
    passes held positions alone, and above the knee the slots are full."""
    rows = cfg["engine"]["batch_size"] if rows is None else rows
    count = sum(math.prod(shape) for name, shape, _ in param_specs(cfg)
                if name != "embed.word")
    mean = int(held_positions / max(rows, 1))
    block = cfg["sparse_config"]["block_size"]
    sparse_layers = _count(cfg, "minicpm4")
    return count * weight_bytes + lightning_state_bytes(cfg, rows) \
        + sparse_layers * block_select_bytes(
            cfg, -(-held_positions // block), cache_bytes) \
        + sparse_layers * block_read_bytes(
            cfg, rows * blocks_a_row_reads(cfg, mean), cache_bytes)
