"""BERT pretraining (Devlin et al. 2018, ``google-research/bert``
``modeling.py`` / ``run_pretraining.py``): post-LN encoder, erf GELU, masked
LM over gathered positions plus next-sentence classification, Adam.

Departures from the publication, each because the program under test makes
it and the two must compute one function: the masked-LM decoder has its own
(vocab, hidden) matrix instead of sharing the word embedding; LayerNorm's
epsilon is the configuration's ``layer_norm_eps``; Adam has no weight decay
and no warm-up (a constant learning rate).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .norms import leaf_diff_norms, leaf_norms
from .precision import dense, einsum, layer_norm


def param_specs(cfg):
    """(name, shape, init) for every parameter; init is ``("normal", std)``,
    ``"ones"`` or ``"zeros"``. The order is the order weights are drawn in."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = ("normal", cfg["initializer_range"])
    out = [("embed.word", (v, h), std),
           ("embed.type", (cfg["type_vocab_size"], h), std),
           ("embed.position", (cfg["max_position_embeddings"], h), std),
           ("embed.ln.gamma", (h,), "ones"), ("embed.ln.beta", (h,), "zeros")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        out += [(p + "qkv.w", (3 * h, h), std), (p + "qkv.b", (3 * h,), "zeros"),
                (p + "proj.w", (h, h), std), (p + "proj.b", (h,), "zeros"),
                (p + "ln1.gamma", (h,), "ones"), (p + "ln1.beta", (h,), "zeros"),
                (p + "ffn1.w", (f, h), std), (p + "ffn1.b", (f,), "zeros"),
                (p + "ffn2.w", (h, f), std), (p + "ffn2.b", (h,), "zeros"),
                (p + "ln2.gamma", (h,), "ones"), (p + "ln2.beta", (h,), "zeros")]
    out += [("pooler.w", (h, h), std), ("pooler.b", (h,), "zeros"),
            ("mlm.transform.w", (h, h), std), ("mlm.transform.b", (h,), "zeros"),
            ("mlm.ln.gamma", (h,), "ones"), ("mlm.ln.beta", (h,), "zeros"),
            ("mlm.decoder.w", (v, h), std), ("mlm.decoder.b", (v,), "zeros"),
            ("nsp.w", (2, h), std), ("nsp.b", (2,), "zeros")]
    return out


def forward(params, cfg, ids, types, valid, positions, precision="float32"):
    """(mlm logits (B, M, V), nsp logits (B, 2)) for a block of rows."""
    b, t = ids.shape
    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    x = (params["embed.word"][ids] + params["embed.position"][:t][None]
         + params["embed.type"][types])
    x = layer_norm(x, params["embed.ln.gamma"], params["embed.ln.beta"], eps)
    keep = (jnp.arange(t)[None, :] < valid[:, None])[:, None, None, :]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer{i}."
        qkv = dense(x, params[p + "qkv.w"], params[p + "qkv.b"], precision)
        q, k, v = jnp.moveaxis(qkv.reshape(b, t, 3, heads, -1), 2, 0)
        s = einsum("bqhc,bkhc->bhqk", q, k, precision) / math.sqrt(q.shape[-1])
        att = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        ctx = einsum("bhqk,bkhc->bqhc", att, v, precision).reshape(b, t, -1)
        x = layer_norm(x + dense(ctx, params[p + "proj.w"], params[p + "proj.b"],
                                 precision),
                       params[p + "ln1.gamma"], params[p + "ln1.beta"], eps)
        y = jax.nn.gelu(dense(x, params[p + "ffn1.w"], params[p + "ffn1.b"],
                              precision), approximate=False)
        x = layer_norm(x + dense(y, params[p + "ffn2.w"], params[p + "ffn2.b"],
                                 precision),
                       params[p + "ln2.gamma"], params[p + "ln2.beta"], eps)
    pooled = jnp.tanh(dense(x[:, 0], params["pooler.w"], params["pooler.b"],
                            precision))
    g = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    g = jax.nn.gelu(dense(g, params["mlm.transform.w"],
                          params["mlm.transform.b"], precision),
                    approximate=False)
    g = layer_norm(g, params["mlm.ln.gamma"], params["mlm.ln.beta"], eps)
    mlm = dense(g, params["mlm.decoder.w"], params["mlm.decoder.b"], precision)
    nsp = dense(pooled, params["nsp.w"], params["nsp.b"], precision)
    return mlm, nsp


def loss_part(params, cfg, block, weight_total, rows_total, precision):
    """This block's share of the batch loss: the shares of all blocks add up
    to ``-(sum ll*w)/(sum w + 1e-6) - mean nsp ll`` over the whole batch."""
    ids, types, valid, positions, labels, weights, nsp_labels = block
    mlm, nsp = forward(params, cfg, ids, types, valid, positions, precision)
    ll = jnp.take_along_axis(jax.nn.log_softmax(mlm, axis=-1),
                             labels[:, :, None], axis=-1)[..., 0]
    nsp_ll = jnp.take_along_axis(jax.nn.log_softmax(nsp, axis=-1),
                                 nsp_labels[:, None], axis=-1)[:, 0]
    return (-jnp.sum(ll * weights) / (weight_total + 1e-6)
            - jnp.sum(nsp_ll) / rows_total)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _grad_block(params, block, weight_total, rows_total, cfg_key, precision):
    return jax.value_and_grad(loss_part)(params, dict(cfg_key), block,
                                         weight_total, rows_total, precision)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam(params, grads, m, v, t, lr, b1, b2, eps):
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps), params, m, v)
    return params, m, v


def train_steps(params, cfg, opt, batches, block_rows, precision="float32",
                place=lambda block: block):
    """Follow ``len(batches)`` Adam steps from ``params`` (consumed), a block
    of ``block_rows`` rows at a time so that float32 activations fit. Returns
    each step's loss, the first gradient's norm per leaf, and the norm of
    each leaf's change over all the steps. ``place`` puts a block on the
    devices (rows split over chips, where there are several)."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    start = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, batch in enumerate(batches, 1):
        rows = batch[0].shape[0]
        w_total = jnp.float32(batch[5].sum())
        loss, grads = 0.0, None
        for r in range(0, rows, block_rows):
            block = place(tuple(jnp.asarray(a[r:r + block_rows]) for a in batch))
            part, g = _grad_block(params, block, w_total, jnp.float32(rows),
                                  cfg_key, precision)
            loss = loss + part
            grads = g if grads is None else _accumulate(grads, g)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(leaf_norms(grads))
        params, m, v = _adam(params, grads, m, v, jnp.float32(t),
                             jnp.float32(opt["learning_rate"]), opt["beta1"],
                             opt["beta2"], opt["epsilon"])
    change = jax.device_get(leaf_diff_norms(params, start))
    return {"loss": losses,
            "grad_norm": {k: float(x) for k, x in first_grad.items()},
            "change_norm": {k: float(x) for k, x in change.items()}}


def train_flops(cfg, batch, seq, masked):
    """Operations the forward and backward passes of one step require
    (matrix products only, backward counted as twice the forward;
    recomputation not counted). Copied from the repo's ``bench.py``."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_token_layer = 4 * h * h * 2 + 2 * h * f * 2 + 2 * seq * h * 2
    fwd = batch * seq * per_token_layer * cfg["num_hidden_layers"]
    head = batch * masked * h * cfg["vocab_size"] * 2
    return 3 * (fwd + head)


def packed_attention_bytes(cfg, batch, seq, itemsize=2):
    """Bytes the packed attention kernels have to move in one step: in every
    layer the forward reads the packed ``qkv`` projection (3 x hidden a
    token) and writes the context (hidden); the backward, which recomputes
    the scores, reads ``qkv`` and the context's cotangent and writes
    ``dqkv``. bfloat16 as the step computes; the valid lengths (one number a
    row) are not counted."""
    h = cfg["hidden_size"]
    forward = batch * seq * (3 * h + h) * itemsize
    backward = batch * seq * (3 * h + h + 3 * h) * itemsize
    return cfg["num_hidden_layers"] * (forward + backward)
