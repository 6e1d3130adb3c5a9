"""GPT-2 (driver config #5: 345M, multi-host data parallel).

Decoder-only transformer with causal flash attention. Sizes follow the
published GPT-2 family; 345M == ``gpt2_medium``. Pre-LN blocks (as GPT-2).
Parameter names carry the TP sharding markers (qkv_/proj_/ffn1_/ffn2_).
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock
from .. import initializer as init

__all__ = ["GPT2Model", "get_gpt2", "gpt2_configs", "lm_loss"]


def _chunk_positions(F, t, start_pos=None):
    """Position ids for a t-token chunk: ``arange(t)`` for a full forward,
    per-row ``start_pos + arange(t)`` for a cached chunk (rows admitted by
    the batcher at different times sit at different sequence positions)."""
    ar = F.arange(0, t, dtype="int32")
    if start_pos is None:
        return ar
    return start_pos.reshape((-1, 1)).astype("int32") + ar.reshape((1, -1))

gpt2_configs = {
    "gpt2_tiny": dict(num_layers=2, units=128, num_heads=2, max_length=512,
                      vocab_size=50257),
    "gpt2_117m": dict(num_layers=12, units=768, num_heads=12, max_length=1024,
                      vocab_size=50257),
    "gpt2_345m": dict(num_layers=24, units=1024, num_heads=16, max_length=1024,
                      vocab_size=50257),
    "gpt2_774m": dict(num_layers=36, units=1280, num_heads=20, max_length=1024,
                      vocab_size=50257),
}


class GPT2Block(HybridBlock):
    # one pre-LN decoder block = one rematerialization unit under
    # ``net.hybridize(remat=...)``: long-context training recomputes the
    # block's activations (attention scores included) during backward
    # instead of saving them (docs/PERFORMANCE.md "Mixed precision")
    _remat_unit = True

    def __init__(self, units, num_heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._heads = num_heads
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units, prefix="ln1_")
            self.qkv = nn.Dense(3 * units, flatten=False, prefix="qkv_",
                                weight_initializer=init.Normal(0.02))
            self.proj = nn.Dense(units, flatten=False, prefix="proj_",
                                 weight_initializer=init.Normal(0.02))
            self.ln2 = nn.LayerNorm(in_channels=units, prefix="ln2_")
            self.ffn1 = nn.Dense(4 * units, flatten=False, prefix="ffn1_",
                                 weight_initializer=init.Normal(0.02))
            self.ffn2 = nn.Dense(units, flatten=False, prefix="ffn2_",
                                 weight_initializer=init.Normal(0.02))
            self.drop = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None):
        b, t, c = x.shape
        h = self._heads
        y = self.ln1(x)
        qkv = self.qkv(y).reshape((b, t, 3, h, c // h)).transpose((2, 0, 3, 1, 4))
        if cache is None:
            att = F.multi_head_attention(qkv[0], qkv[1], qkv[2], causal=True)
        else:
            # autoregressive path (docs/INFERENCE.md): only the t new tokens
            # flow through; K/V history lives in the static-shape cache —
            # contiguous (B,H,Tmax,Ch) buffers, or page pools indirected
            # through the per-row page_table (paged cache)
            att, k_buf, v_buf = F.multi_head_attention(
                qkv[0], qkv[1], qkv[2], cache=cache, position=start_pos,
                page_table=page_table)
        att = att.transpose((0, 2, 1, 3)).reshape((b, t, c))
        x = x + self.drop(self.proj(att))
        y = self.ffn2(F.Activation(self.ffn1(self.ln2(x)), act_type="tanh_gelu"))
        out = x + self.drop(y)
        return out if cache is None else (out, (k_buf, v_buf))


class GPT2Model(HybridBlock):
    def __init__(self, num_layers=12, units=768, num_heads=12, max_length=1024,
                 vocab_size=50257, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_layers = num_layers
        self._num_heads = num_heads
        self._max_length = max_length
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units, prefix="word_embed_",
                                           weight_initializer=init.Normal(0.02))
            self.position_embed = nn.Embedding(max_length, units,
                                               prefix="position_embed_",
                                               weight_initializer=init.Normal(0.01))
            self.drop = nn.Dropout(dropout)
            self.blocks = nn.HybridSequential(prefix="")
            for i in range(num_layers):
                self.blocks.add(GPT2Block(units, num_heads, dropout,
                                          prefix=f"layer{i}_"))
            self.ln_f = nn.LayerNorm(in_channels=units, prefix="lnf_")

    def init_cache(self, batch_size, max_length=None, dtype="float32"):
        """Allocate per-layer ``(k_buf, v_buf)`` static decode buffers of
        shape (B, H, Tmax, Ch) — the carry of the compiled decode step
        (``mxnet_tpu.inference.GenerationEngine``)."""
        from ..ops.attention import alloc_kv_cache

        return alloc_kv_cache(batch_size, self._num_heads,
                              max_length or self._max_length,
                              self._units // self._num_heads,
                              self._num_layers, dtype=dtype)

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        """Allocate per-layer ``(k_pool, v_pool)`` page pools of shape
        (num_pages + 1, page_size, H * Ch), token-major: the paged decode
        carry; page 0 is the reserved trash page (docs/INFERENCE.md "Paged
        cache", ``ops.attention.alloc_paged_kv_cache``)."""
        from ..ops.attention import alloc_paged_kv_cache

        return alloc_paged_kv_cache(num_pages, self._num_heads, page_size,
                                    self._units // self._num_heads,
                                    self._num_layers, dtype=dtype)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program will read this model's
        ``(k_pool, v_pool)`` pools by: the Pallas kernel that fetches the
        pages a row holds, or the XLA ``pool[page_table]`` gather and why
        (the operator makes the same choice from the same shapes at trace
        time)."""
        import jax

        from ..ops.pallas_paged_attention import paged_attention_refusal

        k_pool = pools[0][0]  # (P+1, page, H*Ch)
        q = jax.ShapeDtypeStruct(
            (batch_size, self._num_heads, 1,
             k_pool.shape[2] // self._num_heads),
            self.word_embed.weight.data()._data.dtype)
        why = paged_attention_refusal(q, k_pool, page_table)
        return f"xla_gather ({why})" if why else "pallas_paged_kernel"

    def logits_width(self):
        """The vocabulary the logits span (the head is the word embedding)."""
        return self.word_embed._input_dim

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None):
        b, t = token_ids.shape
        pos = _chunk_positions(F, t, start_pos)
        x = self.drop(self.word_embed(token_ids) + self.position_embed(pos))
        new_cache = []
        for i, blk in enumerate(self.blocks):
            if cache is None:
                x = blk(x)
            else:
                x, layer_cache = blk(x, cache=cache[i], start_pos=start_pos,
                                     page_table=page_table)
                new_cache.append(layer_cache)
        x = self.ln_f(x)
        # weight-tied LM head (GPT-2 ties input/output embeddings)
        logits = F.dot(x.reshape((b * t, self._units)),
                       self.word_embed.weight.data(), transpose_b=True)
        logits = logits.reshape((b, t, -1))
        return logits if cache is None else (logits, new_cache)


def get_gpt2(model_name="gpt2_345m", dropout=0.1, **overrides):
    cfg = dict(gpt2_configs[model_name])
    cfg.update(overrides)
    return GPT2Model(dropout=dropout, **cfg)


def lm_loss(logits, labels):
    """Next-token cross entropy; labels = input shifted by caller."""
    from .. import ndarray as nd

    b, t, v = logits.shape
    logp = nd.log_softmax(logits, axis=-1)
    ll = nd.pick(logp.reshape((b * t, v)), labels.reshape((b * t,)), axis=-1)
    return -ll.mean()
