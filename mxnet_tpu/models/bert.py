"""BERT (GluonNLP ``scripts/bert`` shape — driver config #3, the north star).

The reference model calls the fused transformer ops
(``src/operator/contrib/transformer.cc`` interleaved matmuls); here the
encoder's attention goes through ``self_attention_packed``, which hands the
``qkv`` projection as the Dense wrote it to a path chosen from what it can
observe: on one TPU, at sequences up to 512 with the key-padding mask, the
Pallas packed-attention kernel (``ops.pallas_packed_attention``: softmax and
both products of a batch row in VMEM, backward recomputed); otherwise
``multi_head_attention``, which is the XLA einsum path whenever a mask is
passed (BERT always passes one) and on every non-TPU backend. Parameter
names carry the ``qkv_/proj_/ffn1_/ffn2_`` markers the TP sharding rules key
on (``parallel.sharding.DEFAULT_BERT_RULES``).

Pretraining heads follow GluonNLP's ``BERTForPretrain``: masked-LM over
gathered positions + next-sentence classifier.
"""
from __future__ import annotations

import math

from ..gluon import nn
from ..gluon.block import HybridBlock
from .. import initializer as init

__all__ = ["BERTModel", "BERTEncoder", "BERTForPretrain", "get_bert", "bert_configs"]

bert_configs = {
    # (num_layers, units, hidden(ffn), heads, max_len, vocab)
    "bert_tiny": dict(num_layers=2, units=128, hidden_size=512, num_heads=2,
                      max_length=512, vocab_size=30522),
    "bert_mini": dict(num_layers=4, units=256, hidden_size=1024, num_heads=4,
                      max_length=512, vocab_size=30522),
    "bert_base": dict(num_layers=12, units=768, hidden_size=3072, num_heads=12,
                      max_length=512, vocab_size=30522),
    "bert_large": dict(num_layers=24, units=1024, hidden_size=4096, num_heads=16,
                       max_length=512, vocab_size=30522),
}


class BERTAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._heads = num_heads
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, prefix="qkv_",
                                weight_initializer=init.Normal(0.02))
            self.proj = nn.Dense(units, flatten=False, prefix="proj_",
                                 weight_initializer=init.Normal(0.02))
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None):
        # x: (B, T, C); the packed projection (B, T, 3C) goes to the
        # attention operator as the Dense wrote it, columns [3][H][D]
        out = F.self_attention_packed(self.qkv(x), mask=mask,
                                      heads=self._heads)
        return self.dropout(self.proj(out))


class BERTEncoderLayer(HybridBlock):
    # remat unit under ``net.hybridize(remat=...)``: the post-LN encoder
    # layer's activations are recomputed in backward instead of saved —
    # the deliberate flops-for-memory trade, replacing GSPMD's involuntary
    # full remat fallback (docs/PERFORMANCE.md "Mixed precision")
    _remat_unit = True

    def __init__(self, units, hidden_size, num_heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = BERTAttention(units, num_heads, dropout, prefix="attn_")
            self.ln1 = nn.LayerNorm(in_channels=units, prefix="ln1_")
            self.ffn1 = nn.Dense(hidden_size, flatten=False, prefix="ffn1_",
                                 weight_initializer=init.Normal(0.02))
            self.ffn2 = nn.Dense(units, flatten=False, prefix="ffn2_",
                                 weight_initializer=init.Normal(0.02))
            self.ln2 = nn.LayerNorm(in_channels=units, prefix="ln2_")
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None):
        # post-LN (original BERT)
        x = self.ln1(x + self.attention(x, mask))
        y = self.ffn2(F.Activation(self.ffn1(x), act_type="gelu"))
        return self.ln2(x + self.dropout(y))


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.1,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.layers = nn.HybridSequential(prefix="")
            for i in range(num_layers):
                self.layers.add(BERTEncoderLayer(units, hidden_size, num_heads,
                                                 dropout, prefix=f"layer{i}_"))

    def hybrid_forward(self, F, x, mask=None):
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Embeddings + encoder + pooler. Inputs follow GluonNLP:
    (token_ids, token_types, valid_length)."""

    def __init__(self, num_layers=12, units=768, hidden_size=3072, num_heads=12,
                 max_length=512, vocab_size=30522, token_type_vocab=2,
                 dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units, prefix="word_embed_",
                                           weight_initializer=init.Normal(0.02))
            self.token_type_embed = nn.Embedding(token_type_vocab, units,
                                                 prefix="token_type_embed_",
                                                 weight_initializer=init.Normal(0.02))
            self.position_embed = nn.Embedding(max_length, units, prefix="position_embed_",
                                               weight_initializer=init.Normal(0.02))
            self.embed_ln = nn.LayerNorm(in_channels=units, prefix="embed_ln_")
            self.embed_dropout = nn.Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                       dropout, prefix="enc_")
            self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                                   prefix="pooler_",
                                   weight_initializer=init.Normal(0.02))

    def hybrid_forward(self, F, token_ids, token_types=None, valid_length=None):
        b, t = token_ids.shape
        positions = F.arange(0, t, dtype="int32")
        emb = self.word_embed(token_ids) + self.position_embed(positions)
        if token_types is not None:
            emb = emb + self.token_type_embed(token_types)
        emb = self.embed_dropout(self.embed_ln(emb))
        mask = None
        if valid_length is not None:
            # (B, 1, 1, T) key-padding mask broadcast over heads and queries
            steps = F.arange(0, t, dtype="int32")
            mask = (steps.reshape((1, 1, 1, t)) <
                    valid_length.astype("int32").reshape((b, 1, 1, 1)))
        seq = self.encoder(emb, mask)
        pooled = self.pooler(seq.slice_axis(axis=1, begin=0, end=1).squeeze(axis=1))
        return seq, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP heads (GluonNLP BERTForPretrain shape)."""

    def __init__(self, bert: BERTModel, vocab_size=30522, **kwargs):
        super().__init__(**kwargs)
        self._vocab = vocab_size
        with self.name_scope():
            self.bert = bert
            self.mlm_transform = nn.Dense(bert._units, flatten=False, prefix="mlmt_",
                                          weight_initializer=init.Normal(0.02))
            self.mlm_ln = nn.LayerNorm(in_channels=bert._units, prefix="mlmln_")
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False, prefix="mlmdec_",
                                        weight_initializer=init.Normal(0.02))
            self.nsp = nn.Dense(2, flatten=False, prefix="nsp_",
                                weight_initializer=init.Normal(0.02))

    def hybrid_forward(self, F, token_ids, token_types, valid_length, masked_positions):
        seq, pooled = self.bert(token_ids, token_types, valid_length)
        # gather masked positions: (B, M) -> (B, M, C)
        b, m = masked_positions.shape
        mp = masked_positions.astype("int32")
        batch_idx = F.arange(0, b, dtype="int32").reshape((b, 1)).broadcast_to((b, m))
        gathered = F.gather_nd(seq, F.stack(batch_idx.reshape((-1,)),
                                            mp.reshape((-1,)), axis=0))
        gathered = gathered.reshape((b, m, -1))
        # pin the gathered activations and MLM logits to batch-over-data-axes
        # (everything else replicated): without this GSPMD reshards the
        # log_softmax cotangent through an involuntary full remat every
        # backward step (round-3 MULTICHIP tail warning)
        gathered = F._sharding_constraint(gathered, spec=("data", None, None))
        h = self.mlm_ln(F.Activation(self.mlm_transform(gathered), act_type="gelu"))
        mlm_scores = F._sharding_constraint(self.mlm_decoder(h),
                                            spec=("data", None, None))
        nsp_scores = self.nsp(pooled)
        return mlm_scores, nsp_scores


def get_bert(model_name="bert_base", pretrain_head=True, dropout=0.1, **overrides):
    cfg = dict(bert_configs[model_name])
    cfg.update(overrides)
    vocab = cfg["vocab_size"]
    bert = BERTModel(dropout=dropout, **cfg)
    if pretrain_head:
        return BERTForPretrain(bert, vocab_size=vocab)
    return bert


def pretrain_loss(mlm_scores, nsp_scores, masked_labels, masked_weights, nsp_labels):
    """Standard BERT pretraining loss as NDArray ops (usable eager or staged)."""
    from .. import ndarray as nd

    b, m, v = mlm_scores.shape
    logp = nd.log_softmax(mlm_scores, axis=-1)
    # keep the log-probs on the same batch-over-data layout as the logits so
    # the backward path never re-lays-out the (B, M, V) tensor
    logp = nd._sharding_constraint(logp, spec=("data", None, None))
    # one-hot multiply-reduce instead of pick: take_along_axis transposes to
    # a scatter whose sharding GSPMD resolves by involuntary full remat
    # (round-3 MULTICHIP tail); the one-hot form keeps the cotangent an
    # elementwise product on the constrained layout and fuses on TPU
    oh = nd.one_hot(masked_labels.reshape((b * m,)), v)
    mlm_ll = (logp.reshape((b * m, v)) * oh).sum(axis=-1)
    w = masked_weights.reshape((b * m,))
    mlm_loss = -(mlm_ll * w).sum() / (w.sum() + 1e-6)
    nsp_logp = nd.log_softmax(nsp_scores, axis=-1)
    nsp_loss = -nd.pick(nsp_logp, nsp_labels, axis=-1).mean()
    return mlm_loss + nsp_loss
