"""DeepSeek-V2 (arXiv:2405.04434; ``deepseek-ai/DeepSeek-V2``).

Pre-norm decoder blocks, RMSNorm, no biases, an untied output head.
Attention is multi-head latent attention: queries through a low-rank pair
(``q_a``, ``q_b``), keys and values through one shared latent per token
(``kv_a`` down, ``kv_b`` up) plus one rotated key all heads share; rotary
positions under YaRN on the decoupled 64 dimensions only. The cache holds
the normalised latent and the rotated key, ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer in a vector of whole lane tiles,
and nothing decompressed (``F.latent_attention``). The first
``first_k_dense`` layers have a dense SwiGLU; the others an expert layer:
group-limited top-k over all routed
experts, the SwiGLUs of the experts THIS chip holds (``held_experts``; the
others' terms are their chips' to add) and the shared experts as one wider
SwiGLU (``F.held_expert_ffn``).

Served through ``inference.GenerationEngine(paged=True)``: the model
declares its per-layer state (``init_paged_cache``), its read path and its
logits' width; a cached forward returns the expert layers' counts of the
step third, beside the logits and the new cache.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock

__all__ = ["DeepseekV2Model", "get_deepseek_v2", "deepseek_v2_configs"]

deepseek_v2_configs = {
    # every size of the published config.json; the tiny one is for tests
    "deepseek_v2": dict(
        num_layers=60, units=5120, num_heads=128, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, hidden_size=12288, expert_hidden_size=1536,
        num_routed_experts=160, num_shared_experts=2, experts_per_token=6,
        n_group=8, topk_group=3, routed_scaling_factor=16.0,
        first_k_dense=1, vocab_size=102400, max_length=163840,
        rope_theta=10000.0, rope_factor=40.0, rope_original_max_length=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
        rope_mscale_all_dim=0.707, rms_norm_eps=1e-6),
    "deepseek_v2_tiny": dict(
        num_layers=3, units=64, num_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, hidden_size=96, expert_hidden_size=24,
        num_routed_experts=16, num_shared_experts=2, experts_per_token=3,
        n_group=4, topk_group=2, routed_scaling_factor=16.0,
        first_k_dense=1, vocab_size=200, max_length=256,
        rope_theta=10000.0, rope_factor=40.0, rope_original_max_length=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
        rope_mscale_all_dim=0.707, rms_norm_eps=1e-6),
}


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _dense(units, in_units, dtype, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    dtype=dtype, prefix=prefix,
                    weight_initializer=init.Normal(0.02))


class RMSNorm(HybridBlock):
    def __init__(self, in_channels, epsilon=1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         dtype=dtype, init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._eps)


class SwiGLU(HybridBlock):
    """``down(silu(gate(x)) * up(x))``."""

    def __init__(self, units, hidden_size, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate = _dense(hidden_size, units, dtype, "gate_")
            self.up = _dense(hidden_size, units, dtype, "up_")
            self.down = _dense(units, hidden_size, dtype, "down_")

    def hybrid_forward(self, F, x):
        return self.down(F.Activation(self.gate(x), act_type="silu")
                         * self.up(x))


class LatentAttention(HybridBlock):
    def __init__(self, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = self._cfg = cfg
        heads, units = c["num_heads"], c["units"]
        self._inv_freq = _rotary_inv_freq(c)
        # cos and sin carry mscale / mscale_all_dim; the scores' scale
        # carries mscale_all_dim squared (the published code's two places)
        self._rope_factor = (_yarn_mscale(c["rope_factor"], c["rope_mscale"])
                             / _yarn_mscale(c["rope_factor"],
                                            c["rope_mscale_all_dim"]))
        m = _yarn_mscale(c["rope_factor"], c["rope_mscale_all_dim"])
        self._scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 \
            * m * m
        with self.name_scope():
            self.q_a = _dense(c["q_lora_rank"], units, dtype, "q_a_")
            self.q_norm = RMSNorm(c["q_lora_rank"], c["rms_norm_eps"], dtype,
                                  prefix="q_norm_")
            self.q_b = _dense(heads * (c["qk_nope_head_dim"]
                                       + c["qk_rope_head_dim"]),
                              c["q_lora_rank"], dtype, "q_b_")
            self.kv_a = _dense(c["kv_lora_rank"] + c["qk_rope_head_dim"],
                               units, dtype, "kv_a_")
            self.kv_norm = RMSNorm(c["kv_lora_rank"], c["rms_norm_eps"], dtype,
                                   prefix="kv_norm_")
            self.kv_b_weight = self.params.get(
                "kv_b_weight", dtype=dtype, init=init.Normal(0.02),
                shape=(heads * (c["qk_nope_head_dim"] + c["v_head_dim"]),
                       c["kv_lora_rank"]))
            self.o = _dense(units, heads * c["v_head_dim"], dtype, "o_")

    def hybrid_forward(self, F, x, kv_b_weight, cache=None, start_pos=None,
                       page_table=None):
        c = self._cfg
        b, t, _ = x.shape
        nope, rope, kl = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                          c["kv_lora_rank"])
        rot = dict(position=start_pos, inv_freq=self._inv_freq,
                   factor=self._rope_factor)
        with jax.named_scope("q"):
            q = self.q_b(self.q_norm(self.q_a(x))).reshape(
                (b, t, c["num_heads"], nope + rope))
            q_nope = F.slice_axis(q, axis=-1, begin=0, end=nope)
            q_rope = F.rotary_embedding(
                F.slice_axis(q, axis=-1, begin=nope, end=nope + rope), **rot)
        with jax.named_scope("kv"):
            kv = self.kv_a(x)
            c_kv = self.kv_norm(F.slice_axis(kv, axis=-1, begin=0, end=kl))
            k_rope = F.rotary_embedding(
                F.slice_axis(kv, axis=-1, begin=kl, end=kl + rope), **rot)
        if cache is None:
            ctx = F.latent_attention(q_nope, q_rope, c_kv, k_rope, kv_b_weight,
                                     scale=self._scale)
        else:
            ctx, pool = F.latent_attention(
                q_nope, q_rope, c_kv, k_rope, kv_b_weight, scale=self._scale,
                cache=cache, position=start_pos, page_table=page_table)
        with jax.named_scope("out"):
            out = self.o(ctx)
        return out if cache is None else (out, (pool,))


#: the names of an ExpertLayer's counts, in the order it returns them
EXPERT_COUNTS = ("moe_pairs_held", "moe_max_load", "moe_whole_path")


class ExpertLayer(HybridBlock):
    """Top-k routed experts (those held here) + shared ones: group-limited
    over softmax probabilities, or (``cfg["scoring"] == "sigmoid"``) over
    sigmoid scores plus a learned selection bias, without groups.
    Returns (output, pairs routed to held experts, largest load of one,
    [1]: 1 where the call walked every sorted pair, 0 where their prefix)."""

    def __init__(self, cfg, held_experts, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = self._cfg = cfg
        self._held = tuple(int(e) for e in held_experts)
        units, width, held = c["units"], c["expert_hidden_size"], len(self._held)
        with self.name_scope():
            std = init.Normal(0.02)
            self.router_weight = self.params.get(
                "router_weight", shape=(c["num_routed_experts"], units),
                dtype=dtype, init=std)
            if c.get("scoring", "softmax") == "sigmoid":
                self.router_bias = self.params.get(
                    "router_bias", shape=(c["num_routed_experts"],),
                    dtype=dtype, init=std)
            # the held experts stacked, (in, out) as the grouped product reads
            self.gate_weight = self.params.get(
                "experts_gate_weight", shape=(held, units, width), dtype=dtype,
                init=std)
            self.up_weight = self.params.get(
                "experts_up_weight", shape=(held, units, width), dtype=dtype,
                init=std)
            self.down_weight = self.params.get(
                "experts_down_weight", shape=(held, width, units), dtype=dtype,
                init=std)
            self.shared = SwiGLU(units, c["num_shared_experts"] * width, dtype,
                                 prefix="shared_")

    def hybrid_forward(self, F, x, router_weight, gate_weight, up_weight,
                       down_weight, router_bias=None):
        c = self._cfg
        how = {} if router_bias is None else dict(
            scoring="sigmoid", router_bias=router_bias,
            norm_topk_prob=c["norm_topk_prob"])
        routed, *counts = F.held_expert_ffn(
            x, router_weight, gate_weight, up_weight, down_weight,
            held_experts=self._held, n_group=c.get("n_group", 1),
            topk_group=c.get("topk_group", 1), top_k=c["experts_per_token"],
            scale=c["routed_scaling_factor"], **how)
        return (routed + self.shared(x), *counts)


class DeepseekV2Block(HybridBlock):
    """Returns ``x``; with ``cache=``, ``(x, layer's cache, counts)``, the
    counts an expert layer's (pairs, largest load, whole-length calls) and a
    dense layer's None."""

    def __init__(self, cfg, dense, held_experts, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        eps = cfg["rms_norm_eps"]
        self._dense = dense
        with self.name_scope():
            self.attn_norm = RMSNorm(cfg["units"], eps, dtype,
                                     prefix="attn_norm_")
            self.mla = LatentAttention(cfg, dtype, prefix="mla_")
            self.ffn_norm = RMSNorm(cfg["units"], eps, dtype,
                                    prefix="ffn_norm_")
            if dense:
                self.ffn = SwiGLU(cfg["units"], cfg["hidden_size"], dtype,
                                  prefix="ffn_")
            else:
                self.ffn = ExpertLayer(cfg, held_experts, dtype, prefix="moe_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None):
        if cache is None:
            x = x + self.mla(self.attn_norm(x))
        else:
            att, cache = self.mla(self.attn_norm(x), cache=cache,
                                  start_pos=start_pos, page_table=page_table)
            x = x + att
        y, counts = self.ffn(self.ffn_norm(x)), None
        if not self._dense:
            y, *counts = y
        x = x + y
        return x if cache is None else (x, cache, counts)


def _rotary_inv_freq(c):
    from ..ops.attention import yarn_inv_freq

    return yarn_inv_freq(c["qk_rope_head_dim"], c["rope_theta"],
                         c["rope_factor"], c["rope_original_max_length"],
                         c["rope_beta_fast"], c["rope_beta_slow"])


class DeepseekV2Model(HybridBlock):
    """``held_experts``: the ids of the routed experts this chip holds in
    every expert layer (default: all of them). ``dtype``: the parameters'."""

    def __init__(self, held_experts=None, dtype="float32", **cfg):
        known = deepseek_v2_configs["deepseek_v2"]
        super().__init__(prefix=cfg.pop("prefix", None))
        if set(cfg) - set(known):
            raise TypeError(f"unknown sizes {sorted(set(cfg) - set(known))}")
        c = self._cfg = dict(known, **cfg)
        self._max_length = c["max_length"]
        self._held = tuple(range(c["num_routed_experts"])
                           if held_experts is None else held_experts)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                c["vocab_size"], c["units"], dtype=dtype, prefix="word_embed_",
                weight_initializer=init.Normal(0.02))
            self.blocks = nn.HybridSequential(prefix="")
            for i in range(c["num_layers"]):
                self.blocks.add(DeepseekV2Block(
                    c, i < c["first_k_dense"], self._held, dtype,
                    prefix=f"layer{i}_"))
            self.norm = RMSNorm(c["units"], c["rms_norm_eps"], dtype,
                                prefix="norm_")
            self.head = _dense(c["vocab_size"], c["units"], dtype, "head_")

    # -- what a paged engine asks of a model (docs/INFERENCE.md) -------------
    @property
    def cache_width(self):
        return self._cfg["kv_lora_rank"] + self._cfg["qk_rope_head_dim"]

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        """Per-layer ``(pool,)`` of shape (num_pages + 1, page_size, W): the
        latent cache, ``W`` the ``cache_width`` values a token in whole lane
        tiles (``alloc_paged_latent_cache``)."""
        from ..ops.attention import alloc_paged_latent_cache

        return alloc_paged_latent_cache(num_pages, page_size, self.cache_width,
                                        self._cfg["num_layers"], dtype=dtype)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program will read the latent pools
        by: the Pallas kernel that fetches the pages a row holds, or the XLA
        ``pool[page_table]`` gather and why (``F.latent_attention`` makes the
        same choice from the same shapes at trace time). One token a row is
        read in the form ``mla_form`` names."""
        from ..ops.attention import mla_form
        from ..ops.pallas_paged_attention import paged_latent_attention_refusal

        c = self._cfg
        form = mla_form(1, c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"], c["kv_lora_rank"])
        q = jax.ShapeDtypeStruct(
            (batch_size, 1, c["num_heads"], c["qk_nope_head_dim"]),
            self.word_embed.weight.data()._data.dtype)
        why = paged_latent_attention_refusal(q, pools[0][0], page_table, form)
        return (f"xla_gather_latent ({form}; {why})" if why
                else f"pallas_paged_latent_kernel ({form})")

    def logits_width(self):
        return self._cfg["vocab_size"]

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None):
        """Logits; with ``cache=``, ``(logits, new_cache, counts)``:
        ``counts`` is {name: (expert layers,) int32} of this forward, the
        pairs routed to held experts and the largest load of one, and
        ``moe_whole_path`` (expert layers, calls a layer): 1 where a call's
        held pairs passed the prefix of ``held_expert_ffn``."""
        x = self.word_embed(token_ids)
        new_cache, counts = [], []
        for i, blk in enumerate(self.blocks):
            if cache is None:
                x = blk(x)
            else:
                x, layer_cache, layer_counts = blk(
                    x, cache=cache[i], start_pos=start_pos,
                    page_table=page_table)
                new_cache.append(layer_cache)
                if layer_counts is not None:
                    counts.append([c._data for c in layer_counts])
        # float32 logits: in bfloat16 neighbouring logits tie and the
        # argmax would take the first of them
        logits = self.head(self.norm(x).astype("float32"))
        if cache is None:
            return logits
        return logits, new_cache, {
            name: jnp.stack(of_layers) for name, of_layers
            in zip(EXPERT_COUNTS, zip(*counts))}


def get_deepseek_v2(model_name="deepseek_v2", **overrides):
    cfg = dict(deepseek_v2_configs[model_name])
    held = overrides.pop("held_experts", None)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return DeepseekV2Model(held_experts=held, dtype=dtype, **cfg)
