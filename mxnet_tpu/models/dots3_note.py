"""dots3-note-prev (``dots-studio/dots3-note-prev`` ``config.json``; 288B-A17B).

Pre-norm decoder blocks, RMSNorm, no biases, an untied output head; every
attention sublayer is multi-head latent attention with a headwise output gate
(arXiv:2505.06708), in two kinds (``layer_types``):

* a *full* layer (``F.sparse_latent_attention``) caches, a token, the
  normalised latent, the rotated shared key and the indexer's key, and
  attends the ``index_topk`` positions its indexer scores highest
  (DeepSeek-V3.2-Exp's sparse attention): a decode step scores the index keys
  of the pages a row holds, selects, and reads the selected latents only;
* a *window* layer (``F.windowed_latent_attention``) has a latent of its own
  sizes (``swa_*``) and attends the last ``sliding_window`` positions; its
  pools belong to the engine's ``window`` page group, which frees the pages
  behind the window while the row lives.

The first ``first_k_dense`` layers have a dense SwiGLU, the others an expert
layer of :mod:`deepseek_v2`'s kind with sigmoid scores, a learned selection
bias and no groups. RMSNorm, SwiGLU and the expert layer are that module's.

Served through ``inference.GenerationEngine(paged=True)``: the model declares
its per-layer state AND the page group each layer's pools belong to
(``init_paged_cache``), takes one page table a group, and is told a prefill's
last real position (``takes_last_pos``) so that a 16,384-token chunk computes
one row of logits and a window layer writes only the pages its row keeps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from .deepseek_v2 import EXPERT_COUNTS, ExpertLayer, RMSNorm, SwiGLU, _dense

__all__ = ["Dots3NoteModel", "get_dots3_note", "dots3_note_configs"]

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3

dots3_note_configs = {
    # every size of the published config.json; the tiny one is for tests
    "dots3_note": dict(
        num_layers=46, units=5120,
        layer_types=("full_attention",) + tuple(
            _PERIOD[i % 4] for i in range(45)),
        num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=8e7, attention_gate=True,
        swa_num_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
        swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64, swa_v_head_dim=128,
        swa_rope_theta=5e4, swa_attention_gate=True, sliding_window=513,
        index_n_heads=64, index_head_dim=128, index_topk=2048,
        lora_rescale=True, hidden_size=13824, expert_hidden_size=1536,
        num_routed_experts=256, num_shared_experts=1, experts_per_token=8,
        routed_scaling_factor=1.0, norm_topk_prob=True, first_k_dense=1,
        vocab_size=152064, max_length=524288, rms_norm_eps=1e-5),
    "dots3_note_tiny": dict(
        num_layers=4, units=64,
        layer_types=("full_attention", "full_attention", "sliding_attention",
                     "sliding_attention"),
        num_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7, attention_gate=True,
        swa_num_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=40,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        swa_rope_theta=5e4, swa_attention_gate=True, sliding_window=5,
        index_n_heads=4, index_head_dim=16, index_topk=8,
        lora_rescale=True, hidden_size=96, expert_hidden_size=24,
        num_routed_experts=16, num_shared_experts=1, experts_per_token=3,
        routed_scaling_factor=1.0, norm_topk_prob=True, first_k_dense=1,
        vocab_size=200, max_length=256, rms_norm_eps=1e-5),
}

#: tokens a feed-forward sublayer takes at once in a long prefill: the
#: gathered (token, expert) pairs of 16,384 tokens would not fit (1.3 GB, and
#: 2.7 GB of float32 behind them). Compiled for a v5e at 16,384 tokens,
#: blocks of 4,096 leave the program 2.47 GB of temporaries and 100 MB of
#: code; blocks of 2,048 2.85 GB and 171 MB (PERF.md, PR 31)
_FFN_TOKENS = 4096
_INDEX_NORM_EPS = 1e-6


def _inv_freq(dim, theta):
    return tuple(1.0 / theta ** (i / dim) for i in range(0, dim, 2))


class _LayerNorm(HybridBlock):
    def __init__(self, in_channels, epsilon, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         dtype=dtype, init="ones")
            self.beta = self.params.get("beta", shape=(in_channels,),
                                        dtype=dtype, init="zeros")

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, eps=self._eps)


class LatentSublayer(HybridBlock):
    """One attention sublayer of either kind, on its (normed) input.
    Returns the output; with ``cache=``, ``(output, layer's cache, counts)``,
    the counts a full layer's (positions read, positions held), a window
    layer's None."""

    def __init__(self, cfg, kind, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c, p = cfg, "swa_" if kind == "window" else ""
        self._kind = kind
        self._heads = heads = c[p + "num_heads"]
        self._nope, self._rope = c[p + "qk_nope_head_dim"], c[p + "qk_rope_head_dim"]
        self._kl, ql, vd = c[p + "kv_lora_rank"], c[p + "q_lora_rank"], c[p + "v_head_dim"]
        units, eps = c["units"], c["rms_norm_eps"]
        self._inv_freq = _inv_freq(self._rope, c[p + "rope_theta"])
        self._scale = (self._nope + self._rope) ** -0.5
        # the latents' rescale (assumed: LongCat-Flash's convention)
        self._q_scale = math.sqrt(units / ql) if c["lora_rescale"] else 1.0
        self._kv_scale = math.sqrt(units / self._kl) if c["lora_rescale"] else 1.0
        self._window, self._top_k = c["sliding_window"], c["index_topk"]
        self._index = (c["index_n_heads"], c["index_head_dim"])
        std = init.Normal(0.02)
        with self.name_scope():
            self.q_a = _dense(ql, units, dtype, "q_a_")
            self.q_norm = RMSNorm(ql, eps, dtype, prefix="q_norm_")
            self.q_b_weight = self.params.get(
                "q_b_weight", dtype=dtype, init=std,
                shape=(heads * (self._nope + self._rope), ql))
            self.kv_a = _dense(self._kl + self._rope, units, dtype, "kv_a_")
            self.kv_norm = RMSNorm(self._kl, eps, dtype, prefix="kv_norm_")
            self.kv_b_weight = self.params.get(
                "kv_b_weight", dtype=dtype, init=std,
                shape=(heads * (self._nope + vd), self._kl))
            self.gate = _dense(heads, units, dtype, "gate_") \
                if c[p + "attention_gate"] else None
            self.o = _dense(units, heads * vd, dtype, "o_")
            if kind == "full":
                j, d = self._index
                self.index_q_b = _dense(j * d, ql, dtype, "index_q_b_")
                self.index_k = _dense(d, units, dtype, "index_k_")
                self.index_k_norm = _LayerNorm(d, _INDEX_NORM_EPS, dtype,
                                               prefix="index_k_norm_")
                self.index_w = _dense(j, units, dtype, "index_w_")

    def _rotated_head(self, F, x, start_pos):
        """``x`` (..., D) with its first ``rope`` dims rotated."""
        r = self._rope
        turned = F.rotary_embedding(
            F.slice_axis(x, axis=-1, begin=0, end=r), position=start_pos,
            inv_freq=self._inv_freq)
        return F.concat(turned, F.slice_axis(x, axis=-1, begin=r, end=None),
                        dim=-1)

    def hybrid_forward(self, F, x, q_b_weight, kv_b_weight, cache=None,
                       start_pos=None, page_table=None, last_pos=None):
        b, t, _ = x.shape
        kl, rope = self._kl, self._rope
        with jax.named_scope("q"):
            c_q = self.q_norm(self.q_a(x))
        with jax.named_scope("kv"):
            kv = self.kv_a(x)
            c_kv = self.kv_norm(F.slice_axis(kv, axis=-1, begin=0, end=kl)) \
                * self._kv_scale
            k_rope = F.rotary_embedding(
                F.slice_axis(kv, axis=-1, begin=kl, end=kl + rope),
                position=start_pos, inv_freq=self._inv_freq)
        paged = {} if cache is None else dict(
            cache=cache, position=start_pos, page_table=page_table)
        common = dict(heads=self._heads, inv_freq=self._inv_freq,
                      scale=self._scale, **paged)
        if self.gate is not None:  # headwise: one scalar a head and token
            with jax.named_scope("attn"), jax.named_scope("gate"):
                common["gate"] = F.sigmoid(
                    self.gate(x).astype("float32")).astype(x.dtype)
        counts = None
        if self._kind == "full":
            j, d = self._index
            with jax.named_scope("dsa"), jax.named_scope("index"):
                idx_q = self._rotated_head(
                    F, self.index_q_b(c_q).reshape((b, t, j, d)), start_pos)
                idx_k = self._rotated_head(
                    F, self.index_k_norm(self.index_k(x)), start_pos)
                idx_w = self.index_w(x).astype("float32") \
                    * (j ** -0.5 * d ** -0.5)
            out = F.sparse_latent_attention(
                c_q * self._q_scale, q_b_weight, c_kv, k_rope, kv_b_weight,
                idx_q, idx_k, idx_w, top_k=self._top_k, **common)
            if cache is not None:
                out, *pools, read, held = out
                counts = (read, held)
        else:
            if cache is not None and last_pos is not None:
                common["last_pos"] = last_pos
            out = F.windowed_latent_attention(
                c_q * self._q_scale, q_b_weight, c_kv, k_rope, kv_b_weight,
                window=self._window, **common)
            if cache is not None:
                out, *pools = out
        with jax.named_scope("out"):
            out = self.o(out)
        return out if cache is None else (out, tuple(pools), counts)


def _in_token_blocks(ffn, *xs):
    """``ffn(*xs)`` with the tokens of every ``x`` (B, T, d) walked in blocks
    of ``_FFN_TOKENS`` where there are more: a dense layer's output alone; of
    an expert layer's (output, pairs, largest load, ...) the pairs added up,
    every later count's largest, and a count with an entry a call (the
    route's, a vector) an entry a block."""
    t = xs[0].shape[1]
    if t <= _FFN_TOKENS or t % _FFN_TOKENS:
        return ffn(*xs)

    # one copy of the sublayer a block, in order (unrolled: inside a
    # ``lax.map`` body XLA:TPU's scatter emitter aborts on the expert
    # layer's scatter-add); each block's rows go where the last one's were
    outs = [ffn(*(NDArray(x._data[:, at:at + _FFN_TOKENS]) for x in xs))
            for at in range(0, t, _FFN_TOKENS)]
    join = lambda ys: NDArray(jnp.concatenate([y._data for y in ys], axis=1))  # noqa: E731
    if isinstance(outs[0], NDArray):
        return join(outs)
    ys, pairs, *counts = zip(*outs)
    return (join(ys), NDArray(sum(p._data for p in pairs)),
            *(NDArray(jnp.concatenate([m._data for m in ms])) if ms[0].ndim
              else NDArray(jnp.stack([m._data for m in ms]).max())
              for ms in counts))


class Dots3NoteBlock(HybridBlock):
    """Returns ``x``; with ``cache=``, ``(x, layer's cache, attention's
    counts, expert layer's counts)``, either None where the layer has none."""

    def __init__(self, cfg, kind, dense, held_experts, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        eps = cfg["rms_norm_eps"]
        self._dense = dense
        with self.name_scope():
            self.attn_norm = RMSNorm(cfg["units"], eps, dtype,
                                     prefix="attn_norm_")
            self.mla = LatentSublayer(cfg, kind, dtype, prefix="mla_")
            self.ffn_norm = RMSNorm(cfg["units"], eps, dtype,
                                    prefix="ffn_norm_")
            if dense:
                self.ffn = SwiGLU(cfg["units"], cfg["hidden_size"], dtype,
                                  prefix="ffn_")
            else:
                self.ffn = ExpertLayer(dict(cfg, scoring="sigmoid"),
                                       held_experts, dtype, prefix="moe_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None,
                       last_pos=None):
        read = None
        if cache is None:
            x = x + self.mla(self.attn_norm(x))
        else:
            att, cache, read = self.mla(
                self.attn_norm(x), cache=cache, start_pos=start_pos,
                page_table=page_table, last_pos=last_pos)
            x = x + att
        y, loads = _in_token_blocks(self.ffn, self.ffn_norm(x)), None
        if not self._dense:
            y, *loads = y
        x = x + y
        return x if cache is None else (x, cache, read, loads)


class Dots3NoteModel(HybridBlock):
    """``held_experts``: the ids of the routed experts this chip holds in
    every expert layer (default: all of them). ``dtype``: the parameters'."""

    #: a paged engine passes ``last_pos=`` ((1,) int32: a prefill's last real
    #: position) and gets the logits of that position alone, (1, 1, V)
    takes_last_pos = True

    def __init__(self, held_experts=None, dtype="float32", **cfg):
        known = dots3_note_configs["dots3_note"]
        super().__init__(prefix=cfg.pop("prefix", None))
        if set(cfg) - set(known):
            raise TypeError(f"unknown sizes {sorted(set(cfg) - set(known))}")
        c = self._cfg = dict(known, **cfg)
        c["layer_types"] = tuple(c["layer_types"])[:c["num_layers"]]
        if len(c["layer_types"]) != c["num_layers"]:
            raise ValueError("layer_types names fewer layers than num_layers")
        self._max_length = c["max_length"]
        self._kinds = tuple("window" if k == "sliding_attention" else "full"
                            for k in c["layer_types"])
        self._held = tuple(range(c["num_routed_experts"])
                           if held_experts is None else held_experts)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                c["vocab_size"], c["units"], dtype=dtype, prefix="word_embed_",
                weight_initializer=init.Normal(0.02))
            self.blocks = nn.HybridSequential(prefix="")
            for i, kind in enumerate(self._kinds):
                self.blocks.add(Dots3NoteBlock(
                    c, kind, i < c["first_k_dense"], self._held, dtype,
                    prefix=f"layer{i}_"))
            self.norm = RMSNorm(c["units"], c["rms_norm_eps"], dtype,
                                prefix="norm_")
            self.head = _dense(c["vocab_size"], c["units"], dtype, "head_")

    # -- what a paged engine asks of a model (docs/INFERENCE.md) -------------
    @property
    def paged_pool_groups(self):
        """{group: rule} of the page groups the layers' pools belong to: a
        full layer keeps every position (``all``), a window layer the last
        ``sliding_window`` (``window``: the engine frees the pages behind)."""
        groups = {}
        if "full" in self._kinds:
            groups["all"] = {}
        if "window" in self._kinds:
            groups["window"] = {"window": self._cfg["sliding_window"]}
        return groups

    def cache_widths(self):
        """Values a token a layer of each kind caches: (latent + rotated
        key, indexer's key) of a full layer, (latent + rotated key,) of a
        window layer."""
        c = self._cfg
        return {"full": (c["kv_lora_rank"] + c["qk_rope_head_dim"],
                         c["index_head_dim"]),
                "window": (c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"],)}

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        """``(pools, groups)``: per layer a tuple of pools of shape (its
        group's pages + 1, page_size, W), ``W`` the cached values in whole
        lane tiles (``alloc_paged_latent_cache``) — a full layer's latent
        pool and index-key pool, a window layer's latent pool — and the
        group each layer's pools belong to. ``num_pages`` is {group: pages}."""
        from ..ops.attention import alloc_paged_latent_cache

        group_of = {"full": "all", "window": "window"}
        widths = self.cache_widths()
        pools = [tuple(alloc_paged_latent_cache(
            num_pages[group_of[kind]], page_size, w, 1, dtype=dtype)[0][0]
            for w in widths[kind]) for kind in self._kinds]
        return pools, tuple(group_of[kind] for kind in self._kinds)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program reads the pools by: a full
        layer's index keys (the Pallas kernel that copies the pages a row
        holds, or the XLA gather and why: ``F.sparse_latent_attention`` makes
        the same choice from the same shapes at trace time), its selection
        and its sparse read; a window layer's ring; and what a prefill
        program's full layers attend their chunk by."""
        from ..ops.attention import SPARSE_READ_BY_XLA
        from ..ops.flash_attention import masked_prefill_refusal
        from ..ops.pallas_common import LANES
        from ..ops.pallas_paged_attention import paged_index_scores_refusal

        c, out = self._cfg, []
        full = next((p for p, k in zip(pools, self._kinds) if k == "full"), None)
        if full is not None:
            table = page_table[0] if isinstance(page_table, tuple) else page_table
            q = jax.ShapeDtypeStruct(
                (batch_size, 1, c["index_n_heads"], c["index_head_dim"]),
                self.word_embed.weight.data()._data.dtype)
            why = paged_index_scores_refusal(q, full[1], table)
            out.append("full layers: index scores "
                       + (f"xla_gather_index ({why})" if why
                          else "paged_index_scores kernel")
                       + ", selection lax.top_k, sparse read xla_gather_rows ("
                       + SPARSE_READ_BY_XLA + ")")
            # a prefill chunk of whole lane tiles, a block of heads at a time
            block = lambda d: jax.ShapeDtypeStruct((1, LANES, 16, d), q.dtype)  # noqa: E731
            qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
            why = masked_prefill_refusal(
                block(qk), block(qk), block(c["v_head_dim"]),
                jax.ShapeDtypeStruct((1, LANES, LANES), bool))
            out.append("full layers: prefill attention "
                       + (f"chunk_mask ({why})" if why
                          else "masked_prefill kernel"))
        if "window" in self._kinds:
            out.append("window layers: xla_gather_ring")
        return "; ".join(out)

    def logits_width(self):
        return self._cfg["vocab_size"]

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None, last_pos=None):
        """Logits; with ``cache=``, ``(logits, new_cache, counts)``:
        ``page_table`` is one table a group in ``paged_pool_groups``' order
        (or the one table where there is one group); ``counts`` is {name:
        (layers that count it,) int32} of this forward: ``dsa_read`` and
        ``dsa_held`` of the full layers, ``moe_pairs_held``,
        ``moe_max_load`` and ``moe_whole_path`` (an entry a call of the
        layer: a token block) of the expert layers. With ``last_pos=`` the
        logits are those of that position alone."""
        x = self.word_embed(token_ids)
        tables = {}
        if cache is not None:
            names = list(self.paged_pool_groups)
            given = page_table if isinstance(page_table, (tuple, list)) \
                else (page_table,)
            tables = dict(zip(names, given))
        new_cache, reads, loads = [], [], []
        for i, (blk, kind) in enumerate(zip(self.blocks, self._kinds)):
            if cache is None:
                x = blk(x)
                continue
            x, layer_cache, read, load = blk(
                x, cache=cache[i], start_pos=start_pos, last_pos=last_pos,
                page_table=tables["all" if kind == "full" else "window"])
            new_cache.append(layer_cache)
            if read is not None:
                reads.append([r._data for r in read])
            if load is not None:
                loads.append([c._data for c in load])
        if last_pos is not None:
            at = jnp.asarray(last_pos._data, jnp.int32).reshape(-1)[0]
            x = NDArray(jax.lax.dynamic_slice_in_dim(x._data, at, 1, axis=1))
        # float32 logits: in bfloat16 neighbouring logits tie and the
        # argmax would take the first of them
        logits = self.head(self.norm(x).astype("float32"))
        if cache is None:
            return logits
        counts = {}
        for names, rows in ((("dsa_read", "dsa_held"), reads),
                            (EXPERT_COUNTS, loads)):
            for name, of_layers in zip(names, zip(*rows)):
                counts[name] = jnp.stack(of_layers).astype(jnp.int32)
        return logits, new_cache, counts


def get_dots3_note(model_name="dots3_note", **overrides):
    cfg = dict(dots3_note_configs[model_name])
    held = overrides.pop("held_experts", None)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return Dots3NoteModel(held_experts=held, dtype=dtype, **cfg)
