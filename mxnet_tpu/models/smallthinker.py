"""SmallThinker-21BA3B-Instruct (``PowerInfer/SmallThinker-21BA3B-Instruct``
``config.json``; arXiv:2507.20984; 21B-A3B).

Pre-norm decoder blocks, RMSNorm, no biases, an untied output head. A block
is ::

    h  = RMSNorm(x)                       # the block's normed INPUT
    r  = h Wr^T                           # router logits: read BEFORE attention
    x1 = x + attention(h)
    u  = RMSNorm(x1)
    x2 = x1 + sum_i p_i * down_i(relu(gate_i u) * (up_i u))   # top-k of r

Attention has grouped key-value heads (``num_heads`` query heads over
``num_kv_heads``) in two kinds of layer: where ``window_layout[l]`` is 1 a
causal window of ``sliding_window`` positions (the token itself counted),
else every position; where ``rope_layout[l]`` is 1 rotary positions on
queries and keys (all of a head's dims, two halves), else none. The
published model pairs them: three window layers with positions to one full
layer without. The expert layer is ``F.held_expert_ffn`` with the router
reading ``h`` and ReLU-gated experts (ReGLU), softmax scores, the chosen
normalised, no shared expert.

Served through ``inference.GenerationEngine(paged=True)``: ordinary key and
value pools ``(P+1, page, Hkv*Ch)`` in two page groups (``init_paged_cache``:
a full layer's in ``all``, a window layer's in ``window``, whose pages behind
the window the engine frees while the row lives), one page table a group,
``takes_last_pos`` (a prefill computes one row of logits, and a window layer
writes only the pages its row keeps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from .deepseek_v2 import RMSNorm, _dense
from .dots3_note import _in_token_blocks

__all__ = ["SmallThinkerModel", "get_smallthinker", "smallthinker_configs"]

_PERIOD = (0, 1, 1, 1)

smallthinker_configs = {
    # every size of the published config.json; the tiny one is for tests
    "smallthinker_21b": dict(
        num_layers=52, units=2560, num_heads=28, num_kv_heads=4, head_dim=128,
        sliding_window=4096, window_layout=_PERIOD * 13,
        rope_layout=_PERIOD * 13, rope_theta=1.5e6, expert_hidden_size=768,
        num_routed_experts=64, experts_per_token=6, norm_topk_prob=True,
        vocab_size=151936, max_length=16384, rms_norm_eps=1e-6),
    "smallthinker_tiny": dict(
        num_layers=4, units=64, num_heads=6, num_kv_heads=2, head_dim=16,
        sliding_window=5, window_layout=_PERIOD, rope_layout=_PERIOD,
        rope_theta=1.5e6, expert_hidden_size=24, num_routed_experts=8,
        experts_per_token=3, norm_topk_prob=True, vocab_size=200,
        max_length=256, rms_norm_eps=1e-6),
}

class GroupedAttention(HybridBlock):
    """One attention sublayer on its (normed) input: grouped key-value
    heads, a window or none, rotary positions or none. Returns the output;
    with ``cache=``, ``(output, (k_pool, v_pool))``."""

    def __init__(self, cfg, window, rope, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = cfg
        self._heads, self._kv_heads = c["num_heads"], c["num_kv_heads"]
        self._ch = ch = c["head_dim"]
        self._window = c["sliding_window"] if window else None
        self._inv_freq = tuple(1.0 / c["rope_theta"] ** (i / ch)
                               for i in range(0, ch, 2)) if rope else None
        units = c["units"]
        with self.name_scope():
            self.q = _dense(self._heads * ch, units, dtype, "q_")
            self.k = _dense(self._kv_heads * ch, units, dtype, "k_")
            self.v = _dense(self._kv_heads * ch, units, dtype, "v_")
            self.o = _dense(units, self._heads * ch, dtype, "o_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None,
                       last_pos=None):
        b, t, _ = x.shape
        with jax.named_scope("qkv"):
            q = self.q(x).reshape((b, t, self._heads, self._ch))
            k = self.k(x).reshape((b, t, self._kv_heads, self._ch))
            v = self.v(x).reshape((b, t, self._kv_heads, self._ch))
        if self._inv_freq is not None:
            with jax.named_scope("rope"):
                q = F.rotary_embedding(q, position=start_pos,
                                       inv_freq=self._inv_freq)
                k = F.rotary_embedding(k, position=start_pos,
                                       inv_freq=self._inv_freq)
        heads_first = lambda z: z.transpose((0, 2, 1, 3))  # noqa: E731
        paged = {} if cache is None else dict(
            cache=cache, position=start_pos, page_table=page_table)
        if cache is not None and self._window is not None \
                and last_pos is not None:
            paged["last_pos"] = last_pos
        with jax.named_scope("core"):
            out = F.multi_head_attention(
                heads_first(q), heads_first(k), heads_first(v), causal=True,
                window=self._window, **paged)
        pools = None
        if cache is not None:
            out, *pools = out
        with jax.named_scope("out"):
            out = self.o(out.transpose((0, 2, 1, 3)).reshape((b, t, -1)))
        return out if cache is None else (out, tuple(pools))


class RoutedExperts(HybridBlock):
    """Top-k routed ReLU-gated experts (those held here), no shared one;
    the router reads another tensor than the experts. Returns (output,
    pairs routed to held experts, largest load of one, held experts that
    drew a pair)."""

    def __init__(self, cfg, held_experts, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = self._cfg = cfg
        self._held = tuple(int(e) for e in held_experts)
        units, width, held = c["units"], c["expert_hidden_size"], len(self._held)
        std = init.Normal(0.02)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(c["num_routed_experts"], units),
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

    def hybrid_forward(self, F, x, router_x, router_weight, gate_weight,
                       up_weight, down_weight):
        c = self._cfg
        return F.held_expert_ffn_hit(
            x, router_weight, gate_weight, up_weight, down_weight,
            router_data=router_x, activation="relu", held_experts=self._held,
            top_k=c["experts_per_token"], norm_topk_prob=c["norm_topk_prob"])


class SmallThinkerBlock(HybridBlock):
    """Returns ``x``; with ``cache=``, ``(x, layer's cache, expert layer's
    counts)``."""

    def __init__(self, cfg, window, rope, held_experts, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        eps = cfg["rms_norm_eps"]
        with self.name_scope():
            self.attn_norm = RMSNorm(cfg["units"], eps, dtype,
                                     prefix="attn_norm_")
            self.attn = GroupedAttention(cfg, window, rope, dtype,
                                         prefix="attn_")
            self.ffn_norm = RMSNorm(cfg["units"], eps, dtype,
                                    prefix="ffn_norm_")
            self.moe = RoutedExperts(cfg, held_experts, dtype, prefix="moe_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None,
                       last_pos=None):
        h = self.attn_norm(x)   # the router reads this, before attention
        if cache is None:
            x = x + self.attn(h)
        else:
            att, cache = self.attn(h, cache=cache, start_pos=start_pos,
                                   page_table=page_table, last_pos=last_pos)
            x = x + att
        # a long prefill's tokens in blocks: the gathered (token, expert)
        # pairs of 8,192 tokens and the float32 products behind them are
        # 1.2 GB, in blocks of 4,096 half that
        y, *loads = _in_token_blocks(self.moe, self.ffn_norm(x), h)
        x = x + y
        return x if cache is None else (x, cache, loads)


class SmallThinkerModel(HybridBlock):
    """``held_experts``: the ids of the routed experts this chip holds in
    every layer (default: all of them). ``dtype``: the parameters'."""

    #: a paged engine passes ``last_pos=`` ((1,) int32: a prefill's last real
    #: position) and gets the logits of that position alone, (1, 1, V)
    takes_last_pos = True

    def __init__(self, held_experts=None, dtype="float32", **cfg):
        known = smallthinker_configs["smallthinker_21b"]
        super().__init__(prefix=cfg.pop("prefix", None))
        if set(cfg) - set(known):
            raise TypeError(f"unknown sizes {sorted(set(cfg) - set(known))}")
        c = self._cfg = dict(known, **cfg)
        n = c["num_layers"]
        for key in ("window_layout", "rope_layout"):
            c[key] = tuple(int(bool(z)) for z in c[key])[:n]
            if len(c[key]) != n:
                raise ValueError(f"{key} names fewer layers than num_layers")
        if c["num_heads"] % c["num_kv_heads"]:
            raise ValueError(f"{c['num_heads']} query heads are not whole "
                             f"groups over {c['num_kv_heads']} key-value heads")
        self._max_length = c["max_length"]
        self._kinds = tuple("window" if w else "full"
                            for w in c["window_layout"])
        self._held = tuple(range(c["num_routed_experts"])
                           if held_experts is None else held_experts)
        with self.name_scope():
            self.word_embed = nn.Embedding(
                c["vocab_size"], c["units"], dtype=dtype, prefix="word_embed_",
                weight_initializer=init.Normal(0.02))
            self.blocks = nn.HybridSequential(prefix="")
            for i, (w, r) in enumerate(zip(c["window_layout"],
                                           c["rope_layout"])):
                self.blocks.add(SmallThinkerBlock(
                    c, w, r, self._held, dtype, prefix=f"layer{i}_"))
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

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        """``(pools, groups)``: per layer ``(k_pool, v_pool)`` of shape (its
        group's pages + 1, page_size, Hkv * Ch)
        (``attention.alloc_paged_kv_cache``), and the group each layer's
        pools belong to. ``num_pages`` is {group: pages}."""
        from ..ops.attention import alloc_paged_kv_cache

        c = self._cfg
        group_of = {"full": "all", "window": "window"}
        pools = [alloc_paged_kv_cache(
            num_pages[group_of[kind]], c["num_kv_heads"], page_size,
            c["head_dim"], 1, dtype=dtype)[0] for kind in self._kinds]
        return pools, tuple(group_of[kind] for kind in self._kinds)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program reads the pools by, a line a
        kind of layer: the Pallas kernel ``paged_gqa_decode`` or the XLA
        gather and why (``F.multi_head_attention`` makes the same choice
        from the same shapes at trace time)."""
        from ..ops.pallas_paged_attention import paged_gqa_refusal

        c, out = self._cfg, []
        tables = dict(zip(self.paged_pool_groups, page_table
                          if isinstance(page_table, tuple) else (page_table,)))
        q = jax.ShapeDtypeStruct(
            (batch_size, c["num_heads"], 1, c["head_dim"]),
            self.word_embed.weight.data()._data.dtype)
        for kind, group, window in (("full", "all", None),
                                    ("window", "window", c["sliding_window"])):
            pool = next((p for p, k in zip(pools, self._kinds) if k == kind),
                        None)
            if pool is None:
                continue
            why = paged_gqa_refusal(q, pool[0], tables[group], window)
            out.append(f"{kind} layers: "
                       + (f"xla_gather ({why})" if why else "gqa_kernel"))
        return "; ".join(out)

    def logits_width(self):
        return self._cfg["vocab_size"]

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None, last_pos=None):
        """Logits; with ``cache=``, ``(logits, new_cache, counts)``:
        ``page_table`` is one table a group in ``paged_pool_groups``' order;
        ``counts`` is {name: (layers,) int32} of this forward:
        ``moe_pairs_held``, ``moe_max_load`` and ``moe_experts_hit`` of every
        layer, ``attn_read_full`` and ``attn_read_window`` (the positions
        the rows' softmaxes read, summed over the rows, a query's share of a
        chunk) of the layers of each kind. With ``last_pos=`` the logits
        are those of that position alone."""
        x = self.word_embed(token_ids)
        tables, reads = {}, {"full": [], "window": []}
        if cache is not None:
            given = page_table if isinstance(page_table, (tuple, list)) \
                else (page_table,)
            tables = dict(zip(self.paged_pool_groups, given))
            t = token_ids.shape[1]
            ends = (jnp.asarray(start_pos._data, jnp.int32).reshape(-1, 1)
                    + jnp.arange(1, t + 1, dtype=jnp.int32)[None, :])
            read = {"full": jnp.sum(ends) // t,
                    "window": jnp.sum(jnp.minimum(
                        ends, self._cfg["sliding_window"])) // t}
        new_cache, loads = [], []
        for i, (blk, kind) in enumerate(zip(self.blocks, self._kinds)):
            if cache is None:
                x = blk(x)
                continue
            x, layer_cache, load = blk(
                x, cache=cache[i], start_pos=start_pos, last_pos=last_pos,
                page_table=tables["all" if kind == "full" else "window"])
            new_cache.append(layer_cache)
            loads.append([z._data for z in load])
            reads[kind].append(read[kind])
        if last_pos is not None:
            at = jnp.asarray(last_pos._data, jnp.int32).reshape(-1)[0]
            x = NDArray(jax.lax.dynamic_slice_in_dim(x._data, at, 1, axis=1))
        # float32 logits: in bfloat16 neighbouring logits tie and the
        # argmax would take the first of them
        logits = self.head(self.norm(x).astype("float32"))
        if cache is None:
            return logits
        counts = {name: jnp.stack(of_layers).astype(jnp.int32)
                  for name, of_layers in zip(
                      ("moe_pairs_held", "moe_max_load", "moe_experts_hit"),
                      zip(*loads))}
        for kind, rows in reads.items():
            if rows:
                counts["attn_read_" + kind] = jnp.stack(rows).astype(jnp.int32)
        return logits, new_cache, counts


def get_smallthinker(model_name="smallthinker_21b", **overrides):
    cfg = dict(smallthinker_configs[model_name])
    held = overrides.pop("held_experts", None)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return SmallThinkerModel(held_experts=held, dtype=dtype, **cfg)
