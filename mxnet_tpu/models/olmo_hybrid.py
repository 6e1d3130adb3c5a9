"""Olmo-Hybrid-7B (``allenai/Olmo-Hybrid-7B`` ``config.json``): Gated
DeltaNet layers (arXiv:2412.06464; negative eigenvalues as arXiv:2411.12537)
three to one with full softmax attention, in OLMo 2's block
(arXiv:2501.00656): no biases, RMSNorm on each sublayer's OUTPUT inside the
residual, an untied head. A block is ::

    x1 = x  + RMSNorm(mixer(x))
    x2 = x1 + RMSNorm(down(silu(gate x1) * (up x1)))

where ``layer_types[l]`` is ``linear_attention`` the mixer is a Gated
DeltaNet layer: q, k, v through a causal depth-wise convolution of width 4
and SiLU, q and k L2-normalised a head, a write strength ``beta = 2
sigmoid(.)`` and a decay ``alpha = exp(-exp(A_log) softplus(. + dt_bias))``
a head, the state ``S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t
k_t v_t^T`` (``ops/pallas_gdn.py``), the read ``S_t^T q_t`` RMS-normed a
head, gated by ``silu(g x)`` and projected. Where it is ``full_attention``:
causal softmax over equal heads, q and k RMS-normed over the whole
projection, no positions of any kind.

Served through ``inference.GenerationEngine(paged=True)``: the full layers
keep ordinary key and value pools ``(P+1, page, H*Ch)`` in the one page
group ``all``; a linear layer keeps SLOT STATE (docs/INFERENCE.md "Slot
state"), arrays whose axis 0 is the engine's slot: the state ``(slots, dk,
H*dv)`` float32, the convolution's last four inputs ``(slots, 4, C)`` and
the number of positions the state has taken in ``(slots,)``. A prefill
writes its row's state from zero and stops at the prompt's length; a decode
step advances the live rows and no other; a step run a second time on rows
that took its token already (the engine drops a step dispatched ahead when a
row changes hands) reads the state and leaves it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from ..ops import pallas_gdn as gdn
from .deepseek_v2 import RMSNorm, SwiGLU, _dense

__all__ = ["OlmoHybridModel", "get_olmo_hybrid", "olmo_hybrid_configs"]

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)

olmo_hybrid_configs = {
    # every size of the published config.json; the tiny one is for tests
    "olmo_hybrid_7b": dict(
        num_layers=32, units=3840, hidden_size=11008, num_heads=30,
        head_dim=128, layer_types=_PERIOD * 8, linear_heads=30,
        linear_key_dim=96, linear_value_dim=192, conv_width=4,
        vocab_size=100352, max_length=65536, rms_norm_eps=1e-6),
    "olmo_hybrid_tiny": dict(
        num_layers=4, units=32, hidden_size=48, num_heads=2, head_dim=16,
        layer_types=_PERIOD, linear_heads=2, linear_key_dim=8,
        linear_value_dim=16, conv_width=4, vocab_size=200, max_length=128,
        rms_norm_eps=1e-6),
}

_CHUNK = 64   # positions a block of the chunked prefill


def _l2_normalise(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


class GatedDeltaNet(HybridBlock):
    """One linear-attention sublayer. Returns the output; with ``cache=``
    (the layer's slot state), ``(output, new state, rows advanced)``."""

    def __init__(self, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = cfg
        self._heads, self._dk = c["linear_heads"], c["linear_key_dim"]
        self._dv, self._width = c["linear_value_dim"], c["conv_width"]
        self._eps = c["rms_norm_eps"]
        h, units = self._heads, c["units"]
        self._channels = h * (2 * self._dk + self._dv)
        f32 = "float32"   # the gates, the convolution and the state
        with self.name_scope():
            self.q = _dense(h * self._dk, units, dtype, "q_")
            self.k = _dense(h * self._dk, units, dtype, "k_")
            self.v = _dense(h * self._dv, units, dtype, "v_")
            self.g = _dense(h * self._dv, units, dtype, "g_")
            self.o = _dense(units, h * self._dv, dtype, "o_")
            self.a_weight = self.params.get(
                "a_weight", shape=(h, units), dtype=f32, init=init.Normal(0.02))
            self.b_weight = self.params.get(
                "b_weight", shape=(h, units), dtype=f32, init=init.Normal(0.02))
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._channels, self._width), dtype=f32,
                init=init.Normal(0.3))
            self.A_log = self.params.get("A_log", shape=(h,), dtype=f32,
                                         init=init.Normal(0.5))
            self.dt_bias = self.params.get("dt_bias", shape=(h,), dtype=f32,
                                           init=init.Normal(0.5))
            self.o_norm = RMSNorm(self._dv, self._eps, f32, prefix="o_norm_")

    # -- the pieces, on raw arrays ------------------------------------------
    def _split(self, c):
        """(q, k, v) heads of the convolution's output ``c`` (..., C)."""
        h, dk, dv = self._heads, self._dk, self._dv
        lead = c.shape[:-1]
        q = _l2_normalise(c[..., :h * dk].reshape(*lead, h, dk)) * dk ** -0.5
        k = _l2_normalise(c[..., h * dk:2 * h * dk].reshape(*lead, h, dk))
        return q, k, c[..., 2 * h * dk:].reshape(*lead, h, dv)

    def _prefill(self, z, g, beta, length, conv_weight):
        """A row's prompt from a zero state: ``z`` (T, C), ``g``, ``beta``
        (T, H). Returns every position's read (T, H, dv), the state and the
        convolution's tail behind the prompt's last REAL token: the
        bucket's padding, positions ``length`` and later, writes nothing."""
        t, width = z.shape[0], self._width
        padded = jnp.pad(z, ((width - 1, 0), (0, 0)))
        with jax.named_scope("conv"):
            c = jax.nn.silu(sum(padded[j:j + t] * conv_weight[:, j]
                                for j in range(width)))
            # the last ``width`` REAL inputs (zeros before the sequence)
            tail = jax.lax.dynamic_slice_in_dim(padded, length - 1, width, 0)
        real = (jnp.arange(t) < length)[:, None]
        with jax.named_scope("core"):
            o, s = gdn.gdn_chunk_prefill(
                *self._split(c), jnp.where(real, g, 0.0),
                jnp.where(real, beta, 0.0), _CHUNK)
        return o, gdn.state_rows(s), tail

    def _decode(self, z, g, beta, state, position, live, conv_weight):
        """One token a row: ``z`` (B, C), ``g``, ``beta`` (B, H). A live row
        whose state has taken this position's token already (``taken ==
        position + 1``: the step is run a second time) reads and leaves it."""
        s, tail, taken = state
        again = live & (taken == position + 1)
        with jax.named_scope("conv"):
            window = jnp.where(
                again[:, None, None], tail,
                jnp.concatenate([tail[:, 1:], z[:, None]], axis=1))
            c = jax.nn.silu(jnp.sum(window * conv_weight.T[None], axis=1))
            tail = jnp.where(live[:, None, None], window, tail)
        with jax.named_scope("core"):
            q, k, v = self._split(c)
            alpha = jnp.where(again[:, None], 1.0, jnp.exp(g))
            beta = jnp.where(again[:, None], 0.0, beta)
            why = gdn.gdn_decode_refusal(s, q, v)
            step = gdn.gdn_decode_xla if why else gdn.gdn_decode_step
            o, s = step(s, q, k, v, alpha, beta, live)
            taken = jnp.where(live, position + 1, taken)
        return o, (s, tail, taken)

    def hybrid_forward(self, F, x, cache=None, start_pos=None, last_pos=None,
                       slot=None, live=None, a_weight=None, b_weight=None,
                       conv_weight=None, A_log=None, dt_bias=None):
        b, t, _ = x.shape
        h, dv = self._heads, self._dv
        with jax.named_scope("proj"):
            z = jnp.concatenate([self.q(x)._data, self.k(x)._data,
                                 self.v(x)._data], -1).astype(jnp.float32)
            gate = self.g(x)
            # the two gates in float32 (on a TPU a plain float32 product is
            # one bfloat16 pass)
            narrow = lambda w: jnp.einsum(  # noqa: E731
                "bth,oh->bto", x._data.astype(jnp.float32), w._data,
                precision=jax.lax.Precision.HIGHEST)
            g = -jnp.exp(A_log._data) * jax.nn.softplus(
                narrow(a_weight) + dt_bias._data)           # log alpha
            beta = 2.0 * jax.nn.sigmoid(narrow(b_weight))
        w = conv_weight._data
        if cache is None:        # one whole chunk a row, no state kept
            o = jnp.stack([self._prefill(z[i], g[i], beta[i],
                                         jnp.asarray(t, jnp.int32), w)[0]
                           for i in range(b)])
        elif slot is not None:   # a prefill: one row's prompt, from zero
            length = jnp.asarray(last_pos._data, jnp.int32).reshape(()) + 1
            o, s_row, last = self._prefill(z[0], g[0], beta[0], length, w)
            o, at = o[None], jnp.asarray(slot._data, jnp.int32).reshape(())
            new = tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    full._data, row[None].astype(full._data.dtype), at, 0)
                for full, row in zip(cache, (s_row, last, length)))
        else:                    # a decode step: one token a row
            position = jnp.asarray(start_pos._data, jnp.int32)
            o, new = self._decode(
                z[:, 0], g[:, 0], beta[:, 0], tuple(c._data for c in cache),
                position, jnp.asarray(live._data, bool), w)
            o = o[:, None]
        with jax.named_scope("out"):
            o = self.o_norm(NDArray(o))._data.astype(gate._data.dtype) \
                * jax.nn.silu(gate._data).reshape(b, t, h, dv)
            out = self.o(NDArray(o.reshape(b, t, h * dv)))
        if cache is None:
            return out
        rows = jnp.sum(jnp.asarray(live._data, jnp.int32)) if slot is None \
            else jnp.asarray(1, jnp.int32)
        return out, tuple(NDArray(c) for c in new), rows


class FullAttention(HybridBlock):
    """Causal softmax attention over equal heads, q and k RMS-normed over
    the whole projection, no positions. Returns the output; with ``cache=``,
    ``(output, (k_pool, v_pool))``."""

    def __init__(self, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._heads, self._ch = cfg["num_heads"], cfg["head_dim"]
        units, width = cfg["units"], cfg["num_heads"] * cfg["head_dim"]
        eps = cfg["rms_norm_eps"]
        with self.name_scope():
            self.q = _dense(width, units, dtype, "q_")
            self.k = _dense(width, units, dtype, "k_")
            self.v = _dense(width, units, dtype, "v_")
            self.o = _dense(units, width, dtype, "o_")
            self.q_norm = RMSNorm(width, eps, dtype, prefix="q_norm_")
            self.k_norm = RMSNorm(width, eps, dtype, prefix="k_norm_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None,
                       page_table=None):
        b, t, _ = x.shape
        heads = lambda z: z.reshape(  # noqa: E731
            (b, t, self._heads, self._ch)).transpose((0, 2, 1, 3))
        with jax.named_scope("qkv"):
            q = heads(self.q_norm(self.q(x)))
            k = heads(self.k_norm(self.k(x)))
            v = heads(self.v(x))
        # the grouped path at a group of one: its decode kernel walks a
        # row's pages in blocks, whatever the row's length
        paged = {} if cache is None else dict(
            cache=cache, position=start_pos, page_table=page_table)
        with jax.named_scope("core"):
            out = F.multi_head_attention(q, k, v, causal=True, grouped=True,
                                         **paged)
        pools = None
        if cache is not None:
            out, *pools = out
        with jax.named_scope("out"):
            out = self.o(out.transpose((0, 2, 1, 3)).reshape((b, t, -1)))
        return out if cache is None else (out, tuple(pools))


class OlmoHybridBlock(HybridBlock):
    """Returns ``x``; with ``cache=``, ``(x, layer's cache, rows whose state
    the layer advanced or None)``."""

    def __init__(self, cfg, kind, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        eps, units = cfg["rms_norm_eps"], cfg["units"]
        self._linear = kind == "linear_attention"
        with self.name_scope():
            if self._linear:
                self.gdn = GatedDeltaNet(cfg, dtype, prefix="gdn_")
            else:
                self.attn = FullAttention(cfg, dtype, prefix="attn_")
            self.mixer_norm = RMSNorm(units, eps, dtype, prefix="mixer_norm_")
            self.ffn = SwiGLU(units, cfg["hidden_size"], dtype, prefix="ffn_")
            self.ffn_norm = RMSNorm(units, eps, dtype, prefix="ffn_norm_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None,
                       last_pos=None, slot=None, live=None):
        rows = None
        if cache is None:
            y = self.gdn(x) if self._linear else self.attn(x)
        elif self._linear:
            y, cache, rows = self.gdn(x, cache=cache, start_pos=start_pos,
                                      last_pos=last_pos, slot=slot, live=live)
        else:
            y, cache = self.attn(x, cache=cache, start_pos=start_pos,
                                 page_table=page_table)
        x = x + self.mixer_norm(y)
        x = x + self.ffn_norm(self.ffn(x))
        return x if cache is None else (x, cache, rows)


class OlmoHybridModel(HybridBlock):
    """``dtype``: the matrices' and the norms'; the linear layers' gates,
    convolution and state are float32 whatever it is."""

    #: a paged engine passes ``last_pos=`` ((1,) int32: a prefill's last real
    #: position) and gets the logits of that position alone, (1, 1, V)
    takes_last_pos = True
    #: the linear layers keep state by SLOT: a paged engine passes
    #: ``init_paged_cache`` its ``slots``, a prefill ``slot=`` ((1,) int32)
    #: and a decode step ``live=`` ((B,) bool), and refuses what would need a
    #: copy of the state (docs/INFERENCE.md "Slot state")
    paged_slot_state = True

    def __init__(self, dtype="float32", **cfg):
        known = olmo_hybrid_configs["olmo_hybrid_7b"]
        super().__init__(prefix=cfg.pop("prefix", None))
        if set(cfg) - set(known):
            raise TypeError(f"unknown sizes {sorted(set(cfg) - set(known))}")
        c = self._cfg = dict(known, **cfg)
        c["layer_types"] = tuple(c["layer_types"])[:c["num_layers"]]
        if len(c["layer_types"]) != c["num_layers"] or set(
                c["layer_types"]) - set(_PERIOD):
            raise ValueError("layer_types names fewer layers than num_layers, "
                             "or a kind that is neither linear_attention nor "
                             "full_attention")
        self._max_length = c["max_length"]
        self._kinds = c["layer_types"]
        with self.name_scope():
            self.word_embed = nn.Embedding(
                c["vocab_size"], c["units"], dtype=dtype, prefix="word_embed_",
                weight_initializer=init.Normal(0.02))
            self.blocks = nn.HybridSequential(prefix="")
            for i, kind in enumerate(self._kinds):
                self.blocks.add(OlmoHybridBlock(c, kind, dtype,
                                                prefix=f"layer{i}_"))
            self.norm = RMSNorm(c["units"], c["rms_norm_eps"], dtype,
                                prefix="norm_")
            self.head = _dense(c["vocab_size"], c["units"], dtype, "head_")

    # -- what a paged engine asks of a model (docs/INFERENCE.md) -------------
    @property
    def paged_pool_groups(self):
        return {"all": {}}

    def init_paged_cache(self, num_pages, page_size, dtype="float32",
                         slots=1):
        """``(cache, groups)``: a full layer's ``(k_pool, v_pool)`` of shape
        (pages + 1, page_size, H * Ch) in the group ``all``; a linear layer's
        slot state in the group ``slot``: the state ``(slots, dk, H * dv)``
        float32, the convolution's last inputs ``(slots, width, C)`` float32
        and the positions taken ``(slots,)`` int32, all zero."""
        from ..ops.attention import alloc_paged_kv_cache

        c = self._cfg
        h, dk, dv = c["linear_heads"], c["linear_key_dim"], c["linear_value_dim"]
        cache = []
        for kind in self._kinds:
            if kind == "full_attention":
                cache.append(alloc_paged_kv_cache(
                    num_pages["all"], c["num_heads"], page_size, c["head_dim"],
                    1, dtype=dtype)[0])
            else:
                cache.append((
                    jnp.zeros((slots, dk, h * dv), jnp.float32),
                    jnp.zeros((slots, c["conv_width"], h * (2 * dk + dv)),
                              jnp.float32),
                    jnp.zeros((slots,), jnp.int32)))
        return cache, tuple("all" if kind == "full_attention" else "slot"
                            for kind in self._kinds)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program reads its caches by: the
        full layers' pools (``paged_gqa_decode`` at a group of one, or the
        XLA gather and why) and the linear layers' state (``gdn_decode_step``
        or the XLA form and why), as the operators choose at trace time."""
        from ..ops.pallas_paged_attention import paged_gqa_refusal

        c = self._cfg
        dtype = self.word_embed.weight.data()._data.dtype
        table = page_table[0] if isinstance(page_table, tuple) else page_table
        of = lambda kind: next(  # noqa: E731
            (p for p, k in zip(pools, self._kinds) if k == kind), None)
        out, shape, f32 = [], jax.ShapeDtypeStruct, jnp.float32
        if of("full_attention") is not None:
            why = paged_gqa_refusal(
                shape((batch_size, c["num_heads"], 1, c["head_dim"]), dtype),
                of("full_attention")[0], table)
            out.append("full layers: "
                       + (f"xla_gather ({why})" if why else "gqa_kernel"))
        if of("linear_attention") is not None:
            heads = (batch_size, c["linear_heads"])
            why = gdn.gdn_decode_refusal(
                of("linear_attention")[0],
                shape((*heads, c["linear_key_dim"]), f32),
                shape((*heads, c["linear_value_dim"]), f32))
            out.append("linear layers: "
                       + (f"gdn_xla ({why})" if why else "gdn_kernel"))
        return "; ".join(out)

    def logits_width(self):
        return self._cfg["vocab_size"]

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None, last_pos=None, slot=None, live=None):
        """Logits; with ``cache=``, ``(logits, new_cache, counts)``:
        ``counts`` is {name: (layers,) int32} of this forward:
        ``state_rows`` (rows whose state each linear layer advanced) and
        ``attn_read_full`` (the positions the rows' softmaxes read in each
        full layer, summed over the rows, a query's share of a chunk). With
        ``last_pos=`` the logits are those of that position alone."""
        x = self.word_embed(token_ids)
        if cache is not None:
            table = page_table[0] if isinstance(page_table, (tuple, list)) \
                else page_table
            t = token_ids.shape[1]
            ends = (jnp.asarray(start_pos._data, jnp.int32).reshape(-1, 1)
                    + jnp.arange(1, t + 1, dtype=jnp.int32)[None, :])
            read = jnp.sum(ends) // t
        new_cache, reads, rows = [], [], []
        for i, (blk, kind) in enumerate(zip(self.blocks, self._kinds)):
            if cache is None:
                x = blk(x)
                continue
            x, layer_cache, advanced = blk(
                x, cache=cache[i], start_pos=start_pos, page_table=table,
                last_pos=last_pos, slot=slot, live=live)
            new_cache.append(layer_cache)
            if kind == "full_attention":
                reads.append(read)
            else:
                rows.append(advanced)
        if last_pos is not None:
            at = jnp.asarray(last_pos._data, jnp.int32).reshape(-1)[0]
            x = NDArray(jax.lax.dynamic_slice_in_dim(x._data, at, 1, axis=1))
        # float32 logits: in bfloat16 neighbouring logits tie and the
        # argmax would take the first of them
        logits = self.head(self.norm(x).astype("float32"))
        if cache is None:
            return logits
        counts = {name: jnp.stack(of_layers).astype(jnp.int32)
                  for name, of_layers in (("state_rows", rows),
                                          ("attn_read_full", reads))
                  if of_layers}
        return logits, new_cache, counts


def get_olmo_hybrid(model_name="olmo_hybrid_7b", **overrides):
    cfg = dict(olmo_hybrid_configs[model_name])
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return OlmoHybridModel(dtype=dtype, **cfg)
