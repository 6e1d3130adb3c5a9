"""MiniCPM-SALA (``openbmb/MiniCPM-SALA`` ``config.json``): by
``mixer_types`` one ``minicpm4`` layer (InfLLM-v2 block-sparse attention:
MiniCPM4, arXiv:2506.07900; InfLLM-V2, arXiv:2509.24663) to three
``lightning-attn`` layers (Lightning Attention, arXiv:2401.04658), in
MiniCPM's block: no bias, RMSNorm on each sublayer's INPUT, an untied head ::

    c   = scale_depth / sqrt(published layers)
    h_0 = scale_emb * E[token]
    x   = x + c * mixer(RMSNorm(x))
    x   = x + c * down(silu(gate u) * (up u)),  u = RMSNorm(x)
    logits = head(RMSNorm(x_last) / (units / dim_model_base))

A ``minicpm4`` mixer: q and k RMS-normed a head, no positions, few key-value
heads under many query heads. A query that sees at most ``dense_len`` keys
reads them all; a later one reads ``topk`` BLOCKS of ``block_size`` keys,
one choice a key-value head: the block of every key scores the largest,
over the compressed keys that touch it (``c_j``: the mean of ``kernel_size``
keys every ``kernel_stride``), of the group's summed softmax weights over
the compressed keys; ``init_blocks`` first blocks and the ``window_size``
last positions' blocks are taken whatever they score. The output is gated
by ``sigmoid(g u)``. A ``lightning-attn`` mixer: q and k RMS-normed a head
and rotated, a decayed outer-product state ``S_t = lambda_h S_(t-1) + k_t
v_t^T`` a head (``ops/pallas_gdn.py``, ``delta=False``), the read ``S_t^T
q_t`` RMS-normed a head and gated by ``sigmoid(g u)``.

Served through ``inference.GenerationEngine(paged=True)`` with a page the
size of a block: a sparse layer keeps, in the page group ``all``, its key
and value pools ``(P+1, page, Hkv*Ch)`` and beside them the COMPRESSED-KEY
pool ``(P+1, page / kernel_stride, Hkv*Ch)``, the selector's cache: entry j
of a row lies in the page of position ``kernel_stride * j`` and is written
when its last key is (by the prefill, or by the decode step at that
position, from the keys the pools hold). A decode step scores the row's
compressed keys, selects, and reads the selected blocks through ONE table of
pages, a list a row and key-value head (:func:`selected_pages`;
``pallas_paged_attention.paged_gqa_read(selected=)`` through
``ops.attention.paged_block_attention``); a row under the dense length lists
every block it holds. A prefill reads by the same
selection as a mask over key blocks. Both mixers walk a prefill in stretches
of ``_STRETCH`` tokens and take each through the WHOLE block before the
next (normed, projected, mixed, gated, projected back, added to the residual
and through the feed-forward, its output written over its input): of the
prompt's length one array of the model's width is held, and the keys and
values. A prefill whose prompt ends short of its bucket RUNS ONLY THE
STRETCHES THE PROMPT REACHES: a stretch whose first position is not below
the prompt's length stands under a predicate (``lax.cond``) and costs that,
not a block. Nothing reads what it would have computed (the logits are the
last real position's, attention is causal, a compressed key is written only
where its last key is real, the state stops at the length, the counts are of
real queries); what it leaves at its positions is the block's INPUT passed
through, finite down from the padding's embedding, which a later sparse
layer projects into keys that land on the trash page or behind the prompt in
its last page, as the padding's keys always did. The count
``positions_run`` (an entry a layer) says how many positions a prefill's
stretches ran. A lightning layer keeps SLOT STATE (docs/INFERENCE.md
"Slot state"): the state ``(slots, Ch, H*Ch)`` float32 and the positions it
has taken ``(slots,)``; a prefill writes its row's state from zero and stops
at the prompt's length, a decode step advances the live rows and no other,
and a step run a second time on a row that took its token reads the state
and leaves it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from ..ops import attention as att
from ..ops import pallas_gdn as gdn
from .deepseek_v2 import RMSNorm, SwiGLU, _dense
from .dots3_note import _in_token_blocks

__all__ = ["MiniCPMSALAModel", "get_minicpm_sala", "minicpm_sala_configs"]

_MIXERS = ("minicpm4", "lightning-attn")
_PUBLISHED = tuple("minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
                   else "lightning-attn" for i in range(32))

minicpm_sala_configs = {
    # every size of the published config.json and of MiniCPM4's
    # sparse_config; the tiny one is for tests (the same ratios)
    "minicpm_sala": dict(
        num_layers=32, published_layers=32, units=4096, hidden_size=16384,
        num_heads=32, num_kv_heads=2, head_dim=128, mixer_types=_PUBLISHED,
        lightning_heads=32, lightning_head_dim=128, vocab_size=73448,
        max_length=524288, rms_norm_eps=1e-6, rope_theta=10000.0,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=256,
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048, dense_len=8192),
    "minicpm_sala_tiny": dict(
        num_layers=4, published_layers=32, units=32, hidden_size=48,
        num_heads=4, num_kv_heads=1, head_dim=8,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"),
        lightning_heads=2, lightning_head_dim=8, vocab_size=200,
        max_length=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=2,
        kernel_size=2, kernel_stride=1, block_size=4, topk=5,
        init_blocks=1, window_size=4, dense_len=8),
}

_CHUNK = 128            # positions a block of the lightning prefill
_STRETCH = 4096         # tokens a stretch of a prefill's mixers
_SELECT_QUERIES = 512   # queries whose choice is made at once
_NEG = -1e30            # a masked score (finite: no row is ever all -inf)


def _where_reached(first, length, run, skip, *args):
    """``run(*args)`` where the stretch that starts at ``first`` holds a real
    position (``first < length``), else ``skip(*args)``: one ``lax.cond`` on
    a prefill's traced ``length``; a whole sequence's ``length`` is a Python
    int, all of it real, and its stretches run as they stand."""
    if isinstance(length, int):
        return run(*args)
    return jax.lax.cond(first < length, run, skip, *args)


def block_scores(s, cfg, n_blocks):
    """b_m (..., M) of the compressed keys' summed weights ``s`` (..., J),
    ``-inf`` where a key does not count: the largest over the compressed
    keys that touch block m (those that start in it and the ``kernel_size /
    kernel_stride - 1`` that reach into it from the block before)."""
    per = cfg["block_size"] // cfg["kernel_stride"]
    back = cfg["kernel_size"] // cfg["kernel_stride"] - 1
    lead, total = s.shape[:-1], back + per * n_blocks
    fill = lambda n: jnp.full((*lead, max(n, 0)), -jnp.inf, s.dtype)  # noqa: E731
    s = jnp.concatenate([fill(back), s, fill(total - back - s.shape[-1])],
                        axis=-1)[..., :total]
    b = s[..., back:].reshape(*lead, n_blocks, per).max(axis=-1)
    for i in range(back):
        b = jnp.maximum(b, s[..., i:i + per * n_blocks:per])
    return b


def _whole_keys(n, at, cfg):
    """(..., n) bool: the compressed keys that lie wholly at or before the
    queries at ``at`` (...,)."""
    return (jnp.arange(n) * cfg["kernel_stride"] + cfg["kernel_size"] - 1) \
        <= at[..., None]


def key_weights(q, ck, at, cfg):
    """``s`` (..., Hkv, J) float32: a group's summed softmax weights over
    the compressed keys that lie wholly at or before the query, zero for
    the others. ``q`` (..., Hkv, G, Ch) at positions ``at`` (...,), ``ck``
    the row's compressed keys (J, Hkv, Ch) or (..., J, Hkv, Ch), both in
    the cache's dtype; scores and softmax float32."""
    keys = "jkc" if ck.ndim == 3 else "...jkc"
    dots = jnp.einsum(f"...kgc,{keys}->...kgj", q, ck,
                      preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    seen = _whole_keys(ck.shape[-3], at, cfg)[..., None, None, :]
    p = jax.nn.softmax(jnp.where(seen, dots, _NEG), axis=-1)
    return jnp.where(seen, p, 0.0).sum(axis=-2)


def block_ranks(s, at, cfg, n_blocks):
    """What a query's blocks are chosen by, (..., Hkv, M) float32, of the
    compressed keys' weights ``s`` (..., Hkv, J) (:func:`key_weights`) of
    the query at ``at`` (...,): ``+inf`` for a forced block (the first
    ``init_blocks``, the query's own and the ``window_size / block_size``
    before it), its score ``b_m`` for another block the row holds, ``-inf``
    past the query's own; beside it the forced blocks' mask."""
    b = pooled_weights(s, at, cfg, n_blocks)
    m = jnp.arange(n_blocks)
    return _forced_ranks(b, m, (at // cfg["block_size"])[..., None, None], cfg)


def pooled_weights(s, at, cfg, n_blocks):
    """b_m (..., Hkv, M) of the weights ``s`` (..., Hkv, J)
    (:func:`key_weights`) of the queries at ``at`` (...,): :func:`block_scores`
    over the compressed keys that lie wholly at or before the query, ``-inf``
    where none touches a block (what ``sparse_chunk_scores`` computes in one
    kernel)."""
    valid = _whole_keys(s.shape[-1], at, cfg)[..., None, :]
    return block_scores(jnp.where(valid, s, -jnp.inf), cfg, n_blocks)


def _forced_ranks(b, m, own, cfg):
    """:func:`block_ranks` of the pooled scores ``b`` (..., M) of blocks
    ``m`` (M,), ``own`` the queries' own blocks (broadcast against ``b``)."""
    held = m <= own
    forced = (m < cfg["init_blocks"]) \
        | ((m >= own - cfg["window_size"] // cfg["block_size"]) & held)
    forced = jnp.broadcast_to(forced, b.shape)
    return jnp.where(forced, jnp.inf, jnp.where(held, b, -jnp.inf)), forced


def selected_pages(s, table, position, cfg):
    """A decode step's table of SELECTED pages, a list a row and key-value
    head (a block is a page): ``(page_ids, blocks, counts)``, ``page_ids``
    and ``blocks`` (B, Hkv, L) the listed blocks' pool ids and numbers in
    the blocks' order, ``counts`` (B, Hkv) how many entries count. ``s`` (B,
    Hkv, J) the compressed keys' weights (:func:`key_weights`), ``table``
    (B, columns) the rows' pages, ``position`` (B,). A row whose query sees
    at most ``dense_len`` keys lists every block it holds; a later one the
    forced blocks and the best by score, ``topk`` in all (fewer where it
    holds fewer). No entry that counts names a page the row does not hold."""
    b, hkv, cols = s.shape[0], s.shape[1], table.shape[1]
    block = cfg["block_size"]
    rank, _ = block_ranks(s, position, cfg, cols)           # (B, Hkv, M)
    topk = min(cfg["topk"], cols)
    _, ids = jax.lax.top_k(rank, topk)
    # in the blocks' order; a block the row does not hold ranks -inf, lies
    # past the row's own and so sorts behind the count
    ids = jnp.sort(ids, axis=-1).astype(jnp.int32)
    length = max(topk, cfg["dense_len"] // block)
    every = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32),
                             (b, hkv, length))
    sparse = position + 1 > cfg["dense_len"]
    ids = jnp.where(sparse[:, None, None],
                    jnp.pad(ids, ((0, 0), (0, 0), (0, length - topk))), every)
    listed = jnp.minimum(position // block + 1,
                         jnp.where(sparse, topk, length))
    page_ids = jnp.take_along_axis(
        jnp.broadcast_to(table[:, None], (b, hkv, cols)),
        jnp.minimum(ids, cols - 1), axis=2)
    return page_ids, ids, jnp.broadcast_to(listed[:, None], (b, hkv))


class BlockSparseAttention(HybridBlock):
    """One ``minicpm4`` sublayer. Returns the output; with ``cache=`` (the
    layer's ``(k_pool, v_pool, compressed-key pool)``), ``(output, new
    cache, counts)``: ``counts`` {name: int32 scalar} of this forward:
    ``blocks_read`` (the blocks a key-value head's reads visit, summed over
    the live rows of a decode step or the real queries of a prefill),
    ``blocks_held`` (the blocks those rows or queries hold),
    ``compressed_written`` (compressed keys completed and written) and, of
    a prefill, ``positions_run`` (the positions of the stretches it ran)."""

    COUNTS = ("blocks_read", "blocks_held", "compressed_written",
              "positions_run")

    def __init__(self, cfg, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = self._cfg = cfg
        self._heads, self._kv, self._ch = (c["num_heads"], c["num_kv_heads"],
                                           c["head_dim"])
        if c["block_size"] % c["kernel_stride"] \
                or c["kernel_size"] % c["kernel_stride"] \
                or c["dense_len"] % c["block_size"]:
            raise ValueError(
                "block_size and kernel_size must be multiples of "
                "kernel_stride, and dense_len whole blocks")
        units, width = c["units"], c["num_heads"] * c["head_dim"]
        kv, eps = c["num_kv_heads"] * c["head_dim"], c["rms_norm_eps"]
        with self.name_scope():
            self.q = _dense(width, units, dtype, "q_")
            self.k = _dense(kv, units, dtype, "k_")
            self.v = _dense(kv, units, dtype, "v_")
            self.g = _dense(width, units, dtype, "g_")
            self.o = _dense(units, width, dtype, "o_")
            self.q_norm = RMSNorm(c["head_dim"], eps, dtype, prefix="q_norm_")
            self.k_norm = RMSNorm(c["head_dim"], eps, dtype, prefix="k_norm_")

    # -- the selector's cache ------------------------------------------------
    def _compress(self, keys):
        """c_j (..., J, Hkv*Ch) float32 of ``keys`` (..., T, Hkv*Ch): the
        mean of every ``kernel_size`` keys that start a stride."""
        size, stride = self._cfg["kernel_size"], self._cfg["kernel_stride"]
        *lead, t, w = keys.shape
        sums = keys.astype(jnp.float32).reshape(
            *lead, t // stride, stride, w).sum(axis=-2)
        n = size // stride
        j = t // stride - n + 1
        return sum(sums[..., i:i + j, :] for i in range(n)) / size

    # -- one whole chunk from position 0 -------------------------------------
    def _taken(self, q, ck, at, n_blocks, laid=None):
        """(Hkv, Q, M) bool: the blocks the queries ``q`` (Q, Hkv, G, Ch) at
        positions ``at`` read. With ``laid`` (the compressed keys as
        ``sparse_chunk_keys`` lays them out) the queries are one stretch from
        ``at[0]`` on, their block scores come from the kernel
        ``sparse_chunk_scores`` and the choice is made for all of them at
        once; else XLA weighs every compressed key, ``_SELECT_QUERIES``
        queries at a time."""
        c = self._cfg
        if laid is not None:
            from ..ops import pallas_paged_attention as ppa

            b = ppa.sparse_chunk_scores(
                q, laid, at[0], c["block_size"], c["kernel_size"],
                c["kernel_stride"])[:, :, :n_blocks]         # (Hkv, Q, M)
            rank, _ = _forced_ranks(b, jnp.arange(n_blocks),
                                    (at // c["block_size"])[:, None], c)
            held = rank > -jnp.inf
            taken = att.top_k_mask(rank, min(c["topk"], n_blocks)) & held
            return jnp.where((at + 1 <= c["dense_len"])[:, None], held, taken)
        qb = math.gcd(q.shape[0], _SELECT_QUERIES)

        def of(start):
            cut = lambda z: jax.lax.dynamic_slice_in_dim(z, start, qb, 0)  # noqa: E731
            pos = cut(at)
            rank, _ = block_ranks(key_weights(cut(q), ck, pos, c), pos, c,
                                  n_blocks)                  # (qb, Hkv, M)
            held = rank > -jnp.inf
            taken = att.top_k_mask(rank, min(c["topk"], n_blocks)) & held
            return jnp.where((pos + 1 <= c["dense_len"])[:, None, None], held,
                             taken)

        out = jax.lax.map(of, jnp.arange(0, q.shape[0], qb, dtype=jnp.int32))
        return jnp.moveaxis(out.reshape(q.shape[0], self._kv, n_blocks), 1, 0)

    def _queries(self, x):
        """(B, T, Hkv, G, Ch): the RMS-normed queries of ``x`` (B, T, units)."""
        b, t, _ = x.shape
        return self.q_norm(self.q(x).reshape(
            (b, t, self._kv, self._heads // self._kv, self._ch)))._data

    def _gated(self, a, x):
        """``o(a * sigmoid(g x))``: ``a`` (B, T, H*Ch) raw, ``x`` the
        sublayer's input; raw (B, T, units)."""
        gate = jax.nn.sigmoid(self.g(x)._data)
        return self.o(NDArray(a.astype(gate.dtype) * gate))._data

    def _attend(self, q, k, v, taken, at, kernel):
        """A stretch's queries ``q`` (s, Hkv, G, Ch) at positions ``at``
        against the keys ``k``, ``v`` (Tk, Hkv, Ch) up to the stretch's end:
        causal, and of the blocks ``taken`` (Hkv, s, M) where the selection
        gave them (the mask over keys is built a key-value head at a time).
        ``kernel``: the flash forward kernel (``sparse_prefill`` in a
        trace), which pairs heads one to one: a key-value head is repeated
        for the ``att._KERNEL_HEADS`` query heads of a call and no more, and
        a stretch's calls (its key-value heads, a group's heads so many at
        a time) are ONE ``lax.map`` body, so a stretch compiles one kernel;
        else XLA's masked softmax. (s, Hkv, G, Ch)."""
        from ..ops import flash_attention as fa

        s, _, group, _ = q.shape
        tk, block = k.shape[0], self._cfg["block_size"]
        q = jnp.moveaxis(q, 0, 2)                           # (Hkv, G, s, Ch)
        causal = jnp.arange(tk)[None, :] <= at[:, None]

        def seen(g):
            """(s, Tk) bool of key-value head ``g``, or None: causal alone."""
            return None if taken is None else causal & jnp.broadcast_to(
                jnp.take(taken, g, axis=0)[:, :, None],
                (*taken.shape[1:], block)).reshape(s, -1)[:, :tk]

        if kernel:
            few = math.gcd(group, att._KERNEL_HEADS)
            blk = fa._pick_block(s, att._KERNEL_BLOCK)

            def call(i):
                g = i // (group // few)
                rep = lambda z: jnp.broadcast_to(  # noqa: E731
                    jnp.take(z, g, axis=1)[None, None], (1, few, tk, self._ch))
                mask = seen(g)
                return fa._flash_fwd(
                    jnp.take(q.reshape(-1, few, s, self._ch), i, axis=0)[None],
                    rep(k), rep(v), True, block_q=blk, block_k=blk,
                    interpret=fa._resolve_interpret(None),
                    mask=None if mask is None else mask.astype(jnp.int8),
                    group=few, out_dtype=q.dtype, name="sparse_prefill")[0]

            out = jax.lax.map(call, jnp.arange(self._kv * group // few))
            return jnp.moveaxis(out.reshape(q.shape), 2, 0)
        heads = []
        for g in range(self._kv):
            scores = jnp.einsum("gqc,kc->gqk", q[g], k[:, g],
                                **att._F32) * self._ch ** -0.5
            see = causal if taken is None else seen(g)
            w = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), -1)
            heads.append(jnp.einsum("gqk,kc->gqc", w.astype(v.dtype), v[:, g],
                                    **att._F32))
        return jnp.moveaxis(jnp.stack(heads), 2, 0)

    def _chunk(self, x, norm, tail, k, v, ck, kernel, length, laid=None):
        """The BLOCK over one row's whole chunk from position 0: ``x`` (1,
        T, units) the block's input, ``length`` of its positions real,
        ``k``, ``v`` (T, Hkv, Ch) and ``ck`` (J, Hkv, Ch) as the cache holds
        them. The queries go in stretches of ``_STRETCH``, each normed
        (``norm``), projected, attended against the keys up to its end
        (under the selection's mask where it passes the dense length), gated,
        projected back and taken through the rest of the block (``tail``)
        before the next, so nothing of a stretch but the block's output
        outlives it. ``kernel``: the flash forward kernel (``sparse_prefill``
        in a trace) or XLA's masked softmax; ``laid``: the compressed keys
        for the selection's scoring kernel (:meth:`_taken`) or None. A
        stretch past the first whose first position is not below ``length``
        is not run (:func:`_where_reached`). Returns (the block's output,
        raw (1, T, units), (blocks the first key-value head's real queries
        read, blocks they hold, positions of the stretches that ran))."""
        c, t = self._cfg, x.shape[1]
        block = c["block_size"]
        s = math.gcd(t, _STRETCH)
        n_blocks = -(-t // block)
        zero = jnp.zeros((), jnp.int32)
        out, read, ran = x._data, zero, zero
        for first in range(0, t, s):
            tk = first + s

            def stretch(x_s):   # traced at once, here or by the predicate
                at = first + jnp.arange(s, dtype=jnp.int32)
                x_s = NDArray(x_s)
                taken, real = None, at < length
                with jax.named_scope("sparse"):
                    with jax.named_scope("qkv"):
                        u_s = norm(x_s)
                        q = self._queries(u_s)[0].astype(k.dtype)  # (s, Hkv, G, Ch)
                    if tk > c["dense_len"]:
                        with jax.named_scope("select"):
                            taken = self._taken(q, ck, at, n_blocks, laid)
                            n = jnp.sum(taken[0] & real[:, None],
                                        dtype=jnp.int32)
                    else:
                        n = jnp.sum(jnp.where(real, at // block + 1, 0))
                    with jax.named_scope("read"):
                        a = self._attend(q, k[:tk], v[:tk], taken, at, kernel)
                    with jax.named_scope("out"):
                        y = self._gated(a.reshape(1, s, -1), u_s)
                return tail(x_s, y), n, jnp.asarray(s, jnp.int32)

            # a stretch past the first runs where the prompt reaches it; its
            # output takes its input's place (a stretch not run: its input)
            x_s = out[:, first:tk]
            new, n, positions = stretch(x_s) if first == 0 else _where_reached(
                first, length, stretch, lambda x_s: (x_s, zero, zero), x_s)
            out = jax.lax.dynamic_update_slice_in_dim(out, new, first, axis=1)
            read, ran = read + n, ran + positions
        every = jnp.arange(t, dtype=jnp.int32)
        held = jnp.sum(jnp.where(every < length, every // block + 1, 0))
        return out, (read, held, ran)

    def _chunk_keys(self, ck, stretch):
        """The compressed keys ``ck`` (J, Hkv, Ch) laid out for the scoring
        kernel ``sparse_chunk_scores`` where it weighs a prefill's stretches
        of ``stretch`` queries, else None (XLA's ``key_weights``), counted
        by path and reason."""
        from .. import observability as obs
        from ..ops import pallas_paged_attention as ppa

        c = self._cfg
        sizes = c["block_size"], c["kernel_size"], c["kernel_stride"]
        why = ppa.sparse_chunk_scores_refusal(
            jax.ShapeDtypeStruct((stretch, self._kv, self._heads // self._kv,
                                  self._ch), ck.dtype), ck, *sizes)
        obs.counter("sparse_read_path_total").inc(
            path="chunk_scores_xla" if why else "chunk_scores_kernel",
            reason=why or "")
        if why:
            return None
        with jax.named_scope("sparse"), jax.named_scope("select"):
            return ppa.sparse_chunk_keys(ck, sizes[0], sizes[2])

    def _chunk_path(self, q, t):
        """None where a chunk of ``t`` tokens goes through the flash forward
        kernel, else why it is XLA's masked softmax."""
        from ..ops import flash_attention as fa

        of = lambda h: jax.ShapeDtypeStruct((1, t, h, self._ch), q.dtype)  # noqa: E731
        return fa.masked_prefill_refusal(
            of(self._heads), of(self._heads), of(self._heads),
            jax.ShapeDtypeStruct((1, t, t), jnp.bool_))

    def _prefill(self, x, norm, tail, k, v, pools, table, last):
        """A row's prompt from position 0: keys, values and the compressed
        keys whose last key is real go into the pools, the chunk attends
        itself. ``x`` (1, T, units) the block's input, ``k``, ``v`` (T, Hkv,
        Ch)."""
        from .. import observability as obs

        c, t = self._cfg, k.shape[0]
        k_pool, v_pool, ck_pool = pools
        ps, stride = k_pool.shape[1], c["kernel_stride"]
        pos = jnp.arange(t, dtype=jnp.int32)[None]
        rows = lambda z: z.reshape(1, t, -1)  # noqa: E731
        with jax.named_scope("sparse"), jax.named_scope("qkv"):
            pid = att._linear_pages(table, pos, ps)
            k_pool = att._rows_write(k_pool, rows(k), pid, pos % ps)
            v_pool = att._rows_write(v_pool, rows(v), pid, pos % ps)
            # what the pools now hold (rounded to their dtype) is what is read
            k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
        with jax.named_scope("sparse"), jax.named_scope("compress"):
            ck = self._compress(rows(k)[0]).astype(ck_pool.dtype)  # (J, Hkv*Ch)
            first = jnp.arange(ck.shape[0], dtype=jnp.int32)[None] * stride
            cpid = jnp.where(first + c["kernel_size"] - 1 <= last,
                             att._linear_pages(table, first, ps), 0)
            ck_pool = att._rows_write(ck_pool, ck[None], cpid,
                                      (first // stride) % ck_pool.shape[1])
        why = self._chunk_path(k, t)
        obs.counter("paged_read_path_total").inc(
            path="sparse_chunk_xla" if why else "sparse_chunk_kernel",
            reason=why or "")
        ck = ck.reshape(-1, self._kv, self._ch)
        laid = None
        if t > c["dense_len"]:   # a bucket with stretches that select
            laid = self._chunk_keys(ck, math.gcd(t, _STRETCH))
        y, (read, held, ran) = self._chunk(x, norm, tail, k, v, ck, not why,
                                           last + 1, laid)
        written = jnp.sum(cpid > 0, dtype=jnp.int32)
        return y, (k_pool, v_pool, ck_pool), (read, held, written, ran)

    # -- one token a row -----------------------------------------------------
    def _decode(self, q, k, v, pools, table, position, live):
        """``q`` (B, Hkv, G, Ch), ``k``, ``v`` (B, Hkv*Ch): write the token,
        complete the compressed key it ends, choose, read the lists."""
        from .. import observability as obs
        from ..ops import pallas_paged_attention as ppa

        c = self._cfg
        k_pool, v_pool, ck_pool = pools
        b, ps = q.shape[0], k_pool.shape[1]
        size, stride, block = c["kernel_size"], c["kernel_stride"], c["block_size"]
        per, cols = ck_pool.shape[1], table.shape[1]
        pos = position[:, None]
        with jax.named_scope("qkv"):
            pid = att._linear_pages(table, pos, ps)
            k_pool = att._rows_write(k_pool, k[:, None], pid, pos % ps)
            v_pool = att._rows_write(v_pool, v[:, None], pid, pos % ps)
        with jax.named_scope("compress"):
            # the compressed key whose last key this position is, from the
            # keys the pool holds (a prefill's own arithmetic)
            first = position - (size - 1)
            ends = (first >= 0) & (first % stride == 0)
            span = jnp.maximum(first, 0)[:, None] \
                + jnp.arange(size, dtype=jnp.int32)[None]
            keys = k_pool[att._linear_pages(table, span, ps), span % ps]
            ck = self._compress(keys)                       # (B, 1, Hkv*Ch)
            at = jnp.maximum(first, 0)[:, None]
            cpid = jnp.where(ends[:, None],
                             att._linear_pages(table, at, ps), 0)
            ck_pool = att._rows_write(ck_pool, ck, cpid, (at // stride) % per)
        with jax.named_scope("select"):
            # the row's compressed keys by its table, then their weights:
            # the scoring kernel reads them once, or XLA's einsum
            cks = ck_pool[table].reshape(b, cols * per, -1)
            qc = q.astype(ck_pool.dtype)
            why = ppa.paged_block_scores_refusal(qc, cks)
            obs.counter("sparse_read_path_total").inc(
                path="block_scores_xla" if why else "block_scores_kernel",
                reason=why or "")
            s = key_weights(qc, cks.reshape(b, cols * per, self._kv, self._ch),
                            position, c) if why \
                else ppa.paged_block_scores(qc, cks, position, size, stride)
            page_ids, ids, counts = selected_pages(s, table, position, c)
            listed, held = counts[:, 0], position // block + 1
        with jax.named_scope("read"):
            a = att.paged_block_attention(
                q.reshape(b, self._heads, 1, self._ch), k_pool, v_pool,
                page_ids, ids * block, counts, position)
        n = lambda z: jnp.sum(jnp.where(live, z, 0)).astype(jnp.int32)  # noqa: E731
        return a.reshape(b, self._heads * self._ch), \
            (k_pool, v_pool, ck_pool), (n(listed), n(held), n(ends))

    def _keys(self, x, norm):
        """(k, v) (B, T, Hkv, Ch) raw of the block's input ``x``, a stretch
        at a time: only they outlive a stretch."""
        b, t, _ = x.shape
        s = math.gcd(t, _STRETCH)
        ks, vs = [], []
        for first in range(0, t, s):
            u = norm(NDArray(x._data[:, first:first + s]))
            ks.append(self.k_norm(self.k(u).reshape(
                (b, s, self._kv, self._ch)))._data)
            vs.append(self.v(u)._data.reshape(b, s, self._kv, self._ch))
        return jnp.concatenate(ks, axis=1), jnp.concatenate(vs, axis=1)

    def mix(self, x, norm, tail, cache=None, start_pos=None, page_table=None,
            last_pos=None, live=None):
        """The block around this mixer: ``x`` (B, T, units) the block's
        input, ``norm`` its norm before the mixer, ``tail(x_s, y_s)`` the
        rest of the block on a stretch (the residual sum and the
        feed-forward: raw in, raw out). Returns the block's output; with
        ``cache=``, ``(output, new cache, counts)``."""
        b, t, _ = x.shape
        kv, ch = self._kv, self._ch
        with jax.named_scope("sparse"), jax.named_scope("qkv"):
            k, v = self._keys(x, norm)
        stats = None
        row = lambda i: NDArray(x._data[i:i + 1])  # noqa: E731
        if cache is None:   # one whole chunk a row, nothing kept
            out = jnp.concatenate([
                self._chunk(row(i), norm, tail, k[i], v[i], self._compress(
                    k[i].reshape(t, -1)).reshape(-1, kv, ch), False, t)[0]
                for i in range(b)])
        elif t > 1:         # a prefill: one row's prompt from position 0
            out, cache, stats = self._prefill(
                x, norm, tail, k[0], v[0], tuple(p._data for p in cache),
                jnp.asarray(page_table._data, jnp.int32),
                jnp.asarray(last_pos._data, jnp.int32).reshape(()))
        else:               # a decode step: one token a row
            with jax.named_scope("sparse"):
                with jax.named_scope("qkv"):
                    u = norm(x)
                    q = self._queries(u)[:, 0]
                a, cache, stats = self._decode(
                    q, k[:, 0].reshape(b, -1), v[:, 0].reshape(b, -1),
                    tuple(p._data for p in cache),
                    jnp.asarray(page_table._data, jnp.int32),
                    jnp.asarray(start_pos._data, jnp.int32),
                    jnp.asarray(live._data, bool))
                with jax.named_scope("out"):
                    y = self._gated(a[:, None], u)
            out = tail(x, y)
        if cache is None:
            return NDArray(out)
        return NDArray(out), tuple(NDArray(p) for p in cache), \
            dict(zip(self.COUNTS, stats))


class LightningAttention(HybridBlock):
    """One ``lightning-attn`` sublayer at published layer ``layer``. Returns
    the output; with ``cache=`` (the layer's slot state), ``(output, new
    state, counts)``: ``counts`` {name: int32 scalar}, ``state_rows`` (the
    rows whose state advanced) and, of a prefill, ``positions_run`` (the
    positions of the stretches it ran)."""

    def __init__(self, cfg, layer, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        c = cfg
        self._heads, self._ch = c["lightning_heads"], c["lightning_head_dim"]
        h, ch, units, eps = self._heads, self._ch, c["units"], c["rms_norm_eps"]
        slopes = 2.0 ** (-8.0 * np.arange(1, h + 1) / h)
        #: log lambda a head
        self._log_decay = (-slopes * (
            1.0 - layer / (c["published_layers"] - 1) + 1e-5)).astype(np.float32)
        self._inv_freq = tuple(
            c["rope_theta"] ** (-2.0 * i / ch) for i in range(ch // 2))
        with self.name_scope():
            self.q = _dense(h * ch, units, dtype, "q_")
            self.k = _dense(h * ch, units, dtype, "k_")
            self.v = _dense(h * ch, units, dtype, "v_")
            self.g = _dense(h * ch, units, dtype, "g_")
            self.o = _dense(units, h * ch, dtype, "o_")
            self.q_norm = RMSNorm(ch, eps, dtype, prefix="q_norm_")
            self.k_norm = RMSNorm(ch, eps, dtype, prefix="k_norm_")
            self.o_norm = RMSNorm(ch, eps, "float32", prefix="o_norm_")

    def _decode(self, q, k, v, state, position, live):
        """One token a row: ``q``, ``k``, ``v`` (B, H, Ch) float32. A live
        row whose state has taken this position's token already (``taken ==
        position + 1``: the step is run a second time) reads and leaves it."""
        s, taken = state
        again = (live & (taken == position + 1))[:, None]
        decay = jnp.where(again, 1.0, jnp.exp(self._log_decay)[None])
        write = jnp.where(again, 0.0, jnp.ones_like(decay))
        why = gdn.gdn_decode_refusal(s, q, v)
        step = gdn.gdn_decode_xla if why else gdn.gdn_decode_step
        o, s = step(s, q, k, v, decay, write, live, delta=False)
        return o, (s, jnp.where(live, position + 1, taken))

    def _qkv(self, x, at):
        """(q, k, v (B, T, H, Ch), gate (B, T, H*Ch)) of ``x`` (B, T, units)
        at positions ``at`` (B,) ``+ arange(T)``: q and k normed a head and
        rotated."""
        b, t, _ = x.shape
        heads = lambda z: z.reshape((b, t, self._heads, self._ch))  # noqa: E731
        q = att.rotary_embedding(self.q_norm(heads(self.q(x)))._data, at,
                                 self._inv_freq)
        k = att.rotary_embedding(self.k_norm(heads(self.k(x)))._data, at,
                                 self._inv_freq)
        return q, k, heads(self.v(x))._data, jax.nn.sigmoid(self.g(x)._data)

    def _normed(self, o, dtype):
        """The reads ``o`` (..., H, Ch) times the queries' scale (the read
        is linear in them), RMS-normed a head: (..., H*Ch)."""
        o = self.o_norm(NDArray(o * self._ch ** -0.5))._data
        return o.reshape(*o.shape[:-2], -1).astype(dtype)

    def _chunk(self, x, norm, tail, length):
        """The BLOCK over one row's chunk ``x`` (1, T, units), its input,
        from position 0 and a zero state, ``length`` of its positions real:
        in stretches of ``_STRETCH`` tokens, each normed (``norm``),
        projected, run through the chunked recurrence from the state the
        last one left, normed a head, gated, projected back and taken through
        the rest of the block (``tail``) before the next. The stretches are
        all alike, so they are ONE ``lax.scan`` body (a sixteenth of a
        65,536-token program to compile), and a stretch whose first position
        is not below ``length`` is not run (:func:`_where_reached`: state and
        input pass through). Returns (the block's output, raw (1, T, units),
        (the state (H, Ch, Ch) behind position ``length - 1``, positions of
        the stretches that ran))."""
        t = x.shape[1]
        s = math.gcd(t, _STRETCH)

        def run(carry, first, x_s):
            state, ran = carry
            x_s = NDArray(x_s)
            with jax.named_scope("lightning"):
                with jax.named_scope("proj"):
                    q, k, v, gate = self._qkv(norm(x_s), first[None])
                with jax.named_scope("core"):
                    o, state = gdn.lightning_chunk_prefill(
                        q[0], k[0], v[0], self._log_decay, length - first,
                        _CHUNK, lambda o: self._normed(o, gate.dtype), state)
                with jax.named_scope("out"):
                    y = self.o(NDArray(o[None] * gate))._data
            return (state, ran + s), tail(x_s, y)

        def stretch(carry, at):
            first, x_s = at                         # (), (1, s, units)
            return _where_reached(
                first, length, lambda c, x_s: run(c, first, x_s),
                lambda c, x_s: (c, x_s), carry, x_s)

        carry = (jnp.zeros((self._heads, self._ch, self._ch), jnp.float32),
                 jnp.zeros((), jnp.int32))
        if t == s:
            carry, out = run(carry, jnp.zeros((), jnp.int32), x._data)
            return out, carry
        carry, out = jax.lax.scan(
            stretch, carry, (jnp.arange(0, t, s, dtype=jnp.int32),
                             x._data.reshape(t // s, 1, s, -1)))
        return out.reshape(1, t, -1), carry

    def mix(self, x, norm, tail, cache=None, start_pos=None, last_pos=None,
            slot=None, live=None):
        """The block around this mixer: ``x`` (B, T, units) the block's
        input, ``norm`` its norm before the mixer, ``tail(x_s, y_s)`` the
        rest of the block on a stretch (raw in, raw out). Returns the
        block's output; with ``cache=``, ``(output, new state, counts)``."""
        b, t, _ = x.shape
        row = lambda i: NDArray(x._data[i:i + 1])  # noqa: E731
        if cache is None:        # one whole chunk a row, no state kept
            return NDArray(jnp.concatenate(
                [self._chunk(row(i), norm, tail, t)[0] for i in range(b)]))
        if slot is not None:     # a prefill: one row's prompt, from zero
            length = jnp.asarray(last_pos._data, jnp.int32).reshape(()) + 1
            out, (s, ran) = self._chunk(x, norm, tail, length)
            at = jnp.asarray(slot._data, jnp.int32).reshape(())
            new = tuple(
                jax.lax.dynamic_update_slice_in_dim(
                    full._data, row_[None].astype(full._data.dtype), at, 0)
                for full, row_ in zip(cache, (gdn.state_rows(s), length)))
            counts = {"state_rows": jnp.asarray(1, jnp.int32),
                      "positions_run": ran}
        else:                    # a decode step: one token a row
            position = jnp.asarray(start_pos._data, jnp.int32)
            with jax.named_scope("lightning"):
                with jax.named_scope("proj"):
                    q, k, v, gate = self._qkv(norm(x), position)
                f32 = lambda z: z[:, 0].astype(jnp.float32)  # noqa: E731
                with jax.named_scope("core"):
                    o, new = self._decode(
                        f32(q), f32(k), f32(v), tuple(c._data for c in cache),
                        position, jnp.asarray(live._data, bool))
                with jax.named_scope("out"):
                    y = self.o(NDArray(
                        self._normed(o, gate.dtype)[:, None] * gate))._data
            out = tail(x, y)
            counts = {"state_rows": jnp.sum(jnp.asarray(live._data, jnp.int32))}
        return NDArray(out), tuple(NDArray(c) for c in new), counts


class MiniCPMSALABlock(HybridBlock):
    """Returns ``x``; with ``cache=``, ``(x, layer's cache, the mixer's
    counts)``."""

    def __init__(self, cfg, kind, layer, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        eps, units = cfg["rms_norm_eps"], cfg["units"]
        self._sparse = kind == "minicpm4"
        self._scale = cfg["scale_depth"] / math.sqrt(cfg["published_layers"])
        with self.name_scope():
            if self._sparse:
                self.attn = BlockSparseAttention(cfg, dtype, prefix="attn_")
            else:
                self.lin = LightningAttention(cfg, layer, dtype, prefix="lin_")
            self.mixer_norm = RMSNorm(units, eps, dtype, prefix="mixer_norm_")
            self.ffn = SwiGLU(units, cfg["hidden_size"], dtype, prefix="ffn_")
            self.ffn_norm = RMSNorm(units, eps, dtype, prefix="ffn_norm_")

    def hybrid_forward(self, F, x, cache=None, start_pos=None, page_table=None,
                       last_pos=None, slot=None, live=None):
        def tail(x_s, y):
            """The rest of the block on a stretch of its input ``x_s``
            (NDArray) whose mixer gave ``y`` (raw): raw out."""
            x_s = x_s + NDArray(y) * self._scale
            with jax.named_scope("ffn"):
                return (x_s + _in_token_blocks(self.ffn, self.ffn_norm(x_s))
                        * self._scale)._data

        # the mixers walk a long chunk in stretches and take each through
        # the whole block, so that of a prompt's length only the block's
        # input and output are ever held
        if cache is None:
            return (self.attn if self._sparse else self.lin).mix(
                x, self.mixer_norm, tail)
        if self._sparse:
            return self.attn.mix(
                x, self.mixer_norm, tail, cache=cache, start_pos=start_pos,
                page_table=page_table, last_pos=last_pos, live=live)
        return self.lin.mix(
            x, self.mixer_norm, tail, cache=cache, start_pos=start_pos,
            last_pos=last_pos, slot=slot, live=live)


class MiniCPMSALAModel(HybridBlock):
    """``dtype``: the matrices' and the norms'; the lightning layers' state
    and output norm, the selector's scores and every softmax are float32
    whatever it is."""

    #: a paged engine passes ``last_pos=`` ((1,) int32: a prefill's last real
    #: position) and gets the logits of that position alone, (1, 1, V)
    takes_last_pos = True
    #: the lightning layers keep state by SLOT: a paged engine passes
    #: ``init_paged_cache`` its ``slots``, a prefill ``slot=`` ((1,) int32)
    #: and a decode step ``live=`` ((B,) bool), and refuses what would need a
    #: copy of the state (docs/INFERENCE.md "Slot state")
    paged_slot_state = True
    #: the counts of a PREFILL that a paged engine brings back behind its
    #: first token (one array, so still one blocking read) and writes into
    #: the ``prefill`` record; a decode step's all come back with its tokens
    prefill_counts = ("blocks_read", "blocks_held", "compressed_written",
                      "state_rows", "positions_run")

    def __init__(self, dtype="float32", **cfg):
        known = minicpm_sala_configs["minicpm_sala"]
        super().__init__(prefix=cfg.pop("prefix", None))
        if set(cfg) - set(known):
            raise TypeError(f"unknown sizes {sorted(set(cfg) - set(known))}")
        c = self._cfg = dict(known, **cfg)
        c["mixer_types"] = tuple(c["mixer_types"])[:c["num_layers"]]
        if len(c["mixer_types"]) != c["num_layers"] \
                or set(c["mixer_types"]) - set(_MIXERS):
            raise ValueError("mixer_types names fewer layers than num_layers, "
                             f"or a kind that is none of {_MIXERS}")
        self._max_length = c["max_length"]
        self._kinds = c["mixer_types"]
        with self.name_scope():
            self.word_embed = nn.Embedding(
                c["vocab_size"], c["units"], dtype=dtype, prefix="word_embed_",
                weight_initializer=init.Normal(0.02))
            self.blocks = nn.HybridSequential(prefix="")
            for i, kind in enumerate(self._kinds):
                self.blocks.add(MiniCPMSALABlock(c, kind, i, dtype,
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
        """``(cache, groups)``: a sparse layer's ``(k_pool, v_pool)`` of
        shape (pages + 1, page_size, Hkv * Ch) and its compressed-key pool
        (pages + 1, page_size / kernel_stride, Hkv * Ch) in the group
        ``all``; a lightning layer's slot state in the group ``slot``: the
        state ``(slots, Ch, H * Ch)`` float32 and the positions taken
        ``(slots,)`` int32, all zero. A page is a block."""
        from ..ops.attention import alloc_paged_kv_cache

        c = self._cfg
        if page_size != c["block_size"]:
            raise ValueError(
                f"page_size {page_size}: a sparse layer's read lists blocks "
                f"of {c['block_size']} positions by page, so a page is a block")
        h, ch = c["lightning_heads"], c["lightning_head_dim"]
        cache = []
        for kind in self._kinds:
            if kind == "minicpm4":
                k_pool, v_pool = alloc_paged_kv_cache(
                    num_pages["all"], c["num_kv_heads"], page_size,
                    c["head_dim"], 1, dtype=dtype)[0]
                cache.append((k_pool, v_pool, jnp.zeros(
                    (k_pool.shape[0], page_size // c["kernel_stride"],
                     k_pool.shape[2]), k_pool.dtype)))
            else:
                cache.append((jnp.zeros((slots, ch, h * ch), jnp.float32),
                              jnp.zeros((slots,), jnp.int32)))
        from .. import observability as obs
        obs.gauge("gen_compressed_key_bytes",
                  "bytes of the sparse layers' compressed-key pools (the "
                  "selector's cache), all layers").set(sum(
                      layer[2].size * layer[2].dtype.itemsize
                      for layer, kind in zip(cache, self._kinds)
                      if kind == "minicpm4"))
        return cache, tuple("all" if kind == "minicpm4" else "slot"
                            for kind in self._kinds)

    def paged_read_path(self, batch_size, pools, page_table):
        """What a paged engine's decode program reads its caches by: the
        sparse layers' pools (``paged_gqa_decode_selected`` over the tables of
        selected pages, or the XLA gather of the listed pages and why), their
        selector's compressed keys (``paged_block_scores`` or XLA) and the
        lightning layers' state (``lightning_decode_step`` or the XLA form
        and why), as the operators choose at trace time."""
        from ..ops.pallas_paged_attention import (
            paged_block_scores_refusal, paged_gqa_selected_refusal)

        c = self._cfg
        dtype = self.word_embed.weight.data()._data.dtype
        of = lambda kind: next(  # noqa: E731
            (p for p, k in zip(pools, self._kinds) if k == kind), None)
        out, shape, f32 = [], jax.ShapeDtypeStruct, jnp.float32
        if of("minicpm4") is not None:
            why = paged_gqa_selected_refusal(
                shape((batch_size, c["num_heads"], 1, c["head_dim"]), dtype),
                of("minicpm4")[0],
                shape((batch_size, c["num_kv_heads"],
                       max(c["topk"], c["dense_len"] // c["block_size"])),
                      jnp.int32))
            out.append("sparse layers: "
                       + (f"selected_pages_xla ({why})" if why
                          else "selected_pages_kernel"))
            table = page_table[0] if isinstance(page_table, tuple) \
                else page_table
            ck_pool = of("minicpm4")[2]
            why = paged_block_scores_refusal(
                shape((batch_size, c["num_kv_heads"],
                       c["num_heads"] // c["num_kv_heads"], c["head_dim"]),
                      ck_pool.dtype),
                shape((batch_size, table.shape[1] * ck_pool.shape[1],
                       ck_pool.shape[2]), ck_pool.dtype))
            out.append("selector: " + (f"block_scores_xla ({why})" if why
                                       else "block_scores_kernel"))
        if of("lightning-attn") is not None:
            heads = (batch_size, c["lightning_heads"], c["lightning_head_dim"])
            why = gdn.gdn_decode_refusal(of("lightning-attn")[0],
                                         shape(heads, f32), shape(heads, f32))
            out.append("lightning layers: " + (f"lightning_xla ({why})" if why
                                               else "lightning_kernel"))
        return "; ".join(out)

    def logits_width(self):
        return self._cfg["vocab_size"]

    def hybrid_forward(self, F, token_ids, cache=None, start_pos=None,
                       page_table=None, last_pos=None, slot=None, live=None):
        """Logits; with ``cache=``, ``(logits, new_cache, counts)``:
        ``counts`` is {name: (layers that count it,) int32} of this forward,
        a decode step's or a prefill's (:attr:`prefill_counts`): a sparse
        layer's ``blocks_read`` and ``blocks_held`` (the blocks a key-value
        head's reads visit and the blocks held, over the live rows of a step
        or the real queries of a prompt) and ``compressed_written``; a
        lightning layer's ``state_rows``; of a prefill, every layer's
        ``positions_run`` (the positions of the stretches it ran: the
        prompt's length up to a whole stretch). With ``last_pos=`` the
        logits are those of that position alone."""
        c = self._cfg
        x = self.word_embed(token_ids) * c["scale_emb"]
        table = page_table[0] if isinstance(page_table, (tuple, list)) \
            else page_table
        new_cache, of_layers = [], []
        for i, blk in enumerate(self.blocks):
            if cache is None:
                x = blk(x)
                continue
            x, layer_cache, counts = blk(
                x, cache=cache[i], start_pos=start_pos, page_table=table,
                last_pos=last_pos, slot=slot, live=live)
            new_cache.append(layer_cache)
            of_layers.append(counts)
        if last_pos is not None:
            at = jnp.asarray(last_pos._data, jnp.int32).reshape(-1)[0]
            x = NDArray(jax.lax.dynamic_slice_in_dim(x._data, at, 1, axis=1))
        # float32 logits: in bfloat16 neighbouring logits tie and the
        # argmax would take the first of them
        logits = self.head(self.norm(x).astype("float32")
                           * (c["dim_model_base"] / c["units"]))
        if cache is None:
            return logits
        counts = {}
        for name in self.prefill_counts:   # an entry a layer that counts it
            entries = [layer[name] for layer in of_layers if name in layer]
            if entries:
                counts[name] = jnp.stack(entries).astype(jnp.int32)
        return logits, new_cache, counts


def get_minicpm_sala(model_name="minicpm_sala", **overrides):
    cfg = dict(minicpm_sala_configs[model_name])
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return MiniCPMSALAModel(dtype=dtype, **cfg)
