"""Attention operators.

Re-designs the reference's fused transformer kernels
(``src/operator/contrib/transformer.cc``/``.cu`` —
``_contrib_interleaved_matmul_selfatt_qk`` / ``_valatt`` /
``_contrib_interleaved_matmul_encdec_*`` / ``_contrib_div_sqrt_dim``, the ops
GluonNLP BERT calls) for TPU:

  - the interleaved-matmul API is preserved exactly (projections stored
    interleaved as (T, B, H*3*Ch)) so GluonNLP-shaped model code runs;
  - the *blessed* path is ``multi_head_attention`` which dispatches to a
    Pallas flash-attention kernel on TPU (O(L) memory, MXU-tiled; long
    unmasked sequences only) and a jnp reference path elsewhere — see
    ``mxnet_tpu.ops.flash_attention``;
  - ``self_attention_packed`` takes the packed ``(B, T, 3C)`` projection of
    a self-attention block as its Dense wrote it: short masked sequences
    on a TPU run ``mxnet_tpu.ops.pallas_packed_attention``, everything else
    unpacks and calls ``multi_head_attention``.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..registry import register

logger = logging.getLogger(__name__)


@register("_contrib_div_sqrt_dim")
def div_sqrt_dim(data):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], jnp.float32)).astype(data.dtype)


def _split_interleaved_qkv(qkv, heads):
    """(T, B, H*3*Ch) interleaved per head -> q, k, v each (B, H, T, Ch)."""
    t, b, hc3 = qkv.shape
    ch = hc3 // (heads * 3)
    x = qkv.reshape(t, b, heads, 3, ch)
    q, k, v = x[:, :, :, 0], x[:, :, :, 1], x[:, :, :, 2]
    # (T,B,H,Ch) -> (B,H,T,Ch)
    to_bhtc = lambda a: a.transpose(1, 2, 0, 3)
    return to_bhtc(q), to_bhtc(k), to_bhtc(v)


@register("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(qkv, heads=1):
    """scores = scaled Q @ K^T, output (B*H, T, T) like the reference."""
    from ..contrib.amp import cast_inputs

    orig_dtype = qkv.dtype
    (qkv,) = cast_inputs(qkv)
    q, k, v = _split_interleaved_qkv(qkv, int(heads))
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32)).astype(q.dtype)
    scores = jnp.einsum("bhqc,bhkc->bhqk", q * scale, k)
    b, h, t, _ = scores.shape
    # restore the caller's dtype: downstream mask arithmetic / softmax on the
    # scores must not change precision because a global AMP flag flipped
    return scores.reshape(b * h, t, t).astype(orig_dtype)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(qkv, att, heads=1):
    """out = att @ V, returned (T, B, H*Ch) like the reference."""
    q, k, v = _split_interleaved_qkv(qkv, int(heads))
    b, h, t, ch = v.shape
    att = att.reshape(b, h, t, t)
    out = jnp.einsum("bhqk,bhkc->bhqc", att, v)
    return out.transpose(2, 0, 1, 3).reshape(t, b, h * ch)


@register("_contrib_interleaved_matmul_encdec_qk")
def interleaved_matmul_encdec_qk(q_proj, kv_proj, heads=1):
    tq, b, hc = q_proj.shape
    ch = hc // int(heads)
    q = q_proj.reshape(tq, b, int(heads), ch).transpose(1, 2, 0, 3)
    tk = kv_proj.shape[0]
    kv = kv_proj.reshape(tk, b, int(heads), 2, ch)
    k = kv[:, :, :, 0].transpose(1, 2, 0, 3)
    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32)).astype(q.dtype)
    scores = jnp.einsum("bhqc,bhkc->bhqk", q * scale, k)
    return scores.reshape(b * int(heads), tq, tk)


@register("_contrib_interleaved_matmul_encdec_valatt")
def interleaved_matmul_encdec_valatt(kv_proj, att, heads=1):
    tk, b, hc2 = kv_proj.shape
    ch = hc2 // (2 * int(heads))
    kv = kv_proj.reshape(tk, b, int(heads), 2, ch)
    v = kv[:, :, :, 1].transpose(1, 2, 0, 3)  # (B,H,Tk,Ch)
    h = int(heads)
    tq = att.shape[1]
    att = att.reshape(b, h, tq, tk)
    out = jnp.einsum("bhqk,bhkc->bhqc", att, v)
    return out.transpose(2, 0, 1, 3).reshape(tq, b, h * ch)


# --------------------------------------------------------------------------
# cached (autoregressive) attention
# --------------------------------------------------------------------------
def _unwrap(x):
    # hybrid_forward passes cache entries through the nd kwargs channel,
    # which does not unwrap containers — accept NDArray or raw array
    return getattr(x, "_data", x)


def alloc_kv_cache(batch_size, num_heads, max_length, channels, num_layers,
                   dtype="float32"):
    """Per-layer ``(k_buf, v_buf)`` zero buffers of shape (B, H, Tmax, Ch) —
    the static decode carry both model zoos hand to the cached path
    (``GPT2Model.init_cache`` / ``Transformer.init_decode_cache``)."""
    from ..base import dtype_np

    shape = (int(batch_size), int(num_heads), int(max_length), int(channels))
    return [(jnp.zeros(shape, dtype_np(dtype)), jnp.zeros(shape, dtype_np(dtype)))
            for _ in range(int(num_layers))]


def alloc_paged_kv_cache(num_pages, num_heads, page_size, channels, num_layers,
                         dtype="float32"):
    """Per-layer ``(k_pool, v_pool)`` page pools of shape
    (num_pages + 1, page_size, H * Ch): the global block pool of the paged
    decode cache (docs/INFERENCE.md "Paged cache"). Token-major: the page
    axis first (all the engine, the copy-on-write program and the allocator
    index), a page one contiguous block, a token's heads side by side on the
    minor axis, so a token is written at ``[page, offset]`` and a row is read
    by whole pages. Page 0 is the reserved **trash page**: page-table entries
    of released / past-capacity rows are 0, so their (masked) writes land
    there instead of in live pages."""
    from ..base import dtype_np

    shape = (int(num_pages) + 1, int(page_size), int(num_heads) * int(channels))
    return [(jnp.zeros(shape, dtype_np(dtype)), jnp.zeros(shape, dtype_np(dtype)))
            for _ in range(int(num_layers))]


def _frontier_masked_attention(q, k_hist, v_hist, position):
    """Shared core of the cached paths: every query at row position
    ``position + i`` attends to history entries ``<= position + i`` —
    exactly the causal mask of a full forward. Entries past a row's
    frontier (zeros, stale rejected-draft K/V, trash-page garbage) are
    masked to -inf before the softmax, so they contribute *exactly* 0.0 —
    which is what makes the paged layout bit-identical to the contiguous
    one: both feed this very function.

    The two products take their operands in the history's dtype when that
    is bfloat16 (a float32 query and the float32 weights are rounded where
    the MXU's one default pass rounds them anyway, and the history is not
    converted), else in the promoted dtype; they accumulate in float32, and
    the softmax is float32. Returns float32."""
    tq, ch = q.shape[2], q.shape[3]
    tmax = k_hist.shape[2]
    mm = (k_hist.dtype if k_hist.dtype == jnp.bfloat16
          else jnp.promote_types(q.dtype, k_hist.dtype))
    f32 = dict(preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32))
    scores = jnp.einsum("bhqc,bhkc->bhqk", q.astype(mm), k_hist.astype(mm),
                        **f32) * scale
    key_idx = jnp.arange(tmax, dtype=jnp.int32)[None, None, None, :]
    q_pos = (position[:, None, None, None]
             + jnp.arange(tq, dtype=jnp.int32)[None, None, :, None])
    scores = jnp.where(key_idx <= q_pos, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(mm)
    return jnp.einsum("bhqk,bhkc->bhqc", att, v_hist.astype(mm), **f32)


def _cached_mha(q, k_new, v_new, k_buf, v_buf, position):
    """Incremental attention against static max-length K/V buffers.

    q/k_new/v_new: (B, H, Tq, Ch) — the Tq new positions of each row;
    k_buf/v_buf:   (B, H, Tmax, Ch) — the persistent cache;
    position:      (B,) int32 — per-row start index of this chunk (rows
                   admitted by the batcher at different times carry
                   different positions, no shape change involved).

    The new K/V land in the buffers first (vmapped ``dynamic_update_slice``
    at each row's own offset), then :func:`_frontier_masked_attention`
    reads them back, so logits match a from-scratch re-forward to fp
    tolerance.
    """

    def write(buf, new, p):  # one row: (H, Tmax, Ch) <- (H, Tq, Ch) at p
        return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                            (0, p, 0))

    k_buf = jax.vmap(write)(k_buf, k_new, position)
    v_buf = jax.vmap(write)(v_buf, v_new, position)
    out = _frontier_masked_attention(q, k_buf, v_buf, position)
    return out, k_buf, v_buf


def _paged_cached_mha(q, k_new, v_new, k_pool, v_pool, page_table, position):
    """Incremental attention against a paged (block) KV pool.

    q/k_new/v_new: (B, H, Tq, Ch) — the Tq new positions of each row;
    k_pool/v_pool: (P+1, ps, H*Ch) — the global page pool, token-major
                   (:func:`alloc_paged_kv_cache`; page 0 = trash);
    page_table:    (B, n_pages) int32 — per-row page ids in slot order
                   (slot s holds sequence positions ``s*ps .. (s+1)*ps-1``;
                   unallocated slots are 0 and only ever masked);
    position:      (B,) int32 — per-row start index of this chunk.

    Writes put each new token at ``pool[table[pos // ps], pos % ps]``, two
    leading indices, in place on a donated pool (positions past the table's
    capacity, and any slot a released row's cleared table maps to, redirect
    to the trash page). Reads are one algorithm with two paths, chosen from
    what the operands and the process show
    (:func:`~mxnet_tpu.ops.pallas_paged_attention.paged_attention_refusal`:
    backend, head size and count, page size, dtypes, the VMEM that Tq
    queries against a row's history need): the Pallas kernel copies the
    pages each row HOLDS into VMEM and attends there (decode, speculative
    verification); the XLA path gathers ``pool[page_table]``, the table's
    whole width, into a (B, H, cap, Ch) view and runs the shared
    :func:`_frontier_masked_attention` (long prefill chunks, the CPU, every
    refused shape). Both mask stale/trash/garbage K/V to a softmax weight of
    exactly 0.0; the XLA path is bit-identical to the contiguous cache.
    The trace-time counter ``paged_read_path_total{path, reason}`` says which
    was built, and why the kernel was not.
    """
    from .. import observability as obs
    from . import pallas_paged_attention as ppa

    k_pool, v_pool = _paged_write(k_new, v_new, k_pool, v_pool, page_table,
                                  position)
    why = ppa.paged_attention_refusal(q, k_pool, page_table)
    obs.counter("paged_read_path_total").inc(
        path="xla_gather" if why else "kernel", reason=why or "")
    read = _paged_gather_read if why else ppa.paged_attention_read
    return read(q, k_pool, v_pool, page_table, position), k_pool, v_pool


def _paged_write(k_new, v_new, k_pool, v_pool, page_table, position):
    """The Tq new keys and values of each row, (B, H, Tq, Ch), written into
    the pools at ``[page, offset]``; positions past the table's capacity go
    to the trash page."""
    b, h, tq, ch = k_new.shape
    ps, n_pages = k_pool.shape[1], page_table.shape[1]
    pos = (position[:, None]
           + jnp.arange(tq, dtype=jnp.int32)[None, :])          # (B, Tq)
    slot = jnp.clip(pos // ps, 0, n_pages - 1)
    pid = jnp.take_along_axis(page_table, slot, axis=1)          # (B, Tq)
    pid = jnp.where(pos < n_pages * ps, pid, 0).reshape(-1)      # overflow -> trash
    off = (pos % ps).reshape(-1)

    def token_major(x, pool):   # (B,H,Tq,Ch) -> (B*Tq, H*Ch)
        return x.transpose(0, 2, 1, 3).reshape(b * tq, h * ch).astype(pool.dtype)

    return (k_pool.at[pid, off].set(token_major(k_new, k_pool)),
            v_pool.at[pid, off].set(token_major(v_new, v_pool)))


def _paged_gather_read(q, k_pool, v_pool, page_table, position):
    """The XLA read path: every row's history gathered by its page table,
    the table's whole width, and brought to (B, H, cap, Ch) for the dense
    cache's own arithmetic."""
    b, h, _, ch = q.shape
    cap = page_table.shape[1] * k_pool.shape[1]

    def history(pool):   # (B, n_pages, ps, H*Ch) -> (B, H, cap, Ch)
        return pool[page_table].reshape(b, cap, h, ch).transpose(0, 2, 1, 3)

    return _frontier_masked_attention(q, history(k_pool), history(v_pool),
                                      position)


def _paged_gather_mha(q, k_new, v_new, k_pool, v_pool, page_table, position):
    """:func:`_paged_cached_mha` through the XLA read path whatever the gate
    says: the reference the paged kernel is checked against."""
    k_pool, v_pool = _paged_write(k_new, v_new, k_pool, v_pool, page_table,
                                  position)
    out = _paged_gather_read(q, k_pool, v_pool, page_table, position)
    return out, k_pool, v_pool


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# --------------------------------------------------------------------------
def yarn_inv_freq(dim, base=10000.0, factor=1.0, original_max_position=4096,
                  beta_fast=32.0, beta_slow=1.0):
    """Rotary inverse frequencies under YaRN (Peng et al. 2023), as a tuple
    of ``dim // 2`` floats: each frequency is a blend of the plain one and
    the one interpolated by ``factor``, by a linear ramp between the two
    correction dimensions (the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context). ``factor`` 1 gives the
    plain frequencies."""
    import math

    plain = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    if factor <= 1:
        return tuple(plain)

    def correction_dim(turns):
        return (dim * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0)
            for i in range(dim // 2)]
    return tuple(f / factor * r + f * (1.0 - r) for f, r in zip(plain, ramp))


@register("rotary_embedding")
def rotary_embedding(x, position=None, inv_freq=(), factor=1.0):
    """Rotate the last axis of ``x`` (B, T, ..., dim), laid out as two
    halves (``rotate_half``), by the angles ``pos * inv_freq``; a row's
    positions are ``position[b] + arange(T)`` (``position`` None: from 0).
    Angles, cos and sin are float32; ``factor`` scales both."""
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    if position is not None:
        pos = pos + jnp.asarray(_unwrap(position), jnp.int32).reshape(-1, 1)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)             # (B|1, T, dim)
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:]
    cos, sin = jnp.cos(ang).reshape(shape) * factor, jnp.sin(ang).reshape(shape) * factor
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x.astype(jnp.float32) * cos
            + turned.astype(jnp.float32) * sin).astype(x.dtype)


def alloc_paged_latent_cache(num_pages, page_size, width, num_layers,
                             dtype="float32"):
    """Per-layer ``(pool,)`` of shape (num_pages + 1, page_size, W): the
    paged cache of latent attention, one vector a token a layer (the
    normalised latent and the rotated shared key side by side, ``width``
    values), no head axis. ``W`` is ``width`` rounded up to whole 128-lane
    tiles (576 -> 640): a page is then a block the paged kernel can copy
    (Mosaic refuses a slice of 576 lanes) and the lanes past ``width`` hold
    zeros, always. Page 0 is the trash page, as in
    :func:`alloc_paged_kv_cache`."""
    from ..base import dtype_np
    from .pallas_common import LANES

    shape = (int(num_pages) + 1, int(page_size),
             -(-int(width) // LANES) * LANES)
    return [(jnp.zeros(shape, dtype_np(dtype)),) for _ in range(int(num_layers))]


def mla_form(tq, nope, rope, vd, kl):
    """The cheaper of latent attention's two forms for ``tq`` queries a row,
    by the operations each needs per cached position: absorbed scores and
    outputs are ``kl``-wide per head and query, decompressed ones pay the
    up-projection of every position once and are then head-sized. One token
    a row (decode) is absorbed; a long prefill chunk is decompressed."""
    absorbed = tq * (2 * kl + rope)
    decompressed = kl * (nope + vd) + tq * (nope + rope + vd)
    return "absorbed" if absorbed <= decompressed else "decompressed"


def _mla_softmax(scores, mask, scale, dtype):
    scores = jnp.where(mask[:, None], scores * scale, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def _absorb_queries(q_nope, w_kvb):
    """``q_nope . W_UK``: the queries in the latent space, (B, T, H, kl)."""
    w_uk = w_kvb[:, :q_nope.shape[-1]]                     # (H, d, kl)
    return jnp.einsum("bthd,hdl->bthl", q_nope, w_uk,
                      preferred_element_type=jnp.float32).astype(q_nope.dtype)


def _up_project_values(o_lat, w_kvb, nope):
    """``W_UV`` applied to the weighted latents (B, T, H, kl)."""
    return jnp.einsum("bthl,hvl->bthv", o_lat, w_kvb[:, nope:],
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


def _weighted_latents(q_lat, q_rope, c_hist, r_hist, mask, scale):
    """``softmax((q_lat . c + q_rope . r) * scale) . c``: the absorbed form
    between its two up-projections, (B, T, H, kl) in the queries' dtype."""
    f32 = dict(preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bthl,bkl->bhtk", q_lat, c_hist, **f32)
              + jnp.einsum("bthr,bkr->bhtk", q_rope, r_hist, **f32))
    att = _mla_softmax(scores, mask, scale, q_lat.dtype)
    return jnp.einsum("bhtk,bkl->bthl", att, c_hist, **f32).astype(q_lat.dtype)


def _mla_absorbed(q_nope, q_rope, c_hist, r_hist, w_kvb, mask, scale):
    """Scores and outputs in the latent space: ``W_UK`` is folded into the
    queries and ``W_UV`` applied after the weighted sum, so nothing
    head-sized exists per cached position."""
    o_lat = _weighted_latents(_absorb_queries(q_nope, w_kvb), q_rope, c_hist,
                              r_hist, mask, scale)
    return _up_project_values(o_lat, w_kvb, q_nope.shape[-1])


def _mla_decompressed(q_nope, q_rope, c_hist, r_hist, w_kvb, mask, scale):
    """Keys and values up-projected from the latent, heads in blocks of 16
    so that a long chunk's scores fit."""
    b, t, heads, nope = q_nope.shape
    block = 16 if heads % 16 == 0 else heads
    f32 = dict(preferred_element_type=jnp.float32)

    def heads_of(args):
        qn, qr, w = args          # (B,T,hb,nope), (B,T,hb,rope), (hb,nope+vd,kl)
        kv = jnp.einsum("bkl,hdl->bkhd", c_hist, w, **f32).astype(qn.dtype)
        scores = (jnp.einsum("bthd,bkhd->bhtk", qn, kv[..., :nope], **f32)
                  + jnp.einsum("bthr,bkr->bhtk", qr, r_hist, **f32))
        att = _mla_softmax(scores, mask, scale, qn.dtype)
        return jnp.einsum("bhtk,bkhv->bthv", att, kv[..., nope:],
                          **f32).astype(qn.dtype)

    def blocks(a):                # (B,T,H,d) -> (H/hb, B,T,hb,d)
        return jnp.moveaxis(a.reshape(b, t, heads // block, block, -1), 2, 0)

    out = jax.lax.map(heads_of, (blocks(q_nope), blocks(q_rope),
                                 w_kvb.reshape(heads // block, block,
                                               *w_kvb.shape[1:])))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, heads, -1)


@register("latent_attention")
def latent_attention(q_nope, q_rope, c_kv, k_rope, w_kvb, scale=1.0,
                     cache=None, position=None, page_table=None):
    """Multi-head latent attention over ``(B, T, ...)`` activations.

    ``q_nope`` (B, T, H, nope) and ``q_rope`` (B, T, H, rope, rotated) are
    the queries' two parts; ``c_kv`` (B, T, kl) is the NORMALISED latent and
    ``k_rope`` (B, T, rope) the rotated key all heads share; ``w_kvb``
    (H * (nope + vd), kl) up-projects a latent into each head's key and
    value. Scores are ``(q_nope . k_nope + q_rope . k_rope) * scale``,
    causal; the softmax is float32 whatever the inputs (the policy of
    :func:`multi_head_attention`); returns the context (B, T, H * vd).

    The same mathematics in two forms, chosen by :func:`mla_form` from the
    shapes: ``absorbed`` folds the up-projection into the queries and the
    output; ``decompressed`` up-projects keys and values. The ``mla_path_total{form, read}`` counter says at trace time
    which was built.

    ``cache=(pool,), position=, page_table=`` is the paged path
    (docs/INFERENCE.md "A model's per-layer state"): the new tokens'
    ``[c_kv ; k_rope ; 0]`` (:func:`alloc_paged_latent_cache`'s whole lane
    tiles) are scattered into the pool at their rows' pages, in place on a
    donated pool, and the call returns ``(context, pool')``. Nothing
    decompressed is ever cached. The read is one algorithm with two paths,
    chosen from what the operands and the process show
    (:func:`~mxnet_tpu.ops.pallas_paged_attention.paged_latent_attention_refusal`:
    backend, dtypes, the pool's width and page size, the form, the VMEM
    that Tq queries of every head against a row's history need): the Pallas
    kernel copies the pages each row HOLDS into VMEM and attends there in
    the absorbed form (decode); the XLA path gathers each row's history by
    its page table, the table's whole width (a chunk of more than one token
    whose rows all start at position 0 reads the chunk itself: a
    ``lax.cond`` on the positions). ``mla_path_total{form, read}`` and
    ``paged_read_path_total{path, reason}`` say which was built, and why
    the kernel was not.
    """
    from .. import observability as obs
    from . import pallas_paged_attention as ppa

    b, t, heads, nope = q_nope.shape
    kl, rope = c_kv.shape[-1], k_rope.shape[-1]
    w_kvb = w_kvb.reshape(heads, -1, kl)
    vd = w_kvb.shape[1] - nope
    form = mla_form(t, nope, rope, vd, kl)
    core = {"absorbed": _mla_absorbed, "decompressed": _mla_decompressed}[form]
    q_idx = jnp.arange(t, dtype=jnp.int32)
    if cache is None:
        obs.counter("mla_path_total").inc(form=form, read="none")
        mask = jnp.broadcast_to(q_idx[None, :] <= q_idx[:, None], (b, t, t))
        with jax.named_scope("core"):
            out = core(q_nope, q_rope, c_kv, k_rope, w_kvb, mask, scale)
        return out.reshape(b, t, heads * vd)
    if position is None or page_table is None:
        raise ValueError("latent_attention(cache=...) is paged: it needs "
                         "position= and page_table=")
    (pool,) = (_unwrap(c) for c in cache)
    position = jnp.asarray(_unwrap(position), jnp.int32)
    table = jnp.asarray(_unwrap(page_table), jnp.int32)
    ps, n_pages, width = pool.shape[1], table.shape[1], pool.shape[2]
    cap = n_pages * ps
    why = ppa.paged_latent_attention_refusal(q_nope, pool, table, form)
    read = "xla_gather" if why else "kernel"
    obs.counter("mla_path_total").inc(form=form, read=read)
    obs.counter("paged_read_path_total").inc(path=read, reason=why or "")
    with jax.named_scope("kv"):
        pos = position[:, None] + q_idx[None, :]                   # (B, T)
        pid = jnp.take_along_axis(table, jnp.clip(pos // ps, 0, n_pages - 1),
                                  axis=1)
        pid = jnp.where(pos < cap, pid, 0)                 # overflow -> trash
        # the pool's lanes past kl + rope are written as zeros, and never
        # anything else: a zero query lane does not clear a NaN
        new = jnp.pad(jnp.concatenate([c_kv, k_rope], axis=-1),
                      ((0, 0), (0, 0), (0, width - kl - rope))).astype(pool.dtype)
        pool = pool.at[pid.reshape(-1), (pos % ps).reshape(-1)].set(
            new.reshape(b * t, width))

    def from_kernel():
        # the pages each row holds, in VMEM; W_UK and W_UV stay XLA's
        with jax.named_scope("core"):
            o_lat = ppa.paged_latent_attention_read(
                _absorb_queries(q_nope, w_kvb), q_rope, pool, table, position,
                scale)
            return _up_project_values(o_lat.astype(q_nope.dtype), w_kvb, nope)

    def from_pool():
        # every row's history by its page table: the table's whole width
        with jax.named_scope("kv"):
            hist = pool[table].reshape(b, cap, width).astype(c_kv.dtype)
        mask = jnp.arange(cap, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
        with jax.named_scope("core"):
            return core(q_nope, q_rope, hist[..., :kl], hist[..., kl:kl + rope],
                        w_kvb, mask, scale)

    def from_chunk():
        # rows that start at 0 have no history but the chunk itself, as the
        # pool now holds it (rounded to the pool's dtype)
        held = new.astype(c_kv.dtype)
        mask = jnp.broadcast_to(q_idx[None, :] <= q_idx[:, None], (b, t, t))
        with jax.named_scope("core"):
            return core(q_nope, q_rope, held[..., :kl], held[..., kl:kl + rope],
                        w_kvb, mask, scale)

    # a prefill chunk that opens its rows (no adopted prefix) reads t keys,
    # not the page table's width; the program holds both and the positions
    # it is handed choose. One token a row always has a history.
    if why is None:
        out = from_kernel()
    else:
        out = from_pool() if t == 1 else jax.lax.cond(
            jnp.all(position == 0), from_chunk, from_pool)
    return out.reshape(b, t, heads * vd), pool


# --------------------------------------------------------------------------
# blessed fused attention entry point
# --------------------------------------------------------------------------
def _reference_mha(q, k, v, mask=None, causal=False):
    """jnp O(L^2) reference attention; q,k,v (B,H,T,Ch)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bhqc,bhkc->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((t_q, t_k), bool), t_k - t_q)
        scores = jnp.where(cm, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkc->bhqc", att, v)


@register("multi_head_attention", aliases=("_contrib_multi_head_attention",))
def multi_head_attention(q, k, v, mask=None, causal=False, use_flash="auto",
                         cache=None, position=None, page_table=None):
    """Fused scaled-dot-product attention over (B, H, T, Ch) tensors.

    ``use_flash='auto'`` picks the Pallas flash kernel on TPU backends when
    shapes are tile-friendly, otherwise the XLA einsum path.

    Dtype policy: every path (flash kernel, einsum reference, chunked, and
    the cached decode path below) computes scores, the softmax, and its
    normalizer in float32 regardless of the input dtype, and returns the
    caller's dtype — so a compiled bf16/f16 AMP policy
    (``parallel.TrainStep(amp=...)``) changes ONLY the q/k/v and
    att-times-v matmul precision, never the softmax numerics.

    ``cache=(k_buf, v_buf), position=`` switches to the autoregressive
    cached path (docs/INFERENCE.md): k/v carry only the *new* positions,
    the buffers hold the whole static max-length history, and the call
    returns ``(out, k_buf', v_buf')`` instead of just ``out``. ``position``
    is a per-row ``(B,)`` int32 (or scalar) start index; masking enforces
    the same causal structure as ``causal=True`` on the full sequence.

    With ``page_table=`` ((B, n_pages) int32) the cache entries are read as
    **page pools** ``(P+1, page_size, H*Ch)`` instead of contiguous per-row
    buffers — the paged variant (docs/INFERENCE.md "Paged cache"): same
    frontier mask, same return convention, storage indirected through the
    per-row page table.
    """
    from . import flash_attention as fa
    from ..contrib.amp import cast_inputs

    orig_dtype = q.dtype
    q, k, v = cast_inputs(q, k, v)  # AMP: score/context matmuls on the MXU
    if cache is not None:
        if position is None:
            raise ValueError("multi_head_attention(cache=...) needs position=")
        k_buf, v_buf = (_unwrap(c) for c in cache)
        position = jnp.asarray(_unwrap(position), jnp.int32)
        if position.ndim == 0:
            position = jnp.broadcast_to(position, (q.shape[0],))
        if page_table is not None:
            table = jnp.asarray(_unwrap(page_table), jnp.int32)
            out, k_buf, v_buf = _paged_cached_mha(q, k, v, k_buf, v_buf,
                                                  table, position)
        else:
            out, k_buf, v_buf = _cached_mha(q, k, v, k_buf, v_buf, position)
        return out.astype(orig_dtype), k_buf, v_buf
    if use_flash == "auto":
        use_flash = fa.flash_supported(q, k, v, mask)
    if use_flash:
        out = fa.flash_attention(q, k, v, mask=mask, causal=causal)
    else:
        out = _reference_mha(q, k, v, mask=mask, causal=causal)
    return out.astype(orig_dtype)


# --------------------------------------------------------------------------
# self-attention over the packed q/k/v projection
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _log_refusal_once(why):
    logger.info("self_attention_packed: no packed kernel: %s", why)


def _unpack_qkv(qkv, heads):
    """(B, T, 3C) with columns ordered [3][H][D] -> q, k, v each
    (B, H, T, D): what a ``Dense(3 * units)`` projection emits."""
    b, t, c3 = qkv.shape
    x = qkv.reshape((b, t, 3, heads, c3 // (3 * heads)))
    x = x.transpose((2, 0, 3, 1, 4))
    return x[0], x[1], x[2]


def _merge_heads(x):
    """(B, H, T, D) -> (B, T, H*D): the context as a projection reads it."""
    b, h, t, d = x.shape
    return x.transpose((0, 2, 1, 3)).reshape((b, t, h * d))


@register("self_attention_packed")
def self_attention_packed(qkv, mask=None, heads=1):
    """Self-attention of ``heads`` heads over the packed projection ``qkv``
    ``(B, T, 3C)``, columns ordered ``[3][H][D]`` as a ``Dense(3C)`` writes
    them; returns the context ``(B, T, C)`` as the output projection reads
    it. ``mask`` is anything :func:`multi_head_attention` takes.

    One semantics, the path chosen from what the operands and the process
    show (backend, dtype, shapes, the mask's form, the active mesh; no knob):
    short sequences with a keys-only mask on a TPU run
    :mod:`mxnet_tpu.ops.pallas_packed_attention`, which reads ``qkv`` in
    place and keeps scores and probabilities in VMEM; anything else
    unpacks to ``(B, H, T, D)``, calls :func:`multi_head_attention` (so long
    sequences still reach the flash kernel) and transposes back. The
    ``attention_path_total{path}`` counter says at trace time which ran
    (``packed_kernel``, ``flash`` or ``einsum``); the first reason the
    kernel was refused is logged once. Same dtype policy on every path.
    """
    from .. import observability as obs
    from ..contrib.amp import cast_inputs
    from . import flash_attention as fa
    from . import pallas_packed_attention as ppa

    heads = int(heads)
    orig_dtype = qkv.dtype
    (qkv,) = cast_inputs(qkv)  # AMP, as multi_head_attention casts q, k, v
    why = ppa.packed_attention_refusal(qkv, mask, heads)
    if why is None:
        obs.counter("attention_path_total").inc(path="packed_kernel")
        return ppa.packed_attention(qkv, mask, heads).astype(orig_dtype)
    _log_refusal_once(why)
    q, k, v = _unpack_qkv(qkv, heads)
    # multi_head_attention's own choice, made here so that it can be counted
    use_flash = fa.flash_supported(q, k, v, mask)
    obs.counter("attention_path_total").inc(
        path="flash" if use_flash else "einsum")
    out = multi_head_attention(q, k, v, mask=mask, use_flash=use_flash)
    return _merge_heads(out).astype(orig_dtype)
