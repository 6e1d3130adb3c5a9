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
import math

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
# latent attention over a learned subset of the cache, and under a window
# --------------------------------------------------------------------------
_F32 = dict(preferred_element_type=jnp.float32)
_KEY_STRETCH = 2048  # keys a long chunk's softmax takes at once
# the masked prefill kernel's grid step: heads (unrolled, a mask tile read
# once for them) and the query and key block. On a v5e at 16,384 tokens, 16
# heads: 1,024 x 1,024 and 4 heads 13.9 ms; 512 x 512 and 8 heads in a loop
# 24.3; 2,048 keys 15.0 (PERF.md, PR 32)
_KERNEL_HEADS = 4
_KERNEL_BLOCK = 1024


def _queries_of(c_q, w_qb, heads, nope, position, inv_freq):
    """``c_q`` (B, T, ql) up-projected by ``w_qb`` (heads * (nope + rope), ql)
    into the queries' two parts, the rotary one rotated: (B, T, heads, nope)
    and (B, T, heads, rope)."""
    b, t, _ = c_q.shape
    q = jnp.einsum("btl,ol->bto", c_q, w_qb, **_F32).astype(c_q.dtype)
    q = q.reshape(b, t, heads, -1)
    return q[..., :nope], rotary_embedding(q[..., nope:], position, inv_freq)


def _block_of(t, most):
    return math.gcd(int(t), int(most))


def _rows_write(pool, values, pid, offset):
    """``values`` (B, T, w) set at ``pool[pid, offset]`` (both (B, T)), in
    place on a donated pool; the pool's lanes past ``w`` are written as
    zeros, and never anything else: a zero query lane does not clear a NaN."""
    n, width = values.shape[0] * values.shape[1], pool.shape[2]
    values = jnp.pad(values, ((0, 0), (0, 0), (0, width - values.shape[-1])))
    return pool.at[pid.reshape(n), offset.reshape(n)].set(
        values.reshape(n, width).astype(pool.dtype))


def _linear_pages(table, pos, ps):
    """Page ids of positions ``pos`` (B, T) by a table whose column ``s``
    holds positions ``s * ps ...``; past the table's width the trash page."""
    n_pages = table.shape[1]
    pid = jnp.take_along_axis(table, jnp.clip(pos // ps, 0, n_pages - 1), axis=1)
    return jnp.where(pos < n_pages * ps, pid, 0)


def index_scores(idx_q, idx_k, idx_w):
    """The indexer's scores of queries (B, T, J, D) against keys (B, S, D):
    ``sum_j w[t, j] * relu(q[t, j] . k[s])`` in float32, (B, T, S). The head
    weights ``idx_w`` (B, T, J) carry every constant factor."""
    dots = jnp.einsum("btjd,bsd->btjs", idx_q, idx_k, **_F32)
    scores = jnp.einsum("btjs,btj->bts", jax.nn.relu(dots),
                        idx_w.astype(jnp.float32))
    # one zero: a sort may tell -0.0 from 0.0, a comparison does not
    return jnp.where(scores == 0, 0.0, scores)


def kth_largest(scores, k):
    """The ``k``-th largest of every row of float32 ``scores`` (..., S),
    keepdims, exactly, by bisection on the order-preserving int32 image of
    the floats: 33 counting passes, where ``lax.top_k`` of a large ``k``
    sorts every row (XLA:TPU lowers it to a full ``sort``)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    info = jnp.iinfo(jnp.int32)
    shape = scores.shape[:-1] + (1,)

    def halve(_, bounds):
        lo, hi = bounds   # count(keys >= lo) >= k > count(keys >= hi)
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        enough = jnp.sum(keys >= mid, axis=-1, keepdims=True) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, 33, halve, (jnp.full(shape, info.min, jnp.int32),
                       jnp.full(shape, info.max, jnp.int32)))
    # the largest key the bisection cannot separate from its upper bound
    lo = jnp.where(jnp.sum(keys >= info.max, axis=-1, keepdims=True) >= k,
                   info.max, lo)
    back = jnp.where(lo < 0, lo ^ jnp.int32(0x7FFFFFFF), lo)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def top_k_mask(scores, k):
    """(..., S) bool: the ``k`` largest of every row of ``scores``, equal
    scores in the order of their positions, as ``lax.top_k`` chooses them
    (exactly ``k`` a row: an indexer of few heads scores many positions
    exactly 0). Of the positions that tie with the ``k``-th largest, those
    up to a position found by a second bisection are taken (no prefix sum:
    one over 16,384 positions is a slow operation on a TPU)."""
    kth = kth_largest(scores, k)
    above, tie = scores > kth, scores == kth
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    at = jax.lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    shape = scores.shape[:-1] + (1,)

    def halve(_, bounds):
        lo, hi = bounds   # ties up to lo are too few, up to hi enough
        mid = (lo + hi) // 2
        enough = jnp.sum(tie & (at <= mid), axis=-1, keepdims=True) >= need
        return jnp.where(enough, lo, mid), jnp.where(enough, mid, hi)

    steps = max(int(scores.shape[-1]).bit_length(), 1)
    _, last = jax.lax.fori_loop(
        0, steps, halve, (jnp.full(shape, -1, jnp.int32),
                          jnp.full(shape, scores.shape[-1] - 1, jnp.int32)))
    return above | (tie & (at <= last))


def dsa_selection_mask(idx_q, idx_k, idx_w, top_k, block=64):
    """(B, T, T) bool for one whole chunk from position 0: key ``s`` is seen
    by query ``t`` when ``s <= t`` and its indexer score is among the
    ``top_k`` largest of ``t``'s row (every ``s <= t`` while ``t < top_k``).
    Queries are walked in blocks: neither the scores nor the per-head dots
    are ever T x T at once."""
    b, t = idx_q.shape[:2]
    cols = jnp.arange(t, dtype=jnp.int32)
    if top_k >= t:
        return jnp.broadcast_to(cols[None, :] <= cols[:, None], (b, t, t))
    qb = _block_of(t, block)

    def rows_of(start):
        at = lambda z: jax.lax.dynamic_slice_in_dim(z, start, qb, 1)  # noqa: E731
        causal = cols[None, :] <= (start + jnp.arange(qb, dtype=jnp.int32))[:, None]
        scores = jnp.where(causal[None], index_scores(at(idx_q), idx_k,
                                                      at(idx_w)), -jnp.inf)
        return causal[None] & top_k_mask(scores, top_k)

    seen = jax.lax.map(rows_of, jnp.arange(0, t, qb, dtype=jnp.int32))
    return jnp.moveaxis(seen, 0, 1).reshape(b, t, t)


def _masked_chunk_attention(c_q, w_qb, c_kv, k_rope, w_kvb, heads, seen,
                            position, inv_freq, scale, gate, head_block=16,
                            query_block=256):
    """Decompressed latent attention of one chunk from position 0 under the
    mask ``seen`` (B, T, T): heads in blocks (a block's queries, keys and
    values are made inside the loop, so nothing head-sized exists for all
    heads at once), queries in blocks inside; ``gate`` (B, T, H) or None
    multiplies a head's output where it is made. Returns (B, T, H * vd)."""
    b, t, ql = c_q.shape
    kl, rope = c_kv.shape[-1], k_rope.shape[-1]
    nope = w_qb.shape[0] // heads - rope
    vd = w_kvb.shape[0] // heads - nope
    hb = head_block if heads % head_block == 0 else heads
    qb = _block_of(t, query_block)

    # a query sees no key past its own position: the chunk's queries are
    # taken a quarter at a time, each against the keys up to its end (five
    # eighths of the products and of the softmax's passes over the scores)
    parts = 4 if t >= 4096 and t % (4 * qb) == 0 else 1

    def heads_of(args):
        wq, wkv, g = args    # (hb * (nope + rope), ql), (hb, nope + vd, kl)
        qn, qr = _queries_of(c_q, wq, hb, nope, position, inv_freq)
        q = jnp.concatenate([qn, qr], axis=-1)
        kv = jnp.einsum("bkl,hdl->bkhd", c_kv, wkv, **_F32).astype(c_q.dtype)
        # one product over a head's nope + rope dims: the shared rotated key
        # stands beside every head's own
        keys = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :],
                                              (b, t, hb, rope))], axis=-1)

        def queries_of(start, upto):
            # the keys a stretch at a time under a running maximum and sum
            # (one softmax over more than 4,096 keys is a fusion that XLA:TPU
            # runs forty times slower a byte: PERF.md, PR 31). A stretch in
            # which a query sees nothing adds exp(-1e30 - m) = 0, or, before
            # the query's first key, weights that the first real maximum
            # scales to 0
            at = lambda z: jax.lax.dynamic_slice_in_dim(z, start, qb, 1)  # noqa: E731
            qs, mask = at(q), at(seen)
            top = jnp.full((b, hb, qb, 1), -1e30, jnp.float32)
            total = jnp.zeros((b, hb, qb, 1), jnp.float32)
            out = jnp.zeros((b, qb, hb, vd), jnp.float32)
            for k0 in range(0, upto, _KEY_STRETCH):
                k1 = min(k0 + _KEY_STRETCH, upto)
                scores = jnp.einsum("bthd,bkhd->bhtk", qs, keys[:, k0:k1],
                                    **_F32) * scale
                scores = jnp.where(mask[:, None, :, k0:k1], scores, -1e30)
                new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
                weights, keep = jnp.exp(scores - new_top), jnp.exp(top - new_top)
                total = total * keep + weights.sum(axis=-1, keepdims=True)
                out = out * keep.transpose(0, 2, 1, 3) + jnp.einsum(
                    "bhtk,bkhv->bthv", weights.astype(c_q.dtype),
                    kv[:, k0:k1, :, nope:], **_F32)
                top = new_top
            out = out / total.transpose(0, 2, 1, 3)
            if g is not None:
                out = out * at(g)[..., None]
            return out.astype(c_q.dtype)

        out = jnp.concatenate([
            jax.lax.map(functools.partial(queries_of, upto=(p + 1) * t // parts),
                        jnp.arange(p * t // parts, (p + 1) * t // parts, qb,
                                   dtype=jnp.int32))
            for p in range(parts)], axis=0)
        return jnp.moveaxis(out, 0, 1).reshape(b, t, hb, vd)

    gates = None if gate is None else jnp.moveaxis(
        gate.reshape(b, t, heads // hb, hb), 2, 0)
    out = jax.lax.map(heads_of, (w_qb.reshape(heads // hb, -1, ql),
                                 w_kvb.reshape(heads // hb, hb, nope + vd, kl),
                                 gates))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, heads * vd)


def _masked_chunk_kernel(c_q, w_qb, c_kv, k_rope, w_kvb, heads, seen,
                         position, inv_freq, scale, gate, head_block=16,
                         group=_KERNEL_HEADS, block=None, interpret=None):
    """:func:`_masked_chunk_attention` with the scores kept on the chip: a
    block of heads' queries, keys and values are made as there (XLA's
    products, nothing head-sized for all heads at once) and handed to the
    flash forward kernel under ``seen`` (one mask for every head, read once
    for the ``group`` heads of a grid step; blocks above the diagonal
    skipped). The same precision: bfloat16 operands, float32 sums and
    softmax, the weights cast before the second product, the gate applied
    to the float32 output; only the order of summation differs (key blocks
    of ``block``, 1,024 or the largest that divides T, where XLA takes
    stretches of 2,048). One row (B == 1)."""
    from . import flash_attention as fa

    b, t, ql = c_q.shape
    kl, rope = c_kv.shape[-1], k_rope.shape[-1]
    nope = w_qb.shape[0] // heads - rope
    vd = w_kvb.shape[0] // heads - nope
    hb = head_block if heads % head_block == 0 else heads
    interpret = fa._resolve_interpret(interpret)
    block = block or fa._pick_block(t, _KERNEL_BLOCK)
    mask = seen[0].astype(jnp.int8)   # once, not once a block of heads

    def heads_of(args):
        wq, wkv, g = args    # (hb * (nope + rope), ql), (hb, nope + vd, kl)
        qn, qr = _queries_of(c_q, wq, hb, nope, position, inv_freq)
        q = jnp.concatenate([qn, qr], axis=-1)               # (1, T, hb, d)
        kv = jnp.einsum("bkl,hdl->bhkd", c_kv, wkv, **_F32).astype(c_q.dtype)
        # one product over a head's nope + rope dims: the shared rotated key
        # stands beside every head's own
        keys = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None],
                                              (b, hb, t, rope))], axis=-1)
        out = fa._flash_fwd(jnp.swapaxes(q, 1, 2), keys, kv[..., nope:], True,
                            block_q=block, block_k=block, interpret=interpret,
                            mask=mask, scale=scale, group=math.gcd(hb, group),
                            out_dtype=jnp.float32,
                            name="masked_latent_prefill")    # (1, hb, T, vd)
        out = jnp.swapaxes(out, 1, 2)
        if g is not None:
            out = out * g[..., None]
        return out.astype(c_q.dtype)

    gates = None if gate is None else jnp.moveaxis(
        gate.reshape(b, t, heads // hb, hb), 2, 0)
    out = jax.lax.map(heads_of, (w_qb.reshape(heads // hb, -1, ql),
                                 w_kvb.reshape(heads // hb, hb, nope + vd, kl),
                                 gates))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, heads * vd)


def _gated(out, gate, heads):
    """``out`` (B, T, H * vd) with head ``h``'s part times ``gate[..., h]``."""
    if gate is None:
        return out
    b, t, _ = out.shape
    return (out.reshape(b, t, heads, -1) * gate[..., None].astype(out.dtype)
            ).reshape(b, t, -1)


#: why a decode step's selection and sparse read are XLA operations and not
#: paged kernels: the one place that says it, for an engine's ``read_path``
#: and the trace-time counter ``sparse_read_path_total{path, reason}``. (The
#: scoring read has a kernel and a gate: ``paged_index_scores_refusal``.)
SPARSE_READ_BY_XLA = ("no kernel gathers single cached rows: XLA's gather "
                      "reads the selected rows in one fusion (PERF.md, PR 31)")


@register("sparse_latent_attention")
def sparse_latent_attention(c_q, w_qb, c_kv, k_rope, w_kvb, idx_q, idx_k,
                            idx_w, heads=1, inv_freq=(), scale=1.0,
                            top_k=2048, gate=None, cache=None, position=None,
                            page_table=None):
    """Multi-head latent attention that reads a learned subset of the keys
    (DeepSeek-V3.2-Exp's sparse attention): query ``t`` attends the
    ``top_k`` positions ``s <= t`` whose indexer score
    (:func:`index_scores`) is largest, all of them while ``t < top_k``.

    ``c_q`` (B, T, ql) is the queries' normed latent and ``w_qb`` (H * (nope
    + rope), ql) its up-projection (taken here, so that a long chunk's
    queries are made a block of heads at a time; the rotary part is rotated
    by ``inv_freq``); ``c_kv`` (B, T, kl), ``k_rope`` (B, T, rope, rotated)
    and ``w_kvb`` as :func:`latent_attention` takes them; ``idx_q`` (B, T,
    J, D) and ``idx_k`` (B, T, D) the indexer's rotated queries and keys,
    ``idx_w`` (B, T, J) its head weights with every constant folded in;
    ``gate`` (B, T, H), if given, multiplies head ``h``'s output (a headwise
    output gate: taken here, so that a long chunk's gated context is made a
    block at a time). Returns the context (B, T, H * vd).

    ``cache=(pool, index_pool), position=, page_table=`` is the paged path:
    the new tokens' ``[c_kv ; k_rope ; 0]`` and indexer keys are scattered
    into their pools, in place, and the call returns ``(context, pool',
    index_pool', read, held)``, the last two int32 scalars: the positions
    the rows' softmaxes read and the positions the rows hold. One token a
    row (decode) scores the index keys of the row's pages (scope
    ``dsa/index``: the Pallas kernel ``paged_index_scores`` copies the pages
    a row HOLDS; where its gate refuses, XLA gathers the table's width of
    the 128-wide key pool), selects (``dsa/select``: ``lax.top_k``) and
    reads the selected latents and nothing else, in the absorbed form
    (``mla/core``: an XLA gather of rows): no operation has the shape rows x
    table width x the latent pool's width. A chunk of more
    than one token opens its rows at position 0 (a prefill without an
    adopted prefix: the only chunk an engine with a window group builds) and
    attends itself in the decompressed form, the selection as a mask: one
    algorithm with two paths, chosen by
    :func:`~mxnet_tpu.ops.flash_attention.masked_prefill_refusal` from
    what the operands and the process show. The flash forward kernel under
    the mask (``masked_latent_prefill``: online softmax over key blocks, the
    scores in VMEM only, blocks above the diagonal skipped) or, where it
    refuses, XLA with queries in blocks and keys in stretches
    (:func:`_masked_chunk_attention`, the kernel's oracle).
    ``sparse_read_path_total{path, reason}`` says at trace time what was
    built: ``chunk_mask_kernel``, or ``chunk_mask`` and why the kernel was
    not.
    """
    from .. import observability as obs

    b, t, _ = c_q.shape
    kl, rope = c_kv.shape[-1], k_rope.shape[-1]
    nope = w_qb.shape[0] // heads - rope
    w_kvb3 = w_kvb.reshape(heads, -1, kl)

    # a chunk's own attention: the flash forward kernel under the
    # selection's mask, or XLA with the keys in stretches, and why
    hb = 16 if heads % 16 == 0 else heads
    vd = w_kvb3.shape[1] - nope
    block = lambda d: jax.ShapeDtypeStruct((b, t, hb, d), c_q.dtype)  # noqa: E731
    from .flash_attention import masked_prefill_refusal

    why_chunk = masked_prefill_refusal(
        block(nope + rope), block(nope + rope), block(vd),
        jax.ShapeDtypeStruct((b, t, t), jnp.bool_))

    def from_chunk(c_hist, r_hist, k_hist, start):
        obs.counter("sparse_read_path_total").inc(
            path="chunk_mask" if why_chunk else "chunk_mask_kernel",
            reason=why_chunk or "")
        with jax.named_scope("dsa"):
            with jax.named_scope("index"):
                seen = dsa_selection_mask(idx_q, k_hist, idx_w, top_k)
        core = _masked_chunk_attention if why_chunk else _masked_chunk_kernel
        with jax.named_scope("mla"), jax.named_scope("core"):
            return core(c_q, w_qb, c_hist, r_hist, w_kvb, heads, seen, start,
                        inv_freq, scale,
                        None if gate is None else _unwrap(gate))

    if cache is None:
        return from_chunk(c_kv, k_rope, idx_k, None)
    if position is None or page_table is None:
        raise ValueError("sparse_latent_attention(cache=...) is paged: it "
                         "needs position= and page_table=")
    pool, ipool = (_unwrap(c) for c in cache)
    position = jnp.asarray(_unwrap(position), jnp.int32)
    table = jnp.asarray(_unwrap(page_table), jnp.int32)
    ps, n_pages = pool.shape[1], table.shape[1]
    cap = n_pages * ps
    with jax.named_scope("kv"):
        pos = position[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        pid, off = _linear_pages(table, pos, ps), pos % ps
        pool = _rows_write(pool, jnp.concatenate([c_kv, k_rope], axis=-1),
                           pid, off)
        ipool = _rows_write(ipool, idx_k, pid, off)
    held = jnp.sum(position + t)
    if t > 1:
        read = jnp.sum(jnp.minimum(pos + 1, top_k)) // t
        out = from_chunk(c_kv.astype(pool.dtype).astype(c_kv.dtype),
                         k_rope.astype(pool.dtype).astype(k_rope.dtype),
                         idx_k.astype(ipool.dtype).astype(idx_k.dtype), position)
        return out, pool, ipool, read, held
    from . import pallas_paged_attention as ppa

    why = ppa.paged_index_scores_refusal(idx_q, ipool, table)
    count = obs.counter("sparse_read_path_total")
    count.inc(path="xla_gather_index" if why else "paged_index_scores",
              reason=why or "")
    count.inc(path="xla_gather_rows", reason=SPARSE_READ_BY_XLA)
    obs.counter("mla_path_total").inc(form="absorbed", read="xla_gather_rows")
    k = min(int(top_k), cap)
    with jax.named_scope("dsa"):
        with jax.named_scope("index"):
            if why is None:   # the pages each row holds, in VMEM
                scores = ppa.paged_index_scores(idx_q[:, 0], idx_w[:, 0], ipool,
                                                table, position)
            else:             # every row's keys by its table's whole width
                keys = ipool[table].reshape(b, cap, -1)[..., :idx_q.shape[-1]]
                scores = index_scores(idx_q, keys.astype(idx_q.dtype),
                                      idx_w)[:, 0]                    # (B, cap)
                scores = jnp.where(jnp.arange(cap, dtype=jnp.int32)[None, :]
                                   <= position[:, None], scores, -jnp.inf)
        with jax.named_scope("select"):
            _, chosen = jax.lax.top_k(scores, k)                      # (B, k)
            seen = chosen <= position[:, None]
    with jax.named_scope("mla"), jax.named_scope("core"):
        q_nope, q_rope = _queries_of(c_q, w_qb, heads, nope, position, inv_freq)
        rows = pool[jnp.take_along_axis(table, chosen // ps, axis=1),
                    chosen % ps].astype(c_kv.dtype)                   # (B, k, W)
        # a row short of top_k positions chose some it does not hold: what
        # lies there counts for nothing, whatever it is (a NaN too)
        rows = jnp.where(seen[:, :, None], rows, 0)
        o_lat = _weighted_latents(_absorb_queries(q_nope, w_kvb3), q_rope,
                                  rows[..., :kl], rows[..., kl:kl + rope],
                                  seen[:, None, :], scale)
        out = _up_project_values(o_lat, w_kvb3, nope)
        out = _gated(out.reshape(b, t, -1),
                     None if gate is None else _unwrap(gate), heads)
    read = jnp.sum(jnp.minimum(position + 1, k))
    return out, pool, ipool, read, held


@register("windowed_latent_attention")
def windowed_latent_attention(c_q, w_qb, c_kv, k_rope, w_kvb, heads=1,
                              inv_freq=(), scale=1.0, window=1, gate=None,
                              cache=None, position=None, page_table=None,
                              last_pos=None):
    """Multi-head latent attention under a causal window: query ``t``
    attends ``t - window < s <= t`` (the window counts the token itself).
    Operands as :func:`sparse_latent_attention` takes them (``gate``
    too), less the indexer's. Returns the context (B, T, H * vd).

    ``cache=(pool,), position=, page_table=`` is the paged path over a RING
    table: a row keeps only the pages its window reaches, and column ``c``
    of its table row holds the logical page ``s`` (positions ``s * page
    ...``) with ``s % columns == c`` that is nearest below the row's
    frontier (an engine's ``window`` pool group frees the pages behind the
    window and hands out the columns so; ``columns >= window // page + 3``).
    Returns ``(context, pool')``. One token a row (decode) gathers the
    row's columns (``columns x page`` positions, a window's worth) and
    attends in the absorbed form under the window's mask by position; what
    a freed or never-written column names counts for nothing. A chunk of
    more than one token opens its rows at position 0, attends itself in
    bands of queries, and writes only the pages that the row keeps once its
    last real position ``last_pos`` ((1,) int32: the chunk may be padded)
    is the frontier.
    """
    from .. import observability as obs

    b, t, _ = c_q.shape
    kl, rope = c_kv.shape[-1], k_rope.shape[-1]
    nope = w_qb.shape[0] // heads - rope
    vd = w_kvb.shape[0] // heads - nope
    w_kvb3 = w_kvb.reshape(heads, nope + vd, kl)
    window = int(window)
    gate = None if gate is None else _unwrap(gate)

    def from_chunk(c_hist, r_hist, start):
        qb = _block_of(t, 512)
        span = min(t, qb + -(-window // qb) * qb)

        def queries_of(first_q):
            first_k = jnp.clip(first_q + qb - span, 0, t - span)
            cut = lambda z, at, n: jax.lax.dynamic_slice_in_dim(z, at, n, 1)  # noqa: E731
            begin = first_q if start is None else start + first_q
            qn, qr = _queries_of(cut(c_q, first_q, qb), w_qb, heads, nope,
                                 jnp.broadcast_to(begin, (b,)), inv_freq)
            kv = jnp.einsum("bkl,hdl->bkhd", cut(c_hist, first_k, span),
                            w_kvb3, **_F32).astype(c_q.dtype)
            keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                cut(r_hist, first_k, span)[:, :, None, :],
                (b, span, heads, rope))], axis=-1)
            scores = jnp.einsum("bthd,bkhd->bhtk",
                                jnp.concatenate([qn, qr], axis=-1), keys, **_F32)
            tq = (first_q + jnp.arange(qb, dtype=jnp.int32))[:, None]
            ks = (first_k + jnp.arange(span, dtype=jnp.int32))[None, :]
            seen = jnp.broadcast_to((ks <= tq) & (ks > tq - window),
                                    (b, qb, span))
            att = _mla_softmax(scores, seen, scale, c_q.dtype)
            out = jnp.einsum("bhtk,bkhv->bthv", att, kv[..., nope:], **_F32)
            if gate is not None:
                out = out * cut(gate, first_q, qb)[..., None]
            return out.astype(c_q.dtype)

        with jax.named_scope("swa"), jax.named_scope("core"):
            out = jax.lax.map(queries_of, jnp.arange(0, t, qb, dtype=jnp.int32))
            return jnp.moveaxis(out, 0, 1).reshape(b, t, heads * vd)

    if cache is None:
        obs.counter("mla_path_total").inc(form="decompressed", read="none")
        return from_chunk(c_kv, k_rope, None)
    if position is None or page_table is None:
        raise ValueError("windowed_latent_attention(cache=...) is paged: it "
                         "needs position= and page_table=")
    (pool,) = (_unwrap(c) for c in cache)
    position = jnp.asarray(_unwrap(position), jnp.int32)
    table = jnp.asarray(_unwrap(page_table), jnp.int32)
    ps, cols = pool.shape[1], table.shape[1]
    cap = cols * ps
    new = jnp.concatenate([c_kv, k_rope], axis=-1)
    if t > 1:
        if last_pos is None:
            raise ValueError("a chunk written through a ring table needs "
                             "last_pos=: the row's last real position")
        obs.counter("mla_path_total").inc(form="decompressed", read="chunk")
        with jax.named_scope("kv"):
            last = jnp.asarray(_unwrap(last_pos), jnp.int32).reshape(-1)[0]
            top_page = last // ps
            low_page = jnp.maximum(last - window + 2, 0) // ps
            n = min(t, cap)
            first = jnp.clip((top_page + 1) * ps - n, 0, t - n)
            pos = jnp.broadcast_to(first + jnp.arange(n, dtype=jnp.int32), (b, n))
            page = pos // ps
            kept = (page >= low_page) & (page <= top_page)
            pid = jnp.where(kept, jnp.take_along_axis(table, page % cols, axis=1), 0)
            pool = _rows_write(pool, jax.lax.dynamic_slice_in_dim(new, first, n, 1),
                               pid, pos % ps)
        held = new.astype(pool.dtype).astype(c_kv.dtype)
        return from_chunk(held[..., :kl], held[..., kl:], position), pool
    obs.counter("mla_path_total").inc(form="absorbed", read="xla_gather_ring")
    obs.counter("paged_read_path_total").inc(
        path="xla_gather", reason="a ring table: the latent kernel reads a "
        "table's columns in order from position 0")
    with jax.named_scope("kv"):
        page = position // ps                                         # (B,)
        pool = _rows_write(pool, new, jnp.take_along_axis(
            table, (page % cols)[:, None], axis=1), (position % ps)[:, None])
        hist = pool[table].reshape(b, cap, -1).astype(c_kv.dtype)
        col = jnp.arange(cols, dtype=jnp.int32)[None, :]
        logical = page[:, None] - (page[:, None] - col) % cols        # (B, cols)
        kpos = (logical[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(b, cap)
        seen = ((kpos <= position[:, None]) & (kpos >= 0)
                & (kpos > position[:, None] - window))
        hist = jnp.where(seen[:, :, None], hist, 0)
    with jax.named_scope("swa"), jax.named_scope("core"):
        q_nope, q_rope = _queries_of(c_q, w_qb, heads, nope, position, inv_freq)
        o_lat = _weighted_latents(_absorb_queries(q_nope, w_kvb3), q_rope,
                                  hist[..., :kl], hist[..., kl:kl + rope],
                                  seen[:, None, :], scale)
        out = _gated(_up_project_values(o_lat, w_kvb3, nope).reshape(b, t, -1),
                     gate, heads)
    return out, pool


# --------------------------------------------------------------------------
# grouped key-value heads over paged K/V pools, under a window or none
# --------------------------------------------------------------------------
def _ring_pages(table, pos, ps):
    """Page ids of positions ``pos`` (B, T) by a RING table: logical page
    ``s`` lives in column ``s % columns``."""
    return jnp.take_along_axis(table, (pos // ps) % table.shape[1], axis=1)


def _gqa_products_dtype(q, pool_dtype):
    return (pool_dtype if pool_dtype == jnp.bfloat16
            else jnp.promote_types(q.dtype, pool_dtype))


def _paged_gqa_gather_read(q, k_pool, v_pool, page_table, position,
                           window=None):
    """The XLA read path of grouped heads: every row's columns gathered by
    its page table, the table's whole width, and attended under the frontier
    mask and, with ``window``, the window's by POSITION (the table is then a
    ring: column ``c`` holds the logical page nearest below the frontier
    with ``s % columns == c``). What a freed, stale or never-written column
    names counts for nothing, whatever it is. ``q`` (B, H, Tq, Ch); returns
    (B, H, Tq, Ch) float32: :func:`_frontier_masked_attention`'s precision."""
    b, h, tq, ch = q.shape
    ps, cols = k_pool.shape[1], page_table.shape[1]
    hkv = k_pool.shape[2] // ch
    cap = cols * ps
    mm = _gqa_products_dtype(q, k_pool.dtype)
    if window is None:
        kpos = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
    else:
        page = (position + tq - 1) // ps
        col = jnp.arange(cols, dtype=jnp.int32)[None, :]
        logical = page[:, None] - (page[:, None] - col) % cols    # (B, cols)
        kpos = (logical[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(b, cap)
    q_pos = position[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    seen = (kpos[:, None, :] <= q_pos[:, :, None]) & (kpos[:, None, :] >= 0)
    if window is not None:
        seen &= kpos[:, None, :] > q_pos[:, :, None] - int(window)
    any_seen = seen.any(axis=1)                                   # (B, cap)

    def history(pool):   # (B, cols, ps, Hkv*Ch) -> (B, cap, Hkv, Ch)
        hist = pool[page_table].reshape(b, cap, hkv, ch).astype(mm)
        return jnp.where(any_seen[:, :, None, None], hist, 0)

    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32))
    q5 = q.astype(mm).reshape(b, hkv, h // hkv, tq, ch)
    scores = jnp.einsum("bkgqc,bskc->bkgqs", q5, history(k_pool), **_F32) * scale
    scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(mm)
    out = jnp.einsum("bkgqs,bskc->bkgqc", att, history(v_pool), **_F32)
    return out.reshape(b, h, tq, ch)


def _paged_block_gather_read(q, k_pool, v_pool, page_ids, starts, counts,
                             position):
    """The XLA read of a LIST of blocks a row and key-value head (a block is
    one page): the listed pages gathered, a head's own lanes of each, and
    attended where a block counts (``counts``) and a position is at or
    before the query's. ``q`` (B, H, 1, Ch); ``page_ids``, ``starts`` (B,
    Hkv, L); ``counts`` (B, Hkv); returns (B, H, 1, Ch) float32. The oracle
    of ``pallas_paged_attention.paged_gqa_read(selected=)`` and the CPU's
    path."""
    b, h, _, ch = q.shape
    ps, hkv = k_pool.shape[1], k_pool.shape[2] // ch
    length = page_ids.shape[2]
    mm = _gqa_products_dtype(q, k_pool.dtype)
    kpos = (starts[..., None]
            + jnp.arange(ps, dtype=jnp.int32)).reshape(b, hkv, length * ps)
    listed = jnp.repeat(jnp.arange(length)[None, None, :]
                        < jnp.maximum(counts, 1)[..., None], ps, axis=2)
    seen = listed & (kpos <= position[:, None, None])       # (B, Hkv, L*ps)

    def history(pool):   # a head's own lanes: (B, Hkv, L*ps, Ch)
        hist = jnp.stack([pool[page_ids[:, g], :, g * ch:(g + 1) * ch]
                          for g in range(hkv)], axis=1)
        hist = hist.reshape(b, hkv, length * ps, ch).astype(mm)
        return jnp.where(seen[..., None], hist, 0)

    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32))
    q4 = q.astype(mm).reshape(b, hkv, h // hkv, ch)
    scores = jnp.einsum("bkgc,bksc->bkgs", q4, history(k_pool), **_F32) * scale
    scores = jnp.where(seen[:, :, None, :], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(mm)
    out = jnp.einsum("bkgs,bksc->bkgc", att, history(v_pool), **_F32)
    return out.reshape(b, h, 1, ch)


def paged_block_attention(q, k_pool, v_pool, page_ids, starts, counts,
                          position):
    """One query a row, ``q`` (B, H, 1, Ch), over the pages a table of
    SELECTED pages names, a list a row and key-value head (block selection's
    decode read, a block a page): ``paged_gqa_read(selected=)``
    (``paged_gqa_decode_selected`` in a trace) where
    :func:`~mxnet_tpu.ops.pallas_paged_attention.paged_gqa_selected_refusal`
    lets it, else the XLA gather of the listed pages.
    ``paged_read_path_total{path="selected_pages_kernel"|"selected_pages_xla",
    reason}`` says at trace time what was built."""
    from .. import observability as obs
    from . import pallas_paged_attention as ppa

    why = ppa.paged_gqa_selected_refusal(q, k_pool, page_ids)
    obs.counter("paged_read_path_total").inc(
        path="selected_pages_xla" if why else "selected_pages_kernel",
        reason=why or "")
    if why:
        return _paged_block_gather_read(q, k_pool, v_pool, page_ids, starts,
                                        counts, position)
    return ppa.paged_gqa_read(q, k_pool, v_pool, None, position,
                              selected=(page_ids, starts, counts))


def _gqa_chunk_attention(q, k, v, window=None, query_block=512):
    """Causal attention of one whole chunk from position 0 with grouped
    heads, ``q`` (B, H, T, Ch) over ``k``/``v`` (B, Hkv, T, Ch), under a
    window or none: queries in blocks, each against the ``span`` keys that
    end with its last (a window's worth and a block; every key where there
    is no window), so no score tensor is T x T. Operands as they come,
    float32 sums and softmax; returns float32."""
    b, h, t, ch = q.shape
    hkv = k.shape[1]
    qb = _block_of(t, query_block)
    span = t if window is None else min(t, qb + -(-int(window) // qb) * qb)
    q5 = q.reshape(b, hkv, h // hkv, t, ch)
    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32))

    def queries_of(first_q):
        first_k = jnp.clip(first_q + qb - span, 0, t - span)
        cut = lambda z, at, n, ax: jax.lax.dynamic_slice_in_dim(z, at, n, ax)  # noqa: E731
        scores = jnp.einsum("bkgqc,bksc->bkgqs", cut(q5, first_q, qb, 3),
                            cut(k, first_k, span, 2), **_F32) * scale
        tq = (first_q + jnp.arange(qb, dtype=jnp.int32))[:, None]
        ks = (first_k + jnp.arange(span, dtype=jnp.int32))[None, :]
        seen = ks <= tq
        if window is not None:
            seen &= ks > tq - int(window)
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksc->bkgqc", att.astype(v.dtype),
                          cut(v, first_k, span, 2), **_F32)

    out = jax.lax.map(queries_of, jnp.arange(0, t, qb, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 3).reshape(b, h, t, ch)   # (B,Hkv,G,T/qb,qb,Ch)


def _gqa_chunk_kernel(q, k, v, window=None, interpret=None, block=None):
    """:func:`_gqa_chunk_attention` through the flash forward kernel
    (``gqa_prefill`` in a trace): key blocks under a running maximum, the
    scores in VMEM only, blocks above the diagonal skipped; a chunk longer
    than the window hands it the band as one mask for every head. A
    key-value head is repeated for its query heads (the kernel pairs heads
    one to one). One row (B == 1)."""
    from . import flash_attention as fa

    h, t = q.shape[1:3]
    rep = h // k.shape[1]
    band = None
    if window is not None and t > int(window):
        at = jnp.arange(t, dtype=jnp.int32)
        band = ((at[None, :] <= at[:, None])
                & (at[None, :] > at[:, None] - int(window))).astype(jnp.int8)
    block = block or fa._pick_block(t, _KERNEL_BLOCK)
    return fa._flash_fwd(
        q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), True,
        block_q=block, block_k=block, interpret=fa._resolve_interpret(interpret),
        mask=band, group=math.gcd(h, _KERNEL_HEADS), out_dtype=jnp.float32,
        name="gqa_prefill")


def _paged_gqa_mha(q, k_new, v_new, k_pool, v_pool, page_table, position,
                   window=None, last_pos=None):
    """:func:`_paged_cached_mha` for grouped key-value heads and, with
    ``window``, a causal window over a RING table (an engine's ``window``
    pool group: a row keeps the pages its window reaches and no other).

    q: (B, H, Tq, Ch); k_new/v_new: (B, Hkv, Tq, Ch), ``H`` a multiple of
    ``Hkv`` (query head ``i`` reads key-value head ``i // (H // Hkv)``);
    pools (P+1, ps, Hkv*Ch); page_table (B, columns): in order from position
    0 without a window, the ring with one (``columns >= window // ps + 3``).

    One token a row (decode) writes its key and value at ``[page, offset]``
    and reads by one of two paths, chosen from what the operands and the
    process show (:func:`~mxnet_tpu.ops.pallas_paged_attention.paged_gqa_refusal`):
    the Pallas kernel ``paged_gqa_decode`` walks the pages the row holds AND
    reads in blocks under a running maximum; the XLA path gathers the
    table's whole width (:func:`_paged_gqa_gather_read`, the CPU's path and
    the kernel's oracle). A chunk of more than one token opens its rows at
    position 0 (a prefill without an adopted prefix), attends itself (the
    flash forward kernel as ``gqa_prefill`` where
    :func:`~mxnet_tpu.ops.flash_attention.masked_prefill_refusal` lets it,
    else XLA's query blocks) and, through a ring, writes only the pages the
    row keeps once ``last_pos`` ((1,) int32, its last real position: the
    chunk may be padded) is the frontier. ``paged_read_path_total{path,
    reason}`` says at trace time what was built: ``gqa_kernel`` or
    ``xla_gather`` for a token, ``gqa_chunk_kernel`` or ``gqa_chunk_xla``
    for a chunk."""
    from .. import observability as obs
    from . import flash_attention as fa
    from . import pallas_paged_attention as ppa

    b, _, tq, ch = q.shape
    ps, cols = k_pool.shape[1], page_table.shape[1]
    count = obs.counter("paged_read_path_total")
    mm = _gqa_products_dtype(q, k_pool.dtype)
    # a token's heads side by side, as a pool holds them
    k_rows, v_rows = _merge_heads(k_new), _merge_heads(v_new)
    pos = position[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    if window is None:
        pid = _linear_pages(page_table, pos, ps)
    elif tq == 1:
        pid = _ring_pages(page_table, pos, ps)
    else:
        if last_pos is None:
            raise ValueError("a chunk written through a ring table needs "
                             "last_pos=: the row's last real position")
        # the pages the row keeps once last_pos is its frontier, and of the
        # chunk only the stretch that can reach them
        last = jnp.asarray(_unwrap(last_pos), jnp.int32).reshape(-1)[0]
        top_page = last // ps
        low_page = jnp.maximum(last - int(window) + 2, 0) // ps
        n = min(tq, cols * ps)
        first = jnp.clip((top_page + 1) * ps - n, 0, tq - n)
        pos, k_rows, v_rows = (jax.lax.dynamic_slice_in_dim(z, first, n, 1)
                               for z in (pos, k_rows, v_rows))
        page = pos // ps
        pid = jnp.where((page >= low_page) & (page <= top_page),
                        _ring_pages(page_table, pos, ps), 0)
    k_pool = _rows_write(k_pool, k_rows, pid, pos % ps)
    v_pool = _rows_write(v_pool, v_rows, pid, pos % ps)
    if tq == 1:
        why = ppa.paged_gqa_refusal(q, k_pool, page_table, window)
        count.inc(path="xla_gather" if why else "gqa_kernel", reason=why or "")
        read = _paged_gqa_gather_read if why else ppa.paged_gqa_read
        return (read(q, k_pool, v_pool, page_table, position, window), k_pool,
                v_pool)
    shape = lambda x: jax.ShapeDtypeStruct((b, tq, x.shape[1], ch), mm)  # noqa: E731
    why = fa.masked_prefill_refusal(
        shape(q), shape(k_new), shape(v_new),
        jax.ShapeDtypeStruct((b, tq, tq), jnp.bool_))
    count.inc(path="gqa_chunk_xla" if why else "gqa_chunk_kernel",
              reason=why or "")
    core = _gqa_chunk_attention if why else _gqa_chunk_kernel
    # the chunk as the pool now holds it (rounded to the pool's dtype)
    held = lambda x: x.astype(k_pool.dtype).astype(mm)  # noqa: E731
    return core(q.astype(mm), held(k_new), held(v_new), window), k_pool, v_pool


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
                         cache=None, position=None, page_table=None,
                         window=None, last_pos=None, grouped=False):
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

    **Grouped key-value heads** (``k``/``v`` (B, Hkv, T, Ch) with ``H`` a
    multiple of ``Hkv``: query head ``i`` reads head ``i // (H // Hkv)``)
    and a causal ``window`` (query ``t`` attends ``t - window < s <= t``: the
    window counts the token itself) are causal attention, cached through
    page pools or not at all: :func:`_paged_gqa_mha` (pools ``(P+1, page,
    Hkv*Ch)``; with ``window`` the table is an engine's ring and a chunk of
    more than one token needs ``last_pos=``, its last real position), or
    without a cache one whole chunk by :func:`_gqa_chunk_attention`. Equal
    head counts without a window take the paths above, unchanged, unless
    ``grouped=True`` asks for this path at a group of ONE: its decode kernel
    walks a row's pages in blocks and holds nothing of a history's size in
    VMEM, so rows of thousands of positions under 30 heads of 128 are served
    (the equal-headed kernel copies a row's whole history into VMEM and
    refuses them; its XLA fallback gathers the table's whole width).
    """
    from . import flash_attention as fa
    from ..contrib.amp import cast_inputs

    orig_dtype = q.dtype
    q, k, v = cast_inputs(q, k, v)  # AMP: score/context matmuls on the MXU
    grouped = grouped or k.shape[1] != q.shape[1] or window is not None
    if grouped and (cache is None or page_table is None):
        if cache is not None or mask is not None:
            raise ValueError("grouped key-value heads and a window are causal "
                             "attention over page pools (cache= with "
                             "page_table=) or over one whole chunk: no dense "
                             "cache, no mask")
        return _gqa_chunk_attention(q, k, v, window).astype(orig_dtype)
    if cache is not None:
        if position is None:
            raise ValueError("multi_head_attention(cache=...) needs position=")
        k_buf, v_buf = (_unwrap(c) for c in cache)
        position = jnp.asarray(_unwrap(position), jnp.int32)
        if position.ndim == 0:
            position = jnp.broadcast_to(position, (q.shape[0],))
        if page_table is not None:
            table = jnp.asarray(_unwrap(page_table), jnp.int32)
            paged = functools.partial(_paged_gqa_mha, window=window,
                                      last_pos=last_pos) \
                if grouped else _paged_cached_mha
            out, k_buf, v_buf = paged(q, k, v, k_buf, v_buf, table, position)
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
