"""Paged attention over a token-major page pool, one Pallas kernel
(docs/INFERENCE.md "Paged cache", docs/PERFORMANCE.md "Custom kernels").

The pools are ``(P+1, page_size, H*Ch)``: the page axis first, a page one
contiguous block, the minor axis every head's channels side by side (1,024
lanes for GPT-2 345M whatever the head size). The XLA read path
(``attention._paged_gather_mha``) gathers ``pool[page_table]``, the page
table's whole width for every row, and brings it to ``(B, H, cap, Ch)``.
This kernel reads what a row holds:

  - *pages*: the page table and the positions ride in as scalars, the pools
    stay in HBM, and for row ``b`` the kernel copies pages ``0 .. (position[b]
    + Tq - 1) // page_size`` of each pool into a VMEM history, all of a row's
    copies in flight together and the next row's started before this row's
    products begin (two history slots). The loop bound is read from the
    scalars: one program serves every length.
  - *heads*: columns are taken 128 at a time (a lane tile: two heads of 64,
    one of 128). A tile's heads are stacked over the rows of the left
    operand, each with the other heads' lanes zeroed, so one product
    contracts over the MXU's native 128 and gives every head's scores;
    ``pallas_packed_attention`` reads BERT's projection the same way. No
    64-lane slice is taken and the history is never transposed.
  - *lengths*: the products run over the first 128, 256, 512, ... keys of the
    history, the smallest such stretch that covers the pages fetched
    (branches of one kernel, chosen by the scalars), so a row of 100
    positions does an eighth of the work of a full one.
  - *what was not fetched counts for nothing*: VMEM past a row's pages holds
    another row's values or none at all. A score there is masked to ``-inf``
    by the frontier mask, whatever it is; the VALUES there are zeroed before
    the second product, because a weight of 0 does not clear a NaN.
  - *dtype policy*: ``attention._frontier_masked_attention``'s. Products
    of operands in the pool's dtype with float32 accumulation, the softmax
    in float32 over a whole row at once (no streaming softmax).
  - *name*: ``paged_attention_decode`` in a device trace; the benchmark's
    ``custom_call_share_pct.serve`` finds it by the operation's text.

The gate (:func:`paged_attention_refusal`) reads what it can observe:
backend, head size and count, page size, dtypes, the active mesh, and the
VMEM a row's two histories and score block need, which is what sends decode
and speculative verification here and a long prefill chunk to the XLA path.
On the CPU the operator takes the XLA path (it is the dense cache's own
arithmetic, bit for bit); the tests run this kernel interpreted against it.

**The latent pool's kernel** (``paged_latent_attention_decode``; DeepSeek-V2's
absorbed form, :func:`paged_latent_attention_read`) is the same pattern over
ONE pool ``(P+1, page_size, W)``, ``W`` whole lane tiles holding ``[latent ;
rotated key ; 0]`` a token: a page is one copy, the history ``(cap, W)`` is
key and value at once, and because latent attention has one key for every
head, all heads are simply the rows of one left operand ``(H*Tq, W)`` =
``[q . W_UK ; q_rope ; 0]``: scores are one product over ``W`` lanes, the
output one product over the latent's lanes. Its gate is
:func:`paged_latent_attention_refusal`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mesh_state import current_mesh
from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

# What the kernel may hold: two slots of a row's two histories, the float32
# score-shaped temporaries of one tile and the double-buffered query and
# output blocks. A v5e core has 128 MiB of VMEM, of which a kernel gets
# 16 MiB unless it asks for more; the kernel asks for what _vmem_bytes counts
# and a margin
_MAX_VMEM_BYTES = 24 * 1024 * 1024
_SCORE_TEMPS = 3  # float32 score-shaped values live at once
# Up to this many stacked rows the loop over a row's lane tiles is unrolled:
# the tiles' products are independent, and in one block the scheduler can
# load one tile's keys into an MXU while another's scores are in the softmax
_UNROLL_UP_TO = 64


def _tiling(h, ch):
    """(tile width, heads a tile): 128 lanes where the heads fill whole
    lane tiles, else every head in one tile (the interpreter's toy shapes)."""
    hc = h * ch
    w = _LANES if hc % _LANES == 0 and _LANES % ch == 0 else hc
    return w, w // ch


def _rows(g, tq, itemsize):
    """Rows of a tile's stacked left operand, padded to whole sublane tiles
    of the pool's dtype (8 of float32, 16 of bfloat16)."""
    sub = 8 * (4 // itemsize)
    return -(-g * tq // sub) * sub


def _vmem_bytes(h, ch, tq, cap, itemsize):
    w, g = _tiling(h, ch)
    r = _rows(g, tq, itemsize)
    blocks = 2 * (h * ch // w) * r * w * (itemsize + 4)
    return 4 * cap * h * ch * itemsize + _SCORE_TEMPS * r * cap * 4 + blocks


def paged_attention_refusal(q, k_pool, page_table):
    """Why the paged kernel does NOT read the pools for these operands
    (anything with ``.shape``/``.dtype``), or None when it does: ``q``
    ``(B, H, Tq, Ch)``, ``k_pool`` ``(P+1, page_size, H*Ch)``, ``page_table``
    ``(B, n_pages)``. The first condition that fails is the one named;
    callers take the XLA gather then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    b, h, tq, ch = q.shape
    ps, hc = k_pool.shape[1], k_pool.shape[2]
    cap = page_table.shape[1] * ps
    if k_pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(k_pool.dtype).name} is not float32 or bfloat16"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {jnp.dtype(q.dtype).name} is not float32 or bfloat16"
    if hc != h * ch or hc % _LANES or _LANES % ch:
        return (f"{h} heads of {ch} are not whole {_LANES}-lane tiles of the "
                f"pool's {hc} columns")
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    sub = 8 * (4 // itemsize)
    if ps % sub or (_LANES % ps and ps % _LANES):
        return (f"page size {ps} is not a multiple of {sub} sublanes that "
                f"divides or is a multiple of {_LANES}")
    need = _vmem_bytes(h, ch, tq, cap, itemsize)
    if need > _MAX_VMEM_BYTES:
        return (f"{tq} queries a row against {cap} positions need {need} "
                f"bytes of VMEM (budget {_MAX_VMEM_BYTES})")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def paged_attention_supported(q, k_pool, page_table) -> bool:
    """True when the paged kernel should read the pools (see
    :func:`paged_attention_refusal` for the rules)."""
    return paged_attention_refusal(q, k_pool, page_table) is None


_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _page_buckets(ps, n_pages):
    """The stretches of history the products run over, in pages: 128 keys,
    doubled up to the table's width."""
    out, n = [], max(1, _LANES // ps)
    while n < n_pages:
        out.append(n)
        n *= 2
    return out + [n_pages]


def _kernel(table_ref, pos_ref, q_ref, kp_ref, vp_ref, o_ref, ks, vs, sem, *,
            ps, n_pages, tq, g, scale):
    b, rows = pl.program_id(0), pl.num_programs(0)
    n_tiles, r, w = q_ref.shape[1:]
    slot = b % 2

    def pages_of(row):
        # the pages the row's queries can see; a released or overflowing row
        # reads what its table names, like the XLA path
        return jnp.clip((pos_ref[row] + (tq - 1)) // ps + 1, 1, n_pages)

    def for_each_copy(row, into, act):
        def page(j, carry):
            pid = table_ref[row * n_pages + j]
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for pool, hist in ((kp_ref, ks), (vp_ref, vs)):
                act(pltpu.make_async_copy(pool.at[pid], hist.at[into, at, :],
                                          sem.at[into]))
            return carry

        lax.fori_loop(0, pages_of(row), page, 0)

    @pl.when(b == 0)
    def _():
        for_each_copy(0, 0, lambda copy: copy.start())

    @pl.when(b + 1 < rows)
    def _():
        for_each_copy(b + 1, 1 - slot, lambda copy: copy.start())

    for_each_copy(b, slot, lambda copy: copy.wait())
    held = pages_of(b)

    def attend(n):
        keys = n * ps

        def clear(j, carry):
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            vs[slot, at, :] = jnp.zeros((ps, vs.shape[2]), vs.dtype)
            return carry

        lax.fori_loop(held, n, clear, 0)
        # row s*tq + i of the stacked operand is query i of the tile's head s
        row = lax.broadcasted_iota(jnp.int32, (r, keys), 0)
        i = row
        for s in range(1, g):
            i = jnp.where(row >= s * tq, row - s * tq, i)
        visible = (lax.broadcasted_iota(jnp.int32, (r, keys), 1)
                   <= pos_ref[b] + jnp.minimum(i, tq - 1))

        def tile(t, carry):
            lanes = pl.ds(pl.multiple_of(t * w, w), w)
            k = ks[slot, pl.ds(0, keys), lanes]
            v = vs[slot, pl.ds(0, keys), lanes]
            s = lax.dot_general(q_ref[0, t], k, _NT,
                                preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
            o_ref[0, t] = lax.dot_general(p.astype(v.dtype), v, _NN,
                                          preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, n_tiles, tile, 0, unroll=r <= _UNROLL_UP_TO)

    below = 0
    for n in _page_buckets(ps, n_pages):
        pl.when((held > below) & (held <= n))(functools.partial(attend, n))
        below = n


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _call(table, position, q2, k_pool, v_pool, tq, g, interpret):
    """The kernel over batch rows. Jitted, so that a model's layers trace and
    lower it once."""
    b, n_tiles, r, w = q2.shape
    ps, hc = k_pool.shape[1:]
    n_pages = table.shape[0] // b
    ch = w // g
    row = lambda i, t, p: (i, 0, 0, 0)  # noqa: E731
    need = _vmem_bytes(hc // ch, ch, tq, n_pages * ps,
                       jnp.dtype(k_pool.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(
            _kernel, ps=ps, n_pages=n_pages, tq=tq, g=g,
            scale=float(np.float32(1.0) / np.sqrt(np.float32(ch)))),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles, r, w), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, n_tiles, r, w), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_tiles, r, w), row),
            scratch_shapes=[pltpu.VMEM((2, n_pages * ps, hc), k_pool.dtype),
                            pltpu.VMEM((2, n_pages * ps, hc), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        name="paged_attention_decode",
        interpret=interpret,
        # rows run in order: each starts the next one's copies
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 8 * 1024 * 1024),
    )(table, position, q2, k_pool, v_pool)


def paged_attention_read(q, k_pool, v_pool, page_table, position,
                         interpret=None):
    """Attention of ``q`` ``(B, H, Tq, Ch)`` over each row's paged history
    under the frontier mask (query ``i`` of row ``b`` sees positions ``<=
    position[b] + i``), the pools ``(P+1, page_size, H*Ch)`` read by the
    pages the row holds. Returns ``(B, H, Tq, Ch)`` float32. Callers gate
    via :func:`paged_attention_refusal`."""
    b, h, tq, ch = q.shape
    w, g = _tiling(h, ch)
    n_tiles = h // g
    r = _rows(g, tq, jnp.dtype(k_pool.dtype).itemsize)
    # (B, tiles, g*Tq, W): head s of a tile in rows s*Tq.., its channels in
    # lanes s*Ch.. and zeros in the other heads' lanes
    own = jnp.eye(g, dtype=bool)[None, None, :, None, :, None]
    x = q.astype(k_pool.dtype).reshape(b, n_tiles, g, tq, 1, ch)
    q2 = jnp.where(own, x, jnp.zeros((), x.dtype)).reshape(b, n_tiles, g * tq, w)
    q2 = jnp.pad(q2, ((0, 0), (0, 0), (0, r - g * tq), (0, 0)))
    o2 = _call(jnp.asarray(page_table, jnp.int32).reshape(-1),
               jnp.asarray(position, jnp.int32), q2, k_pool, v_pool, tq, g,
               _resolve_interpret(interpret))
    # each head's own lanes of its own rows
    o = o2[:, :, :g * tq].reshape(b, n_tiles, g, tq, g, ch)
    o = jnp.sum(jnp.where(own, o, 0.0), axis=4) if g > 1 else o[:, :, :, :, 0]
    return o.reshape(b, h, tq, ch)


def paged_attention(q, k_new, v_new, k_pool, v_pool, page_table, position,
                    interpret=None):
    """``attention._paged_cached_mha``'s contract through the kernel: write
    the Tq new keys and values of each row at ``[page, offset]`` (an XLA
    scatter, in place on a donated pool), then read. Returns ``(out, k_pool,
    v_pool)``."""
    from .attention import _paged_write

    k_pool, v_pool = _paged_write(k_new, v_new, k_pool, v_pool, page_table,
                                  position)
    out = paged_attention_read(q, k_pool, v_pool, page_table, position,
                               interpret=interpret)
    return out, k_pool, v_pool


# --------------------------------------------------------------------------
# the latent pool (multi-head latent attention, absorbed form)
# --------------------------------------------------------------------------
def _latent_vmem_bytes(r, w, cap, itemsize):
    """Two history slots, the score-shaped temporaries of ``r`` stacked
    queries, and the double-buffered query and output blocks (the output
    counted at the pool's whole width)."""
    return (2 * cap * w * itemsize + _SCORE_TEMPS * r * cap * 4
            + 2 * r * w * (itemsize + 4))


def paged_latent_attention_refusal(q, pool, page_table, form="absorbed"):
    """Why the latent kernel does NOT read the pool for these operands
    (anything with ``.shape``/``.dtype``), or None when it does: ``q``
    ``(B, Tq, H, d)`` (either part of the queries), ``pool`` ``(P+1,
    page_size, W)``, ``page_table`` ``(B, n_pages)``, ``form`` what
    ``attention.mla_form`` chose for ``Tq``. The first condition that fails
    is the one named; callers take the XLA gather then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    _, tq, h, _ = q.shape
    ps, w = pool.shape[1], pool.shape[2]
    cap = page_table.shape[1] * ps
    if pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(pool.dtype).name} is not float32 or bfloat16"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {jnp.dtype(q.dtype).name} is not float32 or bfloat16"
    if w % _LANES:
        return f"the pool's {w} columns are not whole {_LANES}-lane tiles"
    itemsize = jnp.dtype(pool.dtype).itemsize
    sub = 8 * (4 // itemsize)
    if ps % sub or (_LANES % ps and ps % _LANES):
        return (f"page size {ps} is not a multiple of {sub} sublanes that "
                f"divides or is a multiple of {_LANES}")
    if form != "absorbed":
        return f"the {form} form reads keys and values up-projected by XLA"
    need = _latent_vmem_bytes(_rows(h, tq, itemsize), w, cap, itemsize)
    if need > _MAX_VMEM_BYTES:
        return (f"{tq} queries a row of {h} heads against {cap} positions "
                f"need {need} bytes of VMEM (budget {_MAX_VMEM_BYTES})")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _latent_kernel(table_ref, pos_ref, q_ref, pool_ref, o_ref, hist, sem, *,
                   ps, n_pages, tq, heads, scale):
    b, rows = pl.program_id(0), pl.num_programs(0)
    r, kw = o_ref.shape[1:]
    slot = b % 2

    def pages_of(row):
        # as _kernel: a released or overflowing row reads what its table names
        return jnp.clip((pos_ref[row] + (tq - 1)) // ps + 1, 1, n_pages)

    def for_each_copy(row, into, act):
        def page(j, carry):
            pid = table_ref[row * n_pages + j]
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            act(pltpu.make_async_copy(pool_ref.at[pid], hist.at[into, at, :],
                                      sem.at[into]))
            return carry

        lax.fori_loop(0, pages_of(row), page, 0)

    @pl.when(b == 0)
    def _():
        for_each_copy(0, 0, lambda copy: copy.start())

    @pl.when(b + 1 < rows)
    def _():
        for_each_copy(b + 1, 1 - slot, lambda copy: copy.start())

    for_each_copy(b, slot, lambda copy: copy.wait())
    held = pages_of(b)

    def attend(n):
        keys = n * ps

        def clear(j, carry):
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            hist[slot, at, :] = jnp.zeros((ps, hist.shape[2]), hist.dtype)
            return carry

        lax.fori_loop(held, n, clear, 0)
        # row i*heads + h of the stacked operand is query i of head h
        row = lax.broadcasted_iota(jnp.int32, (r, keys), 0)
        i = jnp.zeros_like(row)
        for s in range(1, tq):
            i = jnp.where(row >= s * heads, s, i)
        visible = (lax.broadcasted_iota(jnp.int32, (r, keys), 1)
                   <= pos_ref[b] + i)
        s = lax.dot_general(q_ref[0], hist[slot, pl.ds(0, keys), :], _NT,
                            preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        # the history is its own value: the latent's lanes
        o_ref[0] = lax.dot_general(
            p.astype(hist.dtype), hist[slot, pl.ds(0, keys), pl.ds(0, kw)],
            _NN, preferred_element_type=jnp.float32)

    below = 0
    for n in _page_buckets(ps, n_pages):
        pl.when((held > below) & (held <= n))(functools.partial(attend, n))
        below = n


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _latent_call(table, position, q2, pool, tq, heads, kw, scale, interpret):
    b, r, w = q2.shape
    ps = pool.shape[1]
    n_pages = table.shape[0] // b
    row = lambda i, t, p: (i, 0, 0)  # noqa: E731
    need = _latent_vmem_bytes(r, w, n_pages * ps,
                              jnp.dtype(pool.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_latent_kernel, ps=ps, n_pages=n_pages, tq=tq,
                          heads=heads, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, r, kw), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, r, w), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, r, kw), row),
            scratch_shapes=[pltpu.VMEM((2, n_pages * ps, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        name="paged_latent_attention_decode",
        interpret=interpret,
        # rows run in order: each starts the next one's copies
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 8 * 1024 * 1024),
    )(table, position, q2, pool)


def paged_latent_attention_read(q_lat, q_rope, pool, page_table, position,
                                scale, interpret=None):
    """Absorbed latent attention of ``q_lat`` ``(B, Tq, H, kl)`` (the
    queries with ``W_UK`` folded in) and ``q_rope`` ``(B, Tq, H, rope)``
    over each row's paged history under the frontier mask, the pool ``(P+1,
    page_size, W)`` of ``[latent ; rotated key ; 0]`` read by the pages the
    row holds: ``softmax((q_lat . c + q_rope . r) * scale) . c``. Returns
    the weighted latents ``(B, Tq, H, kl)`` float32; ``W_UV`` is the
    caller's. Callers gate via :func:`paged_latent_attention_refusal`."""
    b, tq, h, kl = q_lat.shape
    w = pool.shape[2]
    q2 = jnp.concatenate([q_lat, q_rope], axis=-1).astype(pool.dtype)
    r = _rows(h, tq, jnp.dtype(pool.dtype).itemsize)
    q2 = jnp.pad(q2.reshape(b, tq * h, -1),
                 ((0, 0), (0, r - tq * h), (0, w - q2.shape[-1])))
    kw = -(-kl // _LANES) * _LANES  # the latent's lanes, whole tiles
    o2 = _latent_call(jnp.asarray(page_table, jnp.int32).reshape(-1),
                      jnp.asarray(position, jnp.int32), q2, pool, tq, h, kw,
                      float(scale), _resolve_interpret(interpret))
    return o2[:, :tq * h, :kl].reshape(b, tq, h, kl)


# --------------------------------------------------------------------------
# the indexer's key pool (learned sparse attention: scores of what a row holds)
# --------------------------------------------------------------------------
_INDEX_CHUNK = 2048  # keys scored at once: a (J, chunk) float32 block


def _index_chunk(cap):
    return math.gcd(cap, _INDEX_CHUNK)


def paged_index_scores_refusal(idx_q, pool, page_table):
    """Why the kernel does NOT score the index-key pool for these operands
    (anything with ``.shape``/``.dtype``), or None when it does: ``idx_q``
    ``(B, 1, J, D)`` one token a row, ``pool`` ``(P+1, page_size, D)``,
    ``page_table`` ``(B, n_pages)``. The first condition that fails is the
    one named; callers gather ``pool[page_table]`` through XLA then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    _, tq, j, d = idx_q.shape
    ps, w = pool.shape[1], pool.shape[2]
    cap = page_table.shape[1] * ps
    if tq != 1:
        return f"{tq} queries a row: the kernel scores one"
    if pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(pool.dtype).name} is not float32 or bfloat16"
    if w != d or d % _LANES:
        return (f"the pool's {w} columns are not the keys' {d} in whole "
                f"{_LANES}-lane tiles")
    itemsize = jnp.dtype(pool.dtype).itemsize
    sub = 8 * (4 // itemsize)
    if ps % sub or (_LANES % ps and ps % _LANES):
        return (f"page size {ps} is not a multiple of {sub} sublanes that "
                f"divides or is a multiple of {_LANES}")
    if _index_chunk(cap) % _LANES:
        return f"{cap} positions a row are not whole {_LANES}-key stretches"
    need = 2 * cap * d * itemsize + _SCORE_TEMPS * j * _index_chunk(cap) * 4 \
        + 2 * cap * 4
    if need > _MAX_VMEM_BYTES:
        return (f"a row's {cap} index keys need {need} bytes of VMEM (budget "
                f"{_MAX_VMEM_BYTES})")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _index_kernel(table_ref, pos_ref, q_ref, w_ref, pool_ref, o_ref, hist, sem,
                  *, ps, n_pages, chunk):
    b, rows = pl.program_id(0), pl.num_programs(0)
    slot = b % 2

    def pages_of(row):
        return jnp.clip(pos_ref[row] // ps + 1, 1, n_pages)

    def for_each_copy(row, into, act):
        def page(j, carry):
            pid = table_ref[row * n_pages + j]
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            act(pltpu.make_async_copy(pool_ref.at[pid], hist.at[into, at, :],
                                      sem.at[into]))
            return carry

        lax.fori_loop(0, pages_of(row), page, 0)

    @pl.when(b == 0)
    def _():
        for_each_copy(0, 0, lambda copy: copy.start())

    @pl.when(b + 1 < rows)
    def _():
        for_each_copy(b + 1, 1 - slot, lambda copy: copy.start())

    for_each_copy(b, slot, lambda copy: copy.wait())
    # what the row does not hold scores -inf, whatever VMEM has there
    o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, jnp.float32)

    def stretch(c, carry):
        keys = hist[slot, pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :]
        dots = lax.dot_general(q_ref[0], keys, _NT,
                               preferred_element_type=jnp.float32)  # (J, chunk)
        score = jnp.sum(jnp.maximum(dots, 0.0) * w_ref[0], axis=0,
                        keepdims=True)
        score = jnp.where(score == 0, 0.0, score)       # one zero, as XLA's
        at = c * chunk + lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        o_ref[0, pl.ds(c, 1), :] = jnp.where(at <= pos_ref[b], score, -jnp.inf)
        return carry

    lax.fori_loop(0, (pages_of(b) * ps + chunk - 1) // chunk, stretch, 0)


@functools.partial(jax.jit, static_argnums=(5,))
def _index_call(table, position, q, w, pool, interpret):
    b, j, d = q.shape
    ps = pool.shape[1]
    n_pages = table.shape[0] // b
    cap = n_pages * ps
    chunk = _index_chunk(cap)
    row = lambda i, t, p: (i, 0, 0)  # noqa: E731
    itemsize = jnp.dtype(pool.dtype).itemsize
    need = 2 * cap * d * itemsize + _SCORE_TEMPS * j * chunk * 4 + 2 * cap * 4
    return pl.pallas_call(
        functools.partial(_index_kernel, ps=ps, n_pages=n_pages, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((b, cap // chunk, chunk), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, j, d), row),
                      pl.BlockSpec((1, j, 1), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, cap // chunk, chunk), row),
            scratch_shapes=[pltpu.VMEM((2, cap, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        name="paged_index_scores",
        interpret=interpret,
        # rows run in order: each starts the next one's copies
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 8 * 1024 * 1024),
    )(table, position, q, w, pool).reshape(b, cap)


def paged_index_scores(idx_q, idx_w, pool, page_table, position,
                       interpret=None):
    """The indexer's scores of one query a row against the index keys of
    the pages the row holds: ``sum_j w[b, j] * relu(q[b, j] . k[s])`` in
    float32 for ``s <= position[b]``, ``-inf`` past it, (B, cap) with ``cap``
    the table's width in positions. ``idx_q`` ``(B, J, D)``, ``idx_w`` ``(B,
    J)`` float32, ``pool`` ``(P+1, page_size, D)`` read by the pages each row
    HOLDS (copied into VMEM, the next row's started before this row's
    products begin), never the table's whole width. Callers gate via
    :func:`paged_index_scores_refusal`."""
    b = idx_q.shape[0]
    return _index_call(jnp.asarray(page_table, jnp.int32).reshape(-1),
                       jnp.asarray(position, jnp.int32),
                       idx_q.astype(pool.dtype),
                       idx_w.astype(jnp.float32).reshape(b, -1, 1), pool,
                       _resolve_interpret(interpret))


# --------------------------------------------------------------------------
# grouped key-value heads, a window or none (one token a row)
# --------------------------------------------------------------------------
_GQA_BLOCK = 512   # positions fetched and attended at once: a block of pages
#: pages fetched by ONE copy where their pool ids are consecutive (a run);
#: the page allocator (``inference/engine.py``) hands ids out in aligned
#: chunks of as many, so that a served pool keeps its rows in runs
RUN_PAGES = 8
_NEG = -1e30       # a masked score; finite, so that exp(_NEG - m) is 0 and
# never NaN (every row's first block holds a position it sees)


def paged_gqa_refusal(q, k_pool, page_table, window=None):
    """Why the grouped-heads kernel does NOT read the pools for these
    operands (anything with ``.shape``/``.dtype``), or None when it does:
    ``q`` ``(B, H, Tq, Ch)``, ``k_pool`` ``(P+1, page_size, Hkv*Ch)`` with
    ``H`` a multiple of ``Hkv``, ``page_table`` ``(B, columns)``, ``window``
    the layer's causal window or None. The first condition that fails is
    the one named; callers take the XLA gather then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    _, h, tq, ch = q.shape
    ps, hc = k_pool.shape[1], k_pool.shape[2]
    if tq != 1:
        return f"{tq} queries a row: the kernel reads for one"
    if k_pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(k_pool.dtype).name} is not float32 or bfloat16"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {jnp.dtype(q.dtype).name} is not float32 or bfloat16"
    if ch % _LANES or hc % ch or h % (hc // ch):
        return (f"{h} query heads of {ch} over the pool's {hc} columns are "
                f"not whole groups of whole {_LANES}-lane heads")
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    sub = 8 * (4 // itemsize)
    if ps % sub or _GQA_BLOCK % ps:
        return (f"page size {ps} is not a multiple of {sub} sublanes that "
                f"divides a block of {_GQA_BLOCK} positions")
    if window is not None and window > (page_table.shape[1] - 2) * ps:
        return (f"a window of {window} positions does not fit a ring of "
                f"{page_table.shape[1]} pages of {ps}")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _gqa_pages(position, lower, ps, columns, ring):
    """(first, last) logical page a row reads: from its lower bound's page
    to its frontier's; a row past a table in order reads the table's width
    (a released or overflowing row reads what its table names, like the XLA
    path). Scalars in the kernel, vectors beside it: one formula."""
    last = position // ps
    if not ring:
        last = jnp.minimum(last, columns - 1)
    return jnp.clip(lower // ps, 0, last), last


def _mark_runs(table, g, ring):
    """``table`` ``(B, columns)`` with the id NEGATED in every column where a
    run starts: ``g`` logically consecutive pages whose pool ids are
    consecutive (``table[c + i] == table[c] + i`` for ``i < g``; in a ring
    the columns wrap, in a table in order a run ends with the table). The
    trash page 0 is never part of a run. What a paged kernel reads its
    larger copies from: the flag rides in the scalars it already loads."""
    columns = table.shape[1]
    run = table > 0
    for i in range(1, g):
        ok = jnp.roll(table, -i, axis=1) == table + i
        if not ring:
            ok &= jnp.arange(columns) + i < columns
        run &= ok
    return jnp.where(run, -table, table)


def _gqa_kernel(table_ref, pos_ref, low_ref, slot_ref, q_ref, kp_ref, vp_ref,
                o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, ps, columns,
                nb, run, ring, scale):
    b, rows = pl.program_id(0), pl.num_programs(0)
    hkv, r, ch = q_ref.shape[1:]
    blk = nb * ps

    def pages(row):
        return _gqa_pages(pos_ref[row], low_ref[row], ps, columns, ring)

    def extent(row, j):
        """Block ``j`` of ``row``: its first logical page and how many."""
        first, last = pages(row)
        start = first + j * nb
        return start, jnp.clip(last - start + 1, 0, nb)

    def for_each_copy(row, j, into, act):
        """``act`` on the copies of block ``j`` of ``row``: its pages, by
        the ids the table's columns hold. The block's whole groups of
        ``run`` logical pages (``s % run == 0`` ...: the allocator's chunks)
        go as ONE copy each where the table marks a run (the pools are seen
        as rows: a run's pages are neighbours there), every other page as a
        copy of its own."""
        start, n = extent(row, j)

        def copies(pid, p, count):
            at = pl.ds(pl.multiple_of(p * ps, ps), count * ps)
            rows_ = pl.ds(pl.multiple_of(pid * ps, ps), count * ps)
            for pool, buf in ((kp_ref, kbuf), (vp_ref, vbuf)):
                act(pltpu.make_async_copy(pool.at[rows_], buf.at[into, at, :],
                                          sem.at[into]))

        def column(p):
            s = start + p
            return table_ref[row * columns + (s % columns if ring else s)]

        def page(p, carry):
            copies(jnp.abs(column(p)), p, 1)
            return carry

        # pages before the block's first whole group
        head = jnp.minimum((run - start % run) % run, n)
        groups = (n - head) // run

        def group(g, carry):
            p = head + g * run
            pid = column(p)

            @pl.when(pid < 0)
            def _():
                copies(-pid, p, run)

            @pl.when(pid >= 0)
            def _():
                for i in range(run):
                    page(p + i, 0)

            return carry

        lax.fori_loop(0, head, page, 0)
        lax.fori_loop(0, groups, group, 0)
        lax.fori_loop(head + groups * run, n, page, 0)

    first, last = pages(b)
    n_blocks = (last - first) // nb + 1
    slot0 = slot_ref[b]

    @pl.when(b == 0)
    def _():
        for_each_copy(0, 0, 0, lambda copy: copy.start())

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def block(j, carry):
        slot = (slot0 + j) % 2

        # the next block's copies, this row's or the next row's first, are
        # in flight while this block's products run
        @pl.when(j + 1 < n_blocks)
        def _():
            for_each_copy(b, j + 1, 1 - slot, lambda copy: copy.start())

        @pl.when((j + 1 == n_blocks) & (b + 1 < rows))
        def _():
            for_each_copy(b + 1, 0, 1 - slot, lambda copy: copy.start())

        start, n = extent(b, j)

        # a whole block's copies are waited for at once, a pool: the
        # semaphore counts bytes, and the slot's are the block's (the wait
        # reads its descriptor's size alone)
        @pl.when(n == nb)
        def _():
            for buf in (kbuf, vbuf):
                pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                                      sem.at[slot]).wait()

        @pl.when(n < nb)
        def _():
            for_each_copy(b, j, slot, lambda copy: copy.wait())

        def clear(p, c):
            # what was not fetched counts for nothing: a weight of 0 does
            # not clear a NaN that VMEM holds there
            at = pl.ds(pl.multiple_of(p * ps, ps), ps)
            vbuf[slot, at, :] = jnp.zeros((ps, vbuf.shape[2]), vbuf.dtype)
            return c

        lax.fori_loop(n, nb, clear, 0)

        @pl.when(j + 1 == n_blocks)
        def _():
            # nor does what the frontier's page holds past the frontier
            # (a page's last owner's values, or none)
            at = pl.ds(pl.multiple_of((n - 1) * ps, ps), ps)
            past = ((start + n - 1) * ps + lax.broadcasted_iota(
                jnp.int32, (ps, vbuf.shape[2]), 0)) > pos_ref[b]
            vbuf[slot, at, :] = jnp.where(past, jnp.zeros((), vbuf.dtype),
                                          vbuf[slot, at, :])

        at = start * ps + lax.broadcasted_iota(jnp.int32, (r, blk), 1)
        visible = (at >= low_ref[b]) & (at <= pos_ref[b])
        for g in range(hkv):   # a key-value head's query heads are the rows
            lanes = pl.ds(g * ch, ch)
            s = lax.dot_general(q_ref[0, g], kbuf[slot, :, lanes], _NT,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible, s, _NEG)
            m_prev = m_ref[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            keep = jnp.exp(m_prev - m_new)
            l_ref[g] = jnp.broadcast_to(
                keep * l_ref[g, :, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape[1:])
            acc_ref[g] = acc_ref[g] * keep + lax.dot_general(
                p.astype(vbuf.dtype), vbuf[slot, :, lanes], _NN,
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        return carry

    lax.fori_loop(0, n_blocks, block, 0)
    o_ref[0] = acc_ref[...] / l_ref[:, :, :1]


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _gqa_call(table, position, lower, q2, k_pool, v_pool, ring, nb, run,
              interpret):
    b, hkv, r, ch = q2.shape
    ps, hc = k_pool.shape[1:]
    columns = table.shape[1]
    first, last = _gqa_pages(position, lower, ps, columns, ring)
    n_blocks = (last - first) // nb + 1
    # the buffer slot each row's first block lands in: rows take turns
    slot0 = (jnp.cumsum(n_blocks) - n_blocks) % 2
    row = lambda i, *_: (i, 0, 0, 0)  # noqa: E731
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    need = (4 * nb * ps * hc * itemsize + 2 * hkv * r * ch * (itemsize + 4)
            + hkv * r * (2 * _LANES + ch) * 4 + _SCORE_TEMPS * r * nb * ps * 4)
    return pl.pallas_call(
        functools.partial(
            _gqa_kernel, ps=ps, columns=columns, nb=nb, run=run, ring=ring,
            scale=float(np.float32(1.0) / np.sqrt(np.float32(ch)))),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, ch), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hkv, r, ch), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hkv, r, ch), row),
            scratch_shapes=[pltpu.VMEM((2, nb * ps, hc), k_pool.dtype),
                            pltpu.VMEM((2, nb * ps, hc), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((hkv, r, _LANES), jnp.float32),
                            pltpu.VMEM((hkv, r, _LANES), jnp.float32),
                            pltpu.VMEM((hkv, r, ch), jnp.float32)]),
        name="paged_gqa_decode",
        interpret=interpret,
        # rows run in order: each starts the next one's copies
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 16 * 1024 * 1024),
    )(_mark_runs(table, run, ring).reshape(-1), position, lower,
      slot0.astype(jnp.int32), q2,
      # the pools as rows: a page is page_size of them, a run's pages are
      # neighbours (whole sublane tiles a page: no byte moves)
      k_pool.reshape(-1, hc), v_pool.reshape(-1, hc))


def paged_gqa_read(q, k_pool, v_pool, page_table, position, window=None,
                   block_pages=None, run_pages=None, interpret=None,
                   selected=None):
    """Attention of one query a row, ``q`` ``(B, H, 1, Ch)``, over the row's
    paged history: ``H`` query heads over the pools' ``Hkv`` key-value heads
    (``(P+1, page_size, Hkv*Ch)``; query head ``i`` reads head ``i // (H //
    Hkv)``), positions ``s <= position[b]`` and, with ``window``, ``s >
    position[b] - window``. Without a window ``page_table`` ``(B, columns)``
    holds the row's pages in order from position 0; with one it is a RING:
    logical page ``s`` lives in column ``s % columns``.

    The kernel fetches the pages a row holds AND reads, a block of
    ``block_pages`` pages at a time (``_GQA_BLOCK`` positions; copies by page
    id from the scalar-prefetched table, the next block's in flight while
    this one's products run, across rows too), under a running maximum and
    sum in float32: nothing in VMEM has the history's size, so any length
    is served alike. A whole group of ``run_pages`` logical pages
    (``RUN_PAGES``; ``s % run_pages == 0`` ...) whose pool ids are
    consecutive is ONE copy, every other page a copy of its own: the table
    says which (:func:`_mark_runs`), so any valid table gives the
    page-by-page result (``run_pages=1``) bit for bit. A key-value head's
    ``H // Hkv`` query heads are the rows of one left operand. Operands in
    the pools' dtype, float32 scores and softmax. Returns ``(B, H, 1, Ch)`` float32. Callers gate via
    :func:`paged_gqa_refusal`.

    With ``selected=(page_ids, starts, counts)`` the read walks THAT table
    and not every page the row holds (block selection, a block a page:
    :func:`_selected_read`; ``page_table`` is not read; callers gate via
    :func:`paged_gqa_selected_refusal`). A call without it builds the
    program it built before there was one."""
    if selected is not None:
        return _selected_read(q, k_pool, v_pool, *selected, position,
                              block_pages, interpret)
    b, h, tq, ch = q.shape
    ps, hc = k_pool.shape[1:]
    hkv = hc // ch
    g = h // hkv
    nb = int(block_pages or max(1, _GQA_BLOCK // ps))
    run = int(run_pages or min(RUN_PAGES, nb, k_pool.shape[0]))
    r = _rows(g, tq, jnp.dtype(k_pool.dtype).itemsize)
    q2 = q.astype(k_pool.dtype).reshape(b, hkv, g * tq, ch)
    q2 = jnp.pad(q2, ((0, 0), (0, 0), (0, r - g * tq), (0, 0)))
    position = jnp.asarray(position, jnp.int32)
    lower = jnp.zeros_like(position) if window is None else \
        jnp.maximum(position - (int(window) - 1), 0)
    o2 = _gqa_call(jnp.asarray(page_table, jnp.int32), position, lower, q2,
                   k_pool, v_pool, window is not None, nb, run,
                   _resolve_interpret(interpret))
    return o2[:, :, :g * tq].reshape(b, h, tq, ch)


# --------------------------------------------------------------------------
# grouped key-value heads over a TABLE of SELECTED pages a row and key-value
# head (one token a row): block selection's decode read
# --------------------------------------------------------------------------
_LIST_CHUNK = 8    # blocks fetched and attended at once


def paged_gqa_selected_refusal(q, k_pool, page_ids):
    """Why the selected-pages kernel does NOT read the pools for these operands
    (anything with ``.shape``/``.dtype``), or None when it does: ``q`` ``(B,
    H, 1, Ch)``, ``k_pool`` ``(P+1, page_size, Hkv*Ch)``, ``page_ids`` ``(B,
    Hkv, L)``: a block of the list is one page. The first condition that
    fails is the one named; callers take the XLA gather then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    _, h, tq, ch = q.shape
    ps, hc = k_pool.shape[1], k_pool.shape[2]
    if tq != 1:
        return f"{tq} queries a row: the kernel reads for one"
    if k_pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(k_pool.dtype).name} is not float32 or bfloat16"
    if ch % _LANES or hc % ch or h % (hc // ch) or page_ids.shape[1] != hc // ch:
        return (f"{h} query heads of {ch} over the pool's {hc} columns are "
                f"not whole groups of whole {_LANES}-lane heads with a list "
                "each")
    sub = 8 * (4 // jnp.dtype(k_pool.dtype).itemsize)
    if ps % sub:
        return f"page size {ps} is not a multiple of {sub} sublanes"
    if page_ids.shape[2] % _LIST_CHUNK:
        return (f"lists of {page_ids.shape[2]} blocks are not whole chunks "
                f"of {_LIST_CHUNK}")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _block_list_kernel(pid_ref, start_ref, cnt_ref, pos_ref, slot_ref, q_ref,
                       kp_ref, vp_ref, o_ref, kbuf, vbuf, sem, m_ref, l_ref,
                       acc_ref, *, ps, length, nb, scale):
    """One row a grid step; its key-value heads in turn, each list in chunks
    of ``nb`` blocks under a running maximum. The chunks of a row's heads and
    of the rows follow one another through two buffer slots: the next chunk's
    copies (this head's, the next head's first, the next row's first) are in
    flight while this one's products run."""
    b, rows = pl.program_id(0), pl.num_programs(0)
    hkv, r, ch = q_ref.shape[1:]
    blk = nb * ps

    def count(row, g):
        return cnt_ref[row * hkv + g]

    def for_each_copy(row, g, c, into, act):
        """``act`` on the copies of chunk ``c`` of ``row``'s head ``g`` (a
        Python int: its lanes of a page are a static slice)."""
        base = (row * hkv + g) * length + c * nb
        n = jnp.clip(count(row, g) - c * nb, 0, nb)

        def page(i, carry):
            pid = pid_ref[base + i]
            at = pl.ds(pl.multiple_of(i * ps, ps), ps)
            for pool, buf in ((kp_ref, kbuf), (vp_ref, vbuf)):
                act(pltpu.make_async_copy(
                    pool.at[pid, :, pl.ds(g * ch, ch)], buf.at[into, at, :],
                    sem.at[into]))
            return carry

        lax.fori_loop(0, n, page, 0)

    @pl.when(b == 0)
    def _():
        for_each_copy(0, 0, 0, 0, lambda copy: copy.start())

    before = slot_ref[b]
    for g in range(hkv):
        n_chunks = (count(b, g) + nb - 1) // nb
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def chunk(c, carry, g=g, n_chunks=n_chunks, before=before):
            slot = (before + c) % 2

            @pl.when(c + 1 < n_chunks)
            def _():
                for_each_copy(b, g, c + 1, 1 - slot, lambda copy: copy.start())

            if g + 1 < hkv:
                @pl.when(c + 1 == n_chunks)
                def _():
                    for_each_copy(b, g + 1, 0, 1 - slot,
                                  lambda copy: copy.start())
            else:
                @pl.when((c + 1 == n_chunks) & (b + 1 < rows))
                def _():
                    for_each_copy(b + 1, 0, 0, 1 - slot,
                                  lambda copy: copy.start())

            for_each_copy(b, g, c, slot, lambda copy: copy.wait())
            n = jnp.clip(count(b, g) - c * nb, 0, nb)

            def clear(i, carry_):
                # what was not fetched counts for nothing: a weight of 0
                # does not clear a NaN that VMEM holds there
                at = pl.ds(pl.multiple_of(i * ps, ps), ps)
                vbuf[slot, at, :] = jnp.zeros((ps, ch), vbuf.dtype)
                return carry_

            lax.fori_loop(n, nb, clear, 0)
            base = (b * hkv + g) * length + c * nb
            lane = lax.broadcasted_iota(jnp.int32, (r, blk), 1)
            entry = lane // ps
            first = jnp.zeros((r, blk), jnp.int32)
            for i in range(nb):   # each block's first position, by its lanes
                first = jnp.where(entry == i, start_ref[base + i], first)
            visible = (entry < n) & (first + lane % ps <= pos_ref[b])
            s = lax.dot_general(q_ref[0, g], kbuf[slot], _NT,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible, s, _NEG)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            keep = jnp.exp(m_prev - m_new)
            l_ref[...] = jnp.broadcast_to(
                keep * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)
            acc_ref[...] = acc_ref[...] * keep + lax.dot_general(
                p.astype(vbuf.dtype), vbuf[slot], _NN,
                preferred_element_type=jnp.float32)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            return carry

        lax.fori_loop(0, n_chunks, chunk, 0)
        # a list with no visible position (never a served row's) reads zero
        o_ref[0, g] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        before = before + n_chunks


@functools.partial(jax.jit, static_argnums=(7, 8))
def _block_list_call(page_ids, starts, counts, position, q2, k_pool, v_pool,
                     nb, interpret):
    b, hkv, r, ch = q2.shape
    ps = k_pool.shape[1]
    length = page_ids.shape[2]
    chunks = jnp.sum((counts + nb - 1) // nb, axis=1)
    # the buffer slot each row's first chunk lands in: rows take turns
    slot0 = (jnp.cumsum(chunks) - chunks) % 2
    row = lambda i, *_: (i, 0, 0, 0)  # noqa: E731
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    need = (4 * nb * ps * ch * itemsize + 2 * hkv * r * ch * (itemsize + 4)
            + r * (2 * _LANES + ch) * 4 + _SCORE_TEMPS * r * nb * ps * 4)
    return pl.pallas_call(
        functools.partial(
            _block_list_kernel, ps=ps, length=length, nb=nb,
            scale=float(np.float32(1.0) / np.sqrt(np.float32(ch)))),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, ch), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hkv, r, ch), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hkv, r, ch), row),
            scratch_shapes=[pltpu.VMEM((2, nb * ps, ch), k_pool.dtype),
                            pltpu.VMEM((2, nb * ps, ch), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((r, _LANES), jnp.float32),
                            pltpu.VMEM((r, _LANES), jnp.float32),
                            pltpu.VMEM((r, ch), jnp.float32)]),
        name="paged_gqa_decode_selected",
        interpret=interpret,
        # rows run in order: each starts the next one's copies
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 16 * 1024 * 1024),
    )(page_ids.reshape(-1), starts.reshape(-1), counts.reshape(-1), position,
      slot0.astype(jnp.int32), q2, k_pool, v_pool)


def _selected_read(q, k_pool, v_pool, page_ids, starts, counts, position,
                   chunk_blocks=None, interpret=None):
    """Attention of one query a row, ``q`` ``(B, H, 1, Ch)``, over the blocks
    a LIST names, a list a row and key-value head: ``page_ids`` ``(B, Hkv,
    L)`` the pool ids of the blocks (a block is one page of the pools ``(P+1,
    page_size, Hkv*Ch)``), ``starts`` ``(B, Hkv, L)`` each block's first
    position, ``counts`` ``(B, Hkv)`` how many entries of a list count (at
    least one), ``position`` ``(B,)`` the query's: of a listed block the
    positions ``<= position[b]`` are seen. Query head ``i`` reads the list of
    key-value head ``i // (H // Hkv)``, and of a page that head's lanes
    alone. Block-sparse attention's decode read (a row under the dense
    length lists every block it holds, so one kernel serves both regimes).

    The kernel fetches ``chunk_blocks`` blocks at a time (``_LIST_CHUNK``;
    copies by id from the scalar-prefetched lists, the next chunk's in flight
    while this one's products run, across heads and rows too) under a running
    maximum and sum in float32 (``paged_gqa_decode_selected`` in a trace).
    Operands in the pools' dtype, float32 scores and softmax. Returns ``(B,
    H, 1, Ch)`` float32. Reached through ``paged_gqa_read(selected=)``;
    callers gate via :func:`paged_gqa_selected_refusal`."""
    b, h, tq, ch = q.shape
    hkv = k_pool.shape[2] // ch
    g = h // hkv
    nb = int(chunk_blocks or _LIST_CHUNK)
    r = _rows(g, tq, jnp.dtype(k_pool.dtype).itemsize)
    q2 = q.astype(k_pool.dtype).reshape(b, hkv, g * tq, ch)
    q2 = jnp.pad(q2, ((0, 0), (0, 0), (0, r - g * tq), (0, 0)))
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    o2 = _block_list_call(as_i32(page_ids), as_i32(starts),
                          jnp.maximum(as_i32(counts), 1), as_i32(position),
                          q2, k_pool, v_pool, nb, _resolve_interpret(interpret))
    return o2[:, :, :g * tq].reshape(b, h, tq, ch)


# --------------------------------------------------------------------------
# block selection's scoring: a group's summed softmax weights over a row's
# compressed keys (one token a row)
# --------------------------------------------------------------------------
def paged_block_scores_refusal(q, keys):
    """Why the scoring kernel does NOT weigh these operands (anything with
    ``.shape``/``.dtype``), or None when it does: ``q`` ``(B, Hkv, G, Ch)``,
    ``keys`` ``(B, J, Hkv*Ch)`` a row's compressed keys as gathered by its
    page table. The first condition that fails is the one named; callers
    take the XLA einsum then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    _, hkv, _, ch = q.shape
    if keys.dtype not in (jnp.float32, jnp.bfloat16) or q.dtype != keys.dtype:
        return (f"queries {jnp.dtype(q.dtype).name} and keys "
                f"{jnp.dtype(keys.dtype).name} are not both float32 or both "
                "bfloat16")
    if ch % _LANES or keys.shape[2] != hkv * ch or keys.shape[1] % _LANES:
        return (f"{keys.shape[1]} keys of {keys.shape[2]} columns under {hkv} "
                f"heads of {ch} are not whole {_LANES}-lane tiles")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _block_scores_kernel(pos_ref, q_ref, k_ref, o_ref, *, group, size, stride,
                         scale):
    b = pl.program_id(0)
    hkv, r, ch = q_ref.shape[1:]
    n = k_ref.shape[1]
    at = lax.broadcasted_iota(jnp.int32, (r, n), 1)
    valid = at * stride + (size - 1) <= pos_ref[b]
    counts = lax.broadcasted_iota(jnp.int32, (r, n), 0) < group
    for g in range(hkv):
        dots = lax.dot_general(q_ref[0, g], k_ref[0, :, pl.ds(g * ch, ch)],
                               _NT, preferred_element_type=jnp.float32) * scale
        dots = jnp.where(valid, dots, _NEG)
        e = jnp.where(valid, jnp.exp(dots - jnp.max(dots, axis=1,
                                                    keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
        o_ref[0, pl.ds(g, 1), :] = jnp.sum(jnp.where(counts, p, 0.0), axis=0,
                                           keepdims=True)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _block_scores_call(position, q2, keys, group, size, stride, interpret):
    b, hkv, r, ch = q2.shape
    n = keys.shape[1]
    itemsize = jnp.dtype(keys.dtype).itemsize
    need = 2 * n * hkv * ch * itemsize + 2 * hkv * n * 4 \
        + (_SCORE_TEMPS + 1) * r * n * 4
    return pl.pallas_call(
        functools.partial(
            _block_scores_kernel, group=group, size=size, stride=stride,
            scale=float(np.float32(1.0) / np.sqrt(np.float32(ch)))),
        out_shape=jax.ShapeDtypeStruct((b, hkv, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hkv, r, ch), lambda i, *_: (i, 0, 0, 0)),
                      pl.BlockSpec((1, n, hkv * ch), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, hkv, n), lambda i, *_: (i, 0, 0))),
        name="paged_block_scores",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=need + 16 * 1024 * 1024),
    )(position, q2, keys)


def paged_block_scores(q, keys, position, kernel_size, kernel_stride,
                       interpret=None):
    """``s`` ``(B, Hkv, J)`` float32: for every key-value head the sum, over
    its ``G`` query heads, of ``softmax_j(q_h . c_j / sqrt(Ch))`` over the
    compressed keys that lie wholly at or before the query
    (``kernel_stride * j + kernel_size - 1 <= position[b]``), zero for the
    others. ``q`` ``(B, Hkv, G, Ch)``, ``keys`` ``(B, J, Hkv*Ch)`` the row's
    compressed keys in the table's order, both in the cache's dtype. One
    row a grid step, its keys read once (``paged_block_scores`` in a trace).
    Callers gate via :func:`paged_block_scores_refusal`."""
    b, hkv, g, ch = q.shape
    r = _rows(g, 1, jnp.dtype(keys.dtype).itemsize)
    q2 = jnp.pad(q, ((0, 0), (0, 0), (0, r - g), (0, 0)))
    return _block_scores_call(jnp.asarray(position, jnp.int32), q2, keys, g,
                              int(kernel_size), int(kernel_stride),
                              _resolve_interpret(interpret))


# --------------------------------------------------------------------------
# block selection's scoring in a PREFILL: a stretch of queries against the
# row's compressed keys, pooled to blocks (score-shaped values in VMEM only)
# --------------------------------------------------------------------------
_CHUNK_QUERIES = 16   # queries a grid step: with a group's heads its rows
_CHUNK_BLOCKS = 256   # blocks a key tile (a phase's keys of a product)


def _chunk_tiles(n_blocks):
    """(blocks a key tile, key tiles) that cover ``n_blocks`` in whole lane
    tiles."""
    cover = -(-n_blocks // _LANES) * _LANES
    tm = _CHUNK_BLOCKS if cover % _CHUNK_BLOCKS == 0 else _LANES
    return tm, cover // tm


def _chunk_scores_vmem(rows, tq, per, tm, tiles, ch, itemsize):
    """Bytes of VMEM a grid step holds: a head's keys and the query and
    output blocks twice, the rows' scores against every key, the pooled
    phases, the float32 temporaries of one product."""
    keys = per * tiles * tm
    return (2 * keys * ch * itemsize + 2 * rows * ch * itemsize
            + rows * keys * 4 + (per + 2) * tq * tiles * tm * 4
            + _SCORE_TEMPS * rows * tm * 4)


def sparse_chunk_scores_refusal(q, keys, block_size, kernel_size,
                                kernel_stride):
    """Why the prefill's scoring kernel does NOT weigh these operands
    (anything with ``.shape``/``.dtype``), or None when it does: ``q`` ``(S,
    Hkv, G, Ch)`` a stretch's queries, ``keys`` ``(J, Hkv, Ch)`` the row's
    compressed keys. The first condition that fails is the one named;
    callers take the XLA form (``key_weights`` a few queries at a time)
    then."""
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    s, hkv, g, ch = q.shape
    if keys.dtype not in (jnp.float32, jnp.bfloat16) or q.dtype != keys.dtype:
        return (f"queries {jnp.dtype(q.dtype).name} and keys "
                f"{jnp.dtype(keys.dtype).name} are not both float32 or both "
                "bfloat16")
    if ch % _LANES or tuple(keys.shape[1:]) != (hkv, ch) \
            or s % _CHUNK_QUERIES:
        return (f"{s} queries against keys {tuple(keys.shape)} under {hkv} "
                f"heads of {ch} are not whole tiles of {_CHUNK_QUERIES} "
                f"queries and {_LANES} lanes")
    per = block_size // kernel_stride
    if block_size % kernel_stride or kernel_size % kernel_stride \
            or kernel_size // kernel_stride - 1 > per:
        return (f"compressed keys of {kernel_size} every {kernel_stride} do "
                f"not lie in phases of a block of {block_size}")
    tm, tiles = _chunk_tiles(-(-keys.shape[0] // per))
    need = _chunk_scores_vmem(g * _CHUNK_QUERIES, _CHUNK_QUERIES, per, tm,
                              tiles, ch, jnp.dtype(keys.dtype).itemsize)
    if need > _MAX_VMEM_BYTES:
        return (f"a head's {keys.shape[0]} compressed keys and their scores "
                f"need {need} bytes of VMEM (budget {_MAX_VMEM_BYTES})")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _chunk_scores_kernel(first_ref, q_ref, k_ref, o_ref, d_ref, s_ref, *, tq,
                         back, size, stride, scale):
    per, tiles, tm = k_ref.shape[1:4]
    rows = q_ref.shape[2]
    q = q_ref[0, 0]
    start = first_ref[0] + pl.program_id(1) * tq
    # the key tiles that hold a key the tile's LAST query sees whole
    reach = start + tq - size
    nt = jnp.where(reach >= 0,
                   jnp.minimum(reach // (stride * per * tm) + 1, tiles), 0)
    at_q = start + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    at = start + lax.rem(lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tq)
    # the last position of a tile's first phase's keys, less the tile's own
    ends = lax.broadcasted_iota(jnp.int32, (1, tm), 1) * (stride * per)
    past = lambda t, r: stride * (per * tm * t + r) + size - 1  # noqa: E731

    def scores(t, top):
        for r in range(per):
            d = lax.dot_general(q, k_ref[0, r, t], _NT,
                                preferred_element_type=jnp.float32) * scale
            d = jnp.where(ends <= at - past(t, r), d, _NEG)
            d_ref[r, t] = d
            top = jnp.maximum(top, jnp.max(d, axis=1, keepdims=True))
        return top

    top = lax.fori_loop(0, nt, scores, jnp.full((rows, 1), _NEG, jnp.float32))

    def exponents(t, total):
        # a masked score's is 0 (no row of a tile that counts is all masked
        # behind the dense length; one that is gives weights masked below)
        for r in range(per):
            e = jnp.exp(d_ref[r, t] - top)
            d_ref[r, t] = e
            total = total + jnp.sum(e, axis=1, keepdims=True)
        return total

    total = lax.fori_loop(0, nt, exponents, jnp.zeros((rows, 1), jnp.float32))
    share = 1.0 / jnp.maximum(total, 1e-30)

    def weights(t, carry):
        for r in range(per):
            p = d_ref[r, t] * share
            group = p[0:tq]
            for h in range(1, rows // tq):
                group = group + p[h * tq:(h + 1) * tq]
            s_ref[r, t] = jnp.where(ends <= at_q - past(t, r), group, -jnp.inf)
        return carry

    lax.fori_loop(0, nt, weights, 0)
    # block m's score: the largest weight among the keys that start in it
    # and the ``back`` that reach into it from the block before (the last
    # phases, one block to the right)
    first_lane = lax.broadcasted_iota(jnp.int32, (tq, tm), 1) == 0
    phase = lambda r, t: jnp.where(t < nt, s_ref[r, t], -jnp.inf)  # noqa: E731
    for t in range(tiles):
        best = phase(0, t)
        for r in range(1, per):
            best = jnp.maximum(best, phase(r, t))
        for r in range(per - back, per):
            edge = phase(r, t - 1)[:, tm - 1:tm] if t else -jnp.inf
            best = jnp.maximum(best, jnp.where(
                first_lane, edge, pltpu.roll(phase(r, t), 1, 1)))
        o_ref[0, :, t * tm:(t + 1) * tm] = best


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _chunk_scores_call(first, q4, keys, tq, back, size, stride, interpret):
    hkv, steps, rows, ch = q4.shape
    per, tiles, tm = keys.shape[1:4]
    need = _chunk_scores_vmem(rows, tq, per, tm, tiles, ch,
                              jnp.dtype(keys.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(
            _chunk_scores_kernel, tq=tq, back=back, size=size, stride=stride,
            scale=float(np.float32(1.0) / np.sqrt(np.float32(ch)))),
        out_shape=jax.ShapeDtypeStruct((hkv, steps * tq, tiles * tm),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hkv, steps),
            in_specs=[pl.BlockSpec((1, 1, rows, ch),
                                   lambda h, i, *_: (h, i, 0, 0)),
                      pl.BlockSpec((1, per, tiles, tm, ch),
                                   lambda h, i, *_: (h, 0, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, tq, tiles * tm),
                                   lambda h, i, *_: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((per, tiles, rows, tm), jnp.float32),
                            pltpu.VMEM((per, tiles, tq, tm), jnp.float32)]),
        name="sparse_chunk_scores",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=need + 16 * 1024 * 1024),
    )(first, q4, keys)


def sparse_chunk_keys(keys, block_size, kernel_stride):
    """A row's compressed keys ``(J, Hkv, Ch)`` as :func:`sparse_chunk_scores`
    reads them, ``(Hkv, phases, tiles, blocks a tile, Ch)``: key ``j`` lies
    in phase ``j % phases`` at block ``j // phases`` (``phases = block_size /
    kernel_stride`` keys start in a block), zeros behind the last. A prefill
    lays them out once for all its stretches."""
    j, hkv, ch = keys.shape
    per = block_size // kernel_stride
    tm, tiles = _chunk_tiles(-(-j // per))
    keys = jnp.pad(keys, ((0, per * tiles * tm - j), (0, 0), (0, 0)))
    return keys.reshape(tiles, tm, per, hkv, ch).transpose(3, 2, 0, 1, 4)


def sparse_chunk_scores(q, keys, first, block_size, kernel_size,
                        kernel_stride, interpret=None):
    """``b`` ``(Hkv, S, M)`` float32, ``M`` the blocks the laid-out keys
    cover: for the queries ``q`` ``(S, Hkv, G, Ch)`` at positions ``first +
    arange(S)`` and every key-value head, block ``m``'s score: the largest,
    over the compressed keys that touch the block (those that start in it
    and the ``kernel_size / kernel_stride - 1`` before them) and lie wholly
    at or before the query (``kernel_stride * j + kernel_size - 1 <=
    position``), of the sum over the head's ``G`` query heads of
    ``softmax_j(q_h . c_j / sqrt(Ch))`` over the keys that so lie; ``-inf``
    where no such key touches the block. ``keys`` from
    :func:`sparse_chunk_keys`, in the queries' dtype; ``first`` a scalar of
    the RUNNING program, so every stretch of a bucket is one kernel.

    A grid step weighs ``_CHUNK_QUERIES`` queries of one key-value head, a
    group's heads stacked over the rows of one left operand, against the
    head's keys, which stay in VMEM whole: the products go a tile of keys at
    a time up to the last tile the step's last query sees, the scores stay
    in VMEM for the exact softmax (float32; the largest first, no running
    rescale), and the pooling is an element-wise maximum over the phases
    (``sparse_chunk_scores`` in a trace). Callers gate via
    :func:`sparse_chunk_scores_refusal`."""
    s, hkv, g, ch = q.shape
    tq = _CHUNK_QUERIES
    q4 = q.reshape(s // tq, tq, hkv, g, ch).transpose(2, 0, 3, 1, 4)
    return _chunk_scores_call(
        jnp.asarray(first, jnp.int32).reshape(1),
        q4.reshape(hkv, s // tq, g * tq, ch), keys, tq,
        int(kernel_size) // int(kernel_stride) - 1, int(kernel_size),
        int(kernel_stride), _resolve_interpret(interpret))
