"""Paged decode-attention Pallas kernel (profile-directed: memcheck's
``kv_gather_materialize`` detector).

The XLA lowering of the paged decode/verify read path
(``attention._paged_cached_mha``) gathers the whole per-row history out of
the page pool every step::

    k_hist = k_pool[page_table]        # materializes (B, n_pages, H, ps, Ch)

— a full second copy of every live row's KV bytes per decode step, pinned
at ×4 (two pools × two layers) in the committed ``mem_decode_paged.json`` /
``mem_verify_spec.json`` goldens. This kernel deletes that materialization:
the page *table* rides in as a scalar-prefetch operand, the pools stay in
``ANY`` (HBM) memory space, and the kernel DMAs exactly the pages named by
the current row's table into a VMEM scratch history — no pool-wide gather
ever exists in the program.

Numerics contract: the in-kernel read path is the *same composition* as
:func:`mxnet_tpu.ops.attention._frontier_masked_attention` (einsum → f32
scale/mask → ``jax.nn.softmax`` → einsum), evaluated per batch row — so
paged decode/verify logits stay **bit-identical** to the gather path (and
therefore to the contiguous dense cache), which
``tests/test_paged_inference.py`` asserts exactly. No online/streaming
softmax: associativity changes would break bit-identity for zero benefit at
decode history lengths.

Gating: CPU interpret mode always qualifies (tier-1 CI correctness); the
hardware path additionally wants lane-aligned heads and a VMEM-bounded
scratch history — callers fall back to the XLA gather otherwise
(``paged_attention_supported``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

# VMEM budget for the two (H, cap, Ch) scratch histories plus the f32
# score block — half the ~16MB/core so the q/out blocks and DMA staging fit
_MAX_SCRATCH_BYTES = 8 * 1024 * 1024


def paged_attention_refusal(q, k_pool, page_table):
    """Why the paged kernel does NOT replace the XLA pool gather for these
    operands (anything with ``.shape``/``.dtype``), or None when it does.

    Interpret mode (CPU CI) has no tiling constraints, so the only gate
    there is the config knob — this is what keeps the compiled
    decode/verify programs gather-free in the committed memory goldens.
    On a TPU the scratch history must be tile-aligned (``Ch % 128``,
    ``page_size % 8``) and fit the VMEM budget, and the query must be
    float32: Mosaic refuses the kernel's bf16 x bf16 einsums ("'tpu.matmul'
    op Expected matmul acc to be 32-bit"). Callers fall back to the gather
    path otherwise.
    """
    from .. import config as _config

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return None
    b, h, tq, ch = q.shape
    ps = k_pool.shape[2]
    cap = page_table.shape[1] * ps
    if ch % _LANES:
        return f"head size {ch} is not a multiple of {_LANES} lanes"
    if ps % 8:
        return f"page size {ps} is not a multiple of 8 sublanes"
    if q.dtype != jnp.float32:
        return f"query dtype {jnp.dtype(q.dtype).name} is not float32"
    if k_pool.dtype not in (jnp.float32, jnp.bfloat16):
        return f"pool dtype {jnp.dtype(k_pool.dtype).name} is not f32/bf16"
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    scratch = 2 * h * cap * ch * itemsize + 4 * h * tq * cap
    if scratch > _MAX_SCRATCH_BYTES:
        return (f"row history needs {scratch} bytes of VMEM scratch "
                f"(budget {_MAX_SCRATCH_BYTES})")
    return None


def paged_attention_supported(q, k_pool, page_table) -> bool:
    """True when the paged kernel should replace the XLA pool gather
    (see :func:`paged_attention_refusal` for the rules)."""
    return paged_attention_refusal(q, k_pool, page_table) is None


def _paged_kernel(table_ref, pos_ref, q_ref, kp_ref, vp_ref, o_ref,
                  ks, vs, sem, *, ps, n_pages, tq, cap):
    b = pl.program_id(0)

    def gather_page(j, carry):
        # DMA page table[b, j] of each pool into slot j of the row history.
        # Trash-page ids (0) are gathered like the XLA path — their garbage
        # K/V sit past the frontier and get an exact 0.0 softmax weight.
        pid = table_ref[b, j]
        pltpu.make_async_copy(kp_ref.at[pid],
                              ks.at[:, pl.ds(j * ps, ps), :], sem).start()
        pltpu.make_async_copy(kp_ref.at[pid],
                              ks.at[:, pl.ds(j * ps, ps), :], sem).wait()
        pltpu.make_async_copy(vp_ref.at[pid],
                              vs.at[:, pl.ds(j * ps, ps), :], sem).start()
        pltpu.make_async_copy(vp_ref.at[pid],
                              vs.at[:, pl.ds(j * ps, ps), :], sem).wait()
        return carry

    jax.lax.fori_loop(0, n_pages, gather_page, 0)

    # From here on: _frontier_masked_attention verbatim, one batch row.
    q = q_ref[0]                                    # (H, Tq, Ch)
    ch = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(ch, jnp.float32))
    scores = jnp.einsum("hqc,hkc->hqk", q, ks[...]).astype(jnp.float32) * scale
    key_idx = jax.lax.broadcasted_iota(jnp.int32, (tq, cap), 1)
    q_pos = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (tq, cap), 0)
    scores = jnp.where((key_idx <= q_pos)[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o_ref[0] = jnp.einsum("hqk,hkc->hqc", att, vs[...]).astype(o_ref.dtype)


def paged_attention(q, k_new, v_new, k_pool, v_pool, page_table, position,
                    interpret=None):
    """Paged-cache attention with the in-kernel page gather.

    Same contract as the gather path: scatter the Tq new K/V of each row
    into ``pool[table[pos // ps], :, pos % ps]`` (overflow → trash page 0),
    then attend each row's query against its full paged history under the
    frontier mask. Returns ``(out, k_pool, v_pool)``.

    The scatter stays XLA (token-granular ``.at[].set`` is already optimal
    and aliases the donated decode carry); only the read path — where the
    pool-wide gather used to materialize — runs in the kernel.
    """
    interpret = _resolve_interpret(interpret)
    b, h, tq, ch = q.shape
    ps = k_pool.shape[2]
    n_pages = page_table.shape[1]
    cap = n_pages * ps

    pos = (position[:, None]
           + jnp.arange(tq, dtype=jnp.int32)[None, :])          # (B, Tq)
    slot = jnp.clip(pos // ps, 0, n_pages - 1)
    pid = jnp.take_along_axis(page_table, slot, axis=1)          # (B, Tq)
    pid = jnp.where(pos < cap, pid, 0)                           # overflow -> trash
    off = pos % ps
    pid_f, off_f = pid.reshape(-1), off.reshape(-1)
    vals_k = k_new.transpose(0, 2, 1, 3).reshape(b * tq, h, ch)
    vals_v = v_new.transpose(0, 2, 1, 3).reshape(b * tq, h, ch)
    k_pool = k_pool.at[pid_f, :, off_f, :].set(vals_k.astype(k_pool.dtype))
    v_pool = v_pool.at[pid_f, :, off_f, :].set(vals_v.astype(v_pool.dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, tq, ch), lambda b_, t, p: (b_, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, tq, ch), lambda b_, t, p: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, cap, ch), k_pool.dtype),
            pltpu.VMEM((h, cap, ch), v_pool.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ps=ps, n_pages=n_pages,
                          tq=tq, cap=cap),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, ch), q.dtype),
        grid_spec=grid_spec,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(position, jnp.int32),
      q, k_pool, v_pool)
    return out, k_pool, v_pool
