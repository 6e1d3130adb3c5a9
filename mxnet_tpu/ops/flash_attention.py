"""Pallas flash attention for TPU.

The marquee custom kernel (SURVEY §5.7): replaces the reference's O(L^2)
fused attention (``src/operator/contrib/transformer.cu``) with an online-
softmax blocked kernel — O(L) memory, MXU-tiled q/k blocks, f32 accumulation.

Forward is ONE Pallas kernel body (``_fwd_kernel`` through ``_flash_fwd``;
grid = (heads / group, q_blocks, k_blocks), with m/l/acc scratch carried
across the sequential innermost k dimension). It serves two callers. The
training path (``flash_attention``, causal or not, no mask) also has it emit
the per-row logsumexp (lane-replicated, the standard TPU layout) as the
backward residual. A long prefill's masked attention (PR 32:
``attention._masked_chunk_kernel`` for ``F.sparse_latent_attention``,
``masked_latent_prefill`` in a trace) hands it ONE (tq, tk) mask that every
head shares, a value width of its own, several heads a grid step (a mask
tile is read once for them) and asks for no logsumexp; the gate of that
caller is :func:`masked_prefill_refusal`. The products take their operands
as they come (bfloat16 stays bfloat16; float32 sums), the softmax is
float32, the weights are cast to the values' dtype before the second
product; key blocks above the diagonal are neither computed nor fetched.

Backward is a pair of Pallas kernels (FlashAttention-2 recomputation split):
``dkv`` grids over k blocks with q innermost (accumulating dk/dv in VMEM
scratch) and ``dq`` grids over q blocks with k innermost — 5 block matmuls
per (q,k) tile total, O(L) memory, vs the O(L^2) scores buffer of the einsum
VJP. A ``lax.scan`` chunked recompute backward (`_chunked_attention`) is kept
as the escape hatch (`config flash_pallas_bwd=False`) and as the long-seq
correctness oracle. Forward and backward compile under Mosaic and agree
with the einsum reference on a TPU v5e (``chip_smoke.py`` kernels phase:
batch 4, 16 heads, seq 2048, head 64, causal and not). The training path's
speed against the einsum and chunked paths under the installed jax: not
measured (``tools/kernelbench.py --kinds attn``); the masked forward's is
(``--kinds masked_prefill``; PERF.md, PR 32).

On non-TPU backends the kernels run in interpret mode (tests) or callers fall
back to the einsum path via ``flash_supported``. Which model reaches the
training path: none of the listed ones at their published shapes.
``flash_supported`` refuses any mask and any sequence under 2048, BERT
always passes a key-padding mask and GPT-2's context is 1024. BERT's short
masked sequences are the opposite shape and have a kernel of their own
(:mod:`mxnet_tpu.ops.pallas_packed_attention`, reached through
``attention.self_attention_packed``); that operator's fallback still comes
here for long unmasked sequences. The forward kernel runs in dots3-note-prev's
serving cell, in every prefill program's two full layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret


_FLASH_MIN_SEQ = 2048  # where a v5e run under jaxlib 0.4.36 (July 2026,
# record no longer in the tree) saw flash fwd+bwd pull ahead of the XLA
# einsum; the crossover under the installed jax is not measured. Past it
# the kernel also keeps O(L) memory where einsum's [b,h,t,t] scores buffer
# stops fitting HBM

_FLASH_MEM_BYTES = 2 << 30  # engage below _FLASH_MIN_SEQ too when the einsum
# path's f32 scores buffer alone would exceed this (huge batch*heads at
# moderate seq): memory is the kernel's unconditional win


_SCORE_TEMPS = 4  # float32 (bq, bk) blocks a head holds at once: the scores,
# the weights, the mask as a select's operand, the weights cast for the MXU;
# two heads of a step are in flight together


def flash_supported(q, k, v, mask=None) -> bool:
    """Kernel eligibility: TPU backend, no arbitrary mask, tile-able lengths,
    and either past the measured speed crossover or under einsum-memory
    pressure."""
    if mask is not None or not _on_tpu():
        return False
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # the kernel's BlockSpecs put d on the lane dimension; Mosaic wants
    # 128-multiple lane tiles, so sub-128 head dims are zero-padded to 128
    # inside _flash_fwd (zeros in the contraction dim leave scores exact,
    # padded v columns are sliced off). d % 64 == 0 bounds the pad waste at
    # 2x and admits BERT/GPT's d=64 heads (round-2 verdict weak #4)
    # dtype gate: f32/bf16 only — the MXU's native pair, and the kernel's
    # scratch accumulators are f32 either way. A float16 AMP policy
    # (TrainStep(amp='float16')) deliberately falls back to the XLA paths,
    # whose softmax also runs f32 (see multi_head_attention's dtype policy);
    # f16 buys nothing on TPU over bf16 and would need its own Mosaic tiling
    return (tq % 128 == 0 and tk % 128 == 0 and d % 64 == 0
            and (max(tq, tk) >= _FLASH_MIN_SEQ
                 or b * h * tq * tk * 4 >= _FLASH_MEM_BYTES)
            and q.dtype in (jnp.float32, jnp.bfloat16))


def masked_prefill_refusal(q, k, v, seen):
    """Why a prefill chunk's attention under a mask is NOT the forward
    kernel (``_flash_fwd(mask=)``: score-shaped blocks in VMEM only) for
    these operands (anything with ``.shape``/``.dtype``), or None when it
    is: ``q`` and ``k`` (B, T, h, d), ``v`` (B, T, h, dv) a block of heads
    as ``attention._masked_chunk_attention`` makes them, ``seen`` (B, T, T).
    The first condition that fails is the one named; callers take that XLA
    path (keys in stretches) then. Training callers never come here:
    :func:`flash_supported` refuses every mask."""
    from .. import config as _config
    from .._mesh_state import current_mesh

    if not _config.get("paged_attention_kernel"):
        return "paged_attention_kernel knob is off"
    if not _on_tpu():
        return "the backend is not a TPU"
    dtypes = {jnp.dtype(x.dtype) for x in (q, k, v)}
    if len(dtypes) > 1 or dtypes.pop() not in (jnp.float32, jnp.bfloat16):
        return ("queries, keys and values are not all float32 or all "
                "bfloat16")
    b, t = q.shape[:2]
    if b != 1:
        return f"{b} rows: the kernel takes one mask for all its heads"
    if tuple(seen.shape) != (b, t, t):
        return (f"a mask of shape {tuple(seen.shape)} is not one chunk's "
                f"({b}, {t}, {t})")
    if t % _LANES:
        return f"{t} tokens are not whole {_LANES}-key tiles"
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _causal_gated(body, causal, qi, ki, bq, bk, off):
    """Run ``body`` only for (q, k) block pairs with live causal entries:
    the block's max row + off must reach its min col. Shared by the forward
    and both backward kernels so the skip predicate cannot drift."""
    if causal:
        @pl.when(qi * bq + bq - 1 + off >= ki * bk)
        def _():
            body()
    else:
        body()


def _block_mask(s, causal, qi, ki, bq, bk, off):
    """Bottom-right-aligned causal mask: row r attends to cols <= r + off
    (off = tk - tq), matching _ref_attention/_chunked_attention."""
    if not causal:
        return s
    rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(rows + off >= cols, s, -jnp.inf)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, bq, bk, scale, off,
                emit_lse, masked):
    """One (heads, query block, key block) step of the online softmax.
    ``q_ref`` (G, bq, d), ``k_ref`` (G, bk, d) and ``v_ref`` (G, bk, dv) hold
    G heads; ``mask_ref`` (bq, bk) int8, if ``masked``, is one tile of a
    mask that every head shares: it is read once a step, whatever G. The
    products take their operands as they come (bfloat16 stays bfloat16, the
    sums are float32), the softmax is float32, and the weights are cast to
    the values' dtype before the second product: a score-shaped block never
    leaves VMEM."""
    mask_ref = rest[0] if masked else None
    o_ref = rest[masked]
    lse_ref = rest[masked + 1] if emit_lse else None
    m_ref, l_ref, acc_ref = rest[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    heads = q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _body():
        keep = None if mask_ref is None else mask_ref[...].astype(jnp.int32) != 0

        def head(g):
            s = jax.lax.dot_general(q_ref[g], k_ref[g], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if keep is None:
                s = _block_mask(s, causal, qi, ki, bq, bk, off)
            else:   # the mask holds the diagonal: ``causal`` only skips blocks
                s = jnp.where(keep, s, -jnp.inf)
            m_prev = m_ref[g, :, :1]  # (bq, 1), replicated over lanes
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # rows that have seen nothing yet (m_new == -inf) must not make
            # exp(-inf + inf); a masked score is exp(-inf - finite) = 0
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)
            corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
            l_new = corr * l_ref[g, :, :1] + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[g],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[g] = acc_ref[g] * corr + pv
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        # unrolled: one head's softmax (the vector units) stands beside the
        # next head's products (the MXU) in one schedule; a loop ran them in
        # turn, 13% slower at four heads a step (PERF.md, PR 32)
        for g in range(heads):
            head(g)

    _causal_gated(_body, causal, qi, ki, bq, bk, off)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp residual for the backward kernels, lane-replicated.
            # Fully-masked rows (l == 0) store lse = 0: the backward then
            # yields p = exp(-inf - 0) = 0 for every masked score, matching
            # the forward's defined-as-zero output for those rows.
            lg = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
            lse_ref[...] = jnp.where(l_ref[...] == 0.0, 0.0,
                                     m_ref[...] + jnp.log(lg))


def _pick_block(t, prefer=512):
    """Largest MXU-friendly block (<= prefer) that divides the seq length.
    Bigger tiles keep the MXU pipeline full and cut grid-iteration
    overhead; the training path's 512x512 against smaller tiles on the chip:
    not measured (the masked forward's blocks are: PERF.md, PR 32)."""
    for cand in sorted({prefer, 512, 256, 128}, reverse=True):
        if cand <= min(t, prefer) and t % cand == 0:
            return cand
    return t


def _lane_pad(x):
    d = x.shape[-1]
    if d % _LANES == 0:
        return x
    d_pad = ((d + _LANES - 1) // _LANES) * _LANES
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


def _flash_fwd(q, k, v, causal, block_q=None, block_k=None, interpret=False,
               return_lse=False, mask=None, scale=None, group=1,
               out_dtype=None, name=None):
    """The forward kernel over ``q`` (b, h, tq, d), ``k`` (b, h, tk, d) and
    ``v`` (b, h, tk, dv): ``dv`` need not be ``d``. ``mask`` (tq, tk), if
    given, is ONE mask for every batch and head (nonzero: the query sees the
    key), fetched a (block_q, block_k) tile a grid step and shared by the
    ``group`` heads that step holds; with it ``causal`` promises that the
    mask is false above the diagonal and skips those blocks unread.
    ``scale`` multiplies the float32 scores (default ``d ** -0.5``)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)  # true head dim, even when lanes are padded
    dv_orig = v.shape[-1]
    # lane-pad the head dims to whole 128 tiles: zero columns contribute
    # nothing to q·kᵀ, and the padded v columns come out as zeros in the
    # output, sliced off below. XLA fuses the pads/slice; cost is the
    # idle lane fraction of the two block matmuls.
    q, k, v = _lane_pad(q), _lane_pad(k), _lane_pad(v)
    d, dv = q.shape[-1], v.shape[-1]
    bq = _pick_block(tq) if block_q is None else min(block_q, tq)
    bk = _pick_block(tk) if block_k is None else min(block_k, tk)
    n, off = b * h, tk - tq
    if n % group:
        raise ValueError(f"{n} heads are not whole groups of {group}")
    qr = q.reshape(n, tq, d)
    kr = k.reshape(n, tk, d)
    vr = v.reshape(n, tk, dv)
    grid = (n // group, tq // bq, tk // bk)
    kernel = functools.partial(_fwd_kernel, causal=causal, bq=bq, bk=bk,
                               scale=scale, off=off, emit_lse=return_lse,
                               masked=mask is not None)
    scratch = [
        pltpu.VMEM((group, bq, _LANES), jnp.float32),
        pltpu.VMEM((group, bq, _LANES), jnp.float32),
        pltpu.VMEM((group, bq, dv), jnp.float32),
    ]

    def key_block(qi, ki):
        # a block above the diagonal is skipped: naming the last block the
        # queries do read keeps the pipeline from fetching it
        if not causal:
            return ki
        return jnp.minimum(ki, jnp.maximum(qi * bq + bq - 1 + off, 0) // bk)

    in_specs = [
        pl.BlockSpec((group, bq, d), lambda g, qi, ki: (g, qi, 0)),
        pl.BlockSpec((group, bk, d), lambda g, qi, ki: (g, key_block(qi, ki), 0)),
        pl.BlockSpec((group, bk, dv), lambda g, qi, ki: (g, key_block(qi, ki), 0)),
    ]
    operands = [qr, kr, vr]
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (bq, bk), lambda g, qi, ki: (qi, key_block(qi, ki))))
        operands.append(mask.astype(jnp.int8))
    # the lse output exists only on the grad path (return_lse): Pallas can't
    # DCE an unused kernel output, and at padded d=64 it would be as large
    # as the attention output itself
    out_shape = [jax.ShapeDtypeStruct((n, tq, dv), out_dtype or q.dtype)]
    out_specs = [pl.BlockSpec((group, bq, dv), lambda g, qi, ki: (g, qi, 0))]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((n, tq, _LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((group, bq, _LANES), lambda g, qi, ki: (g, qi, 0)))
    itemsize = jnp.dtype(q.dtype).itemsize
    need = (2 * group * (bq * d + bk * d + bk * dv) * itemsize   # two buffers
            + 2 * group * bq * dv * jnp.dtype(out_shape[0].dtype).itemsize
            + 2 * bq * bk * (mask is not None)
            + group * bq * (2 * _LANES + dv) * 4                # the scratch
            + 2 * return_lse * group * bq * _LANES * 4
            + _SCORE_TEMPS * min(group, 2) * bq * bk * 4)
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=interpret,
        name=name,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(need + (8 << 20), 32 << 20)),
    )(*operands)
    out = res[0].reshape(b, h, tq, dv)
    if dv_orig != dv:
        out = out[..., :dv_orig]
    return (out, res[1]) if return_lse else out


def _bwd_recompute(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, causal,
                   bq, bk, scale, off, qi, ki):
    """Shared FlashAttention-2 backward recompute for both kernels: rebuild
    the normalized probabilities p from the saved lse, then
    ds = p * (do·vᵀ - di). Returns (q_scaled, k, do, p, ds)."""
    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)  # (bq, d)
    lse = lse_ref[0][:, :1]  # (bq, 1)
    di = di_ref[0][:, :1]  # (bq, 1)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = _block_mask(s, causal, qi, ki, bq, bk, off)
    p = jnp.exp(s - lse)  # normalized probabilities (exact softmax)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    return q, k, do, p, ds


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal, bq, bk,
                    scale, off):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body():
        q, _k, do, p, ds = _bwd_recompute(
            q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, causal, bq, bk,
            scale, off, qi, ki)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _causal_gated(_body, causal, qi, ki, bq, bk, off)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                   dq_ref, dq_acc, *, causal, bq, bk, scale, off):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body():
        _q, k, _do, _p, ds = _bwd_recompute(
            q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, causal, bq, bk,
            scale, off, qi, ki)
        dq_acc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _causal_gated(_body, causal, qi, ki, bq, bk, off)

    @pl.when(ki == nk - 1)
    def _finalize():
        # chain rule through q_scaled = q * scale
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, block_q=None, block_k=None,
                      interpret=False):
    """FlashAttention-2 backward: recompute p from (q, k, lse); dk/dv kernel
    grids over k blocks (q innermost, VMEM accumulators), dq kernel grids
    over q blocks (k innermost). O(L) memory, ~2.5x forward FLOPs."""
    b, h, tq, d_orig = q.shape
    tk = k.shape[2]
    scale = 1.0 / (d_orig ** 0.5)
    # di = rowsum(do * o) over the TRUE head dim, lane-replicated like lse
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di.reshape(b * h, tq, 1), (b * h, tq, _LANES))
    q, k, v, do = _lane_pad(q), _lane_pad(k), _lane_pad(v), _lane_pad(do)
    d = q.shape[-1]
    bq = _pick_block(tq) if block_q is None else min(block_q, tq)
    bk = _pick_block(tk) if block_k is None else min(block_k, tk)
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    dor = do.reshape(b * h, tq, d)
    off = tk - tq
    common = dict(causal=causal, bq=bq, bk=bk, scale=scale, off=off)
    cparams = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    q_spec_kmaj = pl.BlockSpec((1, bq, d), lambda bh, ki, qi: (bh, qi, 0))
    lse_spec_kmaj = pl.BlockSpec((1, bq, _LANES),
                                 lambda bh, ki, qi: (bh, qi, 0))
    kv_spec_kmaj = pl.BlockSpec((1, bk, d), lambda bh, ki, qi: (bh, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, d), v.dtype)],
        grid=(b * h, tk // bk, tq // bq),
        in_specs=[q_spec_kmaj, q_spec_kmaj, lse_spec_kmaj, lse_spec_kmaj,
                  kv_spec_kmaj, kv_spec_kmaj],
        out_specs=[kv_spec_kmaj, kv_spec_kmaj],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
    )(qr, dor, lse, di, kr, vr)

    q_spec_qmaj = pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0))
    lse_spec_qmaj = pl.BlockSpec((1, bq, _LANES),
                                 lambda bh, qi, ki: (bh, qi, 0))
    kv_spec_qmaj = pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        grid=(b * h, tq // bq, tk // bk),
        in_specs=[q_spec_qmaj, q_spec_qmaj, lse_spec_qmaj, lse_spec_qmaj,
                  kv_spec_qmaj, kv_spec_qmaj],
        out_specs=q_spec_qmaj,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=cparams,
    )(qr, dor, lse, di, kr, vr)

    dq = dq.reshape(b, h, tq, d)[..., :d_orig]
    dk = dk.reshape(b, h, tk, d)[..., :d_orig]
    dv = dv.reshape(b, h, tk, d)[..., :d_orig]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, interpret):
    return _flash_fwd(q, k, v, causal, interpret=interpret)


def _ref_attention(q, k, v, causal):
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.einsum("bhqc,bhkc->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(cm, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkc->bhqc", p, v)


def _chunked_attention(q, k, v, causal, chunk=1024):
    """Memory-efficient attention (Rabe & Staats): online softmax over KV
    chunks via ``lax.scan`` with a rematerialized chunk body — O(tq·chunk)
    live memory instead of the einsum path's O(tq·tk). Numerically identical
    to softmax attention; used as the backward of the Pallas forward so the
    whole train step stays O(L) in sequence length."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # largest chunk <= requested that divides tk (tk=2176 with the default
    # chunk=1024 would otherwise have a ragged tail block)
    chunk = min(chunk, tk)
    chunk = next(c for c in range(chunk, 0, -1) if tk % c == 0)
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    rows = lax.broadcasted_iota(jnp.int32, (tq, chunk), 0)

    @jax.checkpoint
    def body(carry, i):
        m, l, acc = carry
        ks = lax.dynamic_slice_in_dim(k, i * chunk, chunk, 2).astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, i * chunk, chunk, 2).astype(jnp.float32)
        s = jnp.einsum("bhqc,bhkc->bhqk", qf, ks,
                       preferred_element_type=jnp.float32)
        if causal:
            cols = i * chunk + lax.broadcasted_iota(jnp.int32, (tq, chunk), 1)
            s = jnp.where((rows + (tk - tq) >= cols)[None, None], s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum("bhqk,bhkc->bhqc", p, vs,
                                          preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tq, 1), jnp.float32)
    a0 = jnp.zeros((b, h, tq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(tk // chunk))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def _flash_vjp_fwd(q, k, v, causal, interpret):
    o, lse = _flash_fwd(q, k, v, causal, interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, interpret, res, g):
    q, k, v, o, lse = res
    from .. import config as _config

    if _config.get("flash_pallas_bwd"):
        return _flash_bwd_pallas(q, k, v, o, lse, g, causal,
                                 interpret=interpret)
    # escape hatch: XLA chunked-recompute backward (kernel-free; its cost
    # against the Pallas backward on the chip: not measured)
    _, vjp = jax.vjp(lambda q, k, v: _chunked_attention(q, k, v, causal),
                     q, k, v)
    return vjp(g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, mask=None, causal=False, interpret=None):
    """Blocked flash attention over (B, H, T, Ch). ``mask`` unsupported here —
    callers gate via :func:`flash_supported`."""
    if mask is not None:
        raise ValueError("flash_attention kernel does not take arbitrary masks; "
                         "use multi_head_attention which falls back to the einsum path")
    return _flash(q, k, v, bool(causal), _resolve_interpret(interpret))
