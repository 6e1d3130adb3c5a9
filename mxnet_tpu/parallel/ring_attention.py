"""Ring attention: context parallelism over a ``sp`` mesh axis.

New capability (absent in the reference — SURVEY §5.7): sequences sharded
across chips, K/V blocks rotated around the ring with ``lax.ppermute`` while
each chip accumulates online-softmax partials — comm overlaps compute over
ICI. Published pattern: Ring Attention (Liu et al.) / blockwise attention.

Implementation: ``shard_map`` over the sequence axis; per-shard compute uses
the same f32 online-softmax update as the Pallas flash kernel; differentiable
end-to-end (jax AD through shard_map/ppermute gives the rotating backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention"]


def _block_attn(q, k, v, m_prev, l_prev, acc, scale, mask_val=None):
    """One online-softmax accumulation step; q (B,H,Tq,D), k/v (B,H,Tk,D)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if mask_val is not None:
        s = jnp.where(mask_val, s, -jnp.inf)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isinf(s), 0.0, p)
    corr = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_new = corr * l_prev + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = False):
    """Attention over sequence-sharded q/k/v (B, H, T_global, D).

    Each chip holds T_global / sp_size of the sequence; K/V rotate around the
    ring. Returns the sequence-sharded output with the same sharding as q.
    """
    sp = mesh.shape[axis]
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def per_shard(q_blk, k_blk, v_blk):
        idx = lax.axis_index(axis)
        B, H, Tq, D = q_blk.shape
        Tk = k_blk.shape[2]
        if causal and Tq != Tk:
            # the per-step full-skip below (src_idx > idx) is only sound
            # when shards partition one shared sequence axis evenly
            raise ValueError(
                f"causal ring attention requires equal q/kv shards, got "
                f"Tq={Tq} Tk={Tk}")
        m = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, H, Tq), jnp.float32)
        acc = jnp.zeros((B, H, Tq, D), jnp.float32)
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        def body(step, carry):
            m, l, acc, k_cur, v_cur = carry
            src_idx = (idx - step) % sp  # which shard's K/V we now hold
            if causal:
                # ring steps where the visiting K/V shard lies entirely in
                # the future (src_idx > idx) are fully masked — branch them
                # out instead of computing-then-masking, saving ~half the
                # attention FLOPs across the ring on average. The mask is
                # built INSIDE the branch: cond hoists closed-over values,
                # so constructing it outside would materialize the (Tq, Tk)
                # iotas on skipped steps too.
                def _compute(args):
                    m, l, acc = args
                    q_pos = idx * Tq + lax.broadcasted_iota(
                        jnp.int32, (Tq, Tk), 0)
                    k_pos = src_idx * Tk + lax.broadcasted_iota(
                        jnp.int32, (Tq, Tk), 1)
                    mask = (q_pos >= k_pos)[None, None]
                    return _block_attn(q_blk, k_cur, v_cur, m, l, acc, scale,
                                       mask)

                m, l, acc = lax.cond(src_idx <= idx, _compute,
                                     lambda args: args, (m, l, acc))
            else:
                m, l, acc = _block_attn(q_blk, k_cur, v_cur, m, l, acc, scale,
                                        None)
            # rotate K/V to the next chip (overlaps with next step's compute;
            # the collective stays OUTSIDE the cond — every device must
            # participate in every rotation)
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc, _, _ = lax.fori_loop(0, sp, body, (m, l, acc, k_blk, v_blk),
                                        unroll=True)
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q_blk.dtype)

    spec = P(None, None, axis, None)
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
