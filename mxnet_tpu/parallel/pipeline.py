"""GPipe-style pipeline parallelism over a ``pp`` mesh axis.

New capability relative to the reference: MXNet 1.x only had manual
``group2ctx`` placement (``3rdparty/tvm/nnvm/src/pass/place_device.cc`` +
``example/model-parallel/``) — ops pinned to devices with auto-inserted
copies, no microbatching, no overlap. The TPU-native formulation:

  - the S pipeline stages are ONE stacked pytree (leading stage axis,
    sharded ``P('pp', ...)``) — stage dispatch is data movement the compiler
    can see, not Python control flow;
  - inside ``shard_map`` each device runs the classic GPipe schedule as a
    ``lax.scan`` over S + M - 1 ticks: compute its stage, then ``ppermute``
    the activation ring-forward one hop. Bubble overhead is the usual
    (S-1)/(S+M-1); activations stream over ICI with compute/comm overlap;
  - backward is jax autodiff through the scan (ppermute transposes to the
    reverse permute), so training needs no hand-written schedule.

Requires a homogeneous stage signature (activation shape preserved), the
transformer-stack case; embed/head run replicated outside the pipelined
region.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: per-device
    branches on axis_index are intentionally device-varying."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)

__all__ = ["pipeline_apply", "stack_stage_params", "stage_sharding"]


def stack_stage_params(per_stage_params):
    """[pytree_stage0, pytree_stage1, ...] -> one pytree with leading stage
    axis (the layout ``pipeline_apply`` consumes)."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def stage_sharding(mesh: Mesh, params_stacked, axis: str = "pp"):
    """NamedSharding pytree: stage axis over ``axis``, rest replicated."""
    def one(leaf):
        return NamedSharding(mesh, P(axis, *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(one, params_stacked)


def pipeline_apply(stage_fn: Callable, params_stacked, x, mesh: Mesh,
                   axis: str = "pp", num_microbatches: int = None):
    """Run ``x`` through S pipelined stages of ``stage_fn``.

    stage_fn(stage_params, act) -> act', with act' shaped like act.
    params_stacked: pytree whose leaves have leading dim S == mesh.shape[axis].
    x: [B, ...] batch; split into M microbatches along dim 0.
    Returns [B, ...] output of the last stage.
    """
    S = mesh.shape[axis]
    M = num_microbatches or S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])

    def per_device(params_local, xs_full):
        # params_local: stage leaves [1, ...] (this device's stage)
        p_mine = jax.tree_util.tree_map(lambda l: l[0], params_local)
        idx = lax.axis_index(axis)
        T = S + M - 1
        zero = jnp.zeros_like(xs_full[0])
        ys0 = jnp.zeros_like(xs_full)

        def tick(carry, t):
            act_in, ys = carry
            # stage 0 ingests microbatch t (clamped select keeps shapes static)
            feed = lax.dynamic_index_in_dim(xs_full, jnp.clip(t, 0, M - 1),
                                            keepdims=False)
            act = jnp.where(idx == 0, jnp.where(t < M, feed, zero), act_in)
            out = stage_fn(p_mine, act)
            # last stage banks its output at position t-(S-1) when valid
            slot = jnp.clip(t - (S - 1), 0, M - 1)
            bank = lax.dynamic_update_index_in_dim(ys, out, slot, axis=0)
            take = jnp.logical_and(idx == S - 1,
                                   jnp.logical_and(t >= S - 1, t < S - 1 + M))
            ys = jnp.where(take, bank, ys)
            # ring-forward one hop for the next tick
            nxt = lax.ppermute(out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, ys), None

        (_, ys), _ = lax.scan(tick, (zero, ys0), jnp.arange(T))
        # every device carries a ys buffer; only stage S-1's is real. psum
        # after masking broadcasts it (cheap at [M, mb, ...] on ICI; keeps
        # the out_spec replicated so the caller needn't know the pp layout).
        ys = jnp.where(idx == S - 1, ys, jnp.zeros_like(ys))
        return lax.psum(ys, axis)

    in_specs = (jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), params_stacked), P())
    out = shard_map(per_device, mesh=mesh, in_specs=in_specs,
                    out_specs=P())(params_stacked, xs)
    return out.reshape(B, *x.shape[1:])
