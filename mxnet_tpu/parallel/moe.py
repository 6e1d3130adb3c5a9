"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

New capability relative to the reference (MXNet 1.x has no MoE / EP). The
TPU-native shape, after Switch-Transformer / mesh-tensorflow:

  - expert FFN weights carry a leading expert axis sharded ``P('ep', ...)``;
  - tokens are sharded over the same axis (dp == ep here, the common fused
    layout); inside ``shard_map`` each device top-1 routes its local tokens,
    packs them into per-expert capacity slots (einsum dispatch — dense
    one-hot math the MXU eats directly, no host-side sorting), and a pair of
    ``all_to_all`` collectives carries tokens to their expert's device and
    back over ICI;
  - dropped tokens (capacity overflow) pass through with zero contribution,
    the standard Switch behavior; an auxiliary load-balance loss
    (mean_prob · mean_assignment · E) is returned for the trainer to add.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import pallas_grouped_matmul as _grouped


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: per-device
    branches on axis_index are intentionally device-varying."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)

__all__ = ["moe_ffn", "init_moe_params", "moe_param_specs",
           "group_limited_topk", "held_expert_ffn", "held_prefix_rows"]


def init_moe_params(key, d_model: int, d_hidden: int, num_experts: int,
                    dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / math.sqrt(d_model)
    s2 = 1.0 / math.sqrt(d_hidden)
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), dtype) * s1,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden), dtype) * s1,
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model), dtype) * s2,
    }


def moe_param_specs(axis: str = "ep"):
    return {"gate": P(), "w1": P(axis, None, None), "w2": P(axis, None, None)}


def _route(x, gate_w, num_experts, capacity):
    """Top-1 switch routing for local tokens [n, d] -> dispatch/combine
    tensors + aux loss terms (all dense, static-shaped)."""
    logits = x @ gate_w                                   # [n, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                   # [n]
    prob = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)  # [n, E]
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0       # [n, E], -1 elsewhere
    pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)  # [n]
    keep = (pos_in_expert < capacity) & (pos_in_expert >= 0)
    pos_oh = jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)  # [n, C]
    # dispatch[n, e, c] = 1 iff token n goes to slot c of expert e
    dispatch = onehot[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
    combine = dispatch * prob[:, None, None]
    # Switch aux loss: E * sum_e mean_prob_e * mean_frac_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(onehot, axis=0)
    aux = num_experts * jnp.sum(me * ce)
    return dispatch, combine, aux


def moe_ffn(x, params, mesh: Mesh, axis: str = "ep",
            capacity_factor: float = 1.25,
            activation=jax.nn.gelu) -> Tuple[jax.Array, jax.Array]:
    """Apply the expert-parallel MoE FFN.

    x: [B, T, d] (token dims sharded over ``axis`` outside or replicated —
    shard_map partitions dim 0 here). Returns (out [B, T, d], aux_loss)."""
    E = params["w1"].shape[0]
    D = mesh.shape[axis]
    if E % D:
        raise ValueError(f"num_experts {E} must divide over mesh axis {axis}={D}")
    B, T, d = x.shape
    if B % D:
        raise ValueError(f"batch {B} must be divisible by ep={D}")
    n_local = (B // D) * T
    capacity = int(math.ceil(n_local / E * capacity_factor))

    def per_device(x_loc, gate_w, w1_loc, w2_loc):
        # x_loc [B/D, T, d]; w1_loc [E/D, d, h]; w2_loc [E/D, h, d]
        xt = x_loc.reshape(-1, d)                          # [n, d]
        dispatch, combine, aux = _route(xt, gate_w, E, capacity)
        # pack: [E, C, d] tokens bound for each (global) expert
        packed = jnp.einsum("nec,nd->ecd", dispatch, xt.astype(jnp.float32))
        # all_to_all: split expert dim over devices, gather sender shards ->
        # [E/D, D*C, d]: this device's experts, tokens from every peer
        recv = lax.all_to_all(packed, axis, split_axis=0, concat_axis=1,
                              tiled=True)
        h = activation(jnp.einsum("ecd,edh->ech", recv, w1_loc.astype(jnp.float32)))
        y = jnp.einsum("ech,ehd->ecd", h, w2_loc.astype(jnp.float32))
        # return trip: back to the senders' layout [E, C, d]
        back = lax.all_to_all(y, axis, split_axis=1, concat_axis=0, tiled=True)
        out = jnp.einsum("nec,ecd->nd", combine, back)
        return out.reshape(x_loc.shape).astype(x_loc.dtype), lax.pmean(aux, axis)

    out, aux = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P(), P(axis, None, None), P(axis, None, None)),
        out_specs=(P(axis), P()),
    )(x, params["gate"], params["w1"], params["w2"])
    return out, aux


# --------------------------------------------------------------------------
# group-limited top-k routing over held experts (no capacity, no drops)
# --------------------------------------------------------------------------
def group_limited_topk(probs, n_group: int, topk_group: int, top_k: int):
    """``group_limited_greedy`` routing: ``probs`` [n, E] in ``n_group``
    equal groups; a group's score is its largest probability; the best
    ``topk_group`` groups stay open; the ``top_k`` largest probabilities
    among them are the token's experts. Returns (weights [n, k] — the
    probabilities unchanged —, expert ids [n, k])."""
    n, e = probs.shape
    group_best = probs.reshape(n, n_group, e // n_group).max(axis=-1)
    _, keep = lax.top_k(group_best, topk_group)
    is_open = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(is_open, e // n_group, axis=1), probs, 0.0)
    return lax.top_k(masked, top_k)


def _ragged_products(x, w_gate, w_up, w_down, sizes, activation):
    """The three grouped products of the sorted pairs' rows ``x`` by
    ``lax.ragged_dot``: ``[pairs, d]`` float32, the rows of no group as the
    backend leaves them."""
    f32 = dict(preferred_element_type=jnp.float32)
    gate = lax.ragged_dot(x, w_gate, sizes, **f32)
    up = lax.ragged_dot(x, w_up, sizes, **f32)
    return lax.ragged_dot((_grouped.ACTIVATIONS[activation](gate) * up).astype(x.dtype),
                          w_down, sizes, **f32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_products(x, w_gate, w_up, w_down, sizes, activation):
    """:func:`_ragged_products` by the Pallas grouped-matmul kernel
    (``ops/pallas_grouped_matmul.py``); its gradient is the ragged path's."""
    return _grouped.grouped_glu_ffn(x, w_gate, w_up, w_down, sizes, activation)


def _kernel_products_fwd(x, w_gate, w_up, w_down, sizes, activation):
    return (_kernel_products(x, w_gate, w_up, w_down, sizes, activation),
            (x, w_gate, w_up, w_down, sizes))


def _kernel_products_bwd(activation, saved, ct):
    *operands, sizes = saved
    _, pull = jax.vjp(
        lambda *a: _ragged_products(*a, sizes, activation), *operands)
    return (*pull(ct), None)


_kernel_products.defvjp(_kernel_products_fwd, _kernel_products_bwd)


def held_prefix_rows(pairs: int, n_held: int, n_experts: int):
    """The static count of sorted pairs that :func:`held_expert_ffn`'s round
    about the grouped products walks when a call's held pairs fit it, or
    None where it always walks all ``pairs``: four times the even share of
    ``n_held`` of ``n_experts`` experts, rounded up to whole row tiles of
    the grouped kernel; None where that is not under ``pairs`` (every
    expert held, a quarter of them or more)."""
    rows = _grouped.whole_row_tiles(-(-4 * pairs * n_held // n_experts))
    return rows if rows < pairs else None


def held_expert_ffn(h, router_w, w_gate, w_up, w_down, *, held_experts,
                    n_group: int = 1, topk_group: int = 1, top_k: int,
                    scale: float = 1.0, norm_topk_prob: bool = False,
                    scoring: str = "softmax", router_bias=None,
                    router_h=None, activation: str = "silu",
                    count_hit: bool = False, count_route: bool = False):
    """What the experts held here add to an expert layer's output.

    ``h`` [n, d] are the (normed) tokens; ``router_w`` [E, d] routes over
    ALL ``E`` experts in float32, reading ``router_h`` [n, d] where that is
    given (a router placed elsewhere in the block than its experts: before
    attention, say) and ``h`` where not. ``activation`` is the gate's:
    ``silu`` (SwiGLU) or ``relu`` (ReGLU). ``scoring`` ``softmax`` chooses by
    :func:`group_limited_topk` over the probabilities; ``sigmoid`` scores
    each expert alone and chooses the ``top_k`` largest of score +
    ``router_bias`` [E] (a learned selection bias, ``noaux_tc``; no groups),
    the weights being the UNBIASED scores of the chosen;
    ``held_experts`` are the ids of the experts whose SwiGLU weights this
    chip holds, stacked in that order: ``w_gate``/``w_up`` [held, d, w],
    ``w_down`` [held, w, d]. Every (token, expert) pair whose expert is
    held is computed, whatever the load (no capacity, no dropped token):
    the pairs are sorted by expert, the held ones first, and each
    projection is one grouped product whose rows past the held pairs belong
    to no group: the Pallas kernel of ``ops/pallas_grouped_matmul.py``
    (gate and up in one call, row tiles that follow the groups) where its
    gate ``grouped_matmul_refusal`` lets it run (one TPU chip, widths of
    whole lane tiles), ``lax.ragged_dot`` elsewhere. Pairs routed to absent
    experts add nothing here: their chips add them.

    The gather before the products, the products, the mask and the
    scatter-add after them walk the held pairs, not every pair: the sort
    puts the held pairs first, so they are a PREFIX of the sorted pairs, and
    where few of the experts are held that round runs over a prefix of
    static length (:func:`held_prefix_rows`: four times the held experts'
    even share of the pairs, in whole row tiles: an eighth of the pairs with
    8 of 256 held, a fifth with 8 of 160). A call whose held pairs pass that
    length takes the whole length inside the same program (``lax.cond``), so
    nothing is dropped whatever the load; the rows the prefix leaves out are
    those the mask zeroes, and the held rows keep their order in the
    scatter-add, so both lengths give the same float32 sums bit for bit.
    Where the prefix is not shorter than the pairs (every expert held, a toy
    model) no branch is built and the program is the whole-length one.

    Returns ``(y [n, d], stats)``; ``stats`` are two int32 scalars, the
    pairs routed to held experts and the largest load of one, with
    ``count_hit`` a third: the held experts that drew a pair at all, and
    with ``count_route`` one more, ``[1]`` int32 (one entry a call): 1 where
    this call walked the whole length (always, where no branch was built),
    0 where the prefix. The ``moe_path_total{path, reason, route}`` counter
    says at trace time what was built: ``pallas_grouped``, or
    ``sorted_ragged_dot`` and the gate's first failed rule; ``route``
    ``prefix_or_whole`` where the branch is, ``whole`` where not.
    """
    from .. import observability as obs

    n, d = h.shape
    held = tuple(int(e) for e in held_experts)
    n_held, n_experts = len(held), router_w.shape[0]
    if activation not in _grouped.ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}: silu or relu")
    why = _grouped.grouped_matmul_refusal(n * top_k, d, w_gate.shape[2],
                                          h.dtype, w_gate.dtype)
    prefix = held_prefix_rows(n * top_k, n_held, n_experts)
    obs.counter("moe_path_total").inc(                      # trace time
        path="sorted_ragged_dot" if why else "pallas_grouped",
        reason=why or "", route="prefix_or_whole" if prefix else "whole")
    with jax.named_scope("router"):
        logits = jnp.einsum("nd,ed->ne",
                            (h if router_h is None else router_h).astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            score = jax.nn.sigmoid(logits)
            chosen_by = score if router_bias is None else \
                score + router_bias.astype(jnp.float32)[None, :]
            _, ids = lax.top_k(chosen_by, top_k)
            weights = jnp.take_along_axis(score, ids, axis=-1)
        elif scoring == "softmax":
            weights, ids = group_limited_topk(
                jax.nn.softmax(logits, axis=-1), n_group, topk_group, top_k)
        else:
            raise ValueError(f"unknown scoring {scoring!r}: softmax or sigmoid")
        if norm_topk_prob:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
        weights = weights * scale
        # slot of each pair's expert among the held ones; n_held = absent
        slot_of = jnp.full((n_experts,), n_held, jnp.int32).at[
            jnp.asarray(held, jnp.int32)].set(jnp.arange(n_held, dtype=jnp.int32))
        slot = slot_of[ids].reshape(-1)                        # [n * k]
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
        token = order // top_k
        is_held = slot[order] < n_held
        pair_w = weights.reshape(-1)[order]
    products = _ragged_products if why else _kernel_products

    def held_part(rows=None):
        """What the first ``rows`` sorted pairs add (default: all of them),
        ``[n, d]`` float32."""
        at, mask, w = (token, is_held, pair_w) if rows is None else \
            (token[:rows], is_held[:rows], pair_w[:rows])
        y = products(h[at], w_gate, w_up, w_down, sizes, activation)
        # rows past the held pairs belong to no group: a backend may leave
        # them unwritten (the TPU's does), so they are masked, not weighted 0
        return jnp.zeros((n, d), jnp.float32).at[at].add(
            jnp.where(mask[:, None], y * w[:, None], 0.0))

    with jax.named_scope("experts"):
        if prefix:
            # (the held pairs are summed again for the stats below, where the
            # whole-length program has always summed them)
            whole = sizes.sum() > prefix
            out = lax.cond(whole, held_part, lambda: held_part(prefix))
        else:   # the whole length is the one there is
            whole, out = True, held_part()
    stats = (sizes.sum(), sizes.max())
    if count_hit:
        stats += ((sizes > 0).sum().astype(jnp.int32),)
    if count_route:
        stats += (jnp.asarray(whole, jnp.int32).reshape(1),)
    return out.astype(h.dtype), stats
