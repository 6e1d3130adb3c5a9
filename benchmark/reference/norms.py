"""Norms by leaf, and the worst leaf's gap between two sets of them."""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def worst_leaf_gap(got, want):
    """The largest ``|got - want|`` over the leaves, each measured against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    floor = statistics.median(want.values())
    gap, leaf = max((abs(got[k] - want[k]) / max(want[k], floor), k)
                    for k in want)
    return gap, leaf
