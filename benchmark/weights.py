"""Weights from the seed, made on the device in one jitted call."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed, stream=0):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _leaf(key, i, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    return init[1] * jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32)


@functools.partial(jax.jit, static_argnames=("specs",))
def _make(key, specs):
    return {name: _leaf(key, i, shape, init)
            for i, (name, shape, init) in enumerate(specs)}


_make_one = jax.jit(_leaf, static_argnames=("shape", "init"))


def _hashable(specs):
    return tuple((n, tuple(s), i if isinstance(i, str) else tuple(i))
                 for n, s, i in specs)


def make_weights(specs, seed):
    """{name: float32 array} for ``specs`` (a reference's ``param_specs``)."""
    return _make(seed_key(seed), _hashable(specs))


def weights_by_leaf(specs, seed):
    """The same weights, one leaf at a time as (name, array): for a pass
    that must not hold a second copy of the model on the device."""
    key = seed_key(seed)
    for i, (name, shape, init) in enumerate(_hashable(specs)):
        yield name, _make_one(key, i, shape, init)
