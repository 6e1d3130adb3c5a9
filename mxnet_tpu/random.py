"""RNG: ``mx.random.seed`` semantics over jax threefry keys.

The reference keeps per-device counter-based generator state
(``src/common/random_generator.h``) seeded by ``mx.random.seed``. The TPU
design is functional: a process-global key is split on every draw in eager
mode, and *inside a jit trace* draws split deterministically from a key that
the staged computation receives as an argument (so compiled functions stay
pure and every invocation can be fed fresh randomness).

Resource-manager stance (reference ``src/resource.cc``, the other half of
``ResourceRequest``): the reference hands ops two per-device resources —
``kRandom`` (generator state) and ``kTempSpace`` (scratch workspace for
reductions/cuDNN algo workspaces). On TPU, **kTempSpace is deliberately
deleted**: XLA's buffer assignment allocates and reuses every intermediate/
scratch buffer inside the compiled program, so there is nothing for the
framework to pool or hand out — ops never see raw workspace. kRandom is
THIS module. The host-side analog of pooled scratch (input-pipeline staging
buffers) lives in the native StoragePool (``native/src/runtime.cc``).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

__all__ = ["seed", "next_key", "trace_key_scope", "uniform", "normal", "randint"]


class _KeyState(threading.local):
    """Key creation is lazy: materialising a PRNG key initialises the jax
    backend, and importing the library must not claim the chip (host-side
    tools like im2rec import mxnet_tpu without ever touching the device)."""

    def __init__(self):
        self._key = None
        # Inside a jit trace: (traced base key, split counter) or None.
        self.trace = None

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(0)
        return self._key

    @key.setter
    def key(self, v):
        self._key = v


_STATE = _KeyState()


def seed(seed_state: int, ctx=None):  # ctx kept for API compat, placement is moot
    """Reset the global generator (analog of ``mx.random.seed``)."""
    _STATE.key = jax.random.key(int(seed_state))
    _STATE.trace = None


def next_key():
    """Return a fresh PRNG key; safe both eagerly and under tracing."""
    if _STATE.trace is not None:
        base, counter = _STATE.trace
        _STATE.trace = (base, counter + 1)
        return jax.random.fold_in(base, counter)
    if isinstance(_STATE.key, jax.core.Tracer):
        # A leaked tracer from a previous trace scope; re-seed defensively.
        _STATE.key = jax.random.key(0)
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


class trace_key_scope:
    """Bind RNG draws under a trace to ``base_key`` (used by hybridize/jit).
    ``self.uses`` reports how many draws happened — hybridize uses it to skip
    global key consumption for deterministic programs."""

    def __init__(self, base_key):
        self.base_key = base_key
        self.uses = 0

    def __enter__(self):
        self._saved = _STATE.trace
        _STATE.trace = (self.base_key, 0)
        return self

    def __exit__(self, *exc):
        self.uses = _STATE.trace[1] if _STATE.trace is not None else 0
        _STATE.trace = self._saved


# Convenience samplers returning raw jax arrays (the NDArray-facing versions
# live in the op registry / mx.nd.random namespace).
def uniform(low=0.0, high=1.0, shape=(), dtype=jnp.float32):
    return jax.random.uniform(next_key(), shape, dtype, low, high)


def normal(loc=0.0, scale=1.0, shape=(), dtype=jnp.float32):
    return jax.random.normal(next_key(), shape, dtype) * scale + loc


def randint(low, high, shape=(), dtype=jnp.int32):
    return jax.random.randint(next_key(), shape, low, high, dtype)
