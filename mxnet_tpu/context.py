"""Device/context model over jax devices.

Replaces the reference's ``Context{kCPU,kGPU,kCPUPinned}`` + device-id model
(``include/mxnet/base.h``, ``python/mxnet/context.py``). On TPU there is no
pinned-host or stream concept to expose: a Context names a jax device, and
placement happens via ``jax.device_put`` / shardings rather than per-op stream
dispatch. ``mx.gpu()`` is kept as a *compat alias* for the accelerator so
reference training scripts run unchanged.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]

_DEVTYPE_COMPAT = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 2}


class Context:
    """A named device. ``Context('tpu', 0)`` == first TPU chip.

    ``device_typeid`` keeps the MXNet integer encoding so serialized contexts
    and ``ctx.device_typeid`` probes keep working.
    """

    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        device_type = device_type.lower()
        if device_type not in _DEVTYPE_COMPAT:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- jax interop ---------------------------------------------------------
    @property
    def jax_device(self):
        if self.device_type.startswith("cpu"):
            devs = jax.devices("cpu") if _has_platform("cpu") else jax.devices()
        else:
            devs = _accelerator_devices()
            if self.device_id >= len(devs):
                raise RuntimeError(
                    f"{self!r} names accelerator {self.device_id}, but jax "
                    f"sees {len(devs)} (devices: "
                    f"{[d.platform for d in jax.devices()]})")
            return devs[self.device_id]
        return devs[self.device_id % len(devs)]

    @property
    def device_typeid(self) -> int:
        return _DEVTYPE_COMPAT[self.device_type]

    # -- context manager (``with mx.tpu(0):``) -------------------------------
    def __enter__(self):
        stack = getattr(Context._tls, "stack", None)
        if stack is None:
            stack = Context._tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and other.device_type == self.device_type
            and other.device_id == self.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"


def _has_platform(name: str) -> bool:
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    for platform in ("tpu", "gpu"):
        if _has_platform(platform):
            return jax.devices(platform)
    return []


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias: reference scripts say ``mx.gpu(i)``; here it names TPU chip i."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def current_context() -> Context:
    stack = getattr(Context._tls, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)
