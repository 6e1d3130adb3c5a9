"""Runtime feature introspection (reference: ``src/libinfo.cc`` +
``python/mxnet/runtime.py`` — ``mx.runtime.Features()``)."""
from __future__ import annotations

import jax

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    return {
        "TPU": any(d.platform == "tpu" for d in jax.devices()),
        "XLA": True,
        "PALLAS": True,
        "BF16": True,
        "INT64_TENSOR_SIZE": True,
        "DIST_KVSTORE": True,
        "RECORDIO": True,
        "FLASH_ATTENTION": True,
        "RING_ATTENTION": True,
        # reference features intentionally absent on TPU:
        "CUDA": False,
        "CUDNN": False,
        "NCCL": False,
        "MKLDNN": False,
        "TENSORRT": False,
        "OPENCV": False,
    }


class Features(dict):
    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name):
        return self[name.upper()].enabled

    def __repr__(self):
        return "[" + ", ".join(repr(v) for v in self.values()) + "]"


def feature_list():
    return list(Features().values())
