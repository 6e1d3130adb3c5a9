"""Device mesh construction.

Axes follow the scaling-book convention: ``dp`` (data), ``fsdp`` (optional
param/optimizer sharding on the data axis), ``tp`` (tensor/model), ``sp``
(sequence/context), ``pp`` (pipeline stages), ``ep`` (experts). A config
names the axes it uses; unused axes have size 1 and cost nothing.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["AXES", "MeshConfig", "make_mesh", "local_mesh", "refit_config"]

logger = logging.getLogger("mxnet_tpu.parallel")

# the axis vocabulary is owned by the declarative layout spec
# (parallel.layout.AXES — docs/PARALLELISM.md); re-exported here for the
# existing mesh-level callers
from .layout import AXES  # noqa: E402


@dataclasses.dataclass
class MeshConfig:
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1
    ep: int = 1

    def sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXES)

    @property
    def total(self) -> int:
        return math.prod(self.sizes())

    @staticmethod
    def auto(n_devices: int, tp: int = 1, sp: int = 1) -> "MeshConfig":
        """All leftover devices go to dp (the ResNet/BERT DP default)."""
        rest = n_devices // (tp * sp)
        return MeshConfig(dp=rest, tp=tp, sp=sp)


def make_mesh(config: Optional[MeshConfig] = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig(dp=len(devices))
    if config.total < len(devices):
        # a mesh smaller than the host takes the FIRST devices in jax's
        # order; say so — the rest of the host's chips sit idle
        logger.info("mesh %s uses %d of %d visible devices: %s", config,
                    config.total, len(devices), devices[: config.total])
        devices = devices[: config.total]
    if config.total != len(devices):
        raise ValueError(f"mesh {config} needs {config.total} devices, "
                         f"got {len(devices)}")
    arr = np.asarray(devices).reshape(config.sizes())
    return Mesh(arr, AXES)


def local_mesh(n: Optional[int] = None, **axis_sizes) -> Mesh:
    """Mesh over the first n local devices (test/dry-run helper)."""
    devs = jax.devices()[: n or len(jax.devices())]
    cfg = MeshConfig(**axis_sizes) if axis_sizes else MeshConfig(dp=len(devs))
    return make_mesh(cfg, devs)


def refit_config(config: MeshConfig, n_devices: int) -> MeshConfig:
    """Scale a mesh config to a new device count (elastic re-formation).

    The re-formation rule: world-size changes resize the *data* axes only
    (``dp``/``fsdp`` — state along them is resharded from the checkpoint
    manifest), while the model axes (``tp``/``sp``/``pp``/``ep``) encode
    how the network is cut up and must survive unchanged — a world that
    can't hold them is an error, not a silent re-partition.

    The data capacity goes to ``fsdp`` when the old config sharded state
    there (keeping the ZeRO layout, at the new width), else to ``dp``.

    The re-formation rule itself lives on the declarative spec
    (:meth:`~mxnet_tpu.parallel.layout.Layout.refit`) — this wrapper
    keeps the mesh-level calling convention and delegates, so elastic
    code and layout-first code can never disagree about what survives a
    world-size change.
    """
    from .layout import Layout

    refitted = Layout(**{a: getattr(config, a) for a in AXES}) \
        .refit(n_devices)
    return MeshConfig(**refitted.axes)
