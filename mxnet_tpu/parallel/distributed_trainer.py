"""Horovod-style DistributedTrainer + multi-host bootstrap.

Reference: ``horovod.mxnet.DistributedTrainer`` wrapping MPI/NCCL ring
allreduce, and ``tools/launch.py`` exporting ``DMLC_*`` env for ps-lite
(SURVEY §2.3). Here bootstrap is ``jax.distributed.initialize`` (one line,
env-driven exactly like the DMLC vars) and gradient reduction is whatever
GSPMD emits for the mesh — including DCN collectives across hosts. The class
keeps the blessed ``DistributedTrainer`` name and per-process batch-size
semantics (scale by local batch; divide lr or not exactly as horovod did).
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax

from ..gluon.trainer import Trainer

__all__ = ["DistributedTrainer", "init", "shutdown", "rank", "size",
           "local_rank"]

_initialized = False


def init(coordinator_address: Optional[str] = None, num_processes: Optional[int] = None,
         process_id: Optional[int] = None, timeout: Optional[float] = None,
         retries: Optional[int] = None):
    """Multi-host bootstrap (replaces tools/launch.py + ps-lite scheduler).

    Env-var driven like the DMLC vars: MXNET_TPU_COORDINATOR, MXNET_TPU_NPROC,
    MXNET_TPU_PROCID (or the standard jax coordinator envs on TPU pods).

    The bootstrap is fault site ``dist.init`` and runs under the retry
    policy (``retries`` attempts, default the ``dist_init_retries`` knob;
    observable in ``retry_attempts_total{site="dist.init"}``): in an
    elastic re-formation a replacement worker routinely dials the new
    coordinator before its port is listening, which must back off and
    rejoin rather than hard-fail the generation. ``timeout`` bounds each
    attempt (jax's ``initialization_timeout``, seconds).
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MXNET_TPU_COORDINATOR")
    if coordinator_address is None:
        _initialized = True  # single process
        return
    if jax.distributed.is_initialized():
        _initialized = True  # someone (pod runtime, user) already bootstrapped
        return
    plats = (jax.config.jax_platforms or "").split(",")
    if "cpu" in plats:
        # multi-process on the CPU backend (the N-local-process CI shape)
        # needs an actual cross-process collectives impl; 'none' makes
        # every psum fail with "Multiprocess computations aren't
        # implemented". Must be set before the backend initializes.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from .. import config
    from ..resilience import faults, retry

    timeout = config.get("dist_init_timeout") if timeout is None else timeout
    kwargs = {}
    if timeout and timeout > 0:
        # jax takes whole seconds; a sub-second bound must round UP, not
        # truncate to an instant-fail 0-second window
        kwargs["initialization_timeout"] = max(1, math.ceil(timeout))

    # rank 0 may be passed explicitly: `or` would discard it for the (stale)
    # env var — after a re-formation the two legitimately disagree
    nproc = num_processes if num_processes is not None \
        else int(os.environ.get("MXNET_TPU_NPROC", "1"))
    pid = process_id if process_id is not None \
        else int(os.environ.get("MXNET_TPU_PROCID", "0"))

    def _bootstrap():
        faults.fire("dist.init")
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=nproc, process_id=pid, **kwargs)
        except Exception:
            _clear_half_bootstrap()
            raise

    policy = retry.RetryPolicy(
        max_attempts=retries if retries is not None
        else config.get("dist_init_retries"))
    retry.retry_call(_bootstrap, site="dist.init", policy=policy)
    _initialized = True
    # the event log memoizes the host index (jax.process_index costs tens
    # of µs per emit); a bootstrap that just changed this process's rank
    # must drop the stale memo
    from ..observability import events as _ev

    _ev._host_index_cache = None


def _clear_half_bootstrap() -> None:
    """Undo a *failed* bootstrap attempt so the next retry can re-dial.

    jax's ``State.initialize`` registers ``global_state.client`` (and rank
    0's coordinator service) BEFORE ``client.connect()`` — a timed-out dial
    leaves them set, every later attempt dies on "should only be called
    once", and ``jax.distributed.is_initialized()`` would report the failure
    as success. Clear the fields first (so the state is clean even when the
    handles refuse to shut down), then best-effort release the handles."""
    try:
        from jax._src import distributed as _jdist

        state = _jdist.global_state
        client, state.client = state.client, None
        service, state.service = state.service, None
        state.preemption_sync_manager = None
        for h in (client, service):
            if h is not None:
                try:
                    h.shutdown()
                except Exception:
                    pass
    except Exception:  # jax internals moved: fall back to the public path
        try:
            jax.distributed.shutdown()
        except Exception:
            pass


def shutdown() -> None:
    """Tear down the ``jax.distributed`` bootstrap so :func:`init` can
    re-form against a new coordinator/world (elastic re-formation). No-op
    when never initialized; single-process "initialized" state is also
    cleared."""
    global _initialized
    if jax.distributed.is_initialized():
        jax.distributed.shutdown()
    _initialized = False
    from ..observability import events as _ev

    _ev._host_index_cache = None


def rank() -> int:
    return jax.process_index()


def size() -> int:
    return jax.process_count()


def local_rank() -> int:
    """Rank within this host. jax has no first-class notion of it; honor the
    launcher envs (tools/launch.py exports MXNET_TPU_LOCAL_RANK, matching
    horovod's OMPI_COMM_WORLD_LOCAL_RANK convention)."""
    for var in ("MXNET_TPU_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_RANK",
                "LOCAL_RANK"):
        if var in os.environ:
            return int(os.environ[var])
    return 0


def local_size() -> int:
    for var in ("MXNET_TPU_LOCAL_SIZE", "OMPI_COMM_WORLD_LOCAL_SIZE",
                "LOCAL_WORLD_SIZE"):
        if var in os.environ:
            return int(os.environ[var])
    return 1


class DistributedTrainer(Trainer):
    """Data-parallel trainer across all processes/chips.

    With a single controller per host and GSPMD meshes, gradients from a
    globally-sharded batch are already mean-reduced by XLA inside backward;
    this subclass only rescales like horovod (grads averaged over world size
    when the loss is a per-process mean).
    """

    def __init__(self, params, optimizer, optimizer_params=None, kvstore=None,
                 gradient_predivide_factor=1.0):
        optimizer_params = dict(optimizer_params or {})
        super().__init__(params, optimizer, optimizer_params,
                         kvstore=kvstore or ("dist_sync" if size() > 1 else "device"))
        self._world = size()

    def step(self, batch_size, ignore_stale_grad=False):
        # batch_size is per-process (horovod convention): the cross-process
        # mean is applied by the kvstore psum + world division
        super().step(batch_size * self._world if self._kvstore is not None
                     and getattr(self._kvstore, "is_distributed", False) else batch_size,
                     ignore_stale_grad)
