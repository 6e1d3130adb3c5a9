"""KVStore facade (reference: ``src/kvstore/`` + ``python/mxnet/kvstore/``).

Design stance (SURVEY §5.8): the *compiler is the communication library*.
  - ``local`` / ``device``: single-controller — a jax.Array is one logical
    tensor across all chips of the mesh, so push/pull reduce to in-place
    accumulate and copy; cross-chip reduction happens inside compiled
    programs as GSPMD-inserted all-reduces over ICI (not here).
  - ``dist_sync`` / ``dist_async``: multi-process — push performs a psum
    across ``jax.distributed`` processes via a tiny compiled collective
    (DCN), replacing ps-lite's ZMQ parameter server; there is no server
    role — state stays sharded with the workers.
  - ``nccl``: alias of ``device`` (no NCCL anywhere in this build).

``Trainer`` is the blessed path; raw KVStore is kept correct but simple.
"""
from __future__ import annotations

from typing import Dict, Optional

import time

import jax
import jax.numpy as jnp

from . import observability as _obs
from .base import MXNetError
from .ndarray import NDArray
from .resilience import faults, retry
from .resilience.integrity import atomic_file_write

__all__ = ["KVStore", "create"]


class KVStore:
    def __init__(self, kv_type="local"):
        self.type = kv_type
        self._store: Dict = {}
        self._updater = None
        self._optimizer = None
        self._compression = None
        self._residual: Dict = {}
        self.is_distributed = kv_type.startswith("dist")
        self._num_workers = 1
        if self.is_distributed:
            self._num_workers = jax.process_count()

    # -- core API ------------------------------------------------------------
    def init(self, key, value):
        from .ndarray.sparse import BaseSparseNDArray

        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            self._store[k] = v.copy() if isinstance(v, BaseSparseNDArray) else NDArray(jnp.asarray(v._data))

    def push(self, key, value, priority=0):
        from .ndarray import sparse as _sp

        keys, values = self._normalize(key, value)
        if _obs.enabled():
            _obs.counter("kv_push_total").inc(len(keys), type=self.type)
        for k, v in zip(keys, values):
            # row_sparse pushes stay sparse end-to-end so the optimizer's
            # lazy row update path triggers (reference: KVStoreLocal::PushImpl
            # rsp branch); dist/compression paths densify explicitly.
            if isinstance(v, (list, tuple)) and v and isinstance(v[0], _sp.RowSparseNDArray):
                agg_sp = v[0]
                for x in v[1:]:
                    agg_sp = _sp.add(agg_sp, x)
                v = agg_sp
            if isinstance(v, _sp.RowSparseNDArray):
                if self.is_distributed or self._compression is not None:
                    v = v.todense()
                elif self._updater is not None:
                    self._updater(k, v, self._store[k])
                    continue
                else:
                    store = self._store[k]
                    if isinstance(store, _sp.RowSparseNDArray):
                        self._store[k] = _sp.add(store, v)
                    else:
                        store._data = store._data.at[v._aux[0]].add(
                            jnp.asarray(v._data, store._data.dtype))
                    continue
            if isinstance(v, (list, tuple)):
                # multi-device push: the reference reduced replicas here; a
                # jax.Array is already one logical value, so sum the list.
                agg = v[0]._data
                for x in v[1:]:
                    agg = agg + x._data
            else:
                agg = v._data
            if self._compression is not None:
                agg = self._compress(k, agg)
            if self.is_distributed:
                agg = _dcn_psum(agg)
            if self._updater is not None:
                grad = NDArray(agg)
                self._updater(k, grad, self._store[k])
            elif self.type == "dist_async" and k in self._store:
                # async semantics without an updater (reference:
                # KVStoreDistServer::DataHandleDefault, sync_mode_ == false):
                # each worker's push ACCUMULATES into the stored value as it
                # arrives — there is no per-step barrier, so pushes add
                # rather than replace. With an updater set, the updater call
                # above owns the merge instead (reference parity).
                self._store[k] = NDArray(self._store[k]._data + agg)
            else:
                # sync stores replace: the psum above already merged all
                # workers for this step
                self._store[k] = NDArray(agg)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        from .ndarray.sparse import BaseSparseNDArray

        keys, outs = self._normalize(key, out)
        if _obs.enabled():
            _obs.counter("kv_pull_total").inc(len(keys), type=self.type)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized in kvstore")
            val = self._store[k]
            if isinstance(val, BaseSparseNDArray):
                # reference semantics (KVStoreLocal::Pull): ignore_sparse=True
                # SKIPS sparse-stored keys — row_sparse_pull is the sanctioned
                # path; ignore_sparse=False makes the request an error
                if ignore_sparse:
                    continue
                raise MXNetError(f"key {k} has sparse storage; use row_sparse_pull")
            if isinstance(o, (list, tuple)):
                for x in o:
                    x._data = val._data
            else:
                o._data = val._data
        return None

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    def pushpull_batch(self, keys, values):
        """Batched dense push+pull-in-place: the whole list of values rides
        ONE cross-process collective instead of one per key (the batching
        bound the reference exposed as ``MXNET_KVSTORE_BIGARRAY_BOUND``,
        ``src/kvstore/kvstore_dist.h`` — here the batch is always whole).
        Falls back to per-key push/pull when sparse values, compression, or a
        server-side updater demand per-key semantics."""
        from .ndarray import sparse as _sp

        keys, values = self._normalize(keys, values)
        if (self._compression is not None or self._updater is not None
                or self.type == "dist_async"  # push ACCUMULATES into store
                or any(isinstance(v, (_sp.BaseSparseNDArray, list, tuple))
                       for v in values)):
            for k, v in zip(keys, values):
                self.push(k, v)
                self.pull(k, out=v)
            return
        raws = [v._data for v in values]
        if self.is_distributed:
            raws = _dcn_psum_batch(raws)
        for k, v, r in zip(keys, values, raws):
            self._store[k] = NDArray(r)
            v._data = r

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows in ``row_ids`` (reference:
        ``KVStoreLocal::PullRowSparse``, ``src/kvstore/kvstore_local.h``) —
        the embedding-table path where workers fetch just the rows their
        batch touches."""
        from .ndarray import sparse as _sp

        if row_ids is None:
            raise MXNetError("row_sparse_pull requires row_ids")
        keys, outs = self._normalize(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids] * len(keys)
        for k, o, rid in zip(keys, outs, rids):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized in kvstore")
            for x in (o if isinstance(o, (list, tuple)) else [o]):
                if not isinstance(x, _sp.RowSparseNDArray):
                    raise MXNetError("row_sparse_pull requires row_sparse out "
                                     "arrays (reference: KVStoreLocal::PullRowSparse)")
            val = self._store[k]
            if isinstance(val, _sp.RowSparseNDArray):
                got = _sp.retain(val, rid)
            else:
                # dense table: gather the requested rows directly (no
                # densify/compaction pass) — the per-step embedding hot path.
                # as_index_array guards the int64->int32 narrowing: a >2^31
                # row id must hard-error, never wrap to a valid-looking row
                from .base import as_index_array

                rid_raw = jnp.unique(jnp.asarray(as_index_array(
                    rid._data if isinstance(rid, NDArray) else rid,
                    "row_sparse_pull row_ids"), jnp.int32))
                got = _sp.RowSparseNDArray(val._data[rid_raw], (rid_raw,), val.shape)
            for x in (o if isinstance(o, (list, tuple)) else [o]):
                x._data, x._aux, x._shape = got._data, got._aux, got._shape
        return None

    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression with error-feedback residual
        (reference: ``src/kvstore/gradient_compression.cc``). On TPU the
        quantise→transport→dequantise pipeline collapses into one compiled
        quantise step before the DCN all-reduce: values beyond ±threshold
        send ±threshold, the rest send 0, and the quantisation error is
        carried in a per-key residual added to the next push."""
        params = dict(compression_params)
        ctype = params.get("type", "2bit")
        if ctype not in ("2bit", "none"):
            raise MXNetError(f"unsupported gradient compression type {ctype!r}")
        self._compression = None if ctype == "none" else {
            "type": "2bit", "threshold": float(params.get("threshold", 0.5))}
        self._residual.clear()

    def _compress(self, k, agg):
        thr = self._compression["threshold"]
        res = self._residual.get(k)
        acc = agg if res is None else agg + res
        q = jnp.where(acc >= thr, jnp.asarray(thr, acc.dtype),
                      jnp.where(acc <= -thr, jnp.asarray(-thr, acc.dtype),
                                jnp.zeros((), acc.dtype)))
        self._residual[k] = acc - q
        return q

    def set_optimizer(self, optimizer):
        from .optimizer import get_updater

        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    @property
    def rank(self):
        return jax.process_index() if self.is_distributed else 0

    @property
    def num_workers(self):
        return self._num_workers

    def barrier(self):
        if self.is_distributed:
            _dcn_psum(jnp.zeros(()))

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        payload = self._updater.get_states(dump_optimizer)

        def _write():
            faults.fire("kv.save_states")
            # temp file + os.replace: a crash mid-write leaves the previous
            # states file intact instead of a truncated one
            atomic_file_write(fname, payload)

        retry.retry_call(_write, site="kv.save_states")

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")

        def _read():
            faults.fire("kv.load_states")
            with open(fname, "rb") as f:
                return f.read()

        self._updater.set_states(retry.retry_call(_read, site="kv.load_states"))

    @staticmethod
    def _normalize(key, value):
        if isinstance(key, (list, tuple)):
            return list(key), list(value)
        return [key], [value]


def _transfer_dtype(dt):
    """Wire dtype for one array in the batched all-reduce: low-precision
    floats accumulate in f32 (safe_accumulation semantics); f64 and integer
    gradients keep their own dtype — funnelling everything through f32
    silently lost their precision."""
    import numpy as np

    dt = np.dtype(dt)
    if dt in (np.dtype(jnp.float16), np.dtype(jnp.bfloat16)):
        return np.dtype(jnp.float32)
    return dt


def _instrumented_collective(op, arrays, call):
    """Run ``call()`` (the retried DCN collective) with telemetry: latency
    histogram, bytes-moved and call counters, per-transfer-dtype bucket
    counts — the numbers XLA-side fusion makes invisible (DCN psum cost
    can dominate multi-host step time; without explicit timing it is
    indistinguishable from compute)."""
    import numpy as np

    if not _obs.enabled():
        return call()
    t0 = time.perf_counter()
    out = call()
    dt = time.perf_counter() - t0
    # bytes on the WIRE: the batched path widens low-precision floats to
    # their f32 transfer dtype before the allgather, so f16/bf16 leaves
    # move 4 bytes/element, not 2; the per-key path sends the source dtype
    wire_dtype = _transfer_dtype if op == "psum_batch" else (lambda d: d)
    nbytes = sum(int(a.size) * np.dtype(wire_dtype(a.dtype)).itemsize
                 for a in arrays)
    _obs.histogram("kv_psum_seconds", "DCN all-reduce wall clock",
                   unit="s").observe(dt, op=op)
    _obs.counter("kv_psum_calls_total").inc(op=op)
    _obs.counter("kv_psum_bytes_total", unit="bytes").inc(nbytes, op=op)
    if op == "psum_batch":
        buckets = {}
        for a in arrays:
            tdt = _transfer_dtype(a.dtype)
            buckets[str(tdt)] = buckets.get(str(tdt), 0) + 1
        for dtype, n in buckets.items():
            _obs.counter("kv_psum_dtype_buckets_total",
                         "arrays per transfer-dtype bucket in batched "
                         "all-reduces").inc(n, dtype=dtype)
    _obs.emit("kv_psum", op=op, seconds=round(dt, 6), bytes=nbytes,
              arrays=len(arrays))
    return out


def _dcn_psum_batch(raws):
    """Sum a LIST of arrays across processes with one allgather *per dtype
    bucket*: leaves sharing a transfer dtype are flattened into a single
    buffer, reduced, and split back — O(#dtypes) DCN round-trips per
    training step regardless of parameter count (one, for the typical
    uniform-precision model).

    Runs under the retry policy with fault site ``kv.dcn_psum_batch``; the
    gather closure is pure in its inputs, so a retried transient failure
    reproduces the exact same psum. Retry assumes collective failures are
    SYMMETRIC — a failed allgather raises on every participant, so all
    processes re-enter attempt N+1 together. An asymmetric failure (one
    host dead, the rest fine) is not retryable this way; that is the
    elastic-worker-recovery follow-up in ROADMAP.md.
    """
    if not raws or (jax.process_count() == 1 and not faults.armed()):
        return raws

    def _gather():
        faults.fire("kv.dcn_psum_batch")
        if jax.process_count() == 1:
            return list(raws)
        from jax.experimental import multihost_utils

        out = [None] * len(raws)
        buckets = {}  # transfer dtype -> indices into raws
        for i, r in enumerate(raws):
            buckets.setdefault(_transfer_dtype(r.dtype), []).append(i)
        for tdt, idxs in buckets.items():
            flat = [jnp.ravel(raws[i]).astype(tdt) for i in idxs]
            buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
            total = jnp.sum(multihost_utils.process_allgather(buf), axis=0)
            off = 0
            for i in idxs:
                n = raws[i].size
                out[i] = total[off:off + n].reshape(raws[i].shape).astype(raws[i].dtype)
                off += n
        return out

    return _instrumented_collective(
        "psum_batch", raws,
        lambda: retry.retry_call(_gather, site="kv.dcn_psum_batch"))


def _dcn_psum(x):
    """All-reduce across processes (multi-host DP over DCN). Gathers each
    process's host-local value and sums — the explicit-transfer shape of the
    reference's dist_sync push aggregation, minus the server role. Runs
    under the retry policy with fault site ``kv.dcn_psum``."""
    if jax.process_count() == 1 and not faults.armed():
        return x

    def _gather():
        faults.fire("kv.dcn_psum")
        if jax.process_count() == 1:
            return x
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(jnp.asarray(x))
        return jnp.sum(gathered, axis=0)

    return _instrumented_collective(
        "psum", [x],
        lambda: retry.retry_call(_gather, site="kv.dcn_psum"))


def create(name="local"):
    if name is None:
        return None
    if not isinstance(name, str):
        return name
    name = name.lower()
    if name in ("local", "device", "nccl", "local_allreduce_cpu", "local_allreduce_device"):
        return KVStore(name if name in ("local", "device") else "device")
    if name in ("dist_sync", "dist_async", "dist_device_sync", "dist"):
        return KVStore(name)
    if name in ("horovod",):
        return KVStore("device")
    raise MXNetError(f"unknown kvstore type {name!r}")
