"""Unified telemetry subsystem (docs/OBSERVABILITY.md).

Three pieces, one switch:

  - ``metrics``  — process-wide registry of counters / gauges / histograms
                   with labels; Prometheus-textfile + JSON exporters;
  - ``events``   — structured JSONL event log (one writer, run-id / host /
                   monotonic step envelope, size rotation);
  - ``span``     — times a region into the ``span_seconds`` histogram AND
                   forwards the name (+ current step) to
                   ``jax.profiler.TraceAnnotation`` so wall-clock metrics
                   and XPlane trace rows correlate by step id;
  - ``step_record`` — the always-on record of one hot-path call
                   (``TrainStep.__call__``): the spans opened inside it
                   write their end times into one tuple in a bounded
                   process-wide ring (:func:`step_records`), telemetry on
                   or off, without ever touching the device.

The switch: hot-path instrumentation (TrainStep, KVStore collectives, the
DataLoader) is gated on :func:`enabled` — a single module-global bool read,
so telemetry-off overhead is one branch per call site. Low-frequency sites
(retry attempts, checkpoint IO, profiler ``scope()``) always record into
the registry: they are rare, and their counters must be trustworthy even
when nobody asked for full telemetry (e.g. ``make chaos`` asserting retry
counts).

Enable via ``MXNET_TPU_TELEMETRY=1`` (+ ``MXNET_TPU_TELEMETRY_DIR``) or
programmatically::

    from mxnet_tpu import observability as obs
    obs.enable("/tmp/run42")        # events-h0.jsonl + metrics.json on exit
    ...train...
    obs.shutdown()                  # flush metrics.json / metrics.prom
"""
from __future__ import annotations

import atexit
import collections
import os
import threading
import time
import weakref
from contextlib import contextmanager
from typing import NamedTuple, Optional, Tuple

from . import events  # noqa: F401
from . import goodput  # noqa: F401
from . import metrics  # noqa: F401
from .events import emit, read_events, set_step  # noqa: F401
from .metrics import REGISTRY, counter, gauge, histogram  # noqa: F401
from . import profiling  # noqa: F401  (imports events/metrics above)
from . import fleet  # noqa: F401  (imports events/metrics/goodput/profiling)
from . import tracing  # noqa: F401  (imports metrics above)

__all__ = ["metrics", "events", "REGISTRY", "counter", "gauge", "histogram",
           "emit", "set_step", "read_events", "enabled", "enable", "disable",
           "shutdown", "span", "timed_region", "telemetry_dir",
           "throughput_delta", "fleet", "goodput", "profiling", "tracing",
           "StepRecord", "step_record", "step_records", "on_flush", "flush"]


def throughput_delta(prev):
    """samples/sec from the registry's step telemetry since ``prev``.

    The one shared throughput calculation every console reporter uses
    (``Speedometer``, estimator ``LoggingHandler``), so they can never
    drift from each other or from the exporters. Returns ``(speed, state)``
    — pass ``state`` back as ``prev`` on the next call; ``speed`` is None
    until two calls bracket new step telemetry.
    """
    c = REGISTRY.get("train_samples_total")
    h = REGISTRY.get("train_step_seconds")
    if c is None or h is None:
        return None, prev
    cur = (c.total(), h.total_sum())
    if prev is None:
        return None, cur
    ds, dt = cur[0] - prev[0], cur[1] - prev[1]
    return (ds / dt if ds > 0 and dt > 0 else None), cur

_enabled: Optional[bool] = None  # tri-state: None = not yet resolved from config
_dir: Optional[str] = None
_atexit_registered = False


def enabled() -> bool:
    """Fast gate for hot-path instrumentation (one global read after the
    first call resolves the ``MXNET_TPU_TELEMETRY`` config knob)."""
    global _enabled
    if _enabled is None:
        from .. import config

        if config.get("telemetry"):
            enable()
        else:
            _enabled = False
    return _enabled


def telemetry_dir() -> Optional[str]:
    return _dir


def enable(directory: Optional[str] = None, run_id: Optional[str] = None) -> str:
    """Turn telemetry on: open the per-host event log under ``directory``
    (default: the ``telemetry_dir`` config knob) and arrange for
    ``metrics.json`` / ``metrics.prom`` to be written at :func:`shutdown`
    (also registered atexit). Returns the run directory."""
    global _enabled, _dir, _atexit_registered
    from .. import config

    _dir = os.path.abspath(directory or config.get("telemetry_dir"))
    os.makedirs(_dir, exist_ok=True)
    host = events._host_index()
    events.LOG.configure(
        os.path.join(_dir, f"events-h{host}.jsonl"), run_id=run_id,
        rotate_bytes=config.get("telemetry_rotate_mb") * 1024 * 1024,
        keep_bytes=config.get("events_keep_bytes"))
    _enabled = True
    if not _atexit_registered:
        atexit.register(shutdown)
        _atexit_registered = True
    events.emit("telemetry_enabled", dir=_dir)
    # fleet view (docs/OBSERVABILITY.md "Fleet view"): when a shared fleet
    # directory is configured (MXNET_TPU_FLEET_DIR — the elastic supervisor
    # exports it), start the per-rank snapshot writer alongside telemetry
    fleet.ensure_snapshotter()
    return _dir


def disable() -> None:
    """Turn the hot-path gate off and close the event log (registry content
    is kept — counters survive an enable/disable cycle)."""
    global _enabled
    flush()
    _enabled = False
    events.LOG.close()


# telemetry that lags the hot path (TrainStep reads step n's loss some
# dispatches later, so that reading never stalls the device's queue) hands
# its "publish what is still held" method here; weak, so a registered
# object dies when its owner drops it
_flushers: list = []
_flushers_lock = threading.Lock()


def on_flush(method) -> None:
    """Register a bound method that :func:`flush` (and so
    :func:`shutdown` / :func:`disable`) calls to publish lagging
    telemetry. Held weakly."""
    with _flushers_lock:
        _flushers.append(weakref.WeakMethod(method))


def flush() -> None:
    """Publish every reading the hot path still holds back (docs/
    OBSERVABILITY.md "The lag of telemetry-on readings"): after it the
    registry and the event log are level with the steps dispatched. Blocks
    until those steps have run."""
    with _flushers_lock:
        live = [(ref, ref()) for ref in _flushers]
        _flushers[:] = [ref for ref, method in live if method is not None]
    for _, method in live:
        if method is not None:
            method()


def shutdown() -> None:
    """Flush exporters into the run directory and close the event log.
    Idempotent; registered atexit by :func:`enable`."""
    if _dir is None:
        return
    flush()
    # final fleet snapshot BEFORE the event log closes (the snapshot
    # copies the event files; a clean exit must land its tail)
    fleet.shutdown_snapshotter()
    host = events._host_index()
    suffix = f"-h{host}" if host else ""
    try:
        REGISTRY.write_json(os.path.join(_dir, f"metrics{suffix}.json"))
        REGISTRY.write_prometheus(os.path.join(_dir, f"metrics{suffix}.prom"))
    except OSError:
        pass
    events.LOG.close()


@contextmanager
def timed_region(metric_name: str, help: str, name: str, **labels):
    """Always-on core of :func:`span` (and ``profiler.scope``): time a
    region into ``metric_name``'s histogram under a
    ``jax.profiler.TraceAnnotation`` carrying the current step id.
    Exception-safe — the sample records even when the body raises."""
    import jax

    with jax.profiler.TraceAnnotation(name, step=events.current_step()):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            histogram(metric_name, help,
                      unit="s").observe(time.perf_counter() - t0, **labels)


# -- the step record ----------------------------------------------------------
class StepRecord(NamedTuple):
    """One hot-path call as the host saw it. Times are
    ``time.perf_counter_ns()``: ``t0_ns`` at the call's entry, and in
    ``marks`` the end of each span opened inside it, in order. The spans
    are contiguous by construction (one's end is the next one's start), so
    their durations sum to the call's."""

    #: "train_step" / "run_window" / "decode_step" / "serve_step" / "prefill"
    loop: str
    step: int                            # optimizer.num_update once it ran
    t0_ns: int
    marks: Tuple[Tuple[str, int], ...]   # (span name, end ns)
    compiled: bool                       # this call lowered or compiled
    #: what the program itself counted in this call and handed back with
    #: its result ({name: value}; a decode step's expert-layer loads)
    counts: Optional[dict] = None

    @property
    def duration_ns(self) -> int:
        return (self.marks[-1][1] if self.marks else self.t0_ns) - self.t0_ns

    def phase_ns(self) -> dict:
        """{span name: nanoseconds}, each measured from the mark before."""
        out, last = {}, self.t0_ns
        for name, end in self.marks:
            out[name] = out.get(name, 0) + end - last
            last = end
        return out


#: how many calls a loop's ring remembers (a 50 s benchmark window of
#: BERT-large is some 400 steps; at 1 ms a step this is still the last four
#: seconds)
STEP_RECORDS_KEPT = 4096
#: a ring a loop name: nothing a record of one loop does evicts another's (a
#: ``serve_step`` and a ``prefill`` record beside every ``decode_step`` one
#: would else halve what the decode records' readers see of a window)
_records: "dict[str, collections.deque[StepRecord]]" = {}
_records_lock = threading.Lock()  # a ring's creation; appends need none
_open = threading.local()  # .rec: the step_record this thread is inside
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        import jax

        _annotation = jax.profiler.TraceAnnotation
    return _annotation


def step_records(loop: Optional[str] = None) -> list:
    """The records of ``loop``'s ring, oldest first; of every loop, merged
    by ``t0_ns``, where none is named (a record that nests in another, as a
    ``prefill`` in a ``serve_step``, follows it). The rings belong to the
    process, not to the object that wrote them: they are read after a
    ``TrainStep`` is gone."""
    if loop is not None:
        return list(_records.get(loop, ()))
    return sorted((r for ring in list(_records.values()) for r in list(ring)),
                  key=lambda r: r.t0_ns)


class step_record:
    """Context manager around ONE hot-path call: opens the root span
    ``name`` (a ``TraceAnnotation`` carrying ``step``, free when no
    profiler session is open), collects the end time of every
    :func:`span` opened inside it, and appends a :class:`StepRecord` to
    the ring on exit, telemetry on or off. It reads clocks and nothing
    else: no device value is touched. ``rec.compiled = True`` marks a call
    that lowered or compiled a program."""

    __slots__ = ("loop", "step", "name", "t0", "marks", "compiled", "counts",
                 "_ann", "_outer")

    def __init__(self, loop: str, step: int, name: str = "mx.train.step"):
        self.loop, self.step, self.name = loop, int(step), name
        self.marks, self.compiled, self.counts = [], False, None

    @property
    def duration_ns(self) -> int:
        """Entry to the end of the last span closed so far."""
        return (self.marks[-1][1] if self.marks else self.t0) - self.t0

    def __enter__(self):
        self._ann = _trace_annotation()(self.name, step=self.step)
        self._ann.__enter__()
        self._outer = getattr(_open, "rec", None)
        _open.rec = self
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        _open.rec = self._outer
        self._ann.__exit__(*exc)
        ring = _records.get(self.loop)
        if ring is None:  # a loop's first record
            with _records_lock:
                ring = _records.setdefault(
                    self.loop, collections.deque(maxlen=STEP_RECORDS_KEPT))
        ring.append(StepRecord(self.loop, self.step, self.t0,
                               tuple(self.marks), self.compiled,
                               self.counts))
        return False


class span:
    """Time a region. Inside a :class:`step_record` (the train step's hot
    path) it is always on: a ``TraceAnnotation`` with the record's step id,
    so the region lies on the profiler's clock beside the device
    operations, and its end time in the record. Elsewhere it is a no-op
    (one bool check) when telemetry is off. With telemetry on, either way,
    it also times the region into ``span_seconds{span=name,...}``, so a slow
    span found in metrics can be located in the TensorBoard/Perfetto
    timeline (and vice versa)."""

    __slots__ = ("name", "labels", "_rec", "_ann", "_t0")

    def __init__(self, name: str, **labels):
        self.name, self.labels = name, labels

    def __enter__(self):
        rec = self._rec = getattr(_open, "rec", None)
        if rec is None and not enabled():
            self._ann = None
            return self
        if rec is None:
            step, self._t0 = events.current_step(), time.perf_counter_ns()
        else:
            # contiguous: what lies between two spans counts to the later
            step = rec.step
            self._t0 = rec.marks[-1][1] if rec.marks else rec.t0
        self._ann = _trace_annotation()(self.name, step=step)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is None:
            return False
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.marks.append((self.name, end))
        if enabled():
            histogram("span_seconds", "obs.span region wall-clock",
                      unit="s").observe((end - self._t0) * 1e-9,
                                        span=self.name, **self.labels)
        return False
