"""What the readers of the serving step's own records share (PR 38): the
records that ``ContinuousBatcher.step`` (loop ``serve_step``),
``GenerationEngine.prefill`` (``prefill``) and ``decode_step``
(``decode_step``) wrote in this process
(``mxnet_tpu.observability.step_records(loop)``: a bounded ring a loop, so a
long window keeps each loop's last 4,096), cut to the run's window, and the
arithmetic over their marks. A program from before a record existed has
none of it, and every reader then returns None."""
from __future__ import annotations

import bisect

STEP, PREFILL, DECODE = "mx.gen.step", "mx.gen.prefill", "mx.gen.decode"


def window_records(run, loop):
    """The process's records of ``loop`` that began inside the run's window,
    oldest first ([] where the program keeps none)."""
    from mxnet_tpu import observability as obs

    read = getattr(obs, "step_records", None)
    if read is None or run.get("kind") != "serve" or not run.get("window"):
        return []
    lo, hi = run["window"]  # perf_counter seconds, the records' own clock
    return [r for r in read(loop) if lo <= 1e-9 * r.t0_ns < hi]


def end_ns(record):
    """Where the record's last span ended."""
    return record.t0_ns + record.duration_ns


def mark_ns(record, name):
    """The end of the record's first span ``name`` (None where it ran
    none)."""
    return next((end for span, end in record.marks if span == name), None)


def mean_ms(values_ns):
    """The mean of nanoseconds, in ms (None of nothing)."""
    values_ns = list(values_ns)
    return 1e-6 * sum(values_ns) / len(values_ns) if values_ns else None


def inside_ns(inner, starts, lo_ns, hi_ns):
    """Summed durations of the records of ``inner`` (oldest first, their
    ``t0_ns`` in ``starts``) that began in ``[lo_ns, hi_ns)``."""
    return sum(r.duration_ns for r in
               inner[bisect.bisect_left(starts, lo_ns):
                     bisect.bisect_left(starts, hi_ns)])
