"""What the readers of the program's own step record share: the records that
``TrainStep.__call__`` wrote in this process (``mxnet_tpu.observability.
step_records``, a bounded ring that outlives the step object), and medians
over them. A program from before the record existed has none, and every
reader then returns None."""
from __future__ import annotations

import statistics


def train_records():
    """The process's ``train_step`` records, oldest first ([] where the
    program keeps none)."""
    from mxnet_tpu import observability as obs

    read = getattr(obs, "step_records", None)
    return read("train_step") if read is not None else []


def median_ms(run, span=None):
    """The median, in ms, of ``span``'s duration over the process's records
    (of the whole call when ``span`` is None). The median needs no window
    bounds, which ``run`` does not carry: nearly all records are the
    window's, and the first steps and the compile are outliers it ignores."""
    if run["kind"] != "train":
        return None
    if span is None:
        values = [r.duration_ns for r in train_records()]
    else:
        phases = [r.phase_ns() for r in train_records()]
        values = [p[span] for p in phases if span in p]
    return 1e-6 * statistics.median(values) if values else None
