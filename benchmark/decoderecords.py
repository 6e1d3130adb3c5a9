"""What the readers of the serving program's own records share: the decode
step records that ``GenerationEngine`` wrote in this process
(``mxnet_tpu.observability.step_records("decode_step")``: a bounded ring,
so a long window keeps its last 4,096 steps), cut to the run's window, and
the counts the program handed back in them. A program from before they
existed has none, and every reader then returns None."""
from __future__ import annotations


def decode_records(run):
    """The process's ``decode_step`` records that began inside the run's
    window, oldest first ([] where the program keeps none)."""
    from mxnet_tpu import observability as obs

    read = getattr(obs, "step_records", None)
    if read is None or not run.get("window"):
        return []
    lo, hi = run["window"]  # perf_counter seconds, the records' own clock
    return [r for r in read("decode_step") if lo <= 1e-9 * r.t0_ns < hi]


def decode_counts(run, name):
    """``counts[name]`` of every decode step record of the window that has
    it (a list per step, an entry per expert layer)."""
    return [r.counts[name] for r in decode_records(run)
            if getattr(r, "counts", None) and name in r.counts]

