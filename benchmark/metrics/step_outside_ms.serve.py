"""The caller's share of the host's turn-round: the time from the end of one
``serve_step`` record to the start of the next, over successive records of
the window that both ran a decode step (the mark ``mx.gen.step.decode``), the
first leaving a row active (so the caller steps again at once and waits for
no arrival): ``benchmark/serve.py``'s books and ``_submit_due`` between two
``batcher.step()``. The mean, in ms."""
from benchmark.serverecords import (STEP, end_ns, mark_ns, mean_ms,
                                     window_records)

LAYER, UNIT, MOVES = "load generator", "ms", "serve_tokens_per_s"


def _rows_left(record):
    c = record.counts or {}
    return c.get("active", 0) + c.get("admitted", 0) - c.get("finished", 0)


def read(run):
    """None where the program keeps no ``serve_step`` records or the window
    holds no such pair."""
    records = window_records(run, "serve_step")
    return mean_ms(b.t0_ns - end_ns(a) for a, b in zip(records, records[1:])
                   if mark_ns(a, STEP + ".decode") is not None
                   and mark_ns(b, STEP + ".decode") is not None
                   and _rows_left(a) > 0)
