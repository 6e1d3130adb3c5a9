"""The share of the positions the window's prefill programs RAN that are
padding, by the counts of the program's ``prefill`` records: 100 x (1 - sum
of ``suffix`` / sum of the positions run). A program that walks its bucket in
stretches and runs only those its prompt reaches says how many positions
that was in its count ``positions_run`` (an entry a layer: the largest is
the program's); a record without it ran its whole ``bucket``, so there this
reads what ``prefill_pad_pct.serve`` reads. What is left where the
mechanism engages is the mean half stretch behind a prompt's end."""
from benchmark.serverecords import window_records

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no ``prefill`` records."""
    counts = [r.counts for r in window_records(run, "prefill") if r.counts]
    ran = sum(max(c.get("positions_run") or [c["bucket"]]) for c in counts)
    return 100.0 * (1.0 - sum(c["suffix"] for c in counts) / ran) \
        if ran else None
