"""The share of prefilled positions that are padding, by the counts of the
program's ``prefill`` records of the window: 100 x (1 - sum of ``suffix`` /
sum of ``bucket``), the tokens a prefill had to compute over the length of
the program that computed them. A bucket ladder in powers of two pads a
quarter on average; a mix whose prompts cluster just above a bucket's edge
pads near half."""
from benchmark.serverecords import window_records

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no ``prefill`` records."""
    counts = [r.counts for r in window_records(run, "prefill") if r.counts]
    bucket = sum(c["bucket"] for c in counts)
    return 100.0 * (1.0 - sum(c["suffix"] for c in counts) / bucket) \
        if bucket else None
