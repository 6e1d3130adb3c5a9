"""Rows that decoded a token over the slots of the static batch, mean over
the window's decode steps."""
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "scheduler", "%", "serve_tokens_per_s"


def read(run):
    steps = [s for s in window_steps(run) if s["decoded_rows"]]
    if not steps:
        return None
    return 100.0 * sum(s["decoded_rows"] for s in steps) / (
        len(steps) * run["batch_size"])
