"""Summed prefill service time over the window's length."""
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    if run["kind"] != "serve":
        return None
    return 100.0 * sum(s["prefill_s"] for s in window_steps(run)) / run["window_s"]
