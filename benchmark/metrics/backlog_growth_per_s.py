"""Requests queued in the batcher at the window's end less at its start,
over the window: how far the offered load is above what the engine takes."""
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "scheduler", "requests/s", "serve_tokens_per_s"


def read(run):
    steps = window_steps(run)
    if len(steps) < 2:
        return None
    return (steps[-1]["pending"] - steps[0]["pending"]) / run["window_s"]
