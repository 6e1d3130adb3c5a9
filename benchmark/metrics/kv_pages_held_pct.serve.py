"""Pages in use over the pool's pages, mean over the window's steps."""
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return 100.0 * sum(s["pages_in_use"] for s in steps) / (
        len(steps) * run["num_pages"])
