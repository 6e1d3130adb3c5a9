"""How late the load generator handed requests over: hand-off time minus
due time, the generator thread's own delay."""
from benchmark.harness import percentile
from benchmark.records import window_requests

LAYER, UNIT, MOVES = "load generator", "ms", "serve_tokens_per_s"


def read(run):
    late = [r["handoff_t"] - (run["origin"] + r["due"])
            for r in window_requests(run)]
    return 1e3 * percentile(late, 99) if late else None
