"""An observation, not a judgement: the 90th percentile of first-token time
minus due time, over the requests whose first token fell in the window.
Above the knee it grows with the queue; below it, 260 requests a window
spread it by 8% from run to run (PERF.md)."""
LAYER, UNIT, MOVES = "scheduler", "ms", "serve_tokens_per_s"


def read(run):
    return run.get("latency", {}).get("ttft_p90_ms")
