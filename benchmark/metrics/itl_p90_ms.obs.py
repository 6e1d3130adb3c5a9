"""An observation, not a judgement: the 90th percentile of the gaps between
successive tokens of a request that end in the window: a decode step plus
the prefills admitted at its boundary."""
LAYER, UNIT, MOVES = "engine", "ms", "serve_tokens_per_s"


def read(run):
    return run.get("latency", {}).get("itl_p90_ms")
