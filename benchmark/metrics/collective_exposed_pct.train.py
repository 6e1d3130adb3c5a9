"""Device time in collectives during which no other operation ran on that
chip, over the traced window, averaged over the chips."""
from benchmark.records import trace_share

LAYER, UNIT, MOVES = "layout", "%", "train_tokens_per_s"


def read(run):
    if run["kind"] != "train" or run["chips"] < 2:
        return None
    return trace_share(run, "collective_exposed_s")
