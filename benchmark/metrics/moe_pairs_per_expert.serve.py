"""The (token, expert) pairs a held expert draws a decode step, by the
program's own counts in its decode step records (``moe_pairs_held`` over the
experts held, an entry per expert layer), mean over the window's decode
steps and the expert layers. It says how near the expert layer stands to the
load it is judged at: a deployment that holds a whole layer on one chip
sends an expert rows x experts per token / experts pairs a step (4.5 at 48
full rows of six over 64), and the grouped products' time means what it
would there only near that number."""
from benchmark.decoderecords import decode_counts

LAYER, UNIT, MOVES = "expert layer", "pairs", "serve_tokens_per_s"


def read(run):
    pairs = decode_counts(run, "moe_pairs_held")
    held = len(run["config"].get("held_experts", ()))
    if run["kind"] != "serve" or not pairs or not held:
        return None
    per_layer = [p / held for step in pairs for p in step]
    return sum(per_layer) / len(per_layer) if per_layer else None
