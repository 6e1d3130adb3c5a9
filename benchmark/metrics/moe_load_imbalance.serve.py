"""The largest load of one held expert over the mean load of the held
experts, by the program's own counts in its decode step records
(``moe_max_load`` and ``moe_pairs_held``, an entry per expert layer), mean
over the window's decode steps and the expert layers that routed anything
here. 1.0 is an even split; the grouped product's time follows the sum, a
deployment's slowest chip the largest."""
from benchmark.decoderecords import decode_counts

LAYER, UNIT, MOVES = "expert layer", "ratio", "serve_tokens_per_s"


def read(run):
    pairs = decode_counts(run, "moe_pairs_held")
    loads = decode_counts(run, "moe_max_load")
    held = len(run["config"].get("held_experts", ()))
    if run["kind"] != "serve" or not pairs or not held:
        return None
    ratios = [m * held / p for ps, ms in zip(pairs, loads)
              for p, m in zip(ps, ms) if p]
    return sum(ratios) / len(ratios) if ratios else None
