"""The recurrent state's bytes as a share of the bytes a decode step has to
move (``gdn_state_bytes`` over ``decode_step_bytes`` of the configuration's
reference: every weight once, the keys and values of the positions held,
the state of the rows that decode read and written), by the program's own
count of the rows whose state a step advanced (``state_rows`` in its decode
step records, an entry per linear layer) and the positions the rows held,
means over the window's decode steps. It says whether the state still is
the share of a step the cell was shaped for when the traffic or the knee
moves: it grows with the rows that decode and not with their length.
Nothing to read where the reference counts no such bytes or the program
keeps no such count."""
from benchmark.decoderecords import decode_counts
from benchmark.harness import reference_for
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    if run["kind"] != "serve":
        return None
    ref = reference_for(run["config"])
    rows = [sum(c) / len(c) for c in decode_counts(run, "state_rows") if c]
    steps = [s for s in window_steps(run) if s["decoded_rows"]]
    if not hasattr(ref, "gdn_state_bytes") or not rows or not steps:
        return None
    rows = sum(rows) / len(rows)
    held = sum(s["held_positions"] for s in steps) / len(steps)
    return 100.0 * ref.gdn_state_bytes(run["config"], rows) \
        / ref.decode_step_bytes(run["config"], held, rows=rows)
