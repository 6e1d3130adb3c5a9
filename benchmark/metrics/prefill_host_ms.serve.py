"""The host's own part of a prefill, by the program's ``prefill`` records of
the window: the phases ``mx.gen.prefill.pages`` (checks, reclaim, page
allocation in every pool group, the padded prompt and the row's table) +
``.index`` (the rows' books and the prefix cache's insert), mean over the
prefills that ran to their end, in ms. ``.dispatch`` and ``.read`` (the
arguments' hand-over, the call and the wait for the first token) are the
device's and the runtime's."""
from benchmark.serverecords import PREFILL, mean_ms, window_records

LAYER, UNIT, MOVES = "engine", "ms", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no ``prefill`` records."""
    phases = [r.phase_ns() for r in window_records(run, "prefill")]
    return mean_ms(p[PREFILL + ".pages"] + p[PREFILL + ".index"]
                   for p in phases if PREFILL + ".index" in p)
