"""The batcher's own host time a step, by the program's ``serve_step``
records of the window: the phases ``mx.gen.step.sweep`` + ``.admit`` less the
``prefill`` records that began inside the step + ``.books`` + ``.tokens``
(everything of ``batcher.step()`` but the engine's prefills and its decode
step), mean over the window's steps, in ms."""
from benchmark.serverecords import (STEP, end_ns, inside_ns, mean_ms,
                                     window_records)

LAYER, UNIT, MOVES = "scheduler", "ms", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no ``serve_step`` records."""
    prefills = window_records(run, "prefill")
    starts = [p.t0_ns for p in prefills]
    own = []
    for r in window_records(run, "serve_step"):
        phases = r.phase_ns()
        own.append(sum(phases.get(STEP + p, 0)
                       for p in (".sweep", ".admit", ".books", ".tokens"))
                   - inside_ns(prefills, starts, r.t0_ns, end_ns(r)))
    return mean_ms(own)
