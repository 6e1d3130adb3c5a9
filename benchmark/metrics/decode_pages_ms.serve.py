"""The allocator's part of a decode step's host time, by the program's
``decode_step`` records of the window: the phase ``mx.gen.decode.pages``
(``_grow_pages`` over every row and every pool group, and the mask of the
tables to clear), which runs before a step's arguments are handed over and
so ahead of everything the device does for it; mean over the window's steps,
in ms. On a step dispatched ahead it hides behind the step before; on a step
dispatched by its own call the device waits for it (in a cell above its knee
that is every step behind an admission)."""
from benchmark.serverecords import DECODE, mean_ms, window_records

LAYER, UNIT, MOVES = "engine", "ms", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no ``decode_step`` records or marks no
    ``mx.gen.decode.pages`` in them."""
    phases = [r.phase_ns() for r in window_records(run, "decode_step")]
    return mean_ms(p[DECODE + ".pages"] for p in phases
                   if DECODE + ".pages" in p)
