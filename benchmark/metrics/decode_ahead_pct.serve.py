"""The share of the window's decode steps that dispatched the next step
ahead of reading their own tokens, by the program's own decode step records
(a step that did so has the host span ``mx.gen.decode.ahead`` among its
marks). Behind such a step the device goes straight on; behind any other it
waits for the host to read the tokens, keep its books, admit what is due
and dispatch again."""
from benchmark.decoderecords import decode_records

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    """None where the program keeps no decode step records."""
    records = decode_records(run)
    if run["kind"] != "serve" or not records:
        return None
    ahead = sum(any(name == "mx.gen.decode.ahead" for name, _ in r.marks)
                for r in records)
    return 100.0 * ahead / len(records)
