"""Host clock around the decode of ``batcher.step()`` (it ends in a blocking
read of the tokens): the step less the prefills admitted at its boundary."""
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "engine", "ms", "serve_tokens_per_s"


def read(run):
    times = [s["decode_s"] for s in window_steps(run) if s["decoded_rows"]]
    return 1e3 * sum(times) / len(times) if times else None
