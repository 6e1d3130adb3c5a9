"""The host's side of one ``TrainStep.__call__``: the median duration of the
program's own ``mx.train.step`` span over the process's step records."""
from benchmark.steprecords import median_ms

LAYER, UNIT, MOVES = "train step", "ms", "train_tokens_per_s"


def read(run):
    return median_ms(run)
