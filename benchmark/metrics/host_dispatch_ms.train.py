"""The jitted call inside ``TrainStep.__call__`` (flattening, enqueue, and
the wait when the runtime's queue is full): the median of the program's
``mx.train.dispatch`` span over the process's step records."""
from benchmark.steprecords import median_ms

LAYER, UNIT, MOVES = "train step", "ms", "train_tokens_per_s"


def read(run):
    return median_ms(run, "mx.train.dispatch")
