"""Batch conversion and placement inside ``TrainStep.__call__``: the median
of the program's ``mx.train.input`` span over the process's step records."""
from benchmark.steprecords import median_ms

LAYER, UNIT, MOVES = "train step", "ms", "train_tokens_per_s"


def read(run):
    return median_ms(run, "mx.train.input")
