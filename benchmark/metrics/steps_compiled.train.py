"""Calls of ``TrainStep.__call__`` after the process's first that lowered or
compiled a program, by the step record's always-on flag. 0 is the sound
reading."""
from benchmark.steprecords import train_records

LAYER, UNIT, MOVES = "train step", "steps", "train_tokens_per_s"


def read(run):
    records = train_records()
    if run["kind"] != "train" or not records:
        return None
    return sum(r.compiled for r in records[1:])
