"""The readers of the program's own step record (``host_step_ms.train``,
``host_input_ms.train``, ``host_dispatch_ms.train``,
``steps_compiled.train``): each against a hand-made list of records, against
a program that keeps no record, and in the CPU rehearsal of the cell."""
import json

import pytest

from benchmark import harness
from mxnet_tpu import observability as obs

from benchmark_tiny import REPO, make_root, run_cell

READERS = ("host_step_ms.train", "host_input_ms.train",
           "host_dispatch_ms.train", "steps_compiled.train")
MS = 1_000_000


def record(step, t0, input_ms, args_ms, dispatch_ms, after_ms, compiled=False,
           loop="train_step"):
    marks, t = [], t0
    for name, ms in (("mx.train.input", input_ms), ("mx.train.args", args_ms),
                     ("mx.train.dispatch", dispatch_ms),
                     ("mx.train.after", after_ms)):
        t += int(ms * MS)
        marks.append((name, t))
    return obs.StepRecord(loop, step, t0, tuple(marks), compiled)


HAND_MADE = [
    record(1, 0, 5.0, 1.0, 9000.0, 2.0, compiled=True),   # the compile
    record(2, 10_000 * MS, 1.0, 0.5, 3.0, 0.5),
    record(3, 10_010 * MS, 2.0, 0.5, 130.0, 0.5),
    record(4, 10_150 * MS, 3.0, 0.5, 120.0, 0.5, compiled=True),
    record(5, 10_300 * MS, 4.0, 0.5, 125.0, 0.5),
    record(6, 10_450 * MS, 50.0, 50.0, 50.0, 50.0, loop="run_window"),
]
TRAIN = {"kind": "train"}


def read(name, run=TRAIN):
    return harness.load_reader(name, REPO).read(run)


@pytest.mark.parametrize("name,want", [
    # medians over the five train_step records; the window's is not one
    ("host_step_ms.train", 130.0),      # 9008, 5, 133, 124, 130
    ("host_input_ms.train", 3.0),       # 5, 1, 2, 3, 4
    ("host_dispatch_ms.train", 125.0),  # 9000, 3, 130, 120, 125
    ("steps_compiled.train", 1),        # after the first: step 4
])
def test_a_reader_against_hand_made_records(monkeypatch, name, want):
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [
        r for r in HAND_MADE if loop is None or r.loop == loop])
    assert read(name) == pytest.approx(want)
    assert read(name, {"kind": "serve"}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_where_there_is_nothing_to_read(monkeypatch, name):
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [])
    assert read(name) is None  # an empty ring
    monkeypatch.delattr(obs, "step_records")
    assert read(name) is None  # a program from before the record existed


@pytest.mark.parametrize("name", READERS)
def test_the_entry_declares_what_the_reader_does(name):
    # benchmark/parked.json holds no twin for the four-chip cell: a file the
    # benchmark already had is a `benchmark` PR's to edit (PERF.md section 7)
    reader = harness.load_reader(name, REPO)
    bench = harness.load_benchmark(REPO)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["bert_large_train_s128"]
    assert entry["better"] == "lower"
    assert (entry["layer"], entry["unit"], entry["moves"]) == \
        (reader.LAYER, reader.UNIT, reader.MOVES)
    assert entry["source"] == ("program_counter" if "compiled" in name
                               else "program_span")


def test_the_cells_rehearsal_reports_a_value_from_each(tmp_path):
    root = make_root(tmp_path)
    before = len(obs.step_records("train_step"))
    run, stdout = run_cell(root, "tiny_train", seconds=0.5, trace=1)
    line = json.loads(stdout.strip().splitlines()[-1])
    assert run["correct"] is True, stdout
    values = {name: line["metrics"][name]["value"] for name in READERS}
    # the cell's steps were all recorded: three checked, the warm-up, the
    # window and the traced slice, after the step object was deleted
    wrote = len(obs.step_records("train_step")) - before
    assert wrote >= min(run["steps"], obs.STEP_RECORDS_KEPT - before) > 10
    assert values["host_step_ms.train"] > 0
    assert 0 < values["host_input_ms.train"] < values["host_step_ms.train"]
    assert 0 < values["host_dispatch_ms.train"] < values["host_step_ms.train"]
    assert values["steps_compiled.train"] >= 0
    assert {m["unit"] for n, m in line["metrics"].items() if n in READERS} == \
        {"ms", "steps"}
