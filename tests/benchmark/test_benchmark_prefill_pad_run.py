"""``prefill_pad_run_pct.serve`` (PR 46): the share of the positions a
window's prefill programs RAN that are padding. Against hand-made ``prefill``
records (with the program's count ``positions_run``, with ``bucket`` alone as
a program from before the count leaves them, with none), in a tiny traced run
of MiniCPM-SALA's cell whose buckets are several stretches, and its
``per_layer`` entry pinned by name."""
import json

import pytest

from benchmark import harness
from mxnet_tpu import observability as obs
from mxnet_tpu.models import minicpm_sala as model_module

from benchmark_tiny import REPO, run_cell
from test_benchmark_minicpm_sala import TINY, make_root

NAME, CELL = "prefill_pad_run_pct.serve", "minicpm_sala_serve_longdoc"
WINDOW = {"kind": "serve", "window": (1.0, 2.0)}
S = 1_000_000_000


def prefill(t0_s, **counts):
    return obs.StepRecord("prefill", 0, int(t0_s * S),
                          (("mx.gen.prefill.read", int(t0_s * S) + 1000),),
                          False, counts or None)


def read(monkeypatch, records, run=WINDOW):
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [
        r for r in records if loop is None or r.loop == loop])
    return harness.load_reader(NAME, REPO).read(run)


@pytest.mark.parametrize("records,want", [
    # a program that counts the positions it ran: the largest entry a record
    ([prefill(1.1, bucket=64, suffix=13, positions_run=[16, 16, 16, 16]),
      prefill(1.2, bucket=64, suffix=40, positions_run=[48, 48, 48, 48]),
      prefill(1.3, bucket=32, suffix=32, positions_run=[32, 32, 32, 32])],
     100.0 * (1 - 85 / 96)),
    # a program from before the count ran its whole bucket: what
    # ``prefill_pad_pct.serve`` reads
    ([prefill(1.1, bucket=64, suffix=13), prefill(1.2, bucket=64, suffix=40),
      prefill(1.3, bucket=32, suffix=32)], 100.0 * (1 - 85 / 160)),
    # records of both kinds, a record outside the window, a record of
    # another loop and a record without counts
    ([prefill(0.5, bucket=64, suffix=1, positions_run=[16]),
      prefill(1.1, bucket=64, suffix=13, positions_run=[16, 16]),
      prefill(1.2, bucket=64, suffix=40), prefill(1.3),
      obs.StepRecord("decode_step", 0, int(1.4 * S), (), False,
                     {"bucket": 64, "suffix": 1}),
      prefill(2.5, bucket=64, suffix=64)], 100.0 * (1 - 53 / 80)),
    ([], None),
    ([prefill(0.5, bucket=64, suffix=13)], None),   # none in the window
])
def test_the_share_follows_the_records_counts(monkeypatch, records, want):
    got = read(monkeypatch, records)
    assert got == (None if want is None else pytest.approx(want))
    pad = harness.load_reader("prefill_pad_pct.serve", REPO).read(WINDOW)
    if not any(r.counts and "positions_run" in r.counts for r in records):
        assert got == pad
    assert read(monkeypatch, records, {"kind": "train"}) is None


def test_a_program_that_keeps_no_records_reads_nothing(monkeypatch):
    monkeypatch.delattr(obs, "step_records")
    assert harness.load_reader(NAME, REPO).read(WINDOW) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The toy cell with stretches of 16 tokens: its buckets of 32 and 64
    are two and four."""
    patch = pytest.MonkeyPatch()
    patch.setattr(model_module, "_STRETCH", 16)
    patch.setattr(model_module, "_CHUNK", 4)
    patch.setattr(model_module, "_SELECT_QUERIES", 8)
    try:
        run, stdout = run_cell(make_root(tmp_path_factory.mktemp("pad_run")),
                               TINY, seconds=1.5, trace=1)
    finally:
        patch.undo()
    return run, json.loads(stdout.strip().splitlines()[-1]), stdout


def test_a_tiny_traced_run_of_the_cell_reports_it(traced):
    from benchmark.serverecords import window_records

    run, line, stdout = traced
    assert run["correct"] is True and line["failed"] == 0, stdout
    counts = [r.counts for r in window_records(run, "prefill")]
    assert counts and all(len(c["positions_run"]) == 4 for c in counts)
    # every layer ran the stretches the prompt reaches, no more
    for c in counts:
        assert c["positions_run"] == [
            min(-(-c["suffix"] // 16) * 16, c["bucket"])] * 4
    assert any(c["positions_run"][0] < c["bucket"] for c in counts)
    got = line["metrics"][NAME]
    assert got["unit"] == "%"
    assert got["value"] == pytest.approx(100.0 * (1 - sum(
        c["suffix"] for c in counts) / sum(c["positions_run"][0] for c in counts)))
    # the accepted reader keeps dividing by the bucket
    assert 0.0 <= got["value"] < line["metrics"]["prefill_pad_pct.serve"]["value"]


def test_the_entry_is_pinned_by_name():
    bench = harness.load_benchmark(REPO)
    reader = harness.load_reader(NAME, REPO)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert (entry["layer"], entry["unit"], entry["moves"]) == \
        (reader.LAYER, reader.UNIT, reader.MOVES) == \
        ("engine", "%", "serve_tokens_per_s")
    assert (entry["source"], entry["better"]) == ("program_counter", "lower")
    assert CELL in entry["workloads"]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    cell = harness.find_cell(bench, CELL)
    assert NAME in {m["name"] for m in harness.metrics_of(bench, cell, "per_layer")}
    assert "serve_tokens_per_s" in {
        m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
