"""The serving step measured from inside (PR 38; docs/OBSERVABILITY.md "The
serving step's records"): one always-on record a ``ContinuousBatcher.step()``
(loop ``serve_step``) and one a ``GenerationEngine.prefill`` (``prefill``),
their phases tiling the call, their counts the batcher's and the engine's
own; a ring a loop; the decode record's ``mx.gen.decode.pages``; and the six
readers of ``benchmark/metrics`` on hand-written records."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu import observability as obs
from mxnet_tpu.observability import StepRecord

from benchmark import harness
from benchmark.weights import make_weights

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_PHASES = ["mx.gen.step.sweep", "mx.gen.step.admit", "mx.gen.step.books",
               "mx.gen.step.decode", "mx.gen.step.tokens"]
PREFILL_PHASES = ["mx.gen.prefill.pages", "mx.gen.prefill.dispatch",
                  "mx.gen.prefill.read", "mx.gen.prefill.index"]
READERS = ["host_turnround_ms.serve", "step_host_ms.serve",
           "step_outside_ms.serve", "prefill_host_ms.serve",
           "prefill_pad_pct.serve", "decode_pages_ms.serve"]


def since(loop, before):
    """The records of ``loop`` written since ``before`` (its ring then)."""
    recs = obs.step_records(loop)
    if not before:
        return recs
    return recs[next(i for i in range(len(recs) - 1, -1, -1)
                     if recs[i] is before[-1]) + 1:]


@pytest.fixture(scope="module")
def toy():
    """The tiny SmallThinker engine (two page groups: a window of 5 over
    pages of 4, three slots) under a batcher, fresh."""
    import test_smallthinker as small
    from benchmark.reference import smallthinker as ref
    from benchmark.systems import smallthinker as adaptor

    cfg = small.tiny_config()
    weights = make_weights(ref.param_specs(cfg), small.SEED)
    return lambda: adaptor.build_serve(cfg, weights)


def tiles(record):
    """The phases are contiguous and sum to the record's duration."""
    ends = [end for _, end in record.marks]
    phases = record.phase_ns()
    assert record.t0_ns <= ends[0] and ends == sorted(ends)
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) == record.duration_ns == ends[-1] - record.t0_ns
    return phases


# -- the records --------------------------------------------------------------
def test_a_steps_and_a_prefills_phases_tile_the_call_and_count_what_it_did(toy):
    engine, batcher = toy()
    rng = np.random.default_rng(0)
    before = {loop: obs.step_records(loop)
              for loop in ("serve_step", "prefill", "decode_step")}
    lengths = [(5, 6), (13, 2), (30, 9), (7, 4)]
    reqs = [batcher.submit(rng.integers(1, 200, n).tolist(), max_new_tokens=m)
            for n, m in lengths]
    seen = []
    while batcher.pending or batcher.active:
        seen.append((batcher.active, batcher.pending))
        batcher.step()
    steps = since("serve_step", before["serve_step"])
    prefills = since("prefill", before["prefill"])
    decodes = since("decode_step", before["decode_step"])
    assert not obs.enabled()  # always on, telemetry off
    assert len(steps) == len(seen) and len(prefills) == 4
    assert [r.step for r in steps] == list(range(1, len(seen) + 1))
    assert [r.step for r in prefills] == [1, 2, 3, 4]
    for r, (active, queued) in zip(steps, seen):
        phases = tiles(r)
        assert list(phases) == STEP_PHASES  # a name's spans add up
        assert r.counts["active"] == active and r.counts["queued"] == queued
    # three slots: the first step admits three, its decode ends the row of
    # (13, 2), and the second step admits the fourth request into its slot
    assert [r.counts["admitted"] for r in steps][:3] == [3, 1, 0]
    assert [r.counts["finished"] for r in steps][:3] == [1, 0, 0]
    assert sum(r.counts["admitted"] for r in steps) == 4
    assert sum(r.counts["finished"] for r in steps) == 4
    assert all(q.finish_reason == "length" for q in reqs)
    # steady: every slot holds a request with two or more tokens to go
    assert [r.counts["steady"] for r in steps][:4] == [0, 1, 1, 0]
    # the prefill records nest in the step that admitted them, the decode
    # record in its .decode phase
    first = steps[0]
    admit_end = dict(first.marks)["mx.gen.step.admit"]
    sweep_end = dict(first.marks)["mx.gen.step.sweep"]
    for p in prefills[:3]:
        assert sweep_end <= p.t0_ns and p.t0_ns + p.duration_ns <= admit_end
    d = decodes[0]
    books_end = [end for n, end in first.marks if n.endswith(".books")][-1]
    assert books_end <= d.t0_ns
    assert d.t0_ns + d.duration_ns <= dict(first.marks)["mx.gen.step.decode"]
    # a prefill's phases and counts: the engine's own numbers
    for p, (n, _) in zip(prefills, lengths):
        assert list(tiles(p)) == PREFILL_PHASES
        assert p.counts == {"bucket": engine.bucket_for(n), "suffix": n,
                            "prompt": n, "pages": engine.pages_for(n),
                            "adopted": 0}
    assert [p.compiled for p in prefills] == [True, True, True, False]
    # the merged view holds every loop, ordered by entry: a step, then what
    # ran inside it
    merged = [r.loop for r in obs.step_records()
              if r.t0_ns >= first.t0_ns][:5]
    assert merged == ["serve_step", "prefill", "prefill", "prefill",
                      "decode_step"]


def test_a_step_that_finds_no_row_closes_its_record_with_what_it_ran(toy):
    _, batcher = toy()
    before = obs.step_records("serve_step")
    assert batcher.step() is False
    r, = since("serve_step", before)
    assert [n for n, _ in r.marks] == STEP_PHASES[:3]
    assert r.counts == {"active": 0, "queued": 0, "admitted": 0,
                        "finished": 0, "steady": 0}
    tiles(r)


def test_a_cancelled_rows_finish_counts_in_the_step_that_swept_it(toy):
    _, batcher = toy()
    req = batcher.submit([1, 2, 3], max_new_tokens=9)
    batcher.step()
    req.cancel()
    before = obs.step_records("serve_step")
    batcher.step()
    r, = since("serve_step", before)
    assert r.counts["active"] == 1 and r.counts["finished"] == 1
    assert [n for n, _ in r.marks] == STEP_PHASES[:3]  # no row left to decode


def test_a_prefill_that_the_pool_cannot_cover_leaves_a_short_record(toy):
    engine, _ = toy()
    while len(engine._pages.free):   # a pool run dry
        engine._pages.free.take()
    before = obs.step_records("prefill")
    with pytest.raises(RuntimeError, match="insufficient free pages"):
        engine.prefill([1, 2, 3, 4, 5], slot=0)
    r, = since("prefill", before)
    assert [n for n, _ in r.marks] == PREFILL_PHASES[:1] and r.counts is None


def test_the_decode_record_has_the_allocators_part_apart(toy):
    engine, _ = toy()
    engine.prefill([5, 6, 7], slot=0)
    engine.decode_step()
    marks = [n for n, _ in obs.step_records("decode_step")[-1].marks]
    assert marks == ["mx.gen.decode.pages", "mx.gen.decode.dispatch",
                     "mx.gen.decode.read"]


def test_a_loops_ring_is_not_evicted_by_another_loops_records(toy):
    engine, _ = toy()
    engine.prefill([5, 6, 7], slot=0)
    engine.decode_step()
    kept = obs.step_records("decode_step")
    for i in range(5000):
        with obs.step_record("another_loop", i, name="unit.other"):
            pass
    assert len(obs.step_records("another_loop")) == obs.STEP_RECORDS_KEPT
    now = obs.step_records("decode_step")
    assert len(now) == len(kept) and now[-1] is kept[-1] and now[0] is kept[0]
    assert obs.step_records("no_such_loop") == []
    obs._records.pop("another_loop")


def test_telemetry_on_feeds_the_old_histograms_from_the_records(toy, tmp_path):
    engine, batcher = toy()

    def count(name, **labels):
        h = obs.REGISTRY.get(name)
        s = h.stats(**labels) if h is not None else None
        return s["count"] if s else 0

    before = (count("gen_prefill_seconds", bucket=8),
              count("gen_decode_step_seconds"), count("ttft_service_seconds"))
    obs.enable(str(tmp_path))
    try:
        batcher.submit([1, 2, 3, 4], max_new_tokens=3)
        batcher.run_until_idle()
    finally:
        obs.disable()
    assert count("gen_prefill_seconds", bucket=8) == before[0] + 1
    assert count("gen_decode_step_seconds") == before[1] + 2
    assert count("ttft_service_seconds") == before[2] + 1
    svc = obs.REGISTRY.get("ttft_service_seconds").stats()
    assert svc["max"] >= 1e-9 * obs.step_records("prefill")[-1].duration_ns > 0.0


# -- the readers --------------------------------------------------------------
MS = 1_000_000


def rec(loop, step, t0_ms, phases, counts=None):
    """A record whose spans last ``phases`` = [(name, ms)], from ``t0_ms``."""
    marks, t = [], t0_ms * MS
    for name, ms in phases:
        t += int(ms * MS)
        marks.append((name, t))
    return StepRecord(loop, step, t0_ms * MS, tuple(marks), False, counts)


def decode(step, t0_ms, own=True, ahead=False):
    d = "mx.gen.decode."
    phases = ([(d + "pages", 0.5), (d + "dispatch", 1.0)] if own else []) \
        + ([(d + "pages", 0.25), (d + "ahead", 1.0)] if ahead else []) \
        + [(d + "read", 6.0)]
    return rec("decode_step", step, t0_ms, phases)


def step(n, t0_ms, admit_ms, decode_ms, counts, early=False):
    s = "mx.gen.step."
    phases = [(s + "sweep", 0.25), (s + "admit", admit_ms), (s + "books", 0.5)]
    if not early:
        phases += [(s + "books", 0.25), (s + "decode", decode_ms),
                   (s + "tokens", 1.0), (s + "tokens", 0.5)]
    return rec("serve_step", n, t0_ms, phases, dict(
        {"active": 1, "queued": 0, "admitted": 0, "finished": 0, "steady": 0},
        **counts))


def prefill(n, t0_ms, bucket, suffix, failed=False):
    p = "mx.gen.prefill."
    phases = [(p + "pages", 2.0)] + ([] if failed else [
        (p + "dispatch", 1.0), (p + "read", 16.0), (p + "index", 1.0)])
    return rec("prefill", n, t0_ms, phases, None if failed else {
        "bucket": bucket, "suffix": suffix, "prompt": suffix, "pages": 1,
        "adopted": 0})


#: a window of 1 s from t = 1 s: seven steps, the second admits one prompt
#: (and fails another for pages), the third dispatches ahead, the fourth
#: takes it, the fifth finds no row, the sixth a prompt that arrived while
#: none was active, the seventh goes on; a step before the window and one
#: after it
HAND_MADE = [
    step(1, 900, 0.5, 8.0, {}), decode(1, 901.5),
    # 1000: sweep .25 admit .5 books .75 decode 8 tokens 1.5 -> ends 1011
    step(2, 1000, 0.5, 8.0, {}), decode(2, 1001.5),        # read ends 1009
    # 1013 (2 ms outside): admit 30.5 = 0.5 of its own + two prefills 20 + 2
    step(3, 1013, 30.5, 8.0, {"admitted": 1}),
    prefill(1, 1013.5, 32, 20), prefill(2, 1034, 64, 33, failed=True),
    decode(3, 1044.5),                                     # read ends 1052
    # ends 1054; 1055 (1 ms outside): dispatches the next step ahead
    step(4, 1055, 0.5, 9.0, {"steady": 1}),
    decode(4, 1056.5, ahead=True),   # pages mark 1057: 5 ms behind 3's read
    # ends 1067; 1068 (1 ms outside): takes the step ahead; its last row ends
    step(5, 1068, 0.5, 6.0, {"finished": 1}), decode(5, 1069.5, own=False),
    # 1200: nothing to decode
    step(6, 1200, 0.5, 0.0, {"active": 0}, early=True),
    # 1300: an arrival after a wait with no row active: a prompt, a decode
    step(7, 1300, 20.5, 8.0, {"active": 0, "admitted": 1}),
    prefill(3, 1300.5, 16, 9), decode(6, 1321.5),          # read ends 1329
    # ends 1331; 1333 (2 ms outside): pages mark 1335, 6 ms behind 6's read
    step(8, 1333, 0.5, 8.0, {}), decode(7, 1334.5),
    step(9, 2100, 0.5, 8.0, {}), decode(8, 2101.5), prefill(4, 2100.5, 8, 1),
]
SERVE = {"kind": "serve", "window": (1.0, 2.0)}


def read(name, run=SERVE):
    return harness.load_reader(name, REPO).read(run)


@pytest.mark.parametrize("name,want", [
    # pairs (2,3) and (5,6): prefills between; (3,4): 1057 - 1052 = 5;
    # (4,5): 4 dispatched ahead and 5 took it; (6,7): 1335 - 1329 = 6
    ("host_turnround_ms.serve", (5.0 + 6.0) / 2),
    # steps 2-8 of the window: 3.0, 2.5 + (30.5 - 22), 3.0, 3.0, 1.25, 3.0, 3.0
    ("step_host_ms.serve", (3.0 + 11.0 + 3.0 + 3.0 + 1.25 + 3.0 + 3.0) / 7),
    # 2 -> 3: 2.0, 3 -> 4: 1.0, 4 -> 5: 1.0; 5 left no row, 6 decoded
    # nothing; 7 -> 8: 2.0
    ("step_outside_ms.serve", (2.0 + 1.0 + 1.0 + 2.0) / 4),
    ("prefill_host_ms.serve", 3.0),          # the two that ran to their end
    ("prefill_pad_pct.serve", 100.0 * (1 - (20 + 9) / (32 + 16))),
    # decodes 2-7 of the window: 4 grew pages twice, 5 took a step ahead
    ("decode_pages_ms.serve", (0.5 + 0.5 + 0.75 + 0.5 + 0.5) / 5),
])
def test_a_reader_against_hand_written_records(monkeypatch, name, want):
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [
        r for r in HAND_MADE if loop is None or r.loop == loop])
    assert read(name) == pytest.approx(want)
    assert read(name, {"kind": "train", "window": (1.0, 2.0)}) is None
    assert read(name, {"kind": "serve", "window": (5.0, 6.0)}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_where_the_program_keeps_no_such_record(
        monkeypatch, name):
    # the parent's records: decode steps without the mark .pages, no
    # serve_step, no prefill
    old = [StepRecord("decode_step", i, (1000 + 10 * i) * MS,
                      (("mx.gen.decode.dispatch", (1002 + 10 * i) * MS),
                       ("mx.gen.decode.read", (1008 + 10 * i) * MS)), False)
           for i in range(5)]
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [
        r for r in old if loop is None or r.loop == loop])
    assert read(name) is None
    monkeypatch.setattr(obs, "step_records", lambda loop=None: [])
    assert read(name) is None  # empty rings
    monkeypatch.delattr(obs, "step_records")
    assert read(name) is None  # a program from before any record


@pytest.mark.parametrize("name", READERS)
def test_the_entry_declares_what_the_reader_does(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    reader = harness.load_reader(name, REPO)
    assert (entry["layer"], entry["unit"], entry["moves"]) == \
        (reader.LAYER, reader.UNIT, reader.MOVES)
    assert entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if "pad" in name
                               else "program_span")
    # the turn-round needs a step not dispatched ahead with no admission
    # before it: a cell above its knee has none (PERF.md, Findings, PR 38)
    # by NAME and from the front: a later cell appended behind these (PR 40's
    # is above its knee too) and later entries leave this green
    above_knee = ["smallthinker_21b_serve_mixed",
                  "olmo_hybrid_7b_serve_longgen"]
    assert entry["workloads"][:3] == ["gpt2_345m_serve_saturate"] + \
        above_knee * (name != "host_turnround_ms.serve")
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in READERS] == list(READERS)


# -- tools/servescope.py --idle: the device's idle time by host span ----------
@pytest.fixture(scope="module")
def servescope():
    from conftest import load_tool

    return load_tool("servescope")


SPANS = [("bench.step", -1, 11), ("mx.gen.step", 0, 10),
         ("mx.gen.step.sweep", 0, 1), ("mx.gen.step.admit", 1, 4),
         ("mx.gen.prefill", 2, 3.5), ("mx.gen.prefill.pages", 2, 2.5),
         ("mx.gen.step.decode", 5, 9)]


def test_every_instant_goes_to_the_span_that_began_last(servescope):
    assert servescope.innermost_segments(SPANS[1:]) == [
        (0, 1, "mx.gen.step.sweep"), (1, 2, "mx.gen.step.admit"),
        (2, 2.5, "mx.gen.prefill.pages"), (2.5, 3.5, "mx.gen.prefill"),
        (3.5, 4, "mx.gen.step.admit"), (4, 5, "mx.gen.step"),
        (5, 9, "mx.gen.step.decode"), (9, 10, "mx.gen.step")]
    assert servescope.innermost_segments([]) == []


def test_idle_time_goes_to_the_innermost_program_span_over_it(servescope):
    ops = [("fusion.1", 0.5, 1.0), ("fusion.2", 6.0, 2.5),  # (name, start, s)
           ("copy.3", 11.5, 0.25), ("copy.4", 11.75 + 1e-6, 0.25 - 1e-6)]
    spans = [(n, a, b - a) for n, a, b in SPANS]
    table = servescope.idle_by_span(ops, spans, -2, 12)
    assert table.pop("device.between_ops") == pytest.approx(1e-6)
    assert table == pytest.approx({
        # the window's 14 s less 4 s of operations
        "mx.gen.step": 2.0, "bench.step": 2.0, "outside batcher.step()": 1.5,
        "mx.gen.step.decode": 1.5, "mx.gen.step.admit": 1.0,
        "mx.gen.prefill": 1.0, "mx.gen.step.sweep": 0.5,
        "mx.gen.prefill.pages": 0.5}, abs=1e-5)


def test_the_tools_rehearsal_finds_the_programs_spans_in_the_trace(servescope):
    """``--idle --tiny``: the toy cell's traffic, a traced slice on the CPU
    (whose "device" is the host's own threads: the numbers mean nothing,
    the names do): the ``mx.gen.*`` spans are in the profiler's host plane
    under their own names, and the slice's records are printed by phase."""
    out = servescope.main(["--tiny", "--idle", "--workload",
                           "smallthinker_21b_serve_mixed"])
    spans = set(out["idle_by_span_s"])
    assert {"mx.gen.decode.dispatch", "mx.gen.decode.read",
            "mx.gen.step.tokens", "mx.gen.step.admit"} <= spans
    assert not any("#" in name for name in spans)
    assert sum(out["idle_by_span_s"].values()) == pytest.approx(
        out["idle_s"], rel=1e-4)
    phases = out["record_phase_ms"]
    assert set(phases) == {"serve_step", "prefill", "decode_step"}
    assert phases["serve_step"]["calls"] == out["steps"] > 10
    assert set(phases["prefill"]) >= set(PREFILL_PHASES)
    # the counts that no benchmark metric reads are read here
    assert set(phases["serve_step"]["counts"]) == {
        "active", "queued", "admitted", "finished", "steady"}
    assert set(phases["prefill"]["counts"]) == {
        "bucket", "suffix", "prompt", "pages", "adopted"}
    assert phases["prefill"]["counts"]["pages"] > 0
    assert phases["prefill"]["compiled"] == 0  # every bucket was warmed
