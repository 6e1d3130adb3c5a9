"""The serving step's order of statements, pinned: a seeded CPU run of the
tiny SmallThinker and the tiny dots3-note-prev engines (two page groups
each) under ``ContinuousBatcher``, with admissions between steps, rows
ending, steps dispatched ahead and one of them dropped by a cancellation,
must leave the history that ``tests/fixtures/serve_golden.json`` holds:
every request's tokens and, after every step, the free pages, the window
group's pages in use, ``_row_epoch`` and the page ids of every row in both
groups. The fixture was recorded at the parent of PR 38 (commit 5d3b4cc),
before ``batcher.step()`` and ``engine.prefill`` got their records: a span
wrapped round a statement moves none of this, a statement moved or added
does (PR 37 was refused for one request reading wrong pages, which no test
saw). PR 39 recorded the page IDS anew (its allocator hands the same
number of pages out from aligned chunks, not from a FIFO list: other ids by
design) after a run that matched the parent's fixture in everything else:
tokens, finishes, free pages, pages in use, epochs, positions, queue, and
every row's number of pages in both groups after every step. To record it
anew: ``PYTHONPATH=. JAX_PLATFORMS=cpu python
tests/test_serve_golden.py``, on a tree whose history is known good."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu import observability as obs

from benchmark.weights import make_weights

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "serve_golden.json")
#: (prompt length, max_new_tokens, the step before which it is submitted)
ARRIVALS = [(5, 30, 0), (13, 34, 0), (30, 28, 0), (7, 9, 4), (16, 25, 9),
            (3, 12, 9), (21, 30, 20), (9, 2, 26), (12, 18, 26), (4, 1, 33),
            (27, 20, 33), (6, 14, 40)]
#: request (by arrival) cancelled before the step: the first while every
#: slot decodes far from its end (a step is in flight ahead: dropped), the
#: second while it waits in the queue
CANCELS = {7: 1, 27: 8}


def _models():
    import test_dots3_note
    import test_smallthinker
    from benchmark.reference import dots3_note, smallthinker
    from benchmark.systems import dots3_note as serve_dots3
    from benchmark.systems import smallthinker as serve_small

    return {"smallthinker": (test_smallthinker, smallthinker, serve_small),
            "dots3_note": (test_dots3_note, dots3_note, serve_dots3)}


def history(model):
    """The run's history as plain lists and ints (what the fixture holds)."""
    toy, ref, adaptor = _models()[model]
    cfg = toy.tiny_config()
    weights = make_weights(ref.param_specs(cfg), toy.SEED)
    engine, batcher = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(38)
    prompts = [rng.integers(1, cfg["n_vocab"], n).tolist()
               for n, _, _ in ARRIVALS]
    ahead = obs.counter("gen_decode_ahead_total")
    before = {o: ahead.value(outcome=o) for o in ("used", "dropped")}
    reqs, steps, step = [], [], 0
    w = engine._groups["window"]
    while step < 48 or batcher.pending or batcher.active:
        for i, (_, new, at) in enumerate(ARRIVALS):
            if at == step:
                reqs.append(batcher.submit(prompts[i], max_new_tokens=new))
        if step in CANCELS:
            reqs[CANCELS[step]].cancel()
        batcher.step()
        steps.append({
            "free": len(engine._pages.free), "window_in_use": w.in_use,
            "row_epoch": engine._row_epoch,
            "pages": [sorted(p) for p in engine._pages.rows],
            "window_pages": [sorted(r.values()) for r in w.rows],
            "positions": engine.positions.tolist(),
            "queued": batcher.pending, "active": batcher.active})
        step += 1
        assert step < 400
    return {"requests": [{"tokens": [int(t) for t in r.output],
                          "finish": r.finish_reason} for r in reqs],
            "steps": steps,
            "ahead": {o: int(ahead.value(outcome=o) - before[o])
                      for o in before}}


@pytest.mark.parametrize("model", ["smallthinker", "dots3_note"])
def test_the_allocators_and_the_tokens_history_is_the_parents(model):
    with open(FIXTURE) as f:
        want = json.load(f)[model]
    got = history(model)
    # the scenario is what the docstring says it is
    assert len(got["steps"]) >= 48
    assert got["ahead"]["used"] >= 3 and got["ahead"]["dropped"] >= 1
    finishes = [r["finish"] for r in got["requests"]]
    assert finishes.count("cancelled") == 2 and finishes.count("length") == 10
    epochs = [s["row_epoch"] for s in got["steps"]]
    assert sum(b > a for a, b in zip(epochs, epochs[1:])) >= 8
    assert got["ahead"] == want["ahead"]
    assert got["requests"] == want["requests"]
    for i, (g, w) in enumerate(zip(got["steps"], want["steps"])):
        assert g == w, f"step {i}"
    assert len(got["steps"]) == len(want["steps"])


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    recorded = {m: history(m) for m in ("smallthinker", "dots3_note")}
    with open(FIXTURE, "w") as f:
        json.dump(recorded, f, separators=(",", ":"))
        f.write("\n")
    for m, h in recorded.items():
        print(m, len(h["steps"]), "steps", h["ahead"],
              [r["finish"] for r in h["requests"]])
