"""The page allocator that keeps a row's pages side by side (PR 39;
``inference/pages.py:_FreePages``, both page groups): ids handed out from
aligned chunks of ``RUN_PAGES`` so that the decode kernel can fetch a run of
neighbouring pages as one copy. Over a seeded churn shaped like the
mixed-length cell's (48 rows, prompts 512-8,192, answers 128-2,048, pools of
24,576 and 12,416 pages, a window of 4,096) the share of held pages that lie
in runs stays high where the FIFO lists it replaced scatter them; the COUNTS
(free pages, pages in use, evictions) are the FIFO lists' on the same
sequence; a dry pool still gives every page out; no page is given twice and
the trash page never; and the engine's gauge ``gen_page_run_share{group}``,
kept from the allocator's own bookkeeping, equals a recount over the rows'
tables after every step of a served run (prefix adoption, fork, copy on
write, a window group's slide)."""
import json
import os
import sys
from collections import deque

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu.inference import pages as E
from mxnet_tpu.observability import REGISTRY

G = E.RUN_PAGES


class Fifo:
    """The free list both groups had before: ids in, ids out, in order."""

    def __init__(self, n):
        self.ids = deque(range(1, n + 1))

    def __len__(self):
        return len(self.ids)

    def take(self, after=0, head=True):
        return self.ids.popleft()

    def take_row(self, n, first=0, after=0):
        return [self.ids.popleft() for _ in range(n)]

    def give(self, pid):
        self.ids.append(pid)


def _length(rng, median, sigma, lo, hi):
    return int(np.clip(rng.lognormal(np.log(median), sigma), lo, hi))


def _copies_a_page(page_of, first, last, nb=32):
    """Copies over pages of the decode kernel's own walk of logical pages
    ``first .. last``: blocks of ``nb`` pages from ``first``; in a block
    every whole group of ``G`` logical pages (``s % G == 0`` ...) whose ids
    are consecutive is one copy, every other page one."""
    copies = 0
    for start in range(first, last + 1, nb):
        end = min(last + 1, start + nb)
        s = start
        while s < end:
            whole = s % G == 0 and s + G <= end and E._is_run(
                [page_of(s + i) for i in range(G)])
            s += G if whole else 1
            copies += 1
    return copies / (last - first + 1)


def churn(fifo, cycles, seed=0, rows=48, ps=16, pages=24576,
          window_pages=12416, window=4096):
    """The engine's calls on both groups' allocators over a saturated queue
    of the cell's lengths until ``cycles`` times the pool has been taken:
    an admission takes a prompt's pages in both groups, every step each row
    takes the page of its next position where it lacks one and its window
    group gives back what lies behind the window, a row that ends gives
    everything back and the slot is admitted anew. Returns the shares at
    the end and the (free, window free) history."""
    rng = np.random.default_rng(seed)
    free = Fifo(pages) if fifo else E._FreePages(pages)
    w = E._WindowPages(window_pages, rows, ps, window)
    if fifo:
        w.free = Fifo(window_pages)
    held = [[] for _ in range(rows)]
    at, end = [0] * rows, [0] * rows
    taken = evicted = 0
    waiting, history = None, []
    while taken < cycles * pages:
        for r in range(rows):
            if held[r]:
                continue
            waiting = waiting or (_length(rng, 4096, 0.7, 512, 8192),
                                  _length(rng, 512, 0.6, 128, 2048))
            prompt, answer = waiting
            need = -(-prompt // ps)
            if len(free) < need or len(w.free) < w.needed(prompt):
                continue
            held[r] = free.take_row(need)
            w.admit(r, prompt)
            taken += need
            at[r], end[r], waiting = prompt, prompt + answer, None
        for r in range(rows):
            if not held[r]:
                continue
            s = at[r] // ps
            dry = s >= len(held[r]) and not len(free)
            if not dry and s >= len(held[r]):
                held[r].append(free.take(held[r][-1], s % G == 0))
                taken += 1
            dry = dry or bool(w.grow(np.arange(rows) != r, at)[3])
            at[r] += 1
            if dry or at[r] >= end[r]:
                evicted += dry
                for pid in held[r]:
                    free.give(pid)
                held[r] = []
                w.release(r)
        history.append((len(free), len(w.free)))
    live = [r for r in range(rows) if held[r]]
    groups = sum(E._is_run(held[r][k * G:(k + 1) * G])
                 for r in live for k in range(len(held[r]) // G))
    window_groups = sum(
        E._is_run([w.rows[r].get(s) for s in range(k * G, k * G + G)])
        for r in live
        for k in range(min(w.rows[r]) // G, max(w.rows[r]) // G + 1))
    return {
        "all": G * groups / sum(len(held[r]) for r in live),
        "window": G * window_groups / w.in_use,
        "window_tally": G * w.n_runs / w.in_use,
        "copies_all": np.mean([_copies_a_page(
            held[r].__getitem__, 0, len(held[r]) - 1) for r in live]),
        "copies_window": np.mean([_copies_a_page(
            lambda s: w.rows[r].get(s), min(w.rows[r]), max(w.rows[r]))
            for r in live]),
        "evicted": evicted}, history


@pytest.fixture(scope="module")
def churned():
    return {fifo: churn(fifo, cycles=10) for fifo in (False, True)}


def test_a_served_pool_keeps_its_rows_in_runs_where_fifo_lists_scatter_them(
        churned):
    kept, _ = churned[False]
    lost, _ = churned[True]
    assert kept["all"] >= 0.85 and kept["window"] >= 0.85, kept
    assert lost["all"] < 0.5 and lost["window"] < 0.5, lost
    # the window group's own tally (what the gauge reads) is the recount
    assert kept["window_tally"] == kept["window"]
    # and what the kernel's walk makes of it: an eighth of a copy a page
    # where every group is a run; the window group's blocks start at the
    # window's lower bound, in the middle of a chunk as often as not
    assert kept["copies_all"] < 0.2 and kept["copies_window"] < 0.4, kept
    assert lost["copies_all"] > 0.6 and lost["copies_window"] > 0.9, lost


def test_the_counts_are_the_fifo_lists_on_the_same_sequence(churned):
    (kept, history), (lost, fifo_history) = churned[False], churned[True]
    assert history == fifo_history
    assert kept["evicted"] == lost["evicted"]
    assert len(history) > 5000


@pytest.mark.parametrize("pages", [1, 7, 8, 9, 64, 203])
def test_at_a_dry_pool_every_free_page_is_still_given_out(pages):
    rng = np.random.default_rng(pages)
    free = E._FreePages(pages)
    got = []
    while len(free):
        got.append(free.take(after=got[-1] if got and rng.random() < 0.7
                             else 0, head=bool(rng.random() < 0.3)))
    assert sorted(got) == list(range(1, pages + 1))   # each once, never 0
    assert list(free) == []
    # some come back, in any order: all of them go out again
    back = [int(p) for p in rng.permutation(got)[:pages // 2 + 1]]
    for pid in back:
        free.give(pid)
    assert len(free) == len(back) and sorted(free) == sorted(back)
    again = [free.take(0, head=bool(i % 2)) for i in range(len(back))]
    assert sorted(again) == sorted(back) and not len(free)


def test_no_page_is_given_twice_and_a_whole_chunk_before_a_broken_one():
    rng = np.random.default_rng(5)
    free, out = E._FreePages(20 * G + 3), set()
    rows = []
    for _ in range(4000):
        if rows and (rng.random() < 0.45 or not len(free)):
            row = rows.pop(int(rng.integers(len(rows))))
            for pid in row[int(rng.integers(len(row))):]:   # a tail, or all
                free.give(pid)
                out.remove(pid)
                row.remove(pid)
            if row:
                rows.append(row)
            continue
        n = min(int(rng.integers(1, 3 * G)), len(free))
        whole = len(free._whole)
        row = free.take_row(n)
        assert not out & set(row) and 0 not in row
        assert len(set(row)) == n
        out |= set(row)
        rows.append(row)
        if whole >= -(-n // G):
            # whole chunks were there for its groups: every group of the
            # row is a run, the last one as far as it goes
            assert all(E._is_run(row[k:k + G]) for k in range(0, n, G))
        assert len(free) + len(out) == free.num_pages
    assert {pid for pid in free} == set(range(1, free.num_pages + 1)) - out


def test_a_page_goes_beside_its_rows_last_where_that_is_free():
    free = E._FreePages(4 * G)
    a = free.take_row(G + 3)
    assert a == list(range(1, G + 4))
    b = free.take_row(2)                       # another row: a chunk of its own
    assert b == [2 * G + 1, 2 * G + 2]
    assert free.take(a[-1], head=False) == G + 4      # beside its last
    assert free.take(b[-1], head=False) == 2 * G + 3
    # a row whose neighbour is taken, in the middle of a group: a partly
    # free chunk's page before a whole chunk is broken
    assert free.take(G + 1, head=False) == G + 5
    # the head of a group: the neighbour chunk where it is whole ...
    assert free.take(3 * G, head=True) == 3 * G + 1
    # ... else any whole one; none is left: the oldest partly free chunk's
    assert not free._whole
    assert free.take(G, head=True) == G + 6


@pytest.mark.parametrize("seed", range(4))
def test_a_rows_pages_at_once_are_what_page_by_page_gives(seed):
    rng = np.random.default_rng(seed)
    a, b = E._FreePages(30 * G + 5), E._FreePages(30 * G + 5)
    rows = []
    for _ in range(300):
        if rows and (rng.random() < 0.4 or len(a) < 4 * G):
            for pid in rows.pop(int(rng.integers(len(rows)))):
                a.give(pid)
                b.give(pid)
            continue
        n = int(rng.integers(1, min(4 * G, len(a)) + 1))
        first = int(rng.integers(0, 2 * G))
        after = int(rng.integers(0, a.num_pages + 1))
        got, want, at = a.take_row(n, first, after), [], after
        for s in range(first, first + n):
            at = b.take(at, s % G == 0)
            want.append(at)
        assert got == want
        assert (list(a), list(a._whole), list(a._partial)) == \
            (list(b), list(b._whole), list(b._partial))
        rows.append(got)


def _recount(engine):
    """``gen_page_run_share`` of both groups by a walk of the rows' pages."""
    rows = engine._pages.rows
    held = sum(map(len, rows))
    runs = sum(E._is_run(r[k * G:(k + 1) * G])
               for r in rows for k in range(len(r) // G))
    shares = {"all": G * runs / held if held else 0.0}
    if "window" in engine._groups:
        w = engine._groups["window"]
        runs = sum(E._is_run([r.get(s) for s in range(k * G, k * G + G)])
                   for r in w.rows if r
                   for k in range(min(r) // G, max(r) // G + 1))
        shares["window"] = G * runs / w.in_use if w.in_use else 0.0
    return shares


def _gauge(engine):
    share = REGISTRY.get("gen_page_run_share")
    return {g: share.value(group=g) for g in engine._groups}


@pytest.mark.parametrize("model", ["smallthinker", "dots3_note"])
def test_the_gauge_is_the_recount_after_every_step_of_a_served_run(
        monkeypatch, model):
    import test_serve_golden as golden
    from benchmark.weights import make_weights

    monkeypatch.setattr(E, "RUN_PAGES", 2)   # the toy rows hold a few pages
    monkeypatch.setitem(globals(), "G", 2)
    toy, ref, adaptor = golden._models()[model]
    cfg = toy.tiny_config()
    engine, batcher = adaptor.build_serve(
        cfg, make_weights(ref.param_specs(cfg), toy.SEED))
    rng = np.random.default_rng(39)
    step, seen = 0, set()
    while step < 48 or batcher.pending or batcher.active:
        for n, new, at in golden.ARRIVALS:
            if at == step:
                batcher.submit(rng.integers(1, cfg["n_vocab"], n).tolist(),
                               max_new_tokens=new)
        batcher.step()
        assert _gauge(engine) == pytest.approx(_recount(engine)), step
        seen |= {round(v, 3) for v in _gauge(engine).values()}
        step += 1
        assert step < 400
    assert len(seen) > 3 and max(seen) > 0.5   # runs were there to count
    assert engine.free_pages == engine.num_pages
    assert [(g.in_use, g.n_runs) for g in engine._groups.values()] == [(0, 0)] * 2


def test_the_gauge_follows_adoption_fork_and_copy_on_write(monkeypatch):
    import test_prefix_sharing as sharing

    monkeypatch.setattr(E, "RUN_PAGES", 2)
    monkeypatch.setitem(globals(), "G", 2)
    engine = sharing._engine(sharing._gpt2(), prefix_cache=True, eos_id=None,
                             num_pages=40)
    prompt = sharing._prompt(16, 39)
    engine.prefill(prompt, slot=0)              # two pages: one group, a run
    assert _gauge(engine) == _recount(engine) == {"all": 1.0}
    engine.fork_slot(0, 1)                      # the same pages again
    assert _gauge(engine) == _recount(engine) == {"all": 1.0}
    for _ in range(20):     # the fork's first write copies a shared page
        engine.decode_step()
        assert _gauge(engine) == pytest.approx(_recount(engine))
    engine.prefill(prompt + sharing._prompt(5, 40), slot=2)   # adopts two
    assert engine._pages.rows[2][:2] == engine._pages.rows[0][:2]
    assert _gauge(engine) == pytest.approx(_recount(engine))
    for slot in range(3):
        engine.release_slot(slot)
        assert _gauge(engine) == pytest.approx(_recount(engine))
    assert engine._pages.n_runs == 0


# -- the two kinds of group, held to the parent's history ---------------------
# PR 47 moved the allocators out of ``GenerationEngine`` into
# ``inference/pages.py``. ``fixtures/page_groups.json`` holds what the
# PARENT's engine (commit f961f7d) left after every operation of the two
# sequences below: every group's rows, runs, reference counts and free ids,
# the table rows, update vectors, copies and evicted rows each operation
# produced, and every gauge and counter of the allocators. Both the group
# classes alone (no engine, no jax program) and the engine that loops over
# them must leave the same. To record anew, on a tree known good:
# ``PYTHONPATH=. JAX_PLATFORMS=cpu python tests/test_page_allocator.py``.

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "page_groups.json")
GAUGES = ("gen_pages_free", "gen_pages_in_use", "gen_page_run_share",
          "gen_page_refcount_max", "gen_pages_reserved")
COUNTERS = ("gen_page_allocs_total", "gen_pages_reclaimed_total",
            "gen_page_evictions_total", "gen_prefix_evictions_total",
            "gen_window_pages_freed_total", "gen_cow_copies_total")


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 90, n).tolist()


def _sequences():
    """{model: (engine settings, operations)}: the ``all`` kind alone under a
    prefix cache (adoption of whole pages, adoption that ends inside a page,
    a fork, copy on write, a session's pages cached, eviction of cached
    pages and of a row under a reservation), and the ``all`` and ``window``
    kinds side by side (a window that slides over whole groups of pages, a
    slot reused, a window pool run dry under a reservation)."""
    a, b = _tokens(21, 1), _tokens(7, 2)
    return {
        "gpt2": (dict(batch_size=4, page_size=2, max_length=64, num_pages=40,
                      prefix_cache=True, prefill_buckets=(8, 16, 32)),
                 [("reserve", 0), ("prefill", 0, a), ("prefill", 1, b)]
                 + [("step",)] * 4
                 + [("fork", 0, 2)] + [("step",)] * 5
                 + [("prefill", 3, a[:16] + [7, 8, 9]), ("release", 1),
                    ("prefill", 1, a[:20]), ("cache", 0, a + [5] * 12),
                    ("reserve", 3)] + [("step",)] * 11
                 + [("reserve", 0), ("release", 0), ("release", 2),
                    ("prefill", 0, b + [3] * 9), ("release", 0),
                    ("release", 1), ("release", 3)]),
        "smallthinker": (dict(batch_size=3, page_size=4, max_length=128,
                              num_pages={"all": 60, "window": 30},
                              prefill_buckets=[16, 64]),
                         [("reserve", 0), ("prefill", 0, _tokens(50, 3))]
                         + [("step",)] * 30
                         + [("prefill", 1, _tokens(9, 4))] + [("step",)] * 12
                         + [("release", 1), ("prefill", 1, _tokens(30, 5))]
                         + [("step",)] * 6
                         + [("reserve", 2), ("prefill", 2, _tokens(60, 6))]
                         + [("step",)] * 12
                         + [("reserve", 0), ("release", 0), ("release", 1),
                            ("release", 2)])}


def _toy_engine(model, settings):
    from mxnet_tpu.inference import GenerationEngine

    if model == "gpt2":
        import test_prefix_sharing as sharing
        return GenerationEngine(sharing._gpt2(), paged=True, **settings)
    import test_smallthinker as toy
    from benchmark.weights import make_weights
    cfg = toy.tiny_config(sliding_window_size=40)
    cfg["engine"] = dict(cfg["engine"], **settings)
    return toy.adaptor.build_serve(
        cfg, make_weights(toy.ref.param_specs(cfg), toy.SEED))[0]


def _groups_of(engine):
    """{group: its allocator}; the recording on the parent's tree swapped a
    view of that engine's own fields in here."""
    return engine._groups


def _lists(x):
    """Arrays, one or one a group, as a list a group of plain lists."""
    x = x if isinstance(x, (list, tuple)) else (x,)
    return [np.asarray(v).tolist() for v in x]


def _state(groups):
    return {name: {
        "rows": [sorted(r.items()) if isinstance(r, dict) else list(r)
                 for r in g.rows],
        "runs": [sorted(r) for r in g.runs], "n_runs": g.n_runs,
        "free": list(g.free), "reserved": int(g.reserved),
        "rc": np.asarray(g.rc).tolist() if hasattr(g, "rc") else None}
        for name, g in groups.items()}


def _readings(groups, zero):
    def series(name):   # of this engine's groups: the registry outlives it
        metric = REGISTRY.get(name)
        return {} if metric is None else {
            json.dumps(s["labels"], sort_keys=True): s["value"]
            for s in metric.snapshot()["series"]
            if s["labels"].get("group", next(iter(groups))) in groups}

    gauges = {name: series(name) for name in GAUGES}
    counters = {name: {k: v - zero.get(name, {}).get(k, 0)
                       for k, v in series(name).items()}
                for name in COUNTERS}
    return gauges, {n: {k: v for k, v in c.items() if v}
                    for n, c in counters.items()}


def engine_history(model):
    """What an engine's allocators hold and hand out after every operation
    of the model's sequence (what the fixture holds)."""
    settings, ops = _sequences()[model]
    engine = _toy_engine(model, settings)
    groups = _groups_of(engine)
    zero = _readings(groups, {})[1]
    out = {}
    grow, cow, prefill = (engine._grow_pages, engine._dispatch_cow,
                          engine._prefill_jit)

    def growing(span):
        out["done"], out["positions"] = (engine.done.tolist(),
                                         engine.positions.tolist())
        slots, pages = grow(span)
        out["slots"], out["pages"] = _lists(slots), _lists(pages)
        out["dry"] = [r for r in range(engine.batch_size)
                      if engine.done[r] and not out["done"][r]]
        return slots, pages

    def copying(copies):
        out.setdefault("copies", []).extend(
            [int(v) for v in c] for c in copies)
        return cow(copies)

    def prefilling(params, carry, tokens, slot, length, new_row, *rest):
        out["table_rows"] = _lists(new_row)
        return prefill(params, carry, tokens, slot, length, new_row, *rest)

    engine._grow_pages, engine._dispatch_cow = growing, copying
    engine._prefill_jit = prefilling
    history = []
    for op in ops:
        out.clear()
        if op[0] == "prefill":
            engine.prefill(op[2], slot=op[1])
        elif op[0] == "step":
            engine.decode_step()
        elif op[0] == "fork":
            engine.fork_slot(op[1], op[2])
            out["table_rows"] = [np.asarray(t)[op[2]].tolist()
                                 for t in _lists(engine.page_table)]
        elif op[0] == "release":
            engine.release_slot(op[1])
        elif op[0] == "cache":
            out["cached"] = engine.cache_sequence(op[1], op[2])
        else:
            engine.reserve_pages(op[1])
        gauges, counters = _readings(groups, zero)
        history.append({"op": op[0], "out": dict(out),
                        "groups": _state(groups), "gauges": gauges,
                        "counters": counters})
    return json.loads(json.dumps(history))


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.mark.parametrize("model", ["gpt2", "smallthinker"])
def test_the_engine_over_its_groups_leaves_the_parents_history(recorded,
                                                               model):
    got, want = engine_history(model), recorded[model]
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"operation {i}: {g['op']}"
    assert len(got) == len(want)
    # the sequences are what their docstring says they are
    counts = want[-1]["counters"]
    outs = [w["out"] for w in want]
    assert sum(bool(o.get("dry")) for o in outs) >= 1
    if model == "gpt2":
        assert counts["gen_cow_copies_total"]["{}"] >= 2
        assert counts["gen_prefix_evictions_total"]["{}"] >= 1
        assert max(v for w in want for v in
                   w["gauges"]["gen_page_refcount_max"].values()) >= 3
    else:
        assert counts["gen_window_pages_freed_total"]["{}"] >= 10
        shares = {w["gauges"]["gen_page_run_share"]['{"group": "window"}']
                  for w in want}
        assert len(shares) > 3 and max(shares) > 0.5
    assert all(not any(g["rows"]) and g["n_runs"] == 0
               for g in want[-1]["groups"].values())


def _adoption(cache, prompt, ps):
    """A prefill's walk of the prefix cache, as ``GenerationEngine.prefill``
    makes it: (adopted pages, the pages eviction must spare, the page the
    adoption ends inside or 0)."""
    if cache is None:
        return [], set(), 0
    pages, matched = cache.lookup(list(prompt))
    start = min(matched, len(prompt) - 1)
    adopt = pages[:start // ps]
    tail = pages[start // ps] if start % ps else 0
    return adopt, set(adopt) | ({tail} if tail else set()), tail


@pytest.mark.parametrize("model", ["gpt2", "smallthinker"])
def test_the_groups_alone_leave_the_parents_history(recorded, model):
    """No engine and no jax program: the group classes take the sequence's
    operations as the engine passes them on (a step's ``done`` and
    ``positions`` are the recorded ones) and must hold and return what the
    parent's engine did, id for id."""
    from mxnet_tpu.inference.prefix_cache import RadixPrefixCache

    settings, ops = _sequences()[model]
    ps, rows = settings["page_size"], settings["batch_size"]
    cache = RadixPrefixCache(ps) if settings.get("prefix_cache") else None
    sizes = settings["num_pages"]
    groups = {"all": E._AllPages(
        sizes["all"] if isinstance(sizes, dict) else sizes, rows, ps,
        settings["max_length"], prefix_cache=cache)}
    if isinstance(sizes, dict):
        groups["window"] = E._WindowPages(sizes["window"], rows, ps, 40)
    positions = [0] * rows
    for i, (op, want) in enumerate(zip(ops, recorded[model])):
        out = {}
        if op[0] == "prefill":
            _, slot, prompt = op
            adopt, protect, tail = _adoption(cache, prompt, ps)
            for g in groups.values():
                g.require(slot, len(prompt), adopt, protect)
                g.release(slot)
            out["table_rows"] = _lists([
                g.admit(slot, len(prompt), adopt, protect)
                for g in groups.values()])
            if tail:
                out["copies"] = [[slot, len(adopt), tail,
                                  groups["all"].rows[slot][len(adopt)]]]
            if cache is not None:
                groups["all"].cache(slot, list(prompt))
            positions[slot] = len(prompt)
        elif op[0] == "step":
            done = list(want["out"]["done"])
            assert positions == want["out"]["positions"]
            out.update(done=list(done), positions=list(positions), dry=[],
                       slots=[], pages=[], copies=[])
            for g in groups.values():
                slots, pages, copies, dry, moved = g.grow(done, positions, 0)
                # (a row left dry keeps no entry for what it had freed)
                assert moved >= np.count_nonzero(pages) + len(copies)
                assert dry or moved == np.count_nonzero(pages) + len(copies)
                out["slots"] += _lists(slots)
                out["pages"] += _lists(pages)
                out["dry"] += dry
                out["copies"] += [list(map(int, c)) for c in copies]
                for row in dry:
                    done[row] = True
            positions = [p + (not d) for p, d in zip(positions, done)]
        elif op[0] == "fork":
            _, src, dst = op
            groups["all"].release(dst)
            out["table_rows"] = _lists([groups["all"].fork(src, dst)])
            positions[dst] = positions[src]
        elif op[0] == "release":
            for g in groups.values():
                g.release(op[1])
        elif op[0] == "cache":
            n = min(len(op[2]), positions[op[1]])
            groups["all"].cache(op[1], list(op[2])[:n])
            out["cached"] = n // ps * ps
        else:
            for g in groups.values():
                g.reserve(op[1])
        assert json.loads(json.dumps(out)) == want["out"], (i, op[0])
        assert json.loads(json.dumps(_state(groups))) == want["groups"], \
            (i, op[0])
        for name, g in groups.items():   # what the gauges are set from
            key = json.dumps({"group": name})
            assert g.in_use == want["gauges"]["gen_pages_in_use"][key]
            assert g.run_share == pytest.approx(
                want["gauges"]["gen_page_run_share"][key])
        assert groups["all"].refcount_max == \
            want["gauges"]["gen_page_refcount_max"]["{}"]


@pytest.mark.parametrize("kind", ["all", "window"])
def test_both_kinds_answer_every_method_of_the_interface(kind):
    """What ``GenerationEngine`` asks of a group, asked of each kind with
    the same calls: a row admitted, grown, released; a reservation; a
    refused admission; and the traced half against the host's table."""
    import jax.numpy as jnp

    g = E.group_for({} if kind == "all" else {"window": 12}, None, 2, 4, 64,
                    2)
    assert g.num_pages == 2 * g.columns and g.in_use == 0
    assert g.shares == (kind == "all") and g.window == (
        None if kind == "all" else 12)
    assert g.counted_as == (None if kind == "all" else "window_pages_in_use")
    assert g.spare() == g.spare(True) == len(g.free) == g.num_pages
    g.reserve(3)
    assert g.reserved == 3 and g.spare(True) == g.num_pages - 3
    need = g.needed(30)
    g.require(0, 30)
    row = g.admit(0, 30)
    assert row.shape == (g.columns,) and np.count_nonzero(row) == need
    # eight pages in order are a run; a window of three pages never is
    assert g.in_use == g.held == need
    assert g.run_share == (1.0 if kind == "all" else 0.0)
    table = jnp.zeros((2, g.columns), jnp.int32).at[0].set(row)
    done, positions = [False, True], [30, 0]
    for _ in range(30):
        slots, pages, copies, dry, moved = g.grow(done, positions, 0)
        assert slots.shape == pages.shape == (2, g.width) and not dry
        assert not copies and moved == np.count_nonzero(pages)
        table = g.apply_updates(table, jnp.asarray(slots),
                                jnp.asarray(pages), jnp.zeros(2, bool))
        positions[0] += 1
    held = g.rows[0]
    want = np.zeros(g.columns, np.int32)
    for s, pid in (held.items() if kind == "window" else enumerate(held)):
        want[s % g.columns] = pid
    assert np.asarray(table)[0].tolist() == want.tolist()
    assert not np.asarray(table)[1].any()
    g.require(1, 30)
    taken = [g.free.take() for _ in range(len(g.free) - 1)]   # a page left
    with pytest.raises(RuntimeError, match="insufficient free pages"):
        g.require(1, 30)
    g.require(0, 30)   # the slot's own pages count: it gives them back first
    for pid in taken:
        g.free.give(pid)
    cleared = g.apply_updates(table, jnp.zeros_like(slots),
                              jnp.zeros_like(pages),
                              jnp.asarray([True, False]))
    assert not np.asarray(cleared).any()
    assert g.release(0) == g.needed(60)   # what a prompt of 60 would hold
    assert g.in_use == 0 and g.n_runs == 0 and g.release(0) == 0


if __name__ == "__main__":
    with open(FIXTURE, "w") as f:
        json.dump({m: engine_history(m) for m in _sequences()}, f)
    print("recorded", FIXTURE)
