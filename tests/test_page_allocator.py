"""The page allocator that keeps a row's pages side by side (PR 39;
``inference/engine.py:_FreePages``, both page groups): ids handed out from
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
import os
import sys
from collections import deque

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu.inference import engine as E
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
            dry = dry or w.step(r, at[r]) is None
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
    rows = engine._row_pages
    held = sum(map(len, rows))
    runs = sum(E._is_run(r[k * G:(k + 1) * G])
               for r in rows for k in range(len(r) // G))
    shares = {"all": G * runs / held if held else 0.0}
    w = engine._window
    if w is not None:
        runs = sum(E._is_run([r.get(s) for s in range(k * G, k * G + G)])
                   for r in w.rows if r
                   for k in range(min(r) // G, max(r) // G + 1))
        shares["window"] = G * runs / w.in_use if w.in_use else 0.0
    return shares


def _gauge(engine):
    share = REGISTRY.get("gen_page_run_share")
    return {g: share.value(group=g) for g in engine._group_names}


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
    assert engine.free_pages == engine.num_pages and engine._n_runs == 0
    assert engine._window.in_use == 0 and engine._window.n_runs == 0


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
    assert engine._row_pages[2][:2] == engine._row_pages[0][:2]
    assert _gauge(engine) == pytest.approx(_recount(engine))
    for slot in range(3):
        engine.release_slot(slot)
        assert _gauge(engine) == pytest.approx(_recount(engine))
    assert engine._n_runs == 0
