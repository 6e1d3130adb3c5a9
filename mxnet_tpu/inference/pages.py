"""The host page allocators of a paged engine's pool groups: how a group's
pages are laid out in its table, handed out and given back, one class a KIND
of group (docs/INFERENCE.md "Page groups" lists what the engine asks of
every kind, and what a new kind implements). ``inference/engine.py`` holds
one object a group, in the model's order, and loops over them. The two
kinds: :class:`_AllPages` (every position kept, an in-order table, reference
counts: the one kind whose rows share pages) and :class:`_WindowPages` (the
last ``window`` positions, a ring table). The host side is authoritative;
the device tables mirror it through the update vectors shipped with each
program (``apply_updates``, traced)."""
from __future__ import annotations

from collections import OrderedDict
from typing import List

import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..ops.pallas_paged_attention import RUN_PAGES


class _FreePages:
    """The free pages of one pool, ids ``1 .. num_pages`` (0 is the trash
    page), kept as aligned CHUNKS of ``RUN_PAGES`` ids so that a row's pages
    stay side by side in a served pool: the decode kernel fetches
    ``RUN_PAGES`` logically consecutive pages whose ids are consecutive as
    one copy (``ops/pallas_paged_attention.py``). Every free page can be
    taken and none is held back; the one rule is a PREFERENCE, told by the
    taker: ``take(after, head)`` gives logical page ``s`` the id next to
    page ``s - 1``'s (``after``) where that id is free, and where ``s``
    starts a group of ``RUN_PAGES`` (``head``) the first id of a wholly
    free chunk (the neighbour chunk's before any other), so the group can
    fill that chunk id by id. A taker that finds neither takes from the
    partly free chunks, and from a whole one last: fragments are used up
    before a whole chunk is broken, and a chunk is whole again when its
    last page comes back. O(1) a page: a count a chunk and two ordered
    sets of chunks."""

    def __init__(self, num_pages: int):
        self.chunk, self.num_pages = RUN_PAGES, int(num_pages)
        self._is_free = bytearray([0]) + bytearray([1]) * self.num_pages \
            + bytearray([0])  # by id; the trash page and an end stop
        whole, rest = divmod(self.num_pages, self.chunk)
        #: free ids a chunk (chunk c holds ids c * chunk + 1 ...)
        self._count = [self.chunk] * whole + [rest] * bool(rest)
        #: chunks wholly free, and chunks partly free (a short last chunk
        #: is never whole), oldest first
        self._whole = OrderedDict.fromkeys(range(whole))
        self._partial = OrderedDict.fromkeys(range(whole, whole + bool(rest)))
        self._len = self.num_pages

    def __len__(self) -> int:
        return self._len

    def __iter__(self):
        return (pid for pid in range(1, self.num_pages + 1)
                if self._is_free[pid])

    def _take_id(self, pid: int) -> int:
        c = (pid - 1) // self.chunk
        self._is_free[pid] = 0
        self._count[c] -= 1
        self._len -= 1
        was_whole = self._whole.pop(c, 0) is None
        if not self._count[c]:
            self._partial.pop(c, None)
        elif was_whole:
            self._partial[c] = None
        return pid

    def give(self, pid: int) -> None:
        """``pid`` comes back (its last reference is gone)."""
        c = (pid - 1) // self.chunk
        self._is_free[pid] = 1
        self._count[c] += 1
        self._len += 1
        if self._count[c] == self.chunk:
            self._partial.pop(c, None)
            self._whole[c] = None
        else:
            self._partial[c] = None

    def take(self, after: int = 0, head: bool = True) -> int:
        """One free page for the logical page behind the one that holds id
        ``after`` (0: the row has none there); ``head``: the page starts a
        group of ``chunk`` logical pages. The caller has seen ``len(self)
        > 0``."""
        nxt = after + 1 if after else 0  # id 0 is never free
        if self._is_free[nxt]:
            if not head or (after % self.chunk == 0
                            and (nxt - 1) // self.chunk in self._whole):
                return self._take_id(nxt)
        if head and self._whole:
            return self._take_id(next(iter(self._whole)) * self.chunk + 1)
        if self._is_free[nxt]:
            return self._take_id(nxt)
        c = next(iter(self._partial or self._whole))
        return self._take_id(self._is_free.index(1, c * self.chunk + 1))

    def take_row(self, n: int, first: int = 0, after: int = 0) -> List[int]:
        """``n`` pages for a row's logical pages ``first .. first + n - 1``
        behind the page that holds ``after`` (a prefill's): what ``take``
        gives page by page, a whole group's chunk taken at once."""
        out, s, end, g = [], first, first + n, self.chunk
        while s < end:
            if s % g or end - s < g or not self._whole:
                after = self.take(after, s % g == 0)
                out.append(after)
                s += 1
                continue
            c = after // g   # the neighbour chunk, if ``after`` ends its own
            if not after or after % g or c not in self._whole:
                c = next(iter(self._whole))
            del self._whole[c]
            self._count[c] = 0
            self._len -= g
            self._is_free[c * g + 1:c * g + g + 1] = bytes(g)
            out.extend(range(c * g + 1, c * g + g + 1))
            after = c * g + g
            s += g
        return out


def _is_run(ids) -> bool:
    """Whether a whole group's page ids, in logical order (None: a page the
    row does not hold), are consecutive: what the decode kernel fetches as
    one copy."""
    return bool(ids[0]) and ids == list(range(ids[0], ids[0] + len(ids)))


def _tally_run(runs: set, k: int, ids) -> int:
    """Keep group ``k`` in a row's ``runs`` exactly while ``ids`` (the
    group's pages as the row holds them now) are a whole run; returns the
    change of the count of runs (``gen_page_run_share``)."""
    is_run = len(ids) == RUN_PAGES and _is_run(ids)
    if is_run == (k in runs):
        return 0
    runs.symmetric_difference_update((k,))
    return 1 if is_run else -1


class _PageGroup:
    """What both kinds keep alike: the free list, the reservation, the tally
    of runs, the check of an admission and a row's release. A kind adds
    ``rows``, ``held``, ``needed``, ``admit``, ``grow``, ``apply_updates``
    and ``_group_ids``/``_give_back``."""

    #: whether rows may share a page; the positions a row keeps (None: all);
    #: the name of the group's pages in use in a decode step's record
    shares, window, counted_as = False, None, None
    _where, _knob = "", "num_pages"  # a refused admission's words

    def __init__(self, num_pages, batch_size, page_size, columns, width):
        #: the table's columns a row, and the update entries a row a step
        self.columns, self.width = int(columns), int(width)
        # `is None`, not falsy: a computed num_pages that underflows to 0
        # must hit the engine's error, not the dense-equivalent default
        self.num_pages = int(batch_size * self.columns if num_pages is None
                             else num_pages)
        self.page_size = int(page_size)
        self.free = _FreePages(self.num_pages)
        #: per row, the groups of RUN_PAGES logical pages it holds whole on
        #: consecutive ids (``gen_page_run_share``); their count
        self.runs: List[set] = [set() for _ in range(batch_size)]
        self.n_runs = 0
        #: free pages kept from a step's growth for a parked queue head
        self.reserved = 0

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self.free)

    @property
    def run_share(self) -> float:
        """The share of the pages the rows hold that lie in runs."""
        held = self.held
        return RUN_PAGES * self.n_runs / held if held else 0.0

    def reserve(self, n: int) -> None:
        self.reserved = max(0, int(n))

    def spare(self, unreserved: bool = False) -> int:
        """Pages an admission may count on: the free ones and what the
        prefix cache would give up (``unreserved``: the free ones less the
        reservation, for a request that bypasses a parked head)."""
        return len(self.free) - self.reserved if unreserved \
            else len(self.free) + self._collectable()

    def _collectable(self, protect=()) -> int:
        return 0

    def _own(self, slot: int, protect=()) -> int:  # freed by a release
        return len(self.rows[slot])

    def require(self, slot: int, length: int, adopt=(), protect=()) -> None:
        """RuntimeError where row ``slot`` cannot be given a
        ``length``-token prompt's pages, counting what its previous occupant
        gives back. Nothing changes: a failed admission must leave the
        slot's pending table-clear and its pages as they were, or a stale
        device row could point at pages later handed to someone else."""
        need = self.needed(length, len(adopt))
        headroom = len(self.free) + self._own(slot, protect)
        if headroom < need:
            headroom += self._collectable(protect)
        if headroom < need:
            raise RuntimeError(
                f"insufficient free pages{self._where} for a {length}-token "
                f"prompt ({need} needed, {len(self.free)} free); release "
                f"slots or raise {self._knob}")

    def release(self, slot: int) -> int:
        """Row ``slot`` gives its pages back; returns how many it held."""
        pages = self.rows[slot]
        self.rows[slot] = type(pages)()
        self.n_runs -= len(self.runs[slot])
        self.runs[slot] = set()
        self._give_back(pages)
        return len(pages)

    def _note_group(self, slot: int, k: int) -> None:
        """Count group ``k`` of row ``slot`` as a run, or no longer."""
        self.n_runs += _tally_run(self.runs[slot], k, self._group_ids(slot, k))


class _AllPages(_PageGroup):
    """A group whose layers keep every position: logical page ``s``
    (positions ``s * page_size ...``) lives in column ``s`` of a table of
    ``ceil(max_length / page_size)`` columns, and a row holds every page up
    to its frontier until it is released. Pages carry reference counts, so
    one can back several rows (``fork``) and the prefix cache (``cache``):
    only a count of 0 frees a page, a shared page is copied before a row
    writes into it (``grow`` returns the copies), and under pressure the
    prefix cache's own pages are evicted, oldest first."""

    shares = True

    def __init__(self, num_pages, batch_size, page_size, max_length,
                 width=2, prefix_cache=None):
        self.max_length = int(max_length)
        super().__init__(num_pages, batch_size, page_size,
                         -(-self.max_length // int(page_size)), width)
        #: per row, its page ids in logical order
        self.rows: List[List[int]] = [[] for _ in range(batch_size)]
        #: per-page reference counts (index 0 = trash page, never counted)
        self.rc = np.zeros(self.num_pages + 1, np.int32)
        #: the engine's radix tree of cached prefixes, or None
        self.prefix_cache = prefix_cache

    @property
    def held(self) -> int:
        return sum(map(len, self.rows))

    @property
    def refcount_max(self) -> int:
        return int(self.rc.max()) if self.num_pages else 0

    def covered(self, slot: int) -> int:
        """Positions row ``slot``'s pages cover."""
        return len(self.rows[slot]) * self.page_size

    def needed(self, length: int, adopted: int = 0) -> int:
        """NEW pages of a ``length``-token prompt, ``adopted`` cached."""
        return -(-int(length) // self.page_size) - adopted

    def _group_ids(self, slot, k):
        return self.rows[slot][k * RUN_PAGES:(k + 1) * RUN_PAGES]

    def _cache_only(self, pid) -> bool:
        return self.rc[pid] == 1

    def _collectable(self, protect=()) -> int:
        return 0 if self.prefix_cache is None else \
            self.prefix_cache.collectable(self._cache_only, protect=protect)

    def _own(self, slot, protect=()):
        return sum(1 for pid in self.rows[slot]
                   if self.rc[pid] == 1 and pid not in protect)

    def _unref(self, pages) -> int:
        """Drop one reference from each page; only refcount-0 pages return
        to the free list (one still backing a row or the cache stays)."""
        freed = 0
        for pid in pages:
            self.rc[pid] -= 1
            if self.rc[pid] <= 0:
                self.rc[pid] = 0
                self.free.give(pid)
                freed += 1
        return freed

    def _give_back(self, pages) -> None:
        freed = self._unref(pages)
        if freed:
            _obs.counter("gen_pages_reclaimed_total",
                         "pages returned to the free pool").inc(freed)

    def evict(self, n: int, protect=()) -> int:
        """Free up to ``n`` pages by LRU-evicting cache-only (refcount-1)
        prefix-cache entries; a page a live row still reads is refused."""
        if self.prefix_cache is None:
            return 0
        evicted = self.prefix_cache.evict(n, self._cache_only,
                                          protect=protect)
        if evicted:
            self._unref(evicted)
            _obs.counter("gen_prefix_evictions_total",
                         "prefix-cache pages evicted under free-page "
                         "pressure").inc(len(evicted))
        return len(evicted)

    def admit(self, slot: int, length: int, adopt=(), protect=()):
        """Give row ``slot`` (released, ``require`` passed) a prompt's
        pages: ``adopt``, the cached prefix's, by a reference each, the rest
        fresh, the prefix cache giving way where the free list is short
        (never a page of ``protect``). Returns its table row."""
        adopt = list(adopt)
        need = self.needed(length, len(adopt))
        if need > len(self.free):
            self.evict(need - len(self.free), protect)
        for pid in adopt:
            self.rc[pid] += 1
        fresh = self.free.take_row(need, len(adopt),
                                   adopt[-1] if adopt else 0)
        self.rc[fresh] = 1
        pages = self.rows[slot] = adopt + fresh
        for k in range(len(pages) // RUN_PAGES):
            self._note_group(slot, k)
        if need:
            _obs.counter("gen_page_allocs_total",
                         "pages taken from the free pool").inc(
                             need, site="prefill")
        return self._table_row(pages)

    def _table_row(self, pages):
        row = np.zeros(self.columns, np.int32)
        row[:len(pages)] = pages
        return row

    def fork(self, src: int, dst: int):
        """Row ``dst`` (released) holds row ``src``'s pages too, a reference
        each; returns its table row."""
        pages = self.rows[dst] = list(self.rows[src])
        for pid in pages:
            self.rc[pid] += 1
        self.runs[dst] = set(self.runs[src])
        self.n_runs += len(self.runs[dst])
        return self._table_row(pages)

    def cache(self, slot: int, tokens) -> None:
        """Index row ``slot``'s whole pages under ``tokens``: a newly
        indexed page gains the prefix cache's reference."""
        for pid in self.prefix_cache.insert(tokens, self.rows[slot]):
            self.rc[pid] += 1

    def _take(self, slot: int, s: int) -> int:
        """One free page (refcount 1) for logical page ``s`` of row
        ``slot``, beside page ``s - 1`` where it can be, evicting prefix
        cache entries under pressure; the reservation is off-limits.
        Returns 0 (the trash page, never allocated) when none is free."""
        if len(self.free) - self.reserved <= 0 and not self.evict(1):
            return 0
        pid = self.free.take(self.rows[slot][s - 1] if s else 0,
                             s % RUN_PAGES == 0)
        self.rc[pid] = 1
        return pid

    def grow(self, done, positions, span: int = 0):
        """Before a step: every live row's pages cover positions ``p ..
        min(p + span, max_length - 1)``, and a shared (refcount > 1) page
        the step writes into gets a private copy first, the copy-on-write
        point. Returns the (B, U) update vectors (column, page id; page 0 =
        no entry), the copies ``(row, column, src, dst)`` to run before the
        step, the rows that cannot make their next write, and how many
        pages were taken."""
        ps, last = self.page_size, self.max_length - 1
        shape = (len(self.rows), self.width)
        upd_slots = np.zeros(shape, np.int32)
        upd_pages = np.zeros(shape, np.int32)
        allocated, copies, dry = 0, [], []
        for row in range(len(self.rows)):
            if done[row]:  # before its pages are touched: a step's fixed
                continue   # cost is what the idle slots cost
            pages, p = self.rows[row], int(positions[row])
            need = min(p + span, last) // ps + 1
            short = False
            for s in range(p // ps, min(need, len(pages))):
                pid = pages[s]
                if self.rc[pid] <= 1:
                    continue
                new = self._take(row, s)
                if not new:
                    short = True
                    break
                allocated += 1
                copies.append((row, s, pid, new))
                self.rc[pid] -= 1
                pages[s] = new
                self._note_group(row, s // RUN_PAGES)
            if short:
                dry.append(row)
                continue
            u = 0
            while len(pages) < need:
                s = len(pages)
                pid = self._take(row, s)
                if not pid:
                    if s * ps <= p:  # cannot write the next token
                        dry.append(row)
                    break
                upd_slots[row, u] = s
                upd_pages[row, u] = pid
                pages.append(pid)
                if s % RUN_PAGES == RUN_PAGES - 1:
                    self._note_group(row, s // RUN_PAGES)
                u += 1
                allocated += 1
        if allocated:
            _obs.counter("gen_page_allocs_total",
                         "pages taken from the free pool").inc(
                             allocated, site="decode")
        return upd_slots, upd_pages, copies, dry, allocated

    def apply_updates(self, table, upd_slots, upd_pages, clear):
        """Traced: install the (B, U) update vectors (page 0 = no-op) in
        the device's table, then zero the rows of released slots."""
        bidx = jnp.arange(table.shape[0], dtype=jnp.int32)[:, None]
        cur = table[bidx, upd_slots]
        table = table.at[bidx, upd_slots].set(
            jnp.where(upd_pages > 0, upd_pages, cur))
        return jnp.where(clear[:, None], 0, table)


class _WindowPages(_PageGroup):
    """A ``window`` group: layers that attend only the last ``window``
    positions. A row holds the pages its window reaches; those behind go
    back to the free list while the row lives. The table is a RING of
    ``columns = window // page_size + 3`` columns: logical page ``s`` lives
    in column ``s % columns``, and a row never holds more than ``columns -
    1`` pages. Pages are never shared: no reference counts."""

    counted_as = "window_pages_in_use"
    _where, _knob = " in the window group", "its num_pages"

    def __init__(self, num_pages, batch_size, page_size, window, width=2):
        self.window = int(window)
        super().__init__(num_pages, batch_size, page_size,
                         self.window // int(page_size) + 3, width)
        #: per row {logical page: page id}
        self.rows: List[dict] = [{} for _ in range(batch_size)]
        self.freed_total = 0

    @property
    def held(self) -> int:
        return self.in_use

    def reserve(self, n: int) -> None:
        # a parked head's window pages: never more than a row can hold
        self.reserved = min(self.columns, max(0, int(n)))

    def low_page(self, position: int) -> int:
        """The first logical page a row whose next token lies at
        ``position`` still reads."""
        return max(0, position - self.window + 1) // self.page_size

    def needed(self, length: int, adopted: int = 0) -> int:
        """Pages a prefill of ``length`` tokens takes (none is adopted)."""
        return (length - 1) // self.page_size - self.low_page(length) + 1

    def _group_ids(self, slot, k):
        held = self.rows[slot]
        return [held.get(s) for s in range(k * RUN_PAGES, (k + 1) * RUN_PAGES)]

    def _give_back(self, pages) -> None:
        for pid in pages.values():
            self.free.give(pid)

    def admit(self, slot: int, length: int, adopt=(), protect=()):
        """Give row ``slot`` (released, ``require`` passed) the pages a
        ``length``-token prompt keeps; returns its table row."""
        row = np.zeros(self.columns, np.int32)
        first, end = self.low_page(length), (length - 1) // self.page_size + 1
        ids = self.free.take_row(end - first, first)
        self.rows[slot] = dict(zip(range(first, end), ids))
        row[np.arange(first, end) % self.columns] = ids
        for k in range(first // RUN_PAGES, (end - 1) // RUN_PAGES + 1):
            self._note_group(slot, k)
        return row

    def grow(self, done, positions, span: int = 0):
        """Before a step: every live row takes the page of its next write
        if it lacks it, and gives back the pages now behind its window.
        Returns the (B, U) update vectors (column, page id, or -1 for a
        freed column), no copies, the rows that found the pool (less its
        reservation) dry, and how many pages moved."""
        shape = (len(self.rows), self.width)
        slots, pages = np.zeros(shape, np.int32), np.zeros(shape, np.int32)
        freed, taken, dry = 0, 0, []
        for row in range(len(self.rows)):
            if done[row]:
                continue
            held, position, u = self.rows[row], int(positions[row]), 0
            for s in [s for s in held if s < self.low_page(position)]:
                self.free.give(held.pop(s))
                self._note_group(row, s // RUN_PAGES)
                slots[row, u], pages[row, u] = s % self.columns, -1
                u += 1
                freed += 1
            page = position // self.page_size
            if page in held:
                continue
            if len(self.free) - self.reserved <= 0:
                dry.append(row)
                slots[row] = pages[row] = 0  # the engine ends the row
                continue
            held[page] = self.free.take(held.get(page - 1, 0),
                                        page % RUN_PAGES == 0)
            slots[row, u], pages[row, u] = page % self.columns, held[page]
            taken += 1
            if (page + 1) % RUN_PAGES == 0:  # the group it completes
                self._note_group(row, page // RUN_PAGES)
        if freed:
            self.freed_total += freed
            _obs.counter("gen_window_pages_freed_total",
                         "pages behind a row's window returned to the "
                         "free pool while the row lived").inc(freed)
        return slots, pages, (), dry, freed + taken

    def apply_updates(self, table, upd_slots, upd_pages, clear):
        """As :meth:`_AllPages.apply_updates`, and a page of -1 zeroes its
        column (freed behind the window). Frees come first in a row's
        vector, so a column freed and given in one step ends up given."""
        bidx = jnp.arange(table.shape[0], dtype=jnp.int32)[:, None]
        for u in range(upd_slots.shape[1]):
            col, page = upd_slots[:, u:u + 1], upd_pages[:, u:u + 1]
            table = table.at[bidx, col].set(
                jnp.where(page > 0, page,
                          jnp.where(page < 0, 0, table[bidx, col])))
        return jnp.where(clear[:, None], 0, table)


def group_for(rule, num_pages, batch_size, page_size, max_length, width,
              prefix_cache=None):
    """The allocator of a group a model declares with ``rule``: ``{"window":
    w}`` keeps the last ``w`` positions, ``{}`` every one (``num_pages``
    None: what every row can hold at once)."""
    if rule.get("window"):
        return _WindowPages(num_pages, batch_size, page_size, rule["window"],
                            width)
    return _AllPages(num_pages, batch_size, page_size, max_length, width,
                     prefix_cache)
