"""Compiled KV-cache generation engine (docs/INFERENCE.md).

The training insight of ``TrainStep.run`` — one donated jit program instead
of a per-step dispatch storm — applied to decoding. A naive sampling loop
re-forwards the whole growing sequence every token: O(N·L²) attention
recompute plus a fresh dispatch (or, hybridized, a fresh *compile* per
growing shape). This engine runs a fixed family of compiled programs:

  - **prefill** — the prompt, padded to a static bucket length, runs one
    cached causal forward that writes the prompt's K/V into one row of the
    decode cache and samples the first new token. One XLA program per
    bucket length; admitting a request never touches the other rows.
  - **decode** — one token for every row of the static batch: cache update,
    attention against the full history, sampling (greedy / temperature /
    top-k) and per-row EOS done-masking all compiled in. The cache is a
    donated carry, so XLA updates it in place.

Two serving-scale extensions ride the same no-shape-change discipline:

  - **paged cache** (``paged=True``) — instead of per-row contiguous
    (B, H, Tmax, Ch) buffers, K/V live in a global pool of fixed-size
    pages; each row owns an int32 *page table* riding the compiled carry.
    Admission is bounded by free pages, not slots, so a batch of short
    sequences no longer pays ``Tmax − actual_len`` dead memory per row.
    Pages are reclaimed on ``release_slot``/EOS; a released row's table is
    cleared in-program and its (masked) writes redirect to a reserved
    trash page, so reallocated pages can never be corrupted.
  - **speculative decoding** (``draft_net=`` + ``speculate_k=``) — a small
    draft model proposes k tokens through its own paged cache in ONE
    compiled ``lax.scan`` program, and one target-model *verify* program
    scores all k+1 positions at once: accepted prefixes advance the page
    table in-place, rejected tails simply don't advance the write frontier
    (stale entries stay masked and are overwritten next round). Greedy
    output is token-identical to the non-speculative path; each round costs
    2 dispatches for up to k+1 tokens.

A third serving-scale extension builds on the paged allocator
(docs/INFERENCE.md "Prefix sharing"):

  - **prefix sharing** (``prefix_cache=True``) — the host allocator keeps
    per-page *refcounts*, so a page can back several rows at once.
    ``fork_slot`` clones a row by bumping refcounts (zero pool bytes
    moved); the first write into a shared page triggers a page-granular
    compiled *copy-on-write* program. A radix tree over token-id prefixes
    (:class:`~mxnet_tpu.inference.prefix_cache.RadixPrefixCache`) maps
    prompt heads to cached page runs: prefill adopts the longest cached
    prefix (refcount bump, zero recompute) and runs only the suffix
    through the bucketed prefill programs — the same per-bucket program
    family, with the start offset a traced argument. Under free-page
    pressure, refcount-1 (cache-only) entries are LRU-evicted. Released
    forks decrement refcounts and only refcount-0 pages return to the
    free list, preserving the trash-page-safe reclaim contract.

Speculative decoding composes with stochastic sampling through
*rejection sampling*: the draft scan samples from its own distribution q
(recording q per drafted token), and the verify program accepts token x
with probability ``min(1, p(x)/q(x))`` against the target distribution p,
resampling the first rejection from the normalized residual
``max(p - q, 0)`` — the emitted tokens are distributed exactly as plain
sampled decode.

Nothing in the serving loop changes a shape, so the compiled-program count
is exactly ``len(buckets used) + 1`` (+1 verify when speculating, +1 the
first copy-on-write dispatch) — counted through the observability registry
(``gen_recompiles_total{reason="prefill_bucket"|"decode"|"verify"|
"cow_copy"}``), the same discipline as ``train_recompiles_total``.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..gluon.block import _HybridTrace
from ..ndarray import NDArray
from ..ops import random_ops as _rops
from ..resilience import faults as _faults
from ..resilience import retry as _retry
from . import pages as _pages
from .prefix_cache import RadixPrefixCache

__all__ = ["GenerationEngine", "SamplingConfig"]

logger = logging.getLogger("mxnet_tpu.inference")


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Decode-time sampling, folded into the compiled programs as constants
    (changing it makes a new engine / new programs, counted as recompiles).
    """

    method: str = "greedy"  # greedy | temperature | top_k
    temperature: float = 1.0
    top_k: int = 40
    seed: int = 0

    def __post_init__(self):
        if self.method not in ("greedy", "temperature", "top_k"):
            raise ValueError(f"unknown sampling method {self.method!r}")

    @property
    def stochastic(self) -> bool:
        return self.method != "greedy" and self.temperature > 0


def _default_buckets(max_length: int) -> Tuple[int, ...]:
    out, b = [], 16
    while b < max_length:
        out.append(b)
        b *= 2
    return tuple(out) or (max_length - 1,)


def _count_routes(whole) -> int:
    """A program's expert-layer calls into ``moe_route_total{path}``:
    ``whole`` is the model's count ``moe_whole_path``, an entry a call, 1
    where the call walked every sorted pair and 0 where their prefix
    (``parallel/moe.py:held_expert_ffn``). Returns the whole-length calls."""
    n = int(np.sum(whole))
    route = _obs.counter("moe_route_total",
                         "expert-layer calls by the sorted pairs they walked")
    route.inc(n, path="whole")
    route.inc(np.size(whole) - n, path="prefix")
    return n


#: the models' counts that feed a counter beside the records they land in
_COUNTED = frozenset(("moe_whole_path", "blocks_read", "blocks_held",
                      "positions_run"))


def _count_stats(name, values):
    """A program's count ``name`` (an entry a layer or a call) as it came
    back with the tokens: into its counter where it has one
    (``moe_route_total{path}``; ``gen_blocks_read_total`` and
    ``gen_blocks_held_total``, the blocks a selecting layer's reads visited
    and the blocks their rows held; ``gen_positions_run_total``, the
    positions of the stretches a stretch-major prefill ran). Returns what a prefill's record keeps
    of it: the whole-length calls of the route, else the entries."""
    if name == "moe_whole_path":
        return _count_routes(values)
    if name in _COUNTED:
        _obs.counter(f"gen_{name}_total",
                     f"the models' count {name}, summed over layers, decode "
                     "steps and prefills").inc(int(np.sum(values)))
    return np.asarray(values).tolist()


class GenerationEngine:
    """Compiled autoregressive generation over a static decode batch.

    Parameters
    ----------
    net : an initialized model of the zoo (GPT-2, DeepSeek-V2) or any block
        that provides what the engine asks of a model:

          - ``hybrid_forward(F, tokens, cache=, start_pos=[, page_table=])``
            returning ``(logits, new_cache)`` when ``cache`` is given, or
            ``(logits, new_cache, counts)``: ``counts`` is {name: small int
            array} of what the forward itself counted (an expert layer's
            loads). A decode step's counts leave its program with the
            tokens and land in the step's record;
          - ``_max_length``; ``init_cache(batch, length, dtype)`` (dense) or
            ``init_paged_cache(num_pages, page_size, dtype)`` (paged): the
            model DECLARES its per-layer state, a list with one tuple of
            arrays a layer. The engine reads nothing from their shapes but
            that axis 0 of a paged array is the page (page 0 the trash
            page): a ``(k_pool, v_pool)`` pair, one latent pool, anything;
          - optionally ``paged_read_path(batch_size, pools, page_table)``
            (what the decode program reads the cache by, for
            ``engine.read_path``) and ``logits_width()`` (the vocabulary
            the logits span);
          - optionally **pool groups** (docs/INFERENCE.md "Pool groups"):
            ``paged_pool_groups`` = {group: rule}, the rule ``{}`` (the
            group's layers keep every position) or ``{"window": w}`` (they
            keep the last ``w``: the engine frees the pages behind the
            window while the row lives). ``init_paged_cache`` is then given
            ``num_pages`` as {group: pages} and returns ``(pools, group of
            each layer)``; the forward takes ``page_table=`` as one table a
            group. Each group has its own host allocator, free list, page
            table and gauges. A model that declares nothing has the one
            group ``all``. The prefix cache, forks and speculation are
            refused for a model with a ``window`` group;
          - optionally ``takes_last_pos = True``: the paged prefill passes
            ``last_pos=`` (the prompt's last real position, (1,) int32) and
            gets that position's logits alone, (1, 1, V);
          - optionally **slot state** (docs/INFERENCE.md "Slot state"):
            ``paged_slot_state = True`` says that some layers keep state by
            SLOT, not by position (a recurrent state, whatever the row's
            length). ``init_paged_cache`` is then given ``slots=`` and
            names the group ``slot`` for such a layer, whose arrays have the
            slot as axis 0; they ride in the donated carry with the pools.
            The forward is told ``slot=`` ((1,) int32) by a prefill, which
            writes that row's state from zero, and ``live=`` ((B,) bool) by
            a decode step, which advances the live rows and no other. The
            prefix cache, forks and speculation are refused: each would
            need a copy of a row's state;
          - optionally ``prefill_counts`` = names of the forward's ``counts``
            that a paged PREFILL brings back behind its first token (one
            array, so still one blocking read) into the ``prefill`` record's
            ``counts``; a decode step's counts all come back with its tokens.

        Dropout should be 0 for exact equivalence (evaluation mode disables
        it regardless).
    batch_size : rows of the static decode batch (= serving slots).
    max_length : per-row sequence capacity (default: the net's max_length).
    prefill_buckets : ascending prompt-length buckets; each bucket used
        costs one prefill compile. Default: powers of two from 16.
    eos_id : token that finishes a row (compiled into the done-mask);
        None = rows only finish by max_new_tokens.
    pad_id : token emitted by finished rows and used for prompt padding.
    sampling : SamplingConfig (or method string), compiled in.
    paged : store K/V in a global page pool instead of per-row contiguous
        buffers (docs/INFERENCE.md "Paged cache").
    page_size : tokens per page (paged mode).
    num_pages : pool capacity in pages, excluding the reserved trash page.
        Default: ``batch_size * ceil(max_length / page_size)`` (the
        dense-equivalent capacity — size it DOWN to oversubscribe slots).
        For a model with pool groups, {group: pages} (an int sizes ``all``;
        a ``window`` group defaults to what every row can hold at once).
    draft_net : small initialized model drafting ``speculate_k`` tokens per
        round through its own paged cache (requires ``paged=True``; pass
        ``net`` itself to self-draft). Greedy sampling verifies by exact
        prefix match; stochastic sampling verifies by rejection sampling
        (distribution-identical to plain sampled decode).
    speculate_k : draft window length per speculative round.
    prefix_cache : index computed prefixes in a radix tree so later
        prompts sharing them skip recompute (requires ``paged=True``;
        docs/INFERENCE.md "Prefix sharing").
    layout : optional :class:`~mxnet_tpu.parallel.Layout` — the same
        declarative spec that drives training places the serving weights:
        each parameter is laid out per the layout's rules on the layout's
        mesh before any program compiles. Serving programs themselves stay
        single-program (no pp/ep dispatch loop yet); a layout whose total
        is 1 (or None) keeps today's replicated placement.
    """

    def __init__(self, net, batch_size: int = 4, max_length: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 sampling=None, cache_dtype: str = "float32",
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 draft_net=None, speculate_k: int = 0,
                 prefix_cache: bool = False, layout=None):
        self.net = net
        self.batch_size = int(batch_size)
        self.max_length = int(max_length or net._max_length)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.pad_id = int(pad_id)
        if sampling is None:
            sampling = SamplingConfig()
        elif isinstance(sampling, str):
            sampling = SamplingConfig(method=sampling)
        self.sampling = sampling
        buckets = tuple(sorted(prefill_buckets or
                               _default_buckets(self.max_length)))
        if not buckets or buckets[-1] >= self.max_length:
            raise ValueError(f"prefill buckets {buckets} must be non-empty "
                             f"and < max_length={self.max_length}")
        self.prefill_buckets = buckets

        self._plist = [p for _, p in sorted(net.collect_params().items())]
        for p in self._plist:
            if p._nd is None:
                raise ValueError(f"parameter {p.name} not initialized; run "
                                 "one forward pass first")

        #: declarative parallelism spec (docs/PARALLELISM.md). Weight
        #: placement only: the layout's rules decide each parameter's
        #: sharding on the layout's mesh, so the spec that trained a model
        #: is the spec that serves it — no separate serving placement code.
        self.layout = layout
        if layout is not None and layout.total > 1:
            from jax.sharding import NamedSharding

            mesh = layout.mesh()
            for p in self._plist:
                d = p._nd._data
                p._nd._data = jax.device_put(
                    d, NamedSharding(mesh,
                                     layout.spec_for(p.name, d.shape, mesh)))

        # -- paged / speculative configuration --------------------------------
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.speculate_k = int(speculate_k)
        self.draft_net = draft_net
        if (self.speculate_k > 0) != (draft_net is not None):
            raise ValueError("speculative decoding needs BOTH draft_net= "
                             "and speculate_k >= 1")
        if draft_net is not None and not self.paged:
            raise ValueError("speculative decoding rides the paged cache; "
                             "pass paged=True")
        if (self.speculate_k and self.sampling.method != "greedy"
                and not self.sampling.stochastic):
            # temperature=0 stochastic methods degenerate to argmax but
            # the rejection-sampling residual would be ill-defined
            raise ValueError("speculative decoding needs greedy sampling "
                             "or a stochastic config (temperature > 0): "
                             "stochastic rounds verify by rejection "
                             "sampling, greedy by exact prefix match")
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache=True rides the paged allocator; "
                             "pass paged=True")

        if self.paged:
            if self.page_size < 1:
                raise ValueError("page_size must be >= 1")
            #: the model's pool groups ({group: rule}); one group ``all``
            #: where it declares none
            rules = dict(getattr(net, "paged_pool_groups", None)
                         or {"all": {}})
            windows = [g for g, r in rules.items() if r.get("window")]
            if len(windows) > 1 or set(rules) - set(windows) != {"all"}:
                raise ValueError(
                    f"pool groups {rules}: the engine keeps one group "
                    "'all' and at most one group with a window")
            per_group = dict(num_pages) if isinstance(num_pages, dict) \
                else {"all": num_pages}
            if set(per_group) - set(rules):
                raise ValueError(f"num_pages names groups {sorted(per_group)}"
                                 f"; the model has {sorted(rules)}")
            #: whether some layers keep state by slot (axis 0 = the slot)
            self._slot_state = bool(getattr(net, "paged_slot_state", False))
            if self._slot_state and (prefix_cache or draft_net is not None):
                raise ValueError(
                    "the model keeps state by slot (a recurrent state beside "
                    "the page pools): a prefix adopted or a draft verified "
                    "there would need a copy of a row's state at an earlier "
                    "position, which no page holds. prefix_cache= and "
                    "draft_net= are refused for such a model")
            self.prefix_cache = (RadixPrefixCache(self.page_size)
                                 if prefix_cache else None)
            # worst-case NEW pages per row per dispatch (window k spans at
            # most k//ps + 2 page slots from an arbitrary start offset)
            self._upd_width = self.speculate_k // self.page_size + 2
            #: {group: its host allocator} (``inference/pages.py``) in the
            #: model's order: authoritative; the device tables mirror them
            #: through compiled update vectors shipped with each program
            self._groups = {
                g: _pages.group_for(rule, per_group.get(g), self.batch_size,
                                    self.page_size, self.max_length,
                                    self._upd_width, self.prefix_cache)
                for g, rule in rules.items()}
            for name, g in self._groups.items():
                if g.num_pages < 1:
                    raise ValueError("num_pages must be >= 1")
                if not g.shares and (prefix_cache or draft_net is not None):
                    raise ValueError(
                        f"the model's pool group {name!r} frees the pages "
                        f"behind a window of {g.window} positions: a prefix "
                        "cached or a draft verified there could need a page "
                        "that is gone. prefix_cache= and draft_net= are "
                        "refused for such a model")
            #: the group whose rows may share pages (it keeps every
            #: position): what the public page counts (``num_pages``,
            #: ``free_pages``, ...), the prefix cache and forks speak of
            self._pages = next(g for g in self._groups.values() if g.shares)
            self.num_pages = self._pages.num_pages
            #: device carry: per-row page tables (0 = unallocated/trash),
            #: one table where there is one group, else a tuple in the
            #: groups' order (the form the models are traced with)
            self.page_table = self._form([
                jnp.zeros((self.batch_size, g.columns), jnp.int32)
                for g in self._groups.values()])
            #: device carry: the model's per-layer state, one tuple of page
            #: pools a layer (axis 0 = pages; GPT-2: (k_pool, v_pool),
            #: DeepSeek-V2: one latent pool)
            if getattr(net, "paged_pool_groups", None):
                pools, self.layer_groups = net.init_paged_cache(
                    {n: g.num_pages for n, g in self._groups.items()},
                    self.page_size, dtype=cache_dtype,
                    **({"slots": self.batch_size}
                       if self._slot_state else {}))
                self.layer_groups = tuple(self.layer_groups)
            else:
                pools = net.init_paged_cache(
                    self.num_pages, self.page_size, dtype=cache_dtype)
                self.layer_groups = ("all",) * len(pools)
            self.pools = [tuple(layer) for layer in pools]
            self.cache = None  # dense-only state
            self._pending_clear: set = set()
            #: rows force-finished because the pool ran dry (the batcher
            #: reports these as finish_reason="page_exhausted")
            self.page_exhausted = np.zeros(self.batch_size, bool)
            #: copy-on-write copies per compiled dispatch (chunked)
            self._cow_width = self.batch_size
            self._cow_jit = None  # lazily lowered page-copy program
            #: per-slot prefill logits (device (V,) arrays) — fork_slot's
            #: resample_first draws an independent first token from them
            self._prefill_logits = {}
            self._page_gauges()
            per_token = _obs.gauge("gen_cache_bytes_per_token",
                                   "bytes the paged cache holds for one "
                                   "token, all layers")
            per_token.set(self.cache_bytes_per_token)
            for g in self._groups:  # and one series a pool group
                per_token.set(self._group_bytes_per_token(g), group=g)
            if self._slot_state:
                _obs.gauge("gen_slot_state_bytes",
                           "bytes of the state the model keeps by slot, all "
                           "layers and slots").set(self.slot_state_bytes)
            #: read path of the paged decode program, as the model says it
            #: (the choice is made per shape at trace time, in the operator)
            describe = getattr(net, "paged_read_path", None)
            self.read_path = (
                describe(self.batch_size, self.pools, self.page_table)
                if describe is not None
                else "unknown (the model names no read path)")
            logger.info("paged decode read path: %s", self.read_path)
        else:
            #: device state: per-layer (k_buf, v_buf), the donated carry
            self.cache = net.init_cache(self.batch_size, self.max_length,
                                        dtype=cache_dtype)
            self.prefix_cache = None
            self._groups, self._pages = {}, None
            self._slot_state = False
        #: the groups whose pages in use a decode step's record keeps
        self._counted = [g for g in self._groups.values() if g.counted_as]

        if draft_net is not None:
            self._draft_plist = [p for _, p in
                                 sorted(draft_net.collect_params().items())]
            for p in self._draft_plist:
                if p._nd is None:
                    raise ValueError(f"draft parameter {p.name} not "
                                     "initialized; run one forward first")
            if draft_net._max_length < self.max_length:
                raise ValueError(f"draft_net.max_length "
                                 f"{draft_net._max_length} < engine "
                                 f"max_length {self.max_length}")
            self.draft_pools = draft_net.init_paged_cache(
                self.num_pages, self.page_size, dtype=cache_dtype)

        #: accept stats of the most recent speculative round (read by the
        #: batcher's degradation governor)
        self.last_round_drafted = 0
        self.last_round_accepted = 0
        self._plain_decode_jit = None  # lazy spec-engine fallback program
        self._decode_calls = 0  # the decode step records' step id
        self._prefill_calls = 0  # the prefill records' step id
        #: a decode step dispatched ahead of its call, and the row state it
        #: was built from (decode_step(ahead=True)); bumped by whatever
        #: gives a row another occupant
        self._ahead = None
        self._row_epoch = 0
        #: RetryPolicy for the in-round gen.verify retry (None = config
        #: defaults); ContinuousBatcher installs its own policy here so
        #: one knob governs every serving retry
        self.retry_policy = None

        # host state (tiny (B,) vectors shipped to the device each step —
        # keeping them host-side makes slot admission trivial)
        self.positions = np.zeros(self.batch_size, np.int32)
        self.done = np.ones(self.batch_size, bool)  # empty slots are "done"
        self.last_tokens = np.full(self.batch_size, self.pad_id, np.int32)

        # keep_unused (paged families): flat input positions must be stable
        # for audit()'s carry_indices even when a program has dead params
        # (e.g. the spec prefill discards the draft's logits, killing its
        # final-LN inputs). The dense pair keeps the default — its programs
        # use every input and its shardcheck goldens predate this knob.
        if not self.paged:
            self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=(1,))
            self._decode_jit = jax.jit(self._decode_fn, donate_argnums=(1,))
        elif self.speculative:
            self._prefill_jit = jax.jit(self._spec_prefill_fn,
                                        donate_argnums=(2,),
                                        keep_unused=True)
            # stochastic sampling swaps the greedy prefix-match round for
            # the rejection-sampling pair (sampled draft scan records q;
            # verify accepts with min(1, p/q) and resamples residuals)
            draft_fn = (self._draft_sample_fn if self.sampling.stochastic
                        else self._draft_fn)
            verify_fn = (self._verify_sample_fn if self.sampling.stochastic
                         else self._verify_fn)
            self._draft_jit = jax.jit(draft_fn, donate_argnums=(1,),
                                      keep_unused=True)
            self._verify_jit = jax.jit(verify_fn, donate_argnums=(1,),
                                       keep_unused=True)
        else:
            self._prefill_jit = jax.jit(self._paged_prefill_fn,
                                        donate_argnums=(1,),
                                        keep_unused=True)
            self._decode_jit = jax.jit(self._paged_decode_fn,
                                       donate_argnums=(1,),
                                       keep_unused=True)
        # lowered-program fingerprints seen (cf. TrainStep._note_recompile):
        # a miss means XLA compiles a new executable. Reasons are fixed by
        # contract ("prefill_bucket"/"decode"/"verify") — the guard supplies
        # the event plumbing and the program count (docs/ANALYSIS.md).
        from ..analysis import RecompileGuard

        self._recompile_guard = RecompileGuard(
            "gen_recompiles_total",
            "generation program lowerings (cache misses)")
        self._key = None  # lazily created PRNG key for stochastic sampling
        self._fixed_key = None

    # -- program accounting --------------------------------------------------
    @property
    def compiled_programs(self) -> int:
        """How many XLA executables this engine has lowered (prefill buckets
        actually used + the decode step [+ the verify step])."""
        return len(self._recompile_guard)

    @property
    def speculative(self) -> bool:
        return self.speculate_k > 0

    def _note_program(self, sig, reason) -> bool:
        """Count the program ``sig`` if it is new to this engine; True
        where it was (the call at hand lowers and compiles it)."""
        from ..analysis import Fingerprint

        return self._recompile_guard.observe(
            Fingerprint.of((), sig=sig), reason=reason, group=reason,
            sig=list(map(str, sig))) is not None

    # -- page accounting (paged mode) ----------------------------------------
    def _form(self, per_group):
        """One value a pool group in the form the programs take it: the
        value itself where there is one group, else a tuple in the groups'
        order (the form the models are traced with)."""
        return per_group[0] if len(self._groups) == 1 else tuple(per_group)

    def _each(self, formed) -> tuple:
        """:meth:`_form`'s inverse: one value a pool group."""
        return (formed,) if len(self._groups) == 1 else formed

    @property
    def free_pages(self) -> int:
        """Unallocated pages in the pool (paged mode)."""
        return len(self._pages.free) if self.paged else 0

    @property
    def pages_in_use(self) -> int:
        return self._pages.in_use if self.paged else 0

    def _group_bytes_per_token(self, group: str) -> float:
        """Bytes the layers of ``group`` hold for one token: their pools'
        bytes over their pool's token capacity."""
        total = sum(b.size * b.dtype.itemsize
                    for layer, g in zip(self.pools, self.layer_groups)
                    if g == group for b in layer)
        return total / float((self._groups[group].num_pages + 1)
                             * self.page_size)

    @property
    def cache_bytes_per_token(self) -> float:
        """Bytes the paged cache holds for one token over all layers: the
        pools' bytes over the pool's token capacity (with a window group,
        the groups' sum: a token within the window)."""
        return sum(map(self._group_bytes_per_token, self._groups), 0.0)

    @property
    def slot_state_bytes(self) -> int:
        """Bytes of the state the model keeps by slot (its layers of the
        group ``slot``), over all slots; 0 for a model that keeps none."""
        if not self.paged:
            return 0
        return sum(b.size * b.dtype.itemsize
                   for layer, g in zip(self.pools, self.layer_groups)
                   if g == "slot" for b in layer)

    @property
    def page_groups(self) -> dict:
        """{group: {"num_pages", "in_use", "window"}} of a paged engine's
        pool groups."""
        return {name: {"num_pages": g.num_pages, "in_use": g.in_use,
                       "window": g.window}
                for name, g in self._groups.items()}

    def covers(self, prompt, unreserved: bool = False) -> bool:
        """Whether every pool group has the pages that admitting ``prompt``
        takes: a group's free pages plus what the prefix cache would give
        up (``unreserved``: its free pages less the reservation, what a
        request that bypasses a parked head may take). The group that runs
        short decides."""
        n = len(prompt)
        adopted = (n - self.suffix_for(prompt)) // self.page_size
        return all(g.spare(unreserved) >= g.needed(n, adopted)
                   for g in self._groups.values())

    def pages_for(self, length: int) -> int:
        """Pages a ``length``-token sequence occupies."""
        return -(-int(length) // self.page_size)

    def suffix_for(self, prompt) -> int:
        """Tokens a prefill would actually compute for ``prompt`` after
        prefix adoption (the full length without a prefix cache). Probes
        the radix tree without touching its LRU clock — admission sizing
        is not traffic."""
        n = len(prompt)
        if not self.paged or self.prefix_cache is None or n == 0:
            return n
        _, mtok = self.prefix_cache.lookup(list(prompt), touch=False)
        return n - min(mtok, n - 1)

    def pages_needed(self, prompt) -> int:
        """NEW pages admitting ``prompt`` must supply after prefix reuse
        (paged mode): adopted full pages are refcount bumps, not
        allocations — the admission/shed watermarks must charge only
        these, or fully-cached prompts would shed on a busy pool."""
        if not self.paged:
            return 0
        n = len(prompt)
        adopted_full = (n - self.suffix_for(prompt)) // self.page_size
        return self.pages_for(n) - adopted_full

    def can_admit(self, prompt) -> bool:
        """Whether a prefill of ``prompt`` has a bucket to run in: the
        suffix after prefix adoption must fit a prefill bucket and the
        prompt must fit the row. Session-resume prompts longer than the
        largest bucket are admissible exactly when their cached history
        shrinks the suffix into one."""
        n = len(prompt)
        if n == 0 or (self.paged and n >= self.max_length):
            return False
        try:
            self.bucket_for(self.suffix_for(prompt))
        except ValueError:
            return False
        return True

    @property
    def available_pages(self) -> int:
        """Free pages plus prefix-cache pages evictable under pressure —
        the admission headroom (``free_pages`` alone undercounts once the
        cache holds refcount-1 pages the allocator can LRU-reclaim)."""
        return self._pages.spare() if self.paged else 0

    @property
    def reserved_pages(self) -> int:
        """Free pages currently held back for a parked queue head."""
        return self._pages.reserved if self.paged else 0

    def reserve_pages(self, n: int) -> None:
        """Hold ``n`` free pages of every pool group back from decode-time
        growth (the batcher's aging guard: a queue head deferred too long
        on ``free_pages`` gets freed pages *reserved* instead of watching
        running rows' growth consume them forever). Reserved pages are
        still visible to :meth:`prefill` — the head's admission is exactly
        what they are being saved for. ``n=0`` releases the reservation.
        Rows that cannot cover their next write because of a reservation
        are evicted through the ordinary page-exhaustion path (explicit
        ``page_exhausted`` finish, never a hang)."""
        if not self.paged:
            return
        for g in self._groups.values():
            g.reserve(n)
        _obs.gauge("gen_pages_reserved",
                   "free pages held back for a parked queue head").set(
                       self._pages.reserved)

    def _page_gauges(self):
        _obs.gauge("gen_pages_free",
                   "free pages in the paged KV pool").set(
                       len(self._pages.free))
        in_use = _obs.gauge("gen_pages_in_use",
                            "allocated pages in the paged KV pool")
        in_use.set(self._pages.in_use)
        share = _obs.gauge("gen_page_run_share",
                           "share of the pages the rows hold that lie in a "
                           "whole group of RUN_PAGES logical pages with "
                           "consecutive ids: what the decode kernel fetches "
                           "as one copy")
        # and one series a pool group, from the allocators' own counts
        for name, g in self._groups.items():
            in_use.set(g.in_use, group=name)
            share.set(g.run_share, group=name)
        _obs.gauge("gen_page_refcount_max",
                   "highest per-page refcount (sharing depth)").set(
                       self._pages.refcount_max)

    def _reclaim_row(self, slot: int) -> None:
        """Row ``slot``'s pages go back to their groups."""
        if sum(g.release(slot) for g in self._groups.values()):
            self._page_gauges()

    def _grow_pages(self, span: int):
        """Before a dispatch every pool group grows the active rows' tables
        to cover positions ``p .. min(p + span, max_length - 1)``
        (``pages.py:grow``); the page copies it asks for run first, so a
        forked row's writes can never mutate a page another row or the
        prefix cache still reads; rows that cannot even cover their next
        write are force-finished (evicted) with
        ``gen_page_evictions_total``. Returns the (B, U) update vectors the
        compiled program scatters into the page-table carry, one a group."""
        slots, pages, copies, changed = [], [], [], 0
        for g in self._groups.values():
            upd_slots, upd_pages, cow, dry, moved = g.grow(
                self.done, self.positions, span)
            for row in dry:  # before the next group looks at the row
                self.done[row] = True
                self.page_exhausted[row] = True
                _obs.counter(
                    "gen_page_evictions_total",
                    "rows force-finished on page exhaustion").inc(
                        reason="exhausted")
            slots.append(upd_slots)
            pages.append(upd_pages)
            copies += cow
            changed += moved
        if changed:
            self._page_gauges()
        self._dispatch_cow(copies)
        return slots, pages

    def _dispatch_cow(self, copies) -> None:
        """Run the page-granular copy-on-write program: each (row, slot,
        src, dst) entry copies pool page ``src`` into the private ``dst``
        (every layer; target AND draft pools on a speculative engine) and
        repoints the row's page-table entry — all in-program on the
        donated carry, BEFORE the step program that writes. Entries are
        chunked to a fixed width so the copy program never relowers."""
        if not copies:
            return
        if self._cow_jit is None:
            self._cow_jit = jax.jit(self._cow_copy_fn, donate_argnums=(0,),
                                    keep_unused=True)
        W = self._cow_width
        for i in range(0, len(copies), W):
            chunk = copies[i:i + W]
            rows = np.zeros(W, np.int32)
            slots = np.zeros(W, np.int32)
            src = np.zeros(W, np.int32)  # dst=0 pads: trash-page no-ops
            dst = np.zeros(W, np.int32)
            for j, (r, s, sp, dp) in enumerate(chunk):
                rows[j], slots[j], src[j], dst[j] = r, s, sp, dp
            self._note_program(("cow", W), "cow_copy")
            if self.speculative:
                carry = (self.page_table, self.pools, self.draft_pools)
                carry = self._cow_jit(carry, jnp.asarray(rows),
                                      jnp.asarray(slots), jnp.asarray(src),
                                      jnp.asarray(dst))
                self.page_table, self.pools, self.draft_pools = carry
            else:
                carry = self._cow_jit((self.page_table, self.pools),
                                      jnp.asarray(rows), jnp.asarray(slots),
                                      jnp.asarray(src), jnp.asarray(dst))
                self.page_table, self.pools = carry
        _obs.counter("gen_cow_copies_total",
                     "copy-on-write page copies").inc(len(copies))

    def _take_clear_mask(self):
        """Rows released since the last dispatch: their device page-table
        rows are zeroed in-program BEFORE any write, so writes of a
        released row can never land in a page the allocator has already
        handed to someone else (they go to the trash page instead)."""
        clear = np.zeros(self.batch_size, bool)
        for s in self._pending_clear:
            clear[s] = True
        self._pending_clear.clear()
        return clear

    # -- sampling (compiled into both programs) ------------------------------
    def _sample(self, logits2d, key):
        cfg = self.sampling
        if cfg.method == "greedy":
            return jnp.argmax(logits2d, axis=-1).astype(jnp.int32)
        if cfg.method == "temperature":
            return _rops.temperature_sampling(
                logits2d, temperature=cfg.temperature, key=key)
        return _rops.top_k_sampling(logits2d, k=cfg.top_k,
                                    temperature=cfg.temperature, key=key)

    def _sample_logits(self, logits):
        """The EXACT logit transform the stochastic samplers draw through
        (ops/random_ops.py): optional top-k masking, then temperature
        scaling. ``softmax`` of the result is the sampling distribution —
        the p and q of the rejection-sampling verify must match it
        bit-for-bit or acceptance tests would drift off the plain-decode
        distribution."""
        cfg = self.sampling
        if cfg.method == "top_k":
            k, vocab = int(cfg.top_k), logits.shape[-1]
            if 0 < k < vocab:
                kth = jax.lax.top_k(logits, k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
        return logits.astype(jnp.float32) / float(cfg.temperature)

    def _next_key(self):
        if not self.sampling.stochastic:
            if self._fixed_key is None:
                self._fixed_key = jax.random.key(self.sampling.seed)
            return self._fixed_key
        if self._key is None:
            self._key = jax.random.key(self.sampling.seed)
        self._key, sub = jax.random.split(self._key)
        return sub

    def _params(self):
        return tuple(p._nd._data for p in self._plist)

    def _last_vocab(self) -> int:
        """Logits width of the target model — shape info for audit()'s
        stochastic-verify dummy. The model says it; a model that does not
        is taken to tie its head to ``word_embed``."""
        width = getattr(self.net, "logits_width", None)
        return int(width() if width is not None
                   else self.net.word_embed._input_dim)

    def _draft_params(self):
        return tuple(p._nd._data for p in self._draft_plist)

    def _cache_nd(self, pools):
        return [tuple(NDArray(b) for b in layer) for layer in pools]

    @staticmethod
    def _cached(out):
        """``(logits, new_cache, counts)`` of a model's cached forward;
        ``counts`` is {} for a model that returns none."""
        return out if len(out) == 3 else (*out, {})

    # -- pure programs (dense) -----------------------------------------------
    def _prefill_fn(self, params, cache, tokens, slot, length, key):
        """(params, cache, (1, Lb) tokens, slot, real length, key) ->
        (cache', first sampled token, last-prompt-position logits)."""
        row_cache = [tuple(jax.lax.dynamic_slice_in_dim(b, slot, 1, axis=0)
                           for b in layer) for layer in cache]
        start = jnp.zeros((1,), jnp.int32)
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_rows, _ = self._cached(self.net(
                NDArray(tokens),
                cache=[(NDArray(k), NDArray(v)) for k, v in row_cache],
                start_pos=NDArray(start)))
        logits = logits._data  # (1, Lb, vocab)
        new_cache = [
            tuple(jax.lax.dynamic_update_slice_in_dim(full, row._data, slot,
                                                      axis=0)
                  for full, row in zip(layer, rows))
            for layer, rows in zip(cache, new_rows)]
        last = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                            keepdims=False)[0]  # (vocab,)
        tok = self._sample(last[None, :], key)[0].astype(jnp.int32)
        return new_cache, tok, last

    def _decode_fn(self, params, cache, tokens, positions, done, key):
        """One token for every row: (cache', next tokens, done', logits).
        Finished rows emit ``pad_id`` and keep their cache frontier."""
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_cache, _ = self._cached(self.net(
                NDArray(tokens.reshape(self.batch_size, 1)),
                cache=[(NDArray(k), NDArray(v)) for k, v in cache],
                start_pos=NDArray(positions)))
        logits = logits._data[:, 0]  # (B, vocab)
        sampled = self._sample(logits, key)
        next_tok = jnp.where(done, jnp.int32(self.pad_id), sampled)
        if self.eos_id is not None:
            done = done | (sampled == self.eos_id)
        new_cache = [tuple(b._data for b in layer) for layer in new_cache]
        return new_cache, next_tok.astype(jnp.int32), done, logits

    # -- pure programs (paged) -----------------------------------------------
    def _apply_table_updates(self, table, upd_slots, upd_pages, clear):
        """Scatter the host allocators' decisions into the page-table
        carry, every group's into its own table
        (``pages.py:apply_updates``), and zero the rows of released slots."""
        return self._form([
            g.apply_updates(t, s, p, clear)
            for g, t, s, p in zip(self._groups.values(), self._each(table),
                                  self._each(upd_slots),
                                  self._each(upd_pages))])

    def _row_tables(self, table, new_row, slot):
        """(tables with ``new_row`` installed at ``slot``, that row's own
        (1, columns) tables), each one a pool group."""
        tables, rows = [], []
        for t, r in zip(self._each(table), self._each(new_row)):
            t = jax.lax.dynamic_update_slice(t, r[None, :], (slot, 0))
            tables.append(t)
            rows.append(jax.lax.dynamic_slice(t, (slot, 0), (1, t.shape[1])))
        return self._form(tables), self._form(rows)

    def _table_nd(self, table):
        """The page tables as the model takes them."""
        return self._form([NDArray(t) for t in self._each(table)])

    def _paged_prefill_fn(self, params, carry, tokens, slot, length,
                          new_row, start, key):
        """Paged admission: install the row's freshly allocated page table,
        run the cached causal forward through the pools (scatter writes land
        only in this row's pages + trash), sample the TTFT token. ``start``
        ((1,) int32, traced) is the adopted-prefix length: a prefix-cache
        hit runs only the suffix through this same per-bucket program
        (cold prefill passes 0 — no extra lowering)."""
        table, pools = carry
        table, row_table = self._row_tables(table, new_row, slot)
        # a model that asks for it is told the last real position, and
        # returns that position's logits alone
        only_last = getattr(self.net, "takes_last_pos", False)
        told = {"last_pos": NDArray((length - 1).reshape(1))} \
            if only_last else {}
        if self._slot_state:  # the row whose state this prompt writes
            told["slot"] = NDArray(slot.reshape(1))
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_pools, stats = self._cached(self.net(
                NDArray(tokens), cache=self._cache_nd(pools),
                start_pos=NDArray(start), page_table=self._table_nd(row_table),
                **told))
        logits = logits._data  # (1, Lb, vocab), or (1, 1, vocab)
        new_pools = [tuple(b._data for b in layer) for layer in new_pools]
        last = logits[0, 0] if only_last else jax.lax.dynamic_index_in_dim(
            logits, length - 1, axis=1, keepdims=False)[0]
        tok = self._sample(last[None, :], key)[0].astype(jnp.int32)
        # the counts the model names (``prefill_counts``; the expert layers'
        # route, an entry a call, where it names none) ride behind the
        # token: one array, so still one blocking read (a model without
        # such counts keeps its scalar and its program)
        names = getattr(self.net, "prefill_counts", None) or tuple(
            n for n in ("moe_whole_path",) if n in stats)
        self._prefill_ride = tuple((n, stats[n].size) for n in names)
        if names:
            tok = jnp.concatenate(
                [tok[None]] + [stats[n].reshape(-1).astype(jnp.int32)
                               for n in names])
        return (table, new_pools), tok, last

    def _spec_prefill_fn(self, params, dparams, carry, tokens, slot, length,
                         new_row, start, key):
        """Speculative admission: one program writes the prompt's K/V into
        BOTH the target and the draft page pools (shared page table)."""
        table, pools, dpools = carry
        table = jax.lax.dynamic_update_slice(table, new_row[None, :],
                                             (slot, 0))
        row_table = jax.lax.dynamic_slice(table, (slot, 0),
                                          (1, table.shape[1]))
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_pools, _ = self._cached(self.net(
                NDArray(tokens), cache=self._cache_nd(pools),
                start_pos=NDArray(start), page_table=NDArray(row_table)))
        with _HybridTrace(self._draft_plist, list(dparams), False, key):
            _, new_dpools, _ = self._cached(self.draft_net(
                NDArray(tokens), cache=self._cache_nd(dpools),
                start_pos=NDArray(start), page_table=NDArray(row_table)))
        logits = logits._data
        new_pools = [tuple(b._data for b in layer) for layer in new_pools]
        new_dpools = [tuple(b._data for b in layer) for layer in new_dpools]
        last = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                            keepdims=False)[0]
        tok = self._sample(last[None, :], key)[0].astype(jnp.int32)
        return (table, new_pools, new_dpools), tok, last

    def _paged_decode_fn(self, params, carry, tokens, positions, done,
                         upd_slots, upd_pages, clear, key):
        """The paged decode step: apply page-table updates, then exactly the
        dense decode semantics with pool-indirect storage. The model's counts
        of the step, if it returns any, leave with the tokens."""
        table, pools = carry
        table = self._apply_table_updates(table, upd_slots, upd_pages, clear)
        # slot state is advanced for the rows that decode and no other
        told = {"live": NDArray(~done)} if self._slot_state else {}
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_pools, stats = self._cached(self.net(
                NDArray(tokens.reshape(self.batch_size, 1)),
                cache=self._cache_nd(pools), start_pos=NDArray(positions),
                page_table=self._table_nd(table), **told))
        logits = logits._data[:, 0]
        sampled = self._sample(logits, key)
        next_tok = jnp.where(done, jnp.int32(self.pad_id), sampled)
        if self.eos_id is not None:
            done = done | (sampled == self.eos_id)
        new_pools = [tuple(b._data for b in layer) for layer in new_pools]
        # stats: {} for a model that keeps none, so its program is unchanged
        return ((table, new_pools), next_tok.astype(jnp.int32), done, logits,
                stats)

    def _draft_fn(self, dparams, carry, tokens, positions, done,
                  upd_slots, upd_pages, clear, key):
        """Draft k tokens greedily through the draft model's paged cache —
        the whole loop is ONE ``lax.scan`` program (one dispatch per
        speculative round, not k). The scan runs k+1 steps: step i consumes
        token i (t0, d1, …) writing its K/V at position p+i, so the LAST
        drafted token's entry lands at p+k too — on a full accept the
        frontier advances past it, and a skipped write there would leave a
        permanent zero-K/V hole below the draft frontier. The k+1-th
        sampled token is discarded."""
        table, pools = carry
        table = self._apply_table_updates(table, upd_slots, upd_pages, clear)

        def step(c, i):
            pools_c, tok = c
            with _HybridTrace(self._draft_plist, list(dparams), False, key):
                logits, new_pools, _ = self._cached(self.draft_net(
                    NDArray(tok.reshape(self.batch_size, 1)),
                    cache=self._cache_nd(pools_c),
                    start_pos=NDArray(positions + i),
                    page_table=NDArray(table)))
            new_pools = [tuple(b._data for b in layer)
                         for layer in new_pools]
            nxt = jnp.argmax(logits._data[:, 0], axis=-1).astype(jnp.int32)
            return (new_pools, nxt), nxt

        (pools, _), drafted = jax.lax.scan(
            step, (pools, tokens),
            jnp.arange(self.speculate_k + 1, dtype=jnp.int32))
        return (table, pools), drafted[:self.speculate_k].T  # (B, k)

    def _verify_fn(self, params, carry, tokens, drafted, positions, done,
                   room, key):
        """One target forward scores all k+1 positions: the longest drafted
        prefix the target's own greedy choices agree with is accepted, plus
        the target's correction token. Emission stops at the first EOS and
        at ``room`` (remaining page-covered capacity); rejected tails just
        don't advance the frontier — their K/V entries stay masked and are
        overwritten next round. Returns (carry', (B, k+1) emitted tokens
        padded with pad_id, per-row emit counts, done', accept counts)."""
        table, pools = carry
        k = self.speculate_k
        x = jnp.concatenate([tokens[:, None], drafted], axis=1)  # (B, k+1)
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_pools, _ = self._cached(self.net(
                NDArray(x), cache=self._cache_nd(pools),
                start_pos=NDArray(positions), page_table=NDArray(table)))
        logits = logits._data  # (B, k+1, vocab)
        new_pools = [tuple(b._data for b in layer) for layer in new_pools]
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy next
        match = (drafted == g[:, :k]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)  # accepted drafts
        m = acc + 1  # + the target's correction/bonus token
        if self.eos_id is not None:
            is_eos = g == self.eos_id
            first = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
            m = jnp.minimum(m, jnp.where(is_eos.any(axis=1), first + 1,
                                         k + 1))
        m = jnp.minimum(m, jnp.maximum(room, 0))
        m = jnp.where(done, 0, m)
        emit = jnp.arange(k + 1, dtype=jnp.int32)[None, :] < m[:, None]
        out = jnp.where(emit, g, jnp.int32(self.pad_id))
        if self.eos_id is not None:
            done = done | (emit & (g == self.eos_id)).any(axis=1)
        return (table, new_pools), out, m, done, acc

    def _cow_copy_fn(self, carry, rows, slots, src, dst):
        """The copy-on-write program: page-granular pool copies on the
        donated carry. For each entry, pool page ``src`` is copied into
        the freshly allocated ``dst`` in every layer (target and draft
        pools share page tables, so a speculative engine copies both) and
        the owning row's page-table slot is repointed. Padding entries
        carry ``dst == 0``: their copy lands in the trash page (garbage
        by contract) and the table is left untouched."""
        if self.speculative:
            table, pools, dpools = carry
        else:
            (table, pools), dpools = carry, None

        def copy(ps):
            return [tuple(b.at[dst].set(b[src]) for b in layer)
                    for layer in ps]

        pools = copy(pools)
        if dpools is not None:
            dpools = copy(dpools)
        cur = table[rows, slots]
        table = table.at[rows, slots].set(jnp.where(dst > 0, dst, cur))
        return ((table, pools, dpools) if self.speculative
                else (table, pools))

    def _draft_sample_fn(self, dparams, carry, tokens, positions, done,
                         upd_slots, upd_pages, clear, key):
        """Stochastic draft scan (rejection-sampling speculation): the
        same k+1-step structure as :meth:`_draft_fn`, but each next token
        is SAMPLED from the draft's own decoding distribution q (the
        identical top-k/temperature transform plain decode compiles in),
        and q itself is recorded per drafted token — the verify program's
        ``min(1, p/q)`` accept test needs it. Returns ``(carry',
        (B, k) drafted tokens, (B, k, V) q distributions)``."""
        table, pools = carry
        table = self._apply_table_updates(table, upd_slots, upd_pages, clear)

        def step(c, i):
            pools_c, tok = c
            with _HybridTrace(self._draft_plist, list(dparams), False, key):
                logits, new_pools, _ = self._cached(self.draft_net(
                    NDArray(tok.reshape(self.batch_size, 1)),
                    cache=self._cache_nd(pools_c),
                    start_pos=NDArray(positions + i),
                    page_table=NDArray(table)))
            new_pools = [tuple(b._data for b in layer)
                         for layer in new_pools]
            lg = self._sample_logits(logits._data[:, 0])  # (B, V)
            q = jax.nn.softmax(lg, axis=-1)
            nxt = jax.random.categorical(
                jax.random.fold_in(key, i), lg, axis=-1).astype(jnp.int32)
            return (new_pools, nxt), (nxt, q)

        (pools, _), (drafted, qdist) = jax.lax.scan(
            step, (pools, tokens),
            jnp.arange(self.speculate_k + 1, dtype=jnp.int32))
        k = self.speculate_k
        # drafted: (k+1, B) -> (B, k); qdist: (k+1, B, V) -> (B, k, V)
        return (table, pools), drafted[:k].T, jnp.moveaxis(qdist[:k], 0, 1)

    def _verify_sample_fn(self, params, carry, tokens, drafted, qdist,
                          positions, done, room, key):
        """Rejection-sampling verify: one target forward scores all k+1
        positions; drafted token x_i is accepted with probability
        ``min(1, p_i(x_i)/q_i(x_i))`` (uniform draw), the first rejection
        is resampled from the normalized residual ``max(p_i - q_i, 0)``,
        and a full accept earns a bonus token drawn from p_k — the
        standard speculative-sampling rule, so the emitted tokens are
        distributed EXACTLY as plain sampled decode (gated statistically
        in tests). EOS/room/done clamps mirror the greedy verify."""
        table, pools = carry
        k = self.speculate_k
        B = self.batch_size
        x = jnp.concatenate([tokens[:, None], drafted], axis=1)  # (B, k+1)
        with _HybridTrace(self._plist, list(params), False, key):
            logits, new_pools, _ = self._cached(self.net(
                NDArray(x), cache=self._cache_nd(pools),
                start_pos=NDArray(positions), page_table=NDArray(table)))
        logits = logits._data  # (B, k+1, vocab)
        new_pools = [tuple(b._data for b in layer) for layer in new_pools]
        p = jax.nn.softmax(self._sample_logits(logits), axis=-1)
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
        iidx = jnp.arange(k, dtype=jnp.int32)[None, :]
        p_tok = p[:, :k][bidx, iidx, drafted]  # (B, k) target prob of draft
        q_tok = qdist[bidx, iidx, drafted]     # (B, k) draft prob of draft
        ukey, rkey = jax.random.split(jax.random.fold_in(key, 7))
        u = jax.random.uniform(ukey, (B, k), jnp.float32)
        # u < p/q  <=>  u*q < p (q(x) > 0 a.s.: x was sampled from q)
        accept = (u * q_tok < p_tok).astype(jnp.int32)
        acc = jnp.cumprod(accept, axis=1).sum(axis=1)  # accepted drafts
        # the token at out-index `acc`: residual resample on a rejection,
        # the bonus draw from p_k on a full accept. All k+1 candidate
        # distributions are sampled at once, then gathered at acc.
        resid = jnp.maximum(p[:, :k] - qdist, 0.0)  # (B, k, V)
        rs = resid.sum(axis=-1, keepdims=True)
        # p == q exactly -> empty residual: any draw from p is unbiased
        resid = jnp.where(rs > 0, resid / jnp.maximum(rs, 1e-30), p[:, :k])
        cand = jnp.concatenate([resid, p[:, k:]], axis=1)  # (B, k+1, V)
        corr = jax.random.categorical(
            rkey, jnp.log(jnp.maximum(cand, 1e-38)), axis=-1).astype(
                jnp.int32)  # (B, k+1)
        correction = corr[jnp.arange(B, dtype=jnp.int32), acc]
        pos_idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        padded = jnp.concatenate(
            [drafted, jnp.zeros((B, 1), jnp.int32)], axis=1)
        g = jnp.where(pos_idx < acc[:, None], padded,
                      jnp.where(pos_idx == acc[:, None], correction[:, None],
                                jnp.int32(self.pad_id)))
        m = acc + 1
        if self.eos_id is not None:
            is_eos = (g == self.eos_id) & (pos_idx <= acc[:, None])
            first = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
            m = jnp.minimum(m, jnp.where(is_eos.any(axis=1), first + 1,
                                         k + 1))
        m = jnp.minimum(m, jnp.maximum(room, 0))
        m = jnp.where(done, 0, m)
        emit = pos_idx < m[:, None]
        out = jnp.where(emit, g, jnp.int32(self.pad_id))
        if self.eos_id is not None:
            done = done | (emit & (out == self.eos_id)).any(axis=1)
        return (table, new_pools), out, m, done, acc

    # -- host API ------------------------------------------------------------
    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if b >= length:
                return b
        raise ValueError(f"prompt length {length} exceeds largest prefill "
                         f"bucket {self.prefill_buckets[-1]}")

    def prefill(self, prompt, slot: int) -> int:
        """Admit a prompt into row ``slot``: write its K/V into the cache,
        sample the first new token (returned as a host int — this sync is
        the time-to-first-token point). Never touches other rows. In paged
        mode, allocates ``pages_for(len(prompt))`` pages up front and raises
        RuntimeError if the pool cannot cover them (the batcher checks
        ``free_pages`` before admitting)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        length = prompt.size
        if not 0 < length:
            raise ValueError("empty prompt")
        if not 0 <= slot < self.batch_size:
            raise ValueError(f"slot {slot} out of range")
        # fault site BEFORE any allocator mutation: a retried admission
        # (ContinuousBatcher wraps prefill in retry_call) must replay
        # against untouched page/clear state
        _faults.fire("gen.prefill")
        self._prefill_calls += 1
        # the always-on record of this call (obs.step_records("prefill")):
        # host clock marks around the statements as they stand, and host
        # integers the code holds anyway; nothing of the device is read
        with _obs.step_record("prefill", self._prefill_calls,
                              name="mx.gen.prefill") as rec:
            if self.paged:
                with _obs.span("mx.gen.prefill.pages"):
                    if length >= self.max_length:
                        raise ValueError(
                            f"prompt length {length} >= max_length="
                            f"{self.max_length}")
                    ps = self.page_size
                    # prefix adoption: walk the radix cache for the longest
                    # cached page run, keeping >= 1 suffix token so this
                    # prefill still produces the last-prompt-position
                    # logits (the TTFT sample)
                    adopt: List[int] = []
                    tail_src = 0
                    start = 0
                    if self.prefix_cache is not None:
                        cpages, mtok = self.prefix_cache.lookup(
                            prompt.tolist())
                        start = min(mtok, length - 1)
                        adopt = cpages[:start // ps]
                        if start % ps:
                            # adoption ends inside a cached page: CoW-copy
                            # it into a private page — stale positions past
                            # `start` stay frontier-masked until the suffix
                            # overwrites them
                            tail_src = cpages[start // ps]
                    suffix = length - start
                    bucket = self.bucket_for(suffix)
                    need = self._pages.needed(length, len(adopt))
                    # capacity check BEFORE any allocator mutation (every
                    # group's, ``pages.py:require``). Pages being adopted
                    # are off-limits to the eviction headroom.
                    protect = set(adopt)
                    if tail_src:
                        protect.add(tail_src)
                    for g in self._groups.values():
                        g.require(slot, length, adopt, protect)
                    # previous occupant's pages, if any
                    self._reclaim_row(slot)
                    # the new row replaces it
                    self._pending_clear.discard(slot)
                    self.page_exhausted[slot] = False
                    # one row a group; an adopted prefix costs a reference
                    # a page, no compute
                    new_row = [g.admit(slot, length, adopt, protect)
                               for g in self._groups.values()]
                    if start:
                        _obs.counter(
                            "gen_prefix_hits_total",
                            "prefills that adopted a cached prefix").inc()
                        _obs.counter("gen_prefix_hit_tokens",
                                     "prompt tokens served from the prefix "
                                     "cache").inc(int(start))
                    self._page_gauges()
                    if tail_src:
                        # the copy must land before the prefill dispatch
                        # writes the suffix into the same page
                        self._dispatch_cow([(
                            slot, len(adopt), tail_src,
                            self._pages.rows[slot][len(adopt)])])
                    padded = np.full((1, bucket), self.pad_id, np.int32)
                    padded[0, :suffix] = prompt[start:]
                    rec.counts = {"bucket": bucket, "suffix": suffix,
                                  "prompt": length, "pages": need,
                                  "adopted": len(adopt)}
                    rec.compiled = self._note_program(
                        ("prefill", bucket), "prefill_bucket")
                with _obs.span("mx.gen.prefill.dispatch"):
                    start_v = jnp.full((1,), start, jnp.int32)
                    new_row = self._form([jnp.asarray(r) for r in new_row])
                    if self.speculative:
                        carry = (self.page_table, self.pools,
                                 self.draft_pools)
                        carry, tok, last = self._prefill_jit(
                            self._params(), self._draft_params(), carry,
                            jnp.asarray(padded),
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(suffix, jnp.int32), new_row,
                            start_v, self._next_key())
                        self.page_table, self.pools, self.draft_pools = \
                            carry
                    else:
                        carry, tok, last = self._prefill_jit(
                            self._params(), (self.page_table, self.pools),
                            jnp.asarray(padded),
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(suffix, jnp.int32), new_row,
                            start_v, self._next_key())
                        self.page_table, self.pools = carry
            else:
                with _obs.span("mx.gen.prefill.pages"):
                    bucket = self.bucket_for(length)
                    padded = np.full((1, bucket), self.pad_id, np.int32)
                    padded[0, :length] = prompt
                    rec.counts = {"bucket": bucket, "suffix": length,
                                  "prompt": length, "pages": 0,
                                  "adopted": 0}
                    rec.compiled = self._note_program(
                        ("prefill", bucket), "prefill_bucket")
                with _obs.span("mx.gen.prefill.dispatch"):
                    cache, tok, last = self._prefill_jit(
                        self._params(), self.cache, jnp.asarray(padded),
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray(length, jnp.int32), self._next_key())
                    self.cache = cache
            with _obs.span("mx.gen.prefill.read"):
                tok = np.asarray(tok)  # host sync: the first token is ready
                if tok.ndim:  # with the model's counts behind it
                    at = 1
                    for name, n in self._prefill_ride:
                        rec.counts[name] = _count_stats(name, tok[at:at + n])
                        at += n
                    tok = tok[0]
                tok = int(tok)
            with _obs.span("mx.gen.prefill.index"):
                self._row_epoch += 1
                self.positions[slot] = length
                self.last_tokens[slot] = tok
                self.done[slot] = (self.eos_id is not None
                                   and tok == self.eos_id)
                if self.paged:
                    self._prefill_logits[slot] = last
                    if self.prefix_cache is not None:
                        # index this prompt's full pages so later prompts
                        # sharing the prefix adopt them (newly indexed
                        # pages gain a cache reference; already-cached
                        # prefixes are kept as-is)
                        self._pages.cache(slot, prompt.tolist())
                        self._page_gauges()
        if _obs.enabled():
            _obs.histogram("gen_prefill_seconds", "prompt prefill wall clock",
                           unit="s").observe(1e-9 * rec.duration_ns,
                                             bucket=bucket)
        self._last_logits = last
        return tok

    def decode_step(self, ahead: bool = False):
        """One compiled step over the whole batch. Returns
        ``(next_tokens (B,) np.int32, done (B,) np.bool_, logits (B, V)
        device array)``. Rows that were already done emit ``pad_id``.

        ``ahead=True`` says that the caller expects to call ``decode_step``
        again before any row changes hands (no prefill, release or fork in
        between). The engine may then dispatch that next step at once,
        behind this one and before it reads this one's tokens, so the
        device does not stand idle while the host turns round
        (docs/INFERENCE.md "Decoding ahead"). Should a row change hands
        after all, the step dispatched ahead is dropped and run again:
        the same tokens, at the cost of one step's device time."""
        if self.speculative:
            raise RuntimeError("speculative engine decodes in rounds; "
                               "use spec_step() (or plain_step() for the "
                               "degrade-to-plain fallback)")
        return self._plain_decode_step(ahead)

    def plain_step(self):
        """One plain (non-speculative) decode step on ANY engine — the
        degrade-to-safe path of a speculative engine when the accept rate
        collapses (docs/RESILIENCE.md "Serving resilience"): one dispatch
        per token through the same paged pools, greedy-token-identical to
        the speculative rounds. The draft model's cache is NOT written
        during fallback, so rows decoded here have draft-cache holes after
        a re-arm — an accept-rate cost only, never a correctness one."""
        return self._plain_decode_step()

    def _plain_decode_step(self, ahead=False):
        _faults.fire("gen.decode")
        self._decode_calls += 1
        # the always-on record of this call (obs.step_records("decode_step")):
        # host clock marks, and the model's own counts of the step, which
        # come back with the tokens in the one blocking read below
        with _obs.step_record("decode_step", self._decode_calls,
                              name="mx.gen.decode") as rec:
            tok, done, logits, active_in, stats = (
                self._take_ahead() or self._dispatch_decode())
            # rows active going into the step consumed one cache index
            positions = self.positions + active_in.astype(np.int32)
            # a row whose frontier hit the buffer end cannot take another
            # token
            full = active_in & (positions >= self.max_length)
            if ahead and self._may_decode_ahead():
                # without an EOS id the rows' state after this step is known
                # before its tokens are: the next step takes them from the
                # device and is queued behind this one
                self.positions, self.done = positions, self.done | full
                self._ahead = [self._dispatch_decode(tok), None]
            with _obs.span("mx.gen.decode.read"):
                # np.array (copy): zero-copy views of jax buffers are
                # read-only, and this host state is mutated by
                # release_slot/prefill
                tok, done, stats = jax.device_get((tok, done, stats))
                tok, done = np.array(tok), np.array(done)
                if stats:
                    rec.counts = {k: v.tolist() for k, v in stats.items()}
                    for name in _COUNTED & set(stats):
                        _count_stats(name, stats[name])
                for g in self._counted:
                    # the engine's own count beside the model's: the
                    # group's pages in use as this step left them
                    rec.counts = {**(rec.counts or {}),
                                  g.counted_as: [g.in_use]}
        self.positions = positions
        if full.any():
            done = done | full
            _obs.counter("gen_cache_overflow_total",
                         "rows force-finished at the KV-cache end").inc(
                             int(full.sum()))
        self.done = done
        self.last_tokens = tok
        if self._ahead is not None:  # the rows as the step ahead left them
            self._ahead[1] = (self._row_epoch, positions.copy(), done.copy(),
                              tok.copy())
        if _obs.enabled():
            _obs.histogram("gen_decode_step_seconds",
                           "one compiled decode step wall clock",
                           unit="s").observe(1e-9 * rec.duration_ns)
            # slot utilization of this step: fraction of the static batch
            # that decoded real tokens (the fleet report's serving rollup)
            _obs.gauge("gen_slot_utilization",
                       "fraction of decode slots active this step").set(
                           float(active_in.sum()) / self.batch_size)
        return tok, done, logits

    def _may_decode_ahead(self):
        """What the next step needs is known before this step's tokens are:
        a plain paged greedy engine with no EOS id (``done`` then follows
        from lengths alone, and a dropped step costs no random key), and a
        free page for every row in every pool group (growing the rows'
        tables cannot evict)."""
        return (self.paged and not self.speculative and self.eos_id is None
                and not self.sampling.stochastic
                and all(len(g.free) >= self.batch_size
                        for g in self._groups.values()))

    def _vectors(self, upd):
        """A dispatch's update vectors (:meth:`_grow_pages`) on the device,
        in the form the programs take them."""
        return self._form([jnp.asarray(u) for u in upd])

    def _take_ahead(self):
        """The step dispatched ahead, if the rows are as it left them;
        else None, and that step is dropped (its writes lie at positions
        the rows' next step writes again, or past a released row's end)."""
        pending, self._ahead = self._ahead, None
        if pending is None:
            return None
        out, rows = pending
        same = (rows is not None and rows[0] == self._row_epoch
                and np.array_equal(rows[1], self.positions)
                and np.array_equal(rows[2], self.done)
                and np.array_equal(rows[3], self.last_tokens))
        _obs.counter("gen_decode_ahead_total",
                     "decode steps dispatched ahead of their call").inc(
                         outcome="used" if same else "dropped")
        return out if same else None

    def _dispatch_decode(self, tokens=None):
        """Dispatch the single-token decode program and commit its carry;
        returns device ``(tokens, done, logits)``, the rows that were
        active going in, and the model's step counts (``{}`` if none).
        ``tokens``: the rows' last tokens still on the device, for a step
        dispatched ahead (host span ``mx.gen.decode.ahead``); default the
        host's."""
        stats = {}
        span = "mx.gen.decode." + ("dispatch" if tokens is None else "ahead")
        if tokens is None:
            tokens = self.last_tokens
        if self.paged:
            # the allocator's part of the step's host time, apart from the
            # arguments' hand-over and the call (the span below)
            with _obs.span("mx.gen.decode.pages"):
                upd_slots, upd_pages = self._grow_pages(0)
                clear = self._take_clear_mask()
            active_in = ~self.done  # exhaustion may have finished rows
            if self.speculative:
                # the spec engine compiled draft+verify, not a single-token
                # decode: lower the fallback program lazily on first use
                # (counted like every other program lowering)
                if getattr(self, "_plain_decode_jit", None) is None:
                    self._plain_decode_jit = jax.jit(
                        self._paged_decode_fn, donate_argnums=(1,),
                        keep_unused=True)
                decode_jit = self._plain_decode_jit
            else:
                decode_jit = self._decode_jit
            self._note_program(("decode", self.batch_size, "paged"), "decode")
            with _obs.span(span):
                carry, tok, done, logits, stats = decode_jit(
                    self._params(), (self.page_table, self.pools),
                    jnp.asarray(tokens), jnp.asarray(self.positions),
                    jnp.asarray(self.done), self._vectors(upd_slots),
                    self._vectors(upd_pages), jnp.asarray(clear),
                    self._next_key())
            self.page_table, self.pools = carry
        else:
            active_in = ~self.done
            self._note_program(("decode", self.batch_size), "decode")
            with _obs.span(span):
                cache, tok, done, logits = self._decode_jit(
                    self._params(), self.cache, jnp.asarray(tokens),
                    jnp.asarray(self.positions), jnp.asarray(self.done),
                    self._next_key())
            self.cache = cache
        return tok, done, logits, active_in, stats

    def spec_step(self):
        """One speculative round: ONE draft dispatch (k tokens through the
        draft cache, compiled scan) + ONE verify dispatch (target scores all
        k+1 positions). Returns ``(tokens (B, k+1) np.int32 padded with
        pad_id, counts (B,) np.int32 emitted per row, done (B,)
        np.bool_)``. Greedy output is token-identical to decode_step
        driven to the same length."""
        if not self.speculative:
            raise RuntimeError("spec_step() needs draft_net=/speculate_k=")
        _faults.fire("gen.decode")  # before any allocator mutation: the
        # batcher's retry_call replays the whole round cleanly
        k = self.speculate_k
        t0 = time.perf_counter()
        upd_slots, upd_pages = self._grow_pages(k)
        clear = self._take_clear_mask()
        active_in = ~self.done  # exhaustion may have finished rows
        # committed entries may only land in page-covered positions: the
        # verify program clamps per-row emission to this window
        room = np.zeros(self.batch_size, np.int32)
        for row in range(self.batch_size):
            room[row] = min(self._pages.covered(row), self.max_length) \
                - int(self.positions[row])
        upd_slots = self._vectors(upd_slots)
        upd_pages = self._vectors(upd_pages)
        key = self._next_key()
        self._note_program(("draft", self.batch_size, k), "decode")
        stochastic = self.sampling.stochastic
        qdist = None
        if stochastic:
            # rejection-sampling round: the draft records its sampling
            # distribution q per drafted token, device-resident into verify
            (table, dpools), drafted, qdist = self._draft_jit(
                self._draft_params(), (self.page_table, self.draft_pools),
                jnp.asarray(self.last_tokens), jnp.asarray(self.positions),
                jnp.asarray(self.done), upd_slots, upd_pages,
                jnp.asarray(clear), key)
        else:
            (table, dpools), drafted = self._draft_jit(
                self._draft_params(), (self.page_table, self.draft_pools),
                jnp.asarray(self.last_tokens), jnp.asarray(self.positions),
                jnp.asarray(self.done), upd_slots, upd_pages,
                jnp.asarray(clear), key)
        # commit the draft half's carry BEFORE the verify dispatch: the
        # old page_table buffer was donated to the draft program, and the
        # gen.verify fault site below must leave the engine re-entrant (a
        # retried spec_step re-runs the draft from the same positions —
        # deterministic overwrites of the same cache entries)
        self.page_table, self.draft_pools = table, dpools
        self._note_program(("verify", self.batch_size, k), "verify")

        def _dispatch_verify():
            _faults.fire("gen.verify")
            if stochastic:
                return self._verify_jit(
                    self._params(), (self.page_table, self.pools),
                    jnp.asarray(self.last_tokens), drafted, qdist,
                    jnp.asarray(self.positions), jnp.asarray(self.done),
                    jnp.asarray(room), key)
            return self._verify_jit(
                self._params(), (self.page_table, self.pools),
                jnp.asarray(self.last_tokens), drafted,
                jnp.asarray(self.positions), jnp.asarray(self.done),
                jnp.asarray(room), key)

        (table, pools), out, m, done, acc = _retry.retry_call(
            _dispatch_verify, site="gen.verify", policy=self.retry_policy)
        self.page_table, self.pools = table, pools
        out = np.array(out)
        m = np.array(m)
        done = np.array(done)
        acc = np.array(acc)
        self.positions = self.positions + m.astype(np.int32)
        took = m > 0
        last = out[np.arange(self.batch_size), np.maximum(m - 1, 0)]
        self.last_tokens = np.where(took, last,
                                    self.last_tokens).astype(np.int32)
        full = active_in & (self.positions >= self.max_length)
        if full.any():
            done = done | full
            _obs.counter("gen_cache_overflow_total",
                         "rows force-finished at the KV-cache end").inc(
                             int(full.sum()))
        self.done = done
        n_active = int(active_in.sum())
        _obs.counter("gen_spec_rounds_total",
                     "speculative draft+verify rounds").inc()
        # per-round accept stats for the degradation governor
        # (resilience.serving.SpeculationGovernor reads them after each
        # round the batcher dispatches)
        self.last_round_drafted = k * n_active
        self.last_round_accepted = int(acc[active_in].sum()) if n_active \
            else 0
        if n_active:
            accepted = int(acc[active_in].sum())
            _obs.counter("gen_spec_drafted_tokens_total",
                         "draft tokens proposed").inc(k * n_active)
            _obs.counter("gen_spec_accepted_tokens_total",
                         "draft tokens the target accepted").inc(accepted)
            _obs.counter("gen_spec_emitted_tokens_total",
                         "tokens emitted by speculative rounds").inc(
                             int(m.sum()))
            _obs.gauge("gen_spec_accept_rate",
                       "accepted/drafted ratio of the last round").set(
                           accepted / float(k * n_active))
        if _obs.enabled():
            _obs.histogram("gen_spec_round_seconds",
                           "one draft+verify round wall clock",
                           unit="s").observe(time.perf_counter() - t0)
            _obs.gauge("gen_slot_utilization",
                       "fraction of decode slots active this step").set(
                           float(active_in.sum()) / self.batch_size)
        return out, m, done

    def lower_decode(self):
        """Lower (don't run) the single-token decode program this engine
        dispatches, through the engine's own jit function — the serving
        counterpart of ``TrainStep.lower_hlo``. ``.as_text()`` /
        ``.compile().as_text()`` of the result show what the program was
        built with (e.g. a ``tpu_custom_call`` per Pallas kernel)."""
        if self.speculative:
            raise RuntimeError("a speculative engine decodes through its "
                               "draft/verify pair; see audit()")
        # constant dummy key: lowering never runs the program, and drawing
        # from _next_key() would advance the stochastic-sampling stream
        key = jax.random.key(0)
        toks = jnp.asarray(self.last_tokens)
        pos = jnp.asarray(self.positions)
        done = jnp.asarray(self.done)
        if not self.paged:
            return self._decode_jit.lower(self._params(), self.cache, toks,
                                          pos, done, key)
        upd = self._form([jnp.zeros((self.batch_size, self._upd_width),
                                    jnp.int32)] * len(self._groups))
        clear = jnp.zeros((self.batch_size,), bool)
        return self._decode_jit.lower(
            self._params(), (self.page_table, self.pools), toks, pos, done,
            upd, upd, clear, key)

    def lower_prefill(self, bucket: int):
        """Lower (don't run) the prefill program of ``bucket`` (a plain
        paged engine's), as :meth:`lower_decode` does the decode step."""
        if not self.paged or self.speculative:
            raise RuntimeError("lower_prefill is for a plain paged engine; "
                               "see audit(bucket=)")
        bucket = self.bucket_for(bucket)
        return self._prefill_jit.lower(
            self._params(), (self.page_table, self.pools),
            jnp.full((1, bucket), self.pad_id, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(bucket, jnp.int32),
            jax.tree.map(lambda t: jnp.zeros((t.shape[1],), jnp.int32),
                         self.page_table),
            jnp.zeros((1,), jnp.int32), jax.random.key(0))

    def op_scopes(self, bucket: Optional[int] = None):
        """{HLO instruction name: scope path} of the decode program (or of
        the prefill program of ``bucket``): the join key for a device
        trace (``MeasuredReport.scope_seconds``; docs/OBSERVABILITY.md
        "Named scopes"). Paths start at the model's own block scope
        (``<model>/layer3/mla/core``). Compiles the lowered program once
        more (``scopes.scoped_text`` says why)."""
        from ..observability.scopes import op_scopes_from_hlo, scoped_text

        lowered = (self.lower_decode() if bucket is None
                   else self.lower_prefill(bucket))
        return op_scopes_from_hlo(scoped_text(lowered),
                                  scopes=(self.net._scope_label(None),))

    def audit(self, bucket: Optional[int] = None, compile: bool = True,
              program: str = "decode"):
        """Structural :class:`~mxnet_tpu.analysis.ProgramAudit` of a
        serving program (docs/ANALYSIS.md). Default: the decode step —
        ``carry_indices`` are the flat positions of the cache buffers (the
        donated carry: KV buffers, or page table + pools in paged mode), so
        ``audit().carry_donation() == 1.0`` is the in-place-cache-update
        check. With ``bucket=`` the prefill program for that bucket length
        is audited instead (same donated cache). On a speculative engine,
        ``program="decode"`` audits the draft program (its decode-family
        program) and ``program="verify"`` the verify pass. On any paged
        engine ``program="cow"`` audits the copy-on-write page-copy
        program (prefix sharing / forks): carry-only inputs, 100%
        donation, zero collectives.

        ``audit(...).memory`` is the buffer-liveness residency estimate:
        cache bytes appear under the ``kv_pages`` (paged) / ``kv_cache``
        (dense) category, model weights under ``params``, and the
        program's own temporaries under ``activations`` /
        ``draft_temp`` / ``verify_temp`` — including the
        ``kv_gather_materialize`` detector for the paged decode's XLA
        gather of the pool (docs/ANALYSIS.md)."""
        from .. import analysis as _analysis

        params = self._params()
        n_pre = len(jax.tree_util.tree_leaves(params))
        # constant dummy key: lower() never runs the program, and drawing
        # from _next_key() would advance the stochastic-sampling stream —
        # an audit() between decode steps must not change the tokens
        key = jax.random.key(0)
        toks = jnp.asarray(self.last_tokens)
        pos = jnp.asarray(self.positions)
        done = jnp.asarray(self.done)
        if not self.paged:
            carry = self.cache
            if bucket is None:
                lowered = self.lower_decode()
            else:
                bucket = self.bucket_for(bucket)
                tokens = jnp.full((1, bucket), self.pad_id, jnp.int32)
                lowered = self._prefill_jit.lower(
                    params, carry, tokens, jnp.asarray(0, jnp.int32),
                    jnp.asarray(bucket, jnp.int32), key)
        else:
            upd = jnp.zeros((self.batch_size, self._upd_width), jnp.int32)
            clear = jnp.zeros((self.batch_size,), bool)
            if bucket is not None and self.speculative:
                bucket = self.bucket_for(bucket)
                dparams = self._draft_params()
                n_pre += len(jax.tree_util.tree_leaves(dparams))
                carry = (self.page_table, self.pools, self.draft_pools)
                lowered = self._prefill_jit.lower(
                    params, dparams, carry,
                    jnp.full((1, bucket), self.pad_id, jnp.int32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(bucket, jnp.int32),
                    jnp.zeros((self.page_table.shape[1],), jnp.int32),
                    jnp.zeros((1,), jnp.int32), key)
            elif bucket is not None:
                carry = (self.page_table, self.pools)
                lowered = self.lower_prefill(bucket)
            elif program == "cow":
                # the copy-on-write page-copy program: no params at all —
                # the donated carry's leaves lead the flat input order
                if self._cow_jit is None:
                    self._cow_jit = jax.jit(self._cow_copy_fn,
                                            donate_argnums=(0,),
                                            keep_unused=True)
                n_pre = 0
                vec = jnp.zeros((self._cow_width,), jnp.int32)
                if self.speculative:
                    carry = (self.page_table, self.pools, self.draft_pools)
                else:
                    carry = (self.page_table, self.pools)
                lowered = self._cow_jit.lower(carry, vec, vec, vec, vec)
            elif program == "verify":
                if not self.speculative:
                    raise ValueError("program='verify' needs a speculative "
                                     "engine (draft_net=/speculate_k=)")
                carry = (self.page_table, self.pools)
                drafted = jnp.zeros((self.batch_size, self.speculate_k),
                                    jnp.int32)
                room = jnp.zeros((self.batch_size,), jnp.int32)
                if self.sampling.stochastic:
                    vocab = self._last_vocab()
                    qd = jnp.zeros((self.batch_size, self.speculate_k,
                                    vocab), jnp.float32)
                    lowered = self._verify_jit.lower(params, carry, toks,
                                                     drafted, qd, pos,
                                                     done, room, key)
                else:
                    lowered = self._verify_jit.lower(params, carry, toks,
                                                     drafted, pos, done,
                                                     room, key)
            elif self.speculative:
                dparams = self._draft_params()
                n_pre = len(jax.tree_util.tree_leaves(dparams))
                carry = (self.page_table, self.draft_pools)
                lowered = self._draft_jit.lower(dparams, carry, toks, pos,
                                                done, upd, upd, clear, key)
            else:
                carry = (self.page_table, self.pools)
                lowered = self.lower_decode()
        n_carry = len(jax.tree_util.tree_leaves(carry))
        # flat arg order: (params [+ draft params]) leaves, then the cache
        # leaves (the donated carry)
        lowered_rep = _analysis.audit_lowered(lowered)
        compiled_rep = (_analysis.audit_compiled(lowered.compile())
                        if compile else None)
        # serving programs run mesh-less today, so the comm report is the
        # "no collectives crept into the decode path" check — any priced
        # collective here is a regression tools/shardcheck.py catches
        rep = compiled_rep if compiled_rep is not None else lowered_rep
        comm = _analysis.comm_report(rep)
        # residency estimate with serving categories: the donated cache
        # carry is "kv_pages" (page table + pools) in paged mode and
        # "kv_cache" (per-layer K/V buffers) in dense mode; the
        # draft/verify programs tag their temporaries distinctly
        kv_cat = "kv_pages" if self.paged else "kv_cache"
        mem_cats = {i: "params" for i in range(n_pre)}
        mem_cats.update({i: kv_cat
                         for i in range(n_pre, n_pre + n_carry)})
        for i in range(n_pre + n_carry, len(rep.inputs)):
            mem_cats[i] = "io"
        if program == "verify":
            default_cat = "verify_temp"
        elif self.speculative and bucket is None and program != "cow":
            default_cat = "draft_temp"
        else:
            default_cat = "activations"
        memory = _analysis.memory_report(rep, categories=mem_cats,
                                         default_category=default_cat)
        return _analysis.ProgramAudit(
            lowered=lowered_rep, compiled=compiled_rep,
            carry_indices=tuple(range(n_pre, n_pre + n_carry)),
            comm=comm, memory=memory)

    def profile(self, prompt=None, steps: int = 8, warmup: int = 2,
                trace_dir: Optional[str] = None):
        """Trace ``steps`` REAL decode steps (speculative rounds on a
        speculative engine) and return the
        :class:`~mxnet_tpu.observability.profiling.Capture` — the
        measured per-op timeline of the serving hot loop, hot-op ranking
        and measured step time (docs/OBSERVABILITY.md "Measured
        profiling"). The dispatch goes through the engine's own
        ``_decode_jit``/``_draft_jit`` caches, so the traced program IS
        the program continuous batching dispatches. ``prompt`` (default
        a short synthetic one) is prefilled into slot 0 first, outside
        the traced window, so the decode has a live row to extend; the
        slot is released afterwards."""
        from ..observability import profiling as _profiling

        if prompt is None:
            prompt = list(range(1, 1 + min(4, self.prefill_buckets[0])))
        self.prefill(prompt, slot=0)
        fn = self.spec_step if self.speculative else self.decode_step
        try:
            return _profiling.capture(fn, steps=steps, warmup=warmup,
                                      trace_dir=trace_dir)
        finally:
            self.release_slot(0)

    def fork_slot(self, src: int, dst: int,
                  resample_first: bool = False) -> int:
        """Copy-on-write fork: row ``dst`` becomes a live clone of row
        ``src`` sharing every page — a refcount bump per page, zero pool
        bytes moved. Divergence is lazy: the first write either row makes
        into a shared page triggers the page-granular copy program
        (:meth:`_grow_pages`), so N forks of a P-page prompt cost P pages
        total plus each fork's private suffix.

        ``resample_first=True`` draws an independent first token from the
        source row's prefill logits (N-way parallel sampling: fork right
        after :meth:`prefill`, before any decode step — later forks would
        re-sample a stale position). Returns ``dst``'s current last token.
        """
        if not self.paged:
            raise RuntimeError("fork_slot needs a paged engine")
        if not all(g.shares for g in self._groups.values()):
            raise RuntimeError(
                "fork_slot shares pages between rows; a model with a window "
                "pool group frees a row's pages behind its window, so its "
                "rows cannot be forked")
        if self._slot_state:
            raise RuntimeError(
                "fork_slot shares pages between rows; a model that keeps "
                "state by slot holds a row's recurrent state in no page, so "
                "its rows cannot be forked")
        if src == dst or not (0 <= src < self.batch_size
                              and 0 <= dst < self.batch_size):
            raise ValueError(f"bad fork {src} -> {dst}")
        if self.done[src] or not self._pages.covered(src):
            raise RuntimeError(f"cannot fork finished/empty row {src}")
        self._row_epoch += 1
        self._reclaim_row(dst)  # previous occupant's pages, if any
        self._pending_clear.discard(dst)
        self.page_exhausted[dst] = False
        # eager device-table install: forks happen at admission
        # boundaries, not in the per-token hot loop
        self.page_table = self._form([
            t.at[dst].set(jnp.asarray(g.fork(src, dst)))
            for g, t in zip(self._groups.values(),
                            self._each(self.page_table))])
        self.positions[dst] = self.positions[src]
        tok = int(self.last_tokens[src])
        if resample_first:
            logits = self._prefill_logits.get(src)
            if logits is None:
                raise RuntimeError(f"row {src} has no prefill logits to "
                                   "resample from")
            tok = int(self._sample(logits[None, :], self._next_key())[0])
            self._prefill_logits[dst] = logits
        self.last_tokens[dst] = tok
        self.done[dst] = (self.eos_id is not None and tok == self.eos_id)
        self._page_gauges()
        _obs.counter("gen_forks_total", "copy-on-write row forks").inc()
        return tok

    def cache_sequence(self, slot: int, tokens) -> int:
        """Index a live row's computed pages under ``tokens`` (the
        sequence the row holds K/V for: prompt + generated output) in the
        radix prefix cache — the multi-turn session-resume hook: the
        batcher calls this right before releasing a finished row, and the
        next turn's prompt (history + new text) adopts the whole history
        as a prefix hit. Only positions the row has actually written
        (``positions[slot]``) and only full pages are indexed. Returns
        the number of tokens now served from cache for this sequence."""
        if not self.paged or self.prefix_cache is None:
            return 0
        n = min(len(tokens), int(self.positions[slot]))
        if n < self.page_size:
            return 0
        self._pages.cache(slot, list(tokens)[:n])
        self._page_gauges()
        return (n // self.page_size) * self.page_size

    def release_slot(self, slot: int) -> None:
        """Mark a row free (emits pad, frontier frozen) — the next prefill
        into this slot overwrites it. In paged mode, the row's references
        are dropped and only refcount-0 pages return to the free pool
        (pages still backing a fork or the prefix cache stay allocated);
        the row's device page-table row is cleared before the next
        compiled step writes anything."""
        self._row_epoch += 1
        self.done[slot] = True
        self.last_tokens[slot] = self.pad_id
        if self.paged:
            self._reclaim_row(slot)
            self._pending_clear.add(slot)
            self._prefill_logits.pop(slot, None)

    # -- convenience: whole-batch generation ---------------------------------
    def generate(self, prompts, max_new_tokens: int = 32) -> List[List[int]]:
        """Generate up to ``max_new_tokens`` for each prompt (≤ batch_size
        prompts, one slot each). Returns the generated token lists (prompt
        excluded); rows stop at EOS, max_new_tokens, or a full cache."""
        if len(prompts) > self.batch_size:
            raise ValueError(f"{len(prompts)} prompts > batch_size="
                             f"{self.batch_size}; use ContinuousBatcher")
        if self.paged:
            for s in range(self.batch_size):  # park rows + reclaim pages
                self.release_slot(s)
        else:
            self.done[:] = True  # park unused rows
        outs: List[List[int]] = []
        for i, p in enumerate(prompts):
            tok = self.prefill(p, slot=i)
            outs.append([tok])
        while True:
            active = [i for i in range(len(prompts))
                      if not self.done[i] and len(outs[i]) < max_new_tokens]
            if not active:
                break
            if self.speculative:
                toks, counts, _ = self.spec_step()
                for i in active:
                    for j in range(int(counts[i])):
                        if len(outs[i]) >= max_new_tokens:
                            break
                        outs[i].append(int(toks[i, j]))
                    if len(outs[i]) >= max_new_tokens and not self.done[i]:
                        self.release_slot(i)  # cap reached: stop advancing
            else:
                tok, done, _ = self.decode_step()
                for i in active:
                    if (self.paged and done[i]
                            and bool(self.page_exhausted[i])):
                        # evicted BEFORE the dispatch (pool ran dry): the
                        # row emitted pad this step, not a token
                        continue
                    outs[i].append(int(tok[i]))
                    if len(outs[i]) >= max_new_tokens and not self.done[i]:
                        self.release_slot(i)  # cap reached: stop advancing
        return outs
