"""Prefix-sharing serving: CoW page tables + radix prefix cache (ISSUE 19):

  - radix cache indexes FULL pages only, walks the longest cached prefix,
    keeps first-writer pages on duplicate inserts, LRU-evicts leaves (a
    freed leaf exposes its parent) and refuses pages the predicate
    rejects — checked against a model dict on random sequences;
  - page refcounts: prefill+cache insert / fork / release each move the
    count by exactly one reference; only refcount-0 pages return to the
    free list; eviction refuses refcount>1 (still row-backed) pages;
  - copy-on-write isolation: rows forked onto SHARED pages and forced to
    divergent suffixes decode bit-identically to isolated rows — the
    first write past the shared frontier got a private copy (extends the
    ISSUE 10 released-row-corruption family);
  - prefix adoption is bit-identical: cold serve == cached re-serve ==
    a no-cache engine, for full and partial prefix hits;
  - admission prices the suffix: a prompt whose prefix is cached admits
    through a tight pool WITHOUT a free_pages deferral, and re-serves
    the exact cold tokens;
  - ``submit(..., samples=N)``: leader prefills once, N-1 siblings are
    admitted by copy-on-write fork (``gen_forks_total``), all complete;
  - session resume: history + new turn longer than the largest prefill
    bucket admits via the cached history and matches a big-bucket run;
  - rejection-sampling speculation is DISTRIBUTION-identical to plain
    sampled decode (fixed seed, total-variation gate on the first
    decode-emitted token's marginal, draft != target so the accept /
    residual rule actually carries the correction);
  - chaos: cancelling a fork mid-decode reclaims ONLY refcount-0 pages;
    the survivor's stream stays bit-identical to a solo run;
  - compiled-program count stays (buckets used + decode + 1 CoW copy
    program), flat under traffic; ``audit(program="cow")``: 100%
    donation, zero host transfers, zero collectives.
"""
import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.inference import (ContinuousBatcher, GenerationEngine,
                                 RadixPrefixCache, SamplingConfig)
from mxnet_tpu.models import gpt2
from mxnet_tpu.observability import REGISTRY

VOCAB, EOS, PAD = 97, 96, 0


def _gpt2(max_length=64, seed=0):
    mx.random.seed(seed)
    net = gpt2.GPT2Model(num_layers=2, units=64, num_heads=4,
                         max_length=max_length, vocab_size=VOCAB, dropout=0.0)
    net.initialize()
    _ = net(nd.array(np.zeros((1, 4)), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def net():
    return _gpt2()


def _engine(net, paged=True, **kw):
    kw.setdefault("batch_size", 3)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("eos_id", EOS)
    kw.setdefault("pad_id", PAD)
    if paged:
        kw.setdefault("page_size", 8)
    return GenerationEngine(net, paged=paged, **kw)


def _prompt(n, seed, lo=1, hi=EOS):
    return list(np.random.RandomState(seed).randint(lo, hi, n))


def _counter_total(name, **labels):
    c = REGISTRY.get(name)
    if c is None:
        return 0
    return c.value(**labels) if labels else c.total()


# ---------------------------------------------------------------------------
# radix tree: insert / walk / evict
# ---------------------------------------------------------------------------
class TestRadixCache:
    def test_full_pages_only(self):
        c = RadixPrefixCache(4)
        assert c.insert([1, 2, 3], [7]) == []  # partial tail: not indexed
        assert len(c) == 0
        assert c.insert([1, 2, 3, 4, 5], [7, 8]) == [7]  # 1 full page
        pages, mtok = c.lookup([1, 2, 3, 4, 5, 6])
        assert (pages, mtok) == ([7], 4)
        assert c.lookup([1, 2, 3])[1] == 0  # shorter than a page: no match

    def test_first_writer_wins(self):
        c = RadixPrefixCache(2)
        assert c.insert([1, 2, 3, 4], [10, 11]) == [10, 11]
        # same prefix re-inserted under different pages: kept as-is
        assert c.insert([1, 2, 5, 6], [90, 12]) == [12]
        assert c.lookup([1, 2, 3, 4])[0] == [10, 11]
        assert c.lookup([1, 2, 5, 6])[0] == [10, 12]
        assert sorted(c.pages()) == [10, 11, 12]

    def test_longest_prefix_stops_at_divergence(self):
        c = RadixPrefixCache(2)
        c.insert([1, 2, 3, 4, 5, 6], [1, 2, 3])
        pages, mtok = c.lookup([1, 2, 3, 4, 9, 9, 9, 9])
        assert (pages, mtok) == ([1, 2], 4)

    def test_lru_evict_and_cascade(self):
        c = RadixPrefixCache(4)
        c.insert(list(range(8)), [1, 2])           # chain 1 -> 2
        c.insert(list(range(4)) + [9] * 4, [1, 3])  # sibling leaf 3
        c.lookup(list(range(8)))                   # touch: leaf 2 is MRU
        assert c.evict(1, lambda p: True) == [3]   # LRU leaf goes first
        # evicting leaf 2 exposes 1 as the next candidate (cascade)
        assert c.evict(2, lambda p: True) == [2, 1]
        assert len(c) == 0 and c.pages() == []

    def test_evict_respects_predicate_and_protect(self):
        c = RadixPrefixCache(4)
        c.insert(list(range(8)), [1, 2])
        assert c.evict(2, lambda p: False) == []   # nothing evictable
        assert c.evict(2, lambda p: True, protect=[2]) == []  # leaf guarded
        assert c.evict(2, lambda p: p != 1) == [2]  # parent refused
        assert c.pages() == [1]

    def test_collectable_simulates_cascade(self):
        c = RadixPrefixCache(4)
        c.insert(list(range(8)), [1, 2])
        c.insert(list(range(4)) + [9] * 4, [1, 3])
        assert c.collectable(lambda p: True) == 3
        assert c.collectable(lambda p: p != 1) == 2  # leaves only
        assert c.collectable(lambda p: True, protect=[2]) == 1  # 3 only
        assert len(c) == 3  # probe never mutates

    def test_random_sequences_match_model(self):
        ps, rs = 4, np.random.RandomState(0)
        c = RadixPrefixCache(ps)
        model, seqs, next_page = {}, [], 1
        for _ in range(40):
            if seqs and rs.rand() < 0.5:  # extend/perturb an existing seq
                base = seqs[rs.randint(len(seqs))]
                seq = (base[:rs.randint(len(base) + 1)]
                       + list(rs.randint(0, 5, rs.randint(0, 12))))
            else:
                seq = list(rs.randint(0, 5, rs.randint(0, 16)))
            seqs.append(seq)
            n_full = len(seq) // ps
            pages = list(range(next_page, next_page + n_full))
            next_page += n_full
            c.insert(seq, pages)
            for i in range(n_full):
                key = tuple(tuple(seq[j * ps:(j + 1) * ps])
                            for j in range(i + 1))
                model.setdefault(key, pages[i])  # first writer wins
        probes = seqs + [list(rs.randint(0, 5, 10)) for _ in range(20)]
        for seq in probes:
            pages, mtok = c.lookup(seq)
            assert mtok == len(pages) * ps <= len(seq)
            want, i = [], 0
            while len(seq) >= (i + 1) * ps:
                key = tuple(tuple(seq[j * ps:(j + 1) * ps])
                            for j in range(i + 1))
                if key not in model:
                    break
                want.append(model[key])
                i += 1
            assert pages == want


# ---------------------------------------------------------------------------
# refcount lifecycle: prefill / fork / release / evict
# ---------------------------------------------------------------------------
class TestRefcountLifecycle:
    def test_fork_release_evict_counts(self, net):
        eng = _engine(net, prefix_cache=True, eos_id=None)
        p = _prompt(16, 400)
        eng.prefill(p, slot=0)
        a, b = eng._pages.rows[0]
        # both full pages indexed at prefill: row + cache = rc 2
        assert eng._pages.rc[a] == eng._pages.rc[b] == 2
        eng.fork_slot(0, 1)
        assert eng._pages.rc[a] == eng._pages.rc[b] == 3
        assert REGISTRY.get("gen_page_refcount_max").value() == 3
        used = eng.pages_in_use
        eng.release_slot(0)
        assert eng._pages.rc[a] == eng._pages.rc[b] == 2
        assert eng.pages_in_use == used  # nothing hit rc 0 yet
        eng.release_slot(1)
        assert eng._pages.rc[a] == eng._pages.rc[b] == 1  # cache-only now
        assert eng.pages_in_use == used
        ev0 = _counter_total("gen_prefix_evictions_total")
        assert eng._pages.evict(2) == 2
        assert _counter_total("gen_prefix_evictions_total") == ev0 + 2
        assert eng._pages.rc[a] == eng._pages.rc[b] == 0
        assert eng.free_pages == eng.num_pages

    def test_eviction_refuses_row_backed_pages(self, net):
        eng = _engine(net, prefix_cache=True, eos_id=None)
        eng.prefill(_prompt(16, 401), slot=0)  # cached pages still rc 2
        ev0 = _counter_total("gen_prefix_evictions_total")
        assert eng._pages.evict(2) == 0  # a live row still reads them
        assert len(eng.prefix_cache) == 2
        assert _counter_total("gen_prefix_evictions_total") == ev0
        eng.release_slot(0)  # rc 1: cache-only, evictable now
        assert eng._pages.evict(2) == 2

    def test_fork_slot_error_paths(self, net):
        dense = _engine(net, paged=False, batch_size=2)
        with pytest.raises(RuntimeError):
            dense.fork_slot(0, 1)
        eng = _engine(net, prefix_cache=True, eos_id=None)
        with pytest.raises(ValueError):
            eng.fork_slot(0, 0)
        with pytest.raises(RuntimeError):
            eng.fork_slot(0, 1)  # empty source row


# ---------------------------------------------------------------------------
# copy-on-write isolation (extends the released-row-corruption family)
# ---------------------------------------------------------------------------
class TestCoWIsolation:
    def test_divergent_forks_match_isolated_rows(self, net):
        # rows 0/1 share every prompt page via fork, then are forced onto
        # divergent suffixes; the reference rows never share anything.
        # Bit-identical streams prove the first write into a shared page
        # copied it instead of mutating the other reader's history.
        eng = _engine(net, prefix_cache=True, eos_id=None)
        ref = _engine(net, eos_id=None)  # paged, no sharing
        p = _prompt(12, 410)
        t0 = eng.prefill(p, slot=0)
        assert eng.fork_slot(0, 1) == t0
        alt = t0 + 1 if t0 + 1 < VOCAB else t0 - 1
        eng.last_tokens[1] = alt  # force divergence on the fork
        cow0 = _counter_total("gen_cow_copies_total")
        got0, got1 = [t0], [alt]
        for _ in range(6):
            tok, _, _ = eng.decode_step()
            got0.append(int(tok[0]))
            got1.append(int(tok[1]))
        assert _counter_total("gen_cow_copies_total") > cow0
        assert ref.prefill(p, slot=0) == t0
        assert ref.prefill(p, slot=1) == t0
        ref.last_tokens[1] = alt
        want0, want1 = [t0], [alt]
        for _ in range(6):
            tok, _, _ = ref.decode_step()
            want0.append(int(tok[0]))
            want1.append(int(tok[1]))
        assert got0 == want0
        assert got1 == want1
        assert got1[1:] != got0[1:]  # the suffixes really diverged


# ---------------------------------------------------------------------------
# prefix adoption: bit-identity + admission accounting
# ---------------------------------------------------------------------------
class TestPrefixAdoption:
    def test_cold_hit_nocache_identical(self, net):
        eng = _engine(net, prefix_cache=True, batch_size=2, eos_id=None)
        plain = _engine(net, batch_size=2, eos_id=None)
        p = _prompt(14, 420)
        want = plain.generate([p], max_new_tokens=6)[0]
        h0 = _counter_total("gen_prefix_hits_total")
        t0 = _counter_total("gen_prefix_hit_tokens")
        cold = eng.generate([p], max_new_tokens=6)[0]
        assert _counter_total("gen_prefix_hits_total") == h0  # cold miss
        hit = eng.generate([p], max_new_tokens=6)[0]
        assert cold == hit == want
        assert _counter_total("gen_prefix_hits_total") == h0 + 1
        assert _counter_total("gen_prefix_hit_tokens") == t0 + 8
        # partial hit: shares only the first full page
        q = p[:8] + _prompt(6, 421)
        want_q = plain.generate([q], max_new_tokens=6)[0]
        assert eng.generate([q], max_new_tokens=6)[0] == want_q
        assert _counter_total("gen_prefix_hits_total") == h0 + 2

    def test_suffix_pricing_and_can_admit(self, net):
        eng = _engine(net, prefix_cache=True, eos_id=None)
        p = _prompt(16, 422)
        assert eng.pages_needed(p) == 2  # nothing cached yet
        assert eng.suffix_for(p) == 16
        eng.prefill(p, slot=0)
        eng.release_slot(0)
        # fully cached, page-aligned: re-read the last position by CoW
        assert eng.suffix_for(p) == 1
        assert eng.pages_needed(p) == 1  # only the CoW tail page
        long = p + _prompt(9, 423)  # 25 > largest bucket 16
        assert eng.can_admit(long)  # suffix 9 fits bucket 16
        assert not _engine(net, eos_id=None).can_admit(long)

    def test_fully_cached_prompt_admits_without_free_pages_reject(self, net):
        # tight pool: 2 holder pages + cached prompt. Suffix pricing
        # charges the cached re-serve ONE page (the CoW tail), so it
        # admits alongside the holder without a free_pages deferral and
        # re-serves the exact cold tokens.
        eng = _engine(net, prefix_cache=True, num_pages=5, eos_id=None)
        bat = ContinuousBatcher(eng)
        p = _prompt(16, 430)
        first = bat.submit(p, max_new_tokens=2)
        bat.run_until_idle(max_steps=100)
        assert first.finish_reason == "length"
        assert len(eng.prefix_cache) == 2  # prompt+output full pages
        r0 = _counter_total("gen_admission_rejects_total",
                            reason="free_pages")
        holder = bat.submit(_prompt(10, 431), max_new_tokens=5)  # 2 pages
        again = bat.submit(p, max_new_tokens=2)
        bat.run_until_idle(max_steps=100)
        assert _counter_total("gen_admission_rejects_total",
                              reason="free_pages") == r0
        assert holder.finish_reason == "length"
        assert again.result() == first.result()


# ---------------------------------------------------------------------------
# fork-based serving: N-way sampling + session resume
# ---------------------------------------------------------------------------
class TestForkServing:
    def test_n_way_sampling_via_forks(self, net):
        eng = _engine(net, prefix_cache=True, eos_id=None,
                      sampling=SamplingConfig(method="temperature",
                                              temperature=1.0))
        bat = ContinuousBatcher(eng)
        f0 = _counter_total("gen_forks_total")
        leader = bat.submit(_prompt(10, 440), max_new_tokens=6, samples=3)
        assert len(leader.samples) == 3 and leader.samples[0] is leader
        bat.run_until_idle(max_steps=200)
        outs = [r.result() for r in leader.samples]
        assert all(len(o) == 6 for o in outs)
        assert [r.forked for r in leader.samples] == [False, True, True]
        assert _counter_total("gen_forks_total") == f0 + 2
        assert len({tuple(o) for o in outs}) >= 2  # samples diverged

    def test_samples_needs_paged_engine(self, net):
        bat = ContinuousBatcher(_engine(net, paged=False, batch_size=2))
        with pytest.raises(ValueError):
            bat.submit(_prompt(5, 441), samples=2)
        with pytest.raises(ValueError):
            bat.submit(_prompt(5, 441), samples=0)

    def test_session_resume_past_largest_bucket(self, net):
        eng = _engine(net, prefix_cache=True, batch_size=2, eos_id=None)
        bat = ContinuousBatcher(eng)
        turn1 = _prompt(12, 450)
        r1 = bat.submit(turn1, max_new_tokens=8)
        bat.run_until_idle(max_steps=100)
        history = turn1 + r1.result()  # 20 tokens, full pages cached
        resume = history + _prompt(5, 451)  # 25 > largest bucket 16
        h0 = _counter_total("gen_prefix_hits_total")
        r2 = bat.submit(resume, max_new_tokens=4)
        bat.run_until_idle(max_steps=100)
        assert _counter_total("gen_prefix_hits_total") == h0 + 1
        big = _engine(net, batch_size=2, eos_id=None,
                      prefill_buckets=(8, 16, 32))
        assert r2.result() == big.generate([resume], max_new_tokens=4)[0]


# ---------------------------------------------------------------------------
# rejection-sampling speculation: distribution-identical to plain decode
# ---------------------------------------------------------------------------
class TestRejectionSampling:
    def test_stochastic_spec_needs_positive_temperature(self, net):
        with pytest.raises(ValueError):
            _engine(net, draft_net=net, speculate_k=3,
                    sampling=SamplingConfig(method="temperature",
                                            temperature=0.0))

    def test_first_token_marginal_matches_plain_decode(self, net):
        # fixed-seed Monte-Carlo gate: the marginal of the FIRST token a
        # sampled speculative round emits must match plain sampled decode
        # for the same context. draft != target, so q != p and the
        # accept/residual rule carries the whole correction (emitting the
        # raw draft samples would put the marginal at q, TV(p, q) >> gate).
        sampling = SamplingConfig(method="top_k", top_k=8, temperature=1.0)
        L, fix, trials = 6, 5, 300
        prompt = _prompt(L, 460)

        def marginal(eng):
            for s in range(eng.batch_size):
                eng.prefill(prompt, slot=s)
            counts = np.zeros(VOCAB)
            for _ in range(trials):
                # rewind to the same frontier: every round is an iid draw
                # from the conditional at position L (the KV written past
                # the frontier is masked and overwritten)
                eng.positions[:] = L
                eng.last_tokens[:] = fix
                eng.done[:] = False
                if eng.speculative:
                    toks, m, _ = eng.spec_step()
                    for b in range(eng.batch_size):
                        assert int(m[b]) >= 1
                        counts[int(toks[b, 0])] += 1
                else:
                    tok, _, _ = eng.decode_step()
                    for b in range(eng.batch_size):
                        counts[int(tok[b])] += 1
            return counts / counts.sum()

        plain = _engine(net, eos_id=None, sampling=sampling)
        spec = _engine(net, eos_id=None, sampling=sampling,
                       draft_net=_gpt2(seed=7), speculate_k=3)
        p_hat, s_hat = marginal(plain), marginal(spec)
        tv = 0.5 * np.abs(p_hat - s_hat).sum()
        # 900 samples over a <=8-token support: sampling noise keeps the
        # two-empirical TV ~0.07; a wrong emission rule lands far above
        assert tv < 0.15, f"total variation {tv:.3f} vs plain decode"
        # both draw inside the target's top-k support
        assert (p_hat > 0).sum() <= 8 and (s_hat > 0).sum() <= 8


# ---------------------------------------------------------------------------
# chaos: cancel a fork mid-decode
# ---------------------------------------------------------------------------
class TestForkCancel:
    def test_cancel_mid_decode_reclaims_only_rc0_pages(self, net):
        solo = _engine(net, batch_size=1, eos_id=None)
        p = _prompt(12, 470)
        want = [solo.prefill(p, slot=0)]
        for _ in range(8):
            tok, _, _ = solo.decode_step()
            want.append(int(tok[0]))

        eng = _engine(net, prefix_cache=True, eos_id=None)
        got = [eng.prefill(p, slot=0)]
        eng.fork_slot(0, 1)
        a = eng._pages.rows[0][0]  # first prompt page: shared + cached
        for i in range(8):
            tok, _, _ = eng.decode_step()
            got.append(int(tok[0]))
            if i == 2:  # cancel the fork mid-decode
                free0 = eng.free_pages
                fork_only = [pid for pid in eng._pages.rows[1]
                             if eng._pages.rc[pid] == 1]
                eng.release_slot(1)
                # only the fork's private (rc-0 after release) pages came
                # back; pages shared with row 0 / the cache survived
                assert eng.free_pages == free0 + len(fork_only)
                assert eng._pages.rc[a] == 2  # row 0 + prefix cache
        assert got == want  # the survivor never saw the cancellation


# ---------------------------------------------------------------------------
# program count + audit
# ---------------------------------------------------------------------------
class TestPrefixPrograms:
    def test_buckets_plus_decode_plus_cow_stable(self, net):
        eng = _engine(net, prefix_cache=True, batch_size=2, eos_id=None)
        p = _prompt(16, 480)
        eng.generate([p], max_new_tokens=4)        # bucket-16 + decode
        eng.generate([p], max_new_tokens=4)        # bucket-8 suffix + cow
        n = eng.compiled_programs
        assert n == 4  # prefill16, prefill8, decode, cow
        eng.generate([p], max_new_tokens=4)
        eng.generate([p[:8] + _prompt(6, 481)], max_new_tokens=4)
        assert eng.compiled_programs == n  # flat under traffic

    def test_cow_program_audit(self):
        mx.random.seed(0)
        net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2,
                            units=32, num_heads=2, max_length=64,
                            vocab_size=64)
        net.initialize()
        _ = net(nd.array(np.zeros((1, 4), np.int32)))
        eng = GenerationEngine(net, batch_size=2, max_length=64,
                               prefill_buckets=(8,), paged=True,
                               page_size=16, prefix_cache=True)
        audit = eng.audit(program="cow")
        assert audit.carry_donation() == 1.0
        assert not audit.compiled.host_transfers()
        assert audit.comm.total_bytes() == 0
