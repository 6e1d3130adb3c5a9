"""Paged KV-cache + speculative decoding (ISSUE 11 acceptance):

  - paged greedy tokens are BIT-IDENTICAL to the dense engine (the page
    indirection changes storage, never math: masked entries get an exact
    0.0 softmax weight in both layouts);
  - pages are reclaimed on release/EOS and safely reused (a released
    row's cleared table redirects its writes to the trash page, so a
    reallocated page can never be corrupted);
  - page exhaustion force-finishes rows (evict counter, batcher
    finish_reason="page_exhausted") instead of overflowing mid-decode;
  - batcher admission is bounded by free pages, with
    ``gen_admission_rejects_total{reason}`` on submit-rejects/deferrals;
  - speculative decoding is token-identical to non-speculative greedy at
    every accept rate — full accept (self-draft), partial accept
    (scripted draft, exact per-round emit counts), full reject — i.e. the
    frontier rollback is correct;
  - compiled-program count stays (buckets used + 1 decode) for the paged
    engine and (buckets + 1 decode + 1 verify) when speculating, flat
    under traffic;
  - ``engine.audit()``: 100% donation on the paged carry (page table +
    pools) and zero host transfers in decode + verify programs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
from mxnet_tpu.models import gpt2
from mxnet_tpu.ndarray import NDArray
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


class ScriptedDraft:
    """Duck-typed draft model whose greedy token at sequence position p is
    exactly ``script[p]`` — lets tests pin the accept/reject pattern."""

    def __init__(self, script, vocab, max_length):
        assert len(script) == max_length
        self._script = jnp.asarray(np.asarray(script, np.int32))
        self._vocab = vocab
        self._max_length = max_length

    def collect_params(self):
        return {}

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        return [(jnp.zeros((num_pages + 1, page_size, 1), jnp.float32),
                 jnp.zeros((num_pages + 1, page_size, 1), jnp.float32))]

    def __call__(self, tokens, cache=None, start_pos=None, page_table=None):
        t = tokens._data.shape[1]
        pos = (start_pos._data.reshape(-1, 1)
               + jnp.arange(t, dtype=jnp.int32)[None, :])
        pos = jnp.clip(pos, 0, self._max_length - 1)
        logits = jax.nn.one_hot(self._script[pos], self._vocab,
                                dtype=jnp.float32) * 10.0
        return NDArray(logits), cache


# ---------------------------------------------------------------------------
# paged == dense, bit-identical greedy
# ---------------------------------------------------------------------------
class TestPagedEquivalence:
    def test_paged_matches_dense_greedy(self, net):
        prompts = [_prompt(5, 10), _prompt(12, 11), _prompt(3, 12)]
        ref = _engine(net, paged=False).generate(prompts, max_new_tokens=10)
        got = _engine(net).generate(prompts, max_new_tokens=10)
        assert got == ref

    def test_paged_logits_match_dense_per_step(self, net):
        dense = _engine(net, paged=False, batch_size=2)
        paged = _engine(net, batch_size=2)
        for i, p in enumerate([_prompt(5, 20), _prompt(12, 21)]):
            dense.prefill(p, slot=i)
            paged.prefill(p, slot=i)
        for _ in range(6):
            _, _, lg_d = dense.decode_step()
            _, _, lg_p = paged.decode_step()
            np.testing.assert_array_equal(np.array(lg_d), np.array(lg_p))

    def test_paged_bf16_cache_matches_dense_bf16(self, net):
        prompts = [_prompt(5, 31), _prompt(9, 32)]
        ref = _engine(net, paged=False, batch_size=2,
                      cache_dtype="bfloat16").generate(prompts,
                                                       max_new_tokens=8)
        eng = _engine(net, batch_size=2, cache_dtype="bfloat16")
        for k_pool, v_pool in eng.pools:
            assert k_pool.dtype == jnp.bfloat16 and v_pool.dtype == jnp.bfloat16
        assert eng.generate(prompts, max_new_tokens=8) == ref

    def test_odd_page_size_rounds_capacity_up(self, net):
        # max_length 64 with page_size 6 -> 11 page slots per row; the
        # extra masked capacity must not change tokens
        prompts = [_prompt(7, 40), _prompt(11, 41)]
        ref = _engine(net, paged=False, batch_size=2).generate(
            prompts, max_new_tokens=9)
        got = _engine(net, batch_size=2, page_size=6).generate(
            prompts, max_new_tokens=9)
        assert got == ref


# ---------------------------------------------------------------------------
# page lifecycle: allocation, reclaim, reuse
# ---------------------------------------------------------------------------
class TestPageLifecycle:
    def test_pages_reclaimed_and_reused(self, net):
        eng = _engine(net, batch_size=2, num_pages=8)  # 8 x 8 = 64 tokens
        total = eng.num_pages
        assert eng.free_pages == total
        ref = _engine(net, paged=False, batch_size=2)
        for wave in range(3):  # reuse the same pool across waves
            prompts = [_prompt(5, 50 + wave), _prompt(9, 60 + wave)]
            want = ref.generate(prompts, max_new_tokens=6)
            assert eng.generate(prompts, max_new_tokens=6) == want
        # rows finished by the token budget release their pages
        assert eng.free_pages == total
        assert _counter_total("gen_pages_reclaimed_total") > 0

    def test_release_slot_returns_pages(self, net):
        eng = _engine(net, batch_size=2)
        eng.prefill(_prompt(9, 70), slot=0)  # 9 tokens -> 2 pages of 8
        assert eng.pages_in_use == 2
        eng.release_slot(0)
        assert eng.pages_in_use == 0 and eng.free_pages == eng.num_pages

    def test_released_row_cannot_corrupt_reused_pages(self, net):
        # row 0 is released mid-decode; its pages go to row 1's prefill.
        # Row 0's next (masked) writes must land in the trash page, so row
        # 1's stream must equal a solo run.
        eng = _engine(net, batch_size=2, num_pages=3)
        solo = _engine(net, paged=False, batch_size=1)
        p1 = _prompt(10, 81)
        want_first = solo.prefill(p1, slot=0)
        want = [want_first]
        for _ in range(5):
            tok, _, _ = solo.decode_step()
            want.append(int(tok[0]))
        eng.prefill(_prompt(6, 80), slot=0)
        eng.decode_step()
        eng.release_slot(0)  # frees its page for row 1
        got = [eng.prefill(p1, slot=1)]  # takes 2 of 3 pages
        for _ in range(5):
            tok, _, _ = eng.decode_step()
            got.append(int(tok[1]))
        assert got == want


# ---------------------------------------------------------------------------
# page exhaustion
# ---------------------------------------------------------------------------
class TestPageExhaustion:
    def test_decode_exhaustion_force_finishes_row(self, net):
        # pool of 3 pages (8 tokens each), two 7-token prompts: one page
        # each; the third page goes to the first row that grows past 8 —
        # the other row is evicted, the winner decodes on
        evict0 = _counter_total("gen_page_evictions_total")
        eng = _engine(net, batch_size=2, num_pages=3, eos_id=None)
        outs = eng.generate([_prompt(7, 90), _prompt(7, 91)],
                            max_new_tokens=6)
        assert _counter_total("gen_page_evictions_total") - evict0 == 1
        assert bool(eng.page_exhausted.any())
        # the evicted row stopped early; the surviving row ran to budget
        lens = sorted(len(o) for o in outs)
        assert lens[0] < 6 and lens[1] == 6

    def test_batcher_reports_page_exhausted(self, net):
        eng = _engine(net, batch_size=2, num_pages=3, eos_id=None)
        bat = ContinuousBatcher(eng)
        reqs = [bat.submit(_prompt(7, 92 + i), max_new_tokens=6)
                for i in range(2)]
        bat.run_until_idle(max_steps=100)
        reasons = sorted(r.finish_reason for r in reqs)
        assert reasons == ["length", "page_exhausted"]
        evicted = next(r for r in reqs if r.finish_reason == "page_exhausted")
        # the pad emitted on the eviction step must not reach the output
        assert PAD not in evicted.output[1:]

    def test_failed_prefill_preserves_pending_clear(self, net):
        # a released slot's device-table clear must survive a prefill that
        # fails on free pages — losing it would let the released row's
        # masked writes corrupt pages reallocated to other rows
        eng = _engine(net, batch_size=2, num_pages=2, eos_id=None)
        eng.prefill(_prompt(6, 96), slot=0)
        eng.prefill(_prompt(6, 97), slot=1)
        eng.release_slot(1)
        assert 1 in eng._pending_clear
        with pytest.raises(RuntimeError):
            eng.prefill(_prompt(16, 98), slot=1)  # needs 2 pages, 1 free
        assert 1 in eng._pending_clear  # not lost on the error path
        # the surviving row's stream must match a solo run (row 0 will
        # grow into the freed page; the shipped clear protects it)
        solo = _engine(net, batch_size=2, num_pages=2, eos_id=None)
        solo.prefill(_prompt(6, 96), slot=0)
        want = [int(solo.decode_step()[0][0]) for _ in range(8)]
        got = [int(eng.decode_step()[0][0]) for _ in range(8)]
        assert got == want

    def test_cache_end_still_reported_as_cache_full(self, net):
        small = _gpt2(max_length=16)
        eng = GenerationEngine(small, batch_size=1, max_length=16,
                               prefill_buckets=(8,), eos_id=EOS,
                               paged=True, page_size=8)
        bat = ContinuousBatcher(eng)
        req = bat.submit(_prompt(6, 95), max_new_tokens=100)
        bat.run_until_idle(max_steps=100)
        assert req.finish_reason == "cache_full"


# ---------------------------------------------------------------------------
# batcher: page-bounded admission
# ---------------------------------------------------------------------------
class TestPagedAdmission:
    def test_admission_bounded_by_free_pages(self, net):
        # 4 slots but the pool only covers 2 concurrent sequences (9-token
        # prompts -> 2 pages each, no growth below position 16): admission
        # must defer, everything completes, and results equal the dense
        # engine's
        prompts = [_prompt(9, 100 + i) for i in range(4)]
        dense = _engine(net, paged=False, batch_size=4)
        bat_d = ContinuousBatcher(dense)
        want = [bat_d.submit(p, max_new_tokens=5) for p in prompts]
        bat_d.run_until_idle(max_steps=200)

        defer0 = _counter_total("gen_admission_rejects_total",
                                reason="free_pages")
        eng = _engine(net, batch_size=4, num_pages=4)
        bat = ContinuousBatcher(eng)
        reqs = [bat.submit(p, max_new_tokens=5) for p in prompts]
        peak = 0
        while bat.step():
            peak = max(peak, bat.active)
        assert peak <= 2  # page-bounded, not slot-bounded
        assert _counter_total("gen_admission_rejects_total",
                              reason="free_pages") > defer0
        assert [r.result() for r in reqs] == [r.result() for r in want]

    def test_submit_rejects_unservable_prompts(self, net):
        eng = _engine(net, batch_size=2, num_pages=1)  # 8-token pool
        bat = ContinuousBatcher(eng)
        r0 = _counter_total("gen_admission_rejects_total",
                            reason="prompt_pages")
        with pytest.raises(ValueError):
            bat.submit(_prompt(12, 110), max_new_tokens=2)  # needs 2 pages
        assert _counter_total("gen_admission_rejects_total",
                              reason="prompt_pages") == r0 + 1
        r1 = _counter_total("gen_admission_rejects_total",
                            reason="prompt_length")
        with pytest.raises(ValueError):
            bat.submit(_prompt(17, 111), max_new_tokens=2)  # no bucket
        assert _counter_total("gen_admission_rejects_total",
                              reason="prompt_length") == r1 + 1


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------
class TestSpeculative:
    def test_self_draft_identical_full_accept(self, net):
        prompts = [_prompt(5, 120), _prompt(12, 121), _prompt(3, 122)]
        ref = _engine(net).generate(prompts, max_new_tokens=11)
        acc0 = _counter_total("gen_spec_accepted_tokens_total")
        d0 = _counter_total("gen_spec_drafted_tokens_total")
        spec = _engine(net, draft_net=net, speculate_k=4)
        assert spec.generate(prompts, max_new_tokens=11) == ref
        acc = _counter_total("gen_spec_accepted_tokens_total") - acc0
        drafted = _counter_total("gen_spec_drafted_tokens_total") - d0
        assert drafted > 0 and acc == drafted  # self-draft: full accept
        assert REGISTRY.get("gen_spec_accept_rate").value() == 1.0

    def test_scripted_partial_accept_exact_counts(self, net):
        # learn the target's greedy continuation, then script a draft that
        # is right once and wrong afterwards: round 1 must accept exactly 1
        # draft + 1 correction (m=2), later rounds reject all (m=1)
        p = _prompt(6, 130)
        probe = _engine(net, batch_size=1, eos_id=None)
        t0 = probe.prefill(p, slot=0)
        cont = []
        for _ in range(6):
            tok, _, _ = probe.decode_step()
            cont.append(int(tok[0]))
        script = np.zeros(64, np.int32)
        L = len(p)
        script[L] = cont[0]                      # d1 correct
        script[L + 1] = (cont[1] + 1) % VOCAB    # d2 wrong
        draft = ScriptedDraft(script, VOCAB, 64)
        spec = GenerationEngine(net, batch_size=1, prefill_buckets=(8, 16),
                                eos_id=None, pad_id=PAD, paged=True,
                                page_size=8, draft_net=draft, speculate_k=3)
        assert spec.prefill(p, slot=0) == t0
        toks, m, _ = spec.spec_step()
        assert int(m[0]) == 2  # 1 accepted draft + the correction token
        assert [int(toks[0, j]) for j in range(2)] == cont[:2]
        toks, m, _ = spec.spec_step()  # all-zero script: full reject
        assert int(m[0]) == 1
        assert int(toks[0, 0]) == cont[2]

    def test_reject_all_rollback_identical(self, net):
        # a draft that is always wrong forces a full rollback every round;
        # the emitted stream must still equal plain greedy
        prompts = [_prompt(5, 140), _prompt(9, 141)]
        ref = _engine(net, batch_size=2).generate(prompts, max_new_tokens=9)
        draft = ScriptedDraft(np.full(64, EOS - 1, np.int32), VOCAB, 64)
        spec = _engine(net, batch_size=2, draft_net=draft, speculate_k=3)
        got = spec.generate(prompts, max_new_tokens=9)
        # (if any ref token happened to equal the constant script the
        # draft would be "right"; identity is the contract either way)
        assert got == ref

    def test_spec_eos_mid_window(self, net):
        # declare the 3rd greedy token EOS: the speculative engine must
        # stop emission exactly there, like the non-speculative engine
        p = _prompt(7, 150)
        probe = _engine(net, batch_size=1, eos_id=None)
        probe.prefill(p, slot=0)
        cont = []
        for _ in range(4):
            tok, _, _ = probe.decode_step()
            cont.append(int(tok[0]))
        eos = cont[2]
        ref = GenerationEngine(net, batch_size=1, prefill_buckets=(8, 16),
                               eos_id=eos, paged=True,
                               page_size=8).generate([p], max_new_tokens=12)
        spec = GenerationEngine(net, batch_size=1, prefill_buckets=(8, 16),
                                eos_id=eos, paged=True, page_size=8,
                                draft_net=net, speculate_k=4)
        got = spec.generate([p], max_new_tokens=12)
        assert got == ref
        assert got[0][-1] == eos or len(got[0]) == 12

    def test_spec_cache_end_clamp(self):
        # rounds near the cache end must clamp emission at capacity and
        # force-finish exactly like the single-token path
        small = _gpt2(max_length=16, seed=2)
        common = dict(batch_size=1, max_length=16, prefill_buckets=(8,),
                      eos_id=None, paged=True, page_size=8)
        ref = GenerationEngine(small, **common).generate(
            [_prompt(6, 160)], max_new_tokens=100)
        spec = GenerationEngine(small, draft_net=small, speculate_k=4,
                                **common)
        got = spec.generate([_prompt(6, 160)], max_new_tokens=100)
        assert got == ref
        assert bool(spec.done[0])

    def test_draft_cache_writes_last_drafted_token(self, net):
        # full-accept rounds advance the frontier past position p+k; the
        # draft scan must have written d_k's K/V there (a skipped write
        # would leave a permanent zero-K/V hole below the draft frontier,
        # silently degrading later accept rates)
        spec = GenerationEngine(net, batch_size=1, prefill_buckets=(8,),
                                eos_id=None, pad_id=PAD, paged=True,
                                page_size=8, draft_net=net, speculate_k=4)
        spec.prefill(_prompt(6, 210), slot=0)
        for _ in range(6):
            spec.spec_step()
        frontier = int(spec.positions[0])
        table = np.array(spec.page_table)[0]
        k_pool = np.array(spec.draft_pools[0][0])
        t_pool = np.array(spec.pools[0][0])
        assert frontier > 12  # several full-accept rounds ran
        for pos in range(frontier):
            pid = table[pos // 8]
            # self-draft: the draft entry must equal the target's, and in
            # particular must not be the all-zero initial page content
            np.testing.assert_array_equal(k_pool[pid, pos % 8],
                                          t_pool[pid, pos % 8])
            assert np.abs(k_pool[pid, pos % 8]).sum() > 0.0

    def test_spec_batcher_matches_solo(self, net):
        prompts = [_prompt(4, 170), _prompt(11, 171), _prompt(7, 172)]
        solo = _engine(net)
        want = solo.generate(prompts, max_new_tokens=7)
        spec = _engine(net, batch_size=2, draft_net=net, speculate_k=4)
        bat = ContinuousBatcher(spec)
        reqs = [bat.submit(p, max_new_tokens=7) for p in prompts]
        bat.run_until_idle(max_steps=100)
        assert [r.result() for r in reqs] == want

    def test_config_validation(self, net):
        with pytest.raises(ValueError):
            _engine(net, draft_net=net)  # speculate_k missing
        with pytest.raises(ValueError):
            _engine(net, speculate_k=4)  # draft_net missing
        with pytest.raises(ValueError):
            _engine(net, paged=False, draft_net=net, speculate_k=4)
        # stochastic speculation is legal (rejection-sampling verify,
        # tests/test_prefix_sharing.py) — only the degenerate
        # temperature=0 non-greedy config is refused (residual undefined)
        from mxnet_tpu.inference import SamplingConfig
        assert _engine(net, draft_net=net, speculate_k=4,
                       sampling="temperature").speculative
        with pytest.raises(ValueError):
            _engine(net, draft_net=net, speculate_k=4,
                    sampling=SamplingConfig(method="temperature",
                                            temperature=0.0))
        with pytest.raises(ValueError):
            _engine(net, num_pages=0)  # explicit 0 must not hit the default


# ---------------------------------------------------------------------------
# compiled-program count: buckets + 1 decode (+ 1 verify), flat under traffic
# ---------------------------------------------------------------------------
class TestPagedProgramCount:
    def test_paged_buckets_plus_one_stable(self, net):
        eng = _engine(net)  # buckets (8, 16)
        prompts = [_prompt(5, 180), _prompt(12, 181), _prompt(3, 182)]
        eng.generate(prompts, max_new_tokens=9)
        used = {eng.bucket_for(len(p)) for p in prompts}
        assert eng.compiled_programs == len(used) + 1
        bat = ContinuousBatcher(eng)
        for i in range(5):
            bat.submit(_prompt(2 + i, 190 + i), max_new_tokens=6)
        bat.run_until_idle(max_steps=200)
        assert eng.compiled_programs == len(used) + 1

    def test_spec_buckets_plus_two_stable(self, net):
        before_v = _counter_total("gen_recompiles_total", reason="verify")
        eng = _engine(net, draft_net=net, speculate_k=4)
        prompts = [_prompt(5, 200), _prompt(12, 201)]
        eng.generate(prompts, max_new_tokens=9)
        used = {eng.bucket_for(len(p)) for p in prompts}
        assert eng.compiled_programs == len(used) + 2  # draft scan + verify
        assert _counter_total("gen_recompiles_total",
                              reason="verify") - before_v == 1
        eng.generate([_prompt(7, 202)], max_new_tokens=12)
        assert eng.compiled_programs == len(used) + 2

    def test_decode_step_refused_on_spec_engine(self, net):
        eng = _engine(net, draft_net=net, speculate_k=2)
        with pytest.raises(RuntimeError):
            eng.decode_step()
        plain = _engine(net)
        with pytest.raises(RuntimeError):
            plain.spec_step()


# ---------------------------------------------------------------------------
# audit: paged carry donation + zero host transfers (ISSUE 11 acceptance)
# ---------------------------------------------------------------------------
class TestPagedAudit:
    def test_paged_decode_and_prefill_audit(self):
        mx.random.seed(0)
        net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2,
                            units=32, num_heads=2, max_length=64,
                            vocab_size=64)
        net.initialize()
        _ = net(nd.array(np.zeros((1, 4), np.int32)))
        eng = GenerationEngine(net, batch_size=2, max_length=64,
                               prefill_buckets=(8,), paged=True,
                               page_size=16)
        for audit in (eng.audit(), eng.audit(bucket=8)):
            assert audit.carry_donation() == 1.0
            assert not audit.compiled.host_transfers()
            assert audit.comm.total_bytes() == 0

    def test_spec_draft_and_verify_audit(self):
        mx.random.seed(0)
        net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2,
                            units=32, num_heads=2, max_length=64,
                            vocab_size=64)
        net.initialize()
        _ = net(nd.array(np.zeros((1, 4), np.int32)))
        eng = GenerationEngine(net, batch_size=2, max_length=64,
                               prefill_buckets=(8,), paged=True,
                               page_size=16, draft_net=net, speculate_k=4)
        for audit in (eng.audit(), eng.audit(program="verify"),
                      eng.audit(bucket=8)):
            assert audit.carry_donation() == 1.0
            assert not audit.compiled.host_transfers()
            assert audit.comm.total_bytes() == 0
