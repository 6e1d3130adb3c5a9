"""Serving resilience (ISSUE 15, docs/RESILIENCE.md "Serving resilience"):

  - per-request deadlines: expired-in-queue vs expired-in-slot, pages
    freed immediately at the step boundary that cancels the row;
  - explicit cancellation with the same slot/page reclaim, proven never
    to corrupt pages reallocated to other rows (bit-identity);
  - overload control: bounded admission queue (reject vs
    shed-oldest-past-deadline policies) and the free-page load-shed
    watermark, with shed decisions observable via counters;
  - the PR 10 admission starvation fix: a page-parked queue head lets
    smaller requests bypass it, but the aging guard reserves freed pages
    for the head after N deferred boundaries (regression reproduces the
    starvation with the guard off);
  - degrade-to-safe speculative decoding: windowed accept-rate collapse
    falls back to plain paged decode (token-identical) and re-arms after
    a cooldown;
  - the dispatch watchdog fires on an injected stall (threading-based,
    no signals);
  - serving fault sites gen.prefill/gen.decode/gen.verify: absorbed by
    the retry layer, counted under retry_attempts_total{site=} like the
    training sites, crashes pass through;
  - the `make chaos-serve` gate (tools/servedrill.py) goes green on a
    real drill and red on tampered evidence.
"""
import copy
import importlib.util
import itertools
import os
import time

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
from mxnet_tpu.resilience import (AcceptRateTracker, DispatchWatchdog,
                                  RetryPolicy, SpeculationGovernor, faults)
from mxnet_tpu.resilience import retry as retry_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
    kw.setdefault("batch_size", 2)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("eos_id", None)
    kw.setdefault("pad_id", PAD)
    if paged:
        kw.setdefault("page_size", 8)
    return GenerationEngine(net, paged=paged, **kw)


def _prompt(n, seed, lo=1, hi=EOS):
    return list(np.random.RandomState(seed).randint(lo, hi, n))


def _counter(name, **labels):
    c = REGISTRY.get(name)
    if c is None:
        return 0
    return c.value(**labels) if labels else c.total()


_FAST_RETRY = dict(base_delay=0.001, jitter=0.0, seed=0)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class ConstDraft:
    """Duck-typed draft that always proposes ``token`` — adversarial
    (accept rate ~0) unless the target agrees by luck."""

    def __init__(self, token, vocab=VOCAB, max_length=64):
        self._token = token
        self._vocab = vocab
        self._max_length = max_length

    def collect_params(self):
        return {}

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        return [(jnp.zeros((num_pages + 1, page_size, 1), jnp.float32),
                 jnp.zeros((num_pages + 1, page_size, 1), jnp.float32))]

    def __call__(self, tokens, cache=None, start_pos=None, page_table=None):
        shape = (tokens._data.shape[0], tokens._data.shape[1])
        logits = jax.nn.one_hot(jnp.full(shape, self._token), self._vocab,
                                dtype=jnp.float32) * 10.0
        return NDArray(logits), cache


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
class TestDeadlines:
    def test_expired_in_queue(self, net):
        clock = FakeClock()
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, clock=clock)
        r1 = bat.submit(_prompt(5, 1), max_new_tokens=12)
        bat.step()
        assert r1.slot == 0
        q0 = _counter("gen_deadline_expired_total", where="queue")
        r2 = bat.submit(_prompt(5, 2), max_new_tokens=4, deadline_s=3.0)
        clock.advance(5.0)
        bat.step()
        assert r2.finish_reason == "deadline" and r2.output == []
        assert r2.slot is None  # never admitted
        assert _counter("gen_deadline_expired_total", where="queue") == q0 + 1
        assert not r1.done  # the active row was untouched

    def test_expired_in_slot_frees_pages_same_boundary(self, net):
        clock = FakeClock()
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, clock=clock)
        s0 = _counter("gen_deadline_expired_total", where="slot")
        r = bat.submit(_prompt(9, 3), max_new_tokens=20, deadline_s=3.0)
        bat.step()
        assert r.slot == 0 and eng.pages_in_use == 2
        clock.advance(5.0)
        # the boundary that expires the slot must free its pages in time
        # for this same boundary's admission
        r2 = bat.submit(_prompt(5, 4), max_new_tokens=2)
        bat.step()
        assert r.finish_reason == "deadline"
        assert len(r.output) >= 1  # partial tokens delivered
        assert r2.slot == 0  # freed slot + pages reused immediately
        assert _counter("gen_deadline_expired_total", where="slot") == s0 + 1
        bat.run_until_idle(max_steps=20)
        assert eng.free_pages == eng.num_pages

    def test_default_deadline_from_config(self, net):
        clock = FakeClock()
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, default_deadline_s=4.0, clock=clock)
        r = bat.submit(_prompt(5, 5), max_new_tokens=50)
        assert r.deadline_t == pytest.approx(4.0)
        bat.step()
        clock.advance(10.0)
        bat.step()
        assert r.finish_reason == "deadline"


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------
class TestCancellation:
    def test_cancel_queued(self, net):
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng)
        r1 = bat.submit(_prompt(5, 10), max_new_tokens=12)
        bat.step()
        r2 = bat.submit(_prompt(5, 11), max_new_tokens=4)
        assert bat.cancel(r2.id)
        bat.step()
        assert r2.finish_reason == "cancelled" and r2.output == []

    def test_cancel_active_releases_pages(self, net):
        eng = _engine(net, batch_size=2)
        bat = ContinuousBatcher(eng)
        r = bat.submit(_prompt(9, 12), max_new_tokens=30)
        bat.step()
        assert r.slot is not None and eng.pages_in_use > 0
        assert bat.cancel(r)
        bat.step()
        assert r.finish_reason == "cancelled"
        assert len(r.output) >= 1  # tokens generated before the cancel
        assert eng.free_pages == eng.num_pages
        # unknown / already-finished requests are refused
        assert not bat.cancel(99999)
        assert not bat.cancel(r.id)

    def test_cancel_then_page_reuse_bit_identity(self, net):
        # row 0 is cancelled mid-decode; its pages go to a new request.
        # The cancelled row's next (masked) writes must land in the trash
        # page, so the new request's stream must equal a solo run.
        ref = _engine(net, paged=False, batch_size=1)
        p1 = _prompt(10, 81)
        want = [ref.prefill(p1, slot=0)]
        for _ in range(5):
            tok, _, _ = ref.decode_step()
            want.append(int(tok[0]))

        eng = _engine(net, batch_size=2, num_pages=3)
        bat = ContinuousBatcher(eng)
        ra = bat.submit(_prompt(6, 80), max_new_tokens=30)
        bat.step()
        bat.step()
        bat.cancel(ra)
        rb = bat.submit(p1, max_new_tokens=6)  # needs 2 of the 3 pages
        bat.run_until_idle(max_steps=50)
        assert ra.finish_reason == "cancelled"
        assert rb.finish_reason == "length"
        assert rb.result() == want


# ---------------------------------------------------------------------------
# overload control
# ---------------------------------------------------------------------------
class TestOverload:
    def test_bounded_queue_reject_policy(self, net):
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, max_queue=1, queue_policy="reject")
        r0 = bat.submit(_prompt(5, 20), max_new_tokens=20)
        bat.step()
        shed0 = _counter("gen_shed_total", cause="queue_full")
        q1 = bat.submit(_prompt(5, 21), max_new_tokens=4)
        q2 = bat.submit(_prompt(5, 22), max_new_tokens=4)
        assert q2.done and q2.finish_reason == "shed"
        assert not q1.done and not r0.done
        assert _counter("gen_shed_total", cause="queue_full") == shed0 + 1

    def test_shed_policy_evicts_expired_queued(self, net):
        clock = FakeClock()
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, max_queue=1, queue_policy="shed",
                                clock=clock)
        bat.submit(_prompt(5, 23), max_new_tokens=20)
        bat.step()
        q1 = bat.submit(_prompt(5, 24), max_new_tokens=4, deadline_s=1.0)
        clock.advance(5.0)  # q1 is now past its deadline, still queued
        q2 = bat.submit(_prompt(5, 25), max_new_tokens=4)
        assert q1.finish_reason == "shed"  # the expired head was evicted
        assert not q2.done  # the new request took its place
        # queue full again, nothing expired -> the NEW request is shed
        q3 = bat.submit(_prompt(5, 26), max_new_tokens=4)
        assert q3.finish_reason == "shed"

    def test_page_floor_watermark(self, net):
        eng = _engine(net, batch_size=2, num_pages=4)
        bat = ContinuousBatcher(eng, shed_page_floor=4)
        r0 = bat.submit(_prompt(9, 27), max_new_tokens=20)  # 2 pages
        bat.step()
        # free pages (2) below the floor but a slot is open: not overload
        r1 = bat.submit(_prompt(9, 28), max_new_tokens=20)
        assert not r1.done
        bat.step()
        assert r1.slot is not None
        shed0 = _counter("gen_shed_total", cause="page_floor")
        r2 = bat.submit(_prompt(5, 29), max_new_tokens=4)
        assert r2.finish_reason == "shed"
        assert _counter("gen_shed_total", cause="page_floor") == shed0 + 1
        assert not r0.done and not r1.done

    def test_queue_policy_validated(self, net):
        with pytest.raises(ValueError):
            ContinuousBatcher(_engine(net), queue_policy="drop-everything")


# ---------------------------------------------------------------------------
# admission starvation: bypass + aging guard (PR 10 fix)
# ---------------------------------------------------------------------------
class TestStarvationAging:
    def _setup(self, net, aging):
        """2 slots over a 3-page pool. Two small (1-page) requests are
        admitted with staggered budgets (2 vs 3 tokens) so exactly one
        slot frees per boundary — free pages oscillate 1..2, never
        reaching the 3 the big head needs — then the big request joins
        the queue head and a stream of budget-3 smalls rides behind it."""
        eng = GenerationEngine(net, batch_size=2, prefill_buckets=(8, 16, 32),
                               eos_id=None, pad_id=PAD, paged=True,
                               page_size=8, num_pages=3)
        bat = ContinuousBatcher(eng, head_aging_steps=aging)
        smalls = [bat.submit(_prompt(3, 100), max_new_tokens=2),
                  bat.submit(_prompt(3, 101), max_new_tokens=3)]
        bat.step()  # both admitted: 2 pages held, 1 free
        big = bat.submit(_prompt(17, 99), max_new_tokens=3)  # 3 pages
        return eng, bat, big, smalls

    def _drive(self, bat, big, smalls, steps):
        seeds = itertools.count(200)
        for _ in range(steps):
            while bat.pending < 3:  # keep the small stream flowing
                smalls.append(bat.submit(_prompt(3, next(seeds)),
                                         max_new_tokens=3))
            bat.step()
            if big.done:
                break
        return smalls

    def test_head_starves_with_guard_off(self, net):
        # regression for the PR 10 hazard: with the aging guard disabled,
        # a 3-page head never sees 3 free pages — every boundary a small
        # request bypasses it and takes the page a finishing row freed
        eng, bat, big, smalls = self._setup(net, aging=0)
        bypass0 = _counter("gen_admission_bypass_total")
        smalls = self._drive(bat, big, smalls, steps=30)
        assert not big.done and big.slot is None  # starved forever
        assert sum(r.done for r in smalls) >= 8  # while traffic flowed
        assert _counter("gen_admission_bypass_total") > bypass0
        assert eng.reserved_pages == 0  # guard off: nothing reserved

    def test_aging_guard_admits_head(self, net):
        eng, bat, big, smalls = self._setup(net, aging=3)
        self._drive(bat, big, smalls, steps=60)
        assert big.finish_reason == "length"  # admitted and completed
        assert eng.reserved_pages == 0  # reservation released afterwards


# ---------------------------------------------------------------------------
# degrade-to-safe speculative decoding
# ---------------------------------------------------------------------------
class TestSpecDegradation:
    def test_tracker_window(self):
        t = AcceptRateTracker(window=3)
        assert t.rate is None
        t.observe(2, 4)
        t.observe(0, 0)  # no-signal round ignored
        t.observe(1, 4)
        assert t.rate is None  # window not full yet
        t.observe(0, 4)
        assert t.rate == pytest.approx(3 / 12)
        t.reset()
        assert t.rate is None

    def test_governor_state_machine(self):
        g = SpeculationGovernor(window=2, floor=0.5, cooldown=3)
        assert g.speculating
        g.observe_round(3, 3)
        g.observe_round(0, 3)  # windowed rate 0.5 == floor: stays armed
        assert g.speculating
        g.observe_round(0, 3)  # window now [0/3, 0/3] -> collapse
        assert not g.speculating and g.fallbacks == 1
        for _ in range(2):
            g.observe_plain_step()
            assert not g.speculating
        g.observe_plain_step()
        assert g.speculating and g.rearms == 1
        assert g.tracker.rate is None  # window cleared on re-arm

    def test_plain_step_on_spec_engine(self, net):
        # decode_step keeps refusing (contract), plain_step is the
        # explicit fallback and costs exactly one extra compiled program
        spec = _engine(net, draft_net=ConstDraft(7), speculate_k=3)
        spec.prefill(_prompt(5, 40), slot=0)
        with pytest.raises(RuntimeError):
            spec.decode_step()
        n0 = spec.compiled_programs
        spec.plain_step()
        assert spec.compiled_programs == n0 + 1
        spec.plain_step()
        assert spec.compiled_programs == n0 + 1  # cached thereafter

    def test_collapse_falls_back_rearms_token_identical(self, net):
        prompts = [_prompt(5, 41), _prompt(9, 42)]
        ref = _engine(net, batch_size=2).generate(prompts, max_new_tokens=16)
        spec = _engine(net, batch_size=2, draft_net=ConstDraft(7),
                       speculate_k=3)
        bat = ContinuousBatcher(spec, spec_window=3, spec_floor=0.5,
                                spec_cooldown=2)
        fb0 = _counter("gen_spec_fallbacks_total")
        ra0 = _counter("gen_spec_rearms_total")
        reqs = [bat.submit(p, max_new_tokens=16) for p in prompts]
        modes = []
        while bat.step():
            modes.append(bat.governor.mode)
        # the ladder ran: spec -> fallback -> (cooldown) -> spec again
        assert "fallback" in modes
        assert bat.governor.fallbacks >= 1 and bat.governor.rearms >= 1
        assert _counter("gen_spec_fallbacks_total") > fb0
        assert _counter("gen_spec_rearms_total") > ra0
        i = modes.index("fallback")
        assert "spec" in modes[i:]
        # mode flapping never changes tokens
        assert [r.result() for r in reqs] == ref
        assert REGISTRY.get("gen_spec_mode").value() in (0.0, 1.0)


# ---------------------------------------------------------------------------
# dispatch watchdog
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_guard_fires_on_stall(self):
        wd = DispatchWatchdog(timeout_s=0.05)
        c0 = _counter("gen_stuck_dispatch_total", family="decode")
        with wd.guard("decode", step_id=7):
            time.sleep(0.25)
        assert wd.stalls == 1
        assert wd.last_stall["family"] == "decode"
        assert wd.last_stall["step_id"] == 7
        assert _counter("gen_stuck_dispatch_total", family="decode") == c0 + 1

    def test_guard_silent_when_fast_or_disabled(self):
        wd = DispatchWatchdog(timeout_s=5.0)
        with wd.guard("decode", step_id=1):
            pass
        assert wd.stalls == 0
        off = DispatchWatchdog(timeout_s=0.0)
        with off.guard("decode", step_id=1):
            time.sleep(0.02)
        assert off.stalls == 0

    def test_batcher_detects_injected_stall(self, net, monkeypatch):
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng, watchdog_s=0.05)
        real = eng.decode_step

        def stalled(**kw):
            time.sleep(0.25)
            return real(**kw)

        monkeypatch.setattr(eng, "decode_step", stalled)
        r = bat.submit(_prompt(5, 50), max_new_tokens=3)
        bat.run_until_idle(max_steps=10)
        assert r.finish_reason == "length"  # the request still completed
        assert bat.watchdog.stalls >= 1
        assert bat.watchdog.last_stall["family"] == "decode"


# ---------------------------------------------------------------------------
# serving fault sites + retry bridge
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestServingFaultSites:
    def test_prefill_fault_absorbed_and_counted(self, net):
        eng = _engine(net, batch_size=1)
        ref = _engine(net, batch_size=1)
        want = ref.generate([_prompt(5, 60)], max_new_tokens=5)[0]
        bat = ContinuousBatcher(eng,
                                retry_policy=RetryPolicy(**_FAST_RETRY))
        f0 = _counter("retry_attempts_total", site="gen.prefill", ok="false")
        with faults.inject("gen.prefill", every=1, times=1):
            r = bat.submit(_prompt(5, 60), max_new_tokens=5)
            bat.run_until_idle(max_steps=20)
        assert r.result() == want  # the retried admission replayed cleanly
        assert _counter("retry_attempts_total", site="gen.prefill",
                        ok="false") == f0 + 1
        log = retry_mod.attempt_log("gen.prefill")
        assert [a["ok"] for a in log[-2:]] == [False, True]

    def test_decode_fault_absorbed(self, net):
        eng = _engine(net, batch_size=1)
        ref = _engine(net, batch_size=1)
        want = ref.generate([_prompt(5, 61)], max_new_tokens=6)[0]
        bat = ContinuousBatcher(eng,
                                retry_policy=RetryPolicy(**_FAST_RETRY))
        f0 = _counter("retry_attempts_total", site="gen.decode", ok="false")
        r = bat.submit(_prompt(5, 61), max_new_tokens=6)
        bat.step()
        with faults.inject("gen.decode", every=1, times=1):
            bat.step()
        bat.run_until_idle(max_steps=20)
        assert r.result() == want
        assert _counter("retry_attempts_total", site="gen.decode",
                        ok="false") == f0 + 1

    def test_verify_fault_absorbed_token_identical(self, net):
        prompts = [_prompt(5, 62), _prompt(9, 63)]
        ref = _engine(net, batch_size=2).generate(prompts, max_new_tokens=8)
        spec = _engine(net, batch_size=2, draft_net=net, speculate_k=4)
        bat = ContinuousBatcher(spec,
                                retry_policy=RetryPolicy(**_FAST_RETRY))
        f0 = _counter("retry_attempts_total", site="gen.verify", ok="false")
        with faults.inject("gen.verify", every=2, times=1):
            reqs = [bat.submit(p, max_new_tokens=8) for p in prompts]
            bat.run_until_idle(max_steps=50)
        assert [r.result() for r in reqs] == ref
        assert _counter("retry_attempts_total", site="gen.verify",
                        ok="false") == f0 + 1

    def test_injected_crash_passes_through(self, net):
        eng = _engine(net, batch_size=1)
        bat = ContinuousBatcher(eng,
                                retry_policy=RetryPolicy(**_FAST_RETRY))
        bat.submit(_prompt(5, 64), max_new_tokens=10)
        bat.step()
        with faults.inject("gen.decode", every=1, times=1, crash=True):
            with pytest.raises(faults.InjectedCrash):
                bat.step()  # process death is never absorbed into a retry


# ---------------------------------------------------------------------------
# the chaos-serve gate (tools/servedrill.py), green + tampered-red
# ---------------------------------------------------------------------------
class TestChaosServeGate:
    @pytest.fixture(scope="class")
    def servedrill(self):
        spec = importlib.util.spec_from_file_location(
            "servedrill_mod", os.path.join(REPO, "tools", "servedrill.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @pytest.fixture(scope="class")
    def drill(self, servedrill, tmp_path_factory):
        try:
            return servedrill.run_drill(
                telemetry_dir=str(tmp_path_factory.mktemp("drill")))
        finally:
            from mxnet_tpu import observability as obs

            obs.disable()

    def test_gate_green(self, servedrill, drill):
        assert servedrill.validate(drill) == []

    def test_page_leak_fails_gate(self, servedrill, drill):
        bad = copy.deepcopy(drill)
        bad["drained"]["free_pages"] -= 1
        assert any("page leak" in p for p in servedrill.validate(bad))

    def test_corrupted_tokens_fail_gate(self, servedrill, drill):
        bad = copy.deepcopy(drill)
        key = next(k for k, v in bad["requests"].items()
                   if v["reason"] == "length" and k in bad["baseline"])
        bad["requests"][key]["output"][0] ^= 1
        assert any("diverge" in p or "prefix" in p
                   for p in servedrill.validate(bad))

    def test_missing_fallback_fails_gate(self, servedrill, drill):
        bad = copy.deepcopy(drill)
        bad["counters"]["fallbacks"] = 0
        assert any("fallbacks" in p for p in servedrill.validate(bad))
