#!/usr/bin/env python
"""Chaos drill for the serving path (`make chaos-serve`,
docs/RESILIENCE.md "Serving resilience").

Drives :class:`ContinuousBatcher` traffic on a tiny GPT-2 speculative
engine under everything the serving-resilience layer is supposed to
absorb, simultaneously:

  - injected transient faults at every serving fault site
    (``gen.prefill`` / ``gen.decode`` / ``gen.verify``, deterministic
    ``every=N`` triggers the 3-attempt retry policy must absorb);
  - deadline pressure (requests expiring both in the queue and mid-slot)
    and explicit client cancellations, on a scripted fake clock so the
    schedule is deterministic;
  - overload (a bounded admission queue + a submit burst that must shed);
  - a forced speculative accept-rate collapse (an adversarial draft model
    that is always wrong), so the governor's fallback → cooldown → re-arm
    ladder is exercised for real;
  - the dispatch watchdog armed (and expected silent).

Gate (exit 1 on any violation):

  - the drill terminates within its step budget — no hang;
  - every submitted request ends with an explicit finish reason from the
    documented set;
  - rows that ran to completion are BIT-IDENTICAL to an undisturbed
    non-speculative baseline, and every interrupted row (deadline /
    cancelled / page_exhausted) emitted a strict prefix of it — injected
    faults, cancellations and page churn never corrupt a surviving row;
  - deadline / cancelled / shed counters are all nonzero, and both
    deadline flavours (``where=queue`` / ``where=slot``) fired;
  - speculative fallback AND re-arm were observed (metrics + events);
  - the retry bridge counted failed attempts for every ``gen.*`` site;
  - the drained end state is clean: no active slots, empty queue, every
    page back in the free pool, no reservation, zero watchdog stalls.

``--inject-leak`` is the tested failure path (like profcheck's
``--inject-empty-trace``): it corrupts the drained-state evidence and the
gate must go red.

``--fleet`` (`make chaos-fleet`) is the tier-level analogue over
``mxnet_tpu.serving``: three replicas behind a telemetry-driven router,
one replica KILLED mid-burst (stops stepping and publishing — a dead
process) and one WEDGED (keeps heartbeating but every dispatch trips the
watchdog — a stuck compiled program). The gate asserts zero dropped
in-deadline requests (every one re-runs somewhere and finishes
bit-identical to an undisturbed single-engine baseline), the wedged
replica walks DEGRADED→DRAINING→DEAD with its work redistributed, a
replacement replica joins under a fresh id, session affinity holds while
the pinned replica stays LIVE, and the surviving replicas drain to a
clean empty end state. Request tracing runs keep-everything: the gate
additionally asserts every terminal request assembled a gap-free trace
whose router-level phase sums match its end-to-end latency within 5%
and whose hop count matches ``router_redistributions_total``
(docs/OBSERVABILITY.md "Request tracing & SLO ledger").
``--inject-drop`` and ``--inject-orphan-span`` are its tested failure
paths.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

VOCAB, PAD = 61, 0
ALLOWED_REASONS = ("eos", "length", "cache_full", "page_exhausted",
                   "deadline", "cancelled", "shed")


class FakeClock:
    """Deterministic clock the batcher's deadline arithmetic runs on."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt=1.0):
        self.t += dt


class AdversarialDraft:
    """Duck-typed draft model that always proposes the same (wrong) token:
    the accept rate collapses to ~0, every round pays 2 dispatches for 1
    token, and the governor must fall back."""

    def __init__(self, vocab, max_length, token=7):
        self._vocab = vocab
        self._max_length = max_length
        self._token = token

    def collect_params(self):
        return {}

    def init_paged_cache(self, num_pages, page_size, dtype="float32"):
        import jax.numpy as jnp

        return [(jnp.zeros((num_pages + 1, page_size, 1), jnp.float32),
                 jnp.zeros((num_pages + 1, page_size, 1), jnp.float32))]

    def __call__(self, tokens, cache=None, start_pos=None, page_table=None):
        import jax

        from mxnet_tpu.ndarray import NDArray

        t = tokens._data.shape[1]
        logits = jax.nn.one_hot(
            jax.numpy.full((tokens._data.shape[0], t), self._token),
            self._vocab, dtype="float32") * 10.0
        return NDArray(logits), cache


def build_net(max_length=64, seed=0):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models import gpt2

    mx.random.seed(seed)
    net = gpt2.GPT2Model(num_layers=2, units=64, num_heads=4,
                         max_length=max_length, vocab_size=VOCAB,
                         dropout=0.0)
    net.initialize()
    _ = net(nd.array(np.zeros((1, 4)), dtype="int32"))
    return net


def _prompt(n, seed):
    import numpy as np

    return list(np.random.RandomState(seed).randint(1, VOCAB, n))


def _counter(name, **labels):
    from mxnet_tpu.observability import REGISTRY

    c = REGISTRY.get(name)
    if c is None:
        return 0.0
    return c.value(**labels) if labels else c.total()


#: (key, prompt seed, prompt len, max_new) — survivors run to their budget
SURVIVORS = [("surv0", 10, 5, 18), ("surv1", 11, 9, 18), ("surv2", 12, 6, 6)]
#: rows interrupted mid-flight must emit a strict prefix of the baseline
PREFIXED = [("slotdl", 20, 5, 18),   # admitted, deadline fires in the slot
            ("cancel", 21, 7, 18)]   # admitted, cancelled mid-decode


def baseline_outputs():
    """Undisturbed plain (non-speculative) paged run of every prompt the
    drill will interrupt or complete — the bit-identity reference."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine

    eng = GenerationEngine(build_net(), batch_size=3, prefill_buckets=(8, 16),
                           eos_id=None, pad_id=PAD, paged=True, page_size=8,
                           num_pages=18)
    bat = ContinuousBatcher(eng)
    reqs = {}
    for key, seed, n, budget in SURVIVORS + PREFIXED:
        reqs[key] = bat.submit(_prompt(n, seed), max_new_tokens=budget)
    bat.run_until_idle(max_steps=500)
    return {k: r.result() for k, r in reqs.items()}


def run_drill(max_steps=250, telemetry_dir=None):
    """Run the drill; returns the evidence dict ``validate`` judges."""
    import mxnet_tpu  # noqa: F401  (package init)
    from mxnet_tpu import observability as obs
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
    from mxnet_tpu.resilience import RetryPolicy, faults
    from mxnet_tpu.resilience import retry as retry_mod

    t_wall = time.perf_counter()
    base = baseline_outputs()

    before = {
        "deadline_q": _counter("gen_deadline_expired_total", where="queue"),
        "deadline_s": _counter("gen_deadline_expired_total", where="slot"),
        "cancelled": _counter("gen_requests_total", reason="cancelled"),
        "shed": _counter("gen_shed_total"),
        "fallbacks": _counter("gen_spec_fallbacks_total"),
        "rearms": _counter("gen_spec_rearms_total"),
        "stuck": _counter("gen_stuck_dispatch_total"),
        "retry_fail": {s: _counter("retry_attempts_total", site=s, ok="false")
                       for s in ("gen.prefill", "gen.decode", "gen.verify")},
    }

    run_dir = telemetry_dir or os.path.join(
        "/tmp", f"servedrill-{os.getpid()}")
    obs.enable(run_dir, run_id="servedrill")
    # deterministic transient noise on every serving site; every>=2 so the
    # default 3-attempt policy can never see a fault twice in a row
    faults.arm("gen.prefill", every=3)
    faults.arm("gen.decode", every=5)
    faults.arm("gen.verify", every=4)

    clock = FakeClock()
    net = build_net()
    eng = GenerationEngine(net, batch_size=3, prefill_buckets=(8, 16),
                           eos_id=None, pad_id=PAD, paged=True, page_size=8,
                           num_pages=18,
                           draft_net=AdversarialDraft(VOCAB, 64),
                           speculate_k=3)
    bat = ContinuousBatcher(
        eng, max_queue=4, queue_policy="shed", head_aging_steps=4,
        spec_window=4, spec_floor=0.3, spec_cooldown=5, watchdog_s=30.0,
        retry_policy=RetryPolicy(base_delay=0.002, jitter=0.0, seed=0),
        clock=clock)

    reqs = {}
    try:
        for key, seed, n, budget in SURVIVORS:
            reqs[key] = bat.submit(_prompt(n, seed), max_new_tokens=budget)
        k, s, n, budget = PREFIXED[0]  # expires mid-slot (admitted at t=0)
        reqs[k] = bat.submit(_prompt(n, s), max_new_tokens=budget,
                             deadline_s=7.0)
        steps = 0
        while True:
            if steps == 2:
                # all 3 slots busy + slotdl queued -> this one expires in
                # the QUEUE (deadline shorter than any plausible wait)
                reqs["queuedl"] = bat.submit(_prompt(6, 22),
                                             max_new_tokens=8, deadline_s=2.0)
            if steps == 3:
                k, s, n, budget = PREFIXED[1]
                reqs[k] = bat.submit(_prompt(n, s), max_new_tokens=budget)
            if steps == 6:
                # submit burst against max_queue=4: the overflow sheds
                for j in range(5):
                    reqs[f"burst{j}"] = bat.submit(
                        _prompt(4, 30 + j), max_new_tokens=4,
                        deadline_s=60.0)
            if (steps >= 8 and not reqs["cancel"].done
                    and reqs["cancel"].slot is not None
                    and not reqs["cancel"].cancel_requested):
                # cancel once the request is decoding in a slot: the next
                # boundary must reclaim it (reason "cancelled")
                assert bat.cancel(reqs["cancel"].id)
            clock.advance(1.0)
            alive = bat.step()
            steps += 1
            if not alive or steps >= max_steps:
                break
        bat.run_until_idle(max_steps=max(0, max_steps - steps))
    finally:
        for site in ("gen.prefill", "gen.decode", "gen.verify"):
            faults.disarm(site)
        obs.disable()

    result = {
        "steps": steps,
        "max_steps": max_steps,
        "wall_s": time.perf_counter() - t_wall,
        "baseline": base,
        "requests": {k: {"reason": r.finish_reason, "output": list(r.output)}
                     for k, r in reqs.items()},
        "counters": {
            "deadline_q": _counter("gen_deadline_expired_total",
                                   where="queue") - before["deadline_q"],
            "deadline_s": _counter("gen_deadline_expired_total",
                                   where="slot") - before["deadline_s"],
            "cancelled": _counter("gen_requests_total", reason="cancelled")
            - before["cancelled"],
            "shed": _counter("gen_shed_total") - before["shed"],
            "fallbacks": _counter("gen_spec_fallbacks_total")
            - before["fallbacks"],
            "rearms": _counter("gen_spec_rearms_total") - before["rearms"],
            "stuck": _counter("gen_stuck_dispatch_total") - before["stuck"],
            "retry_fail": {
                s: _counter("retry_attempts_total", site=s, ok="false")
                - before["retry_fail"][s]
                for s in ("gen.prefill", "gen.decode", "gen.verify")},
        },
        "attempt_log_sites": sorted(
            s for s in ("gen.prefill", "gen.decode", "gen.verify")
            if any(not a["ok"] for a in retry_mod.attempt_log(s))),
        "events": [e["event"] for e in obs.read_events(run_dir)
                   if e.get("event", "").startswith("gen_spec")],
        "drained": {
            "active": bat.active,
            "pending": bat.pending,
            "free_pages": eng.free_pages,
            "num_pages": eng.num_pages,
            "reserved": eng.reserved_pages,
        },
    }
    return result


def validate(result):
    """Judge a drill result; returns the list of violations (empty = OK)."""
    problems = []
    if result["steps"] >= result["max_steps"]:
        problems.append(f"drill did not drain within {result['max_steps']} "
                        "steps (possible hang)")
    base = result["baseline"]
    for key, rec in result["requests"].items():
        reason, out = rec["reason"], rec["output"]
        if reason not in ALLOWED_REASONS:
            problems.append(f"request {key}: finish reason {reason!r} not in "
                            f"{ALLOWED_REASONS}")
            continue
        want = base.get(key)
        if want is None:
            continue
        if reason in ("eos", "length") and out != want:
            problems.append(f"request {key}: completed tokens diverge from "
                            "the undisturbed baseline (corruption)")
        elif reason not in ("eos", "length") and \
                out != want[:len(out)]:
            problems.append(f"request {key}: interrupted tokens are not a "
                            "prefix of the baseline (corruption)")
    for k, v in result["requests"].items():
        if v["reason"] is None:
            problems.append(f"request {k} never terminated")
    c = result["counters"]
    for name in ("deadline_q", "deadline_s", "cancelled", "shed",
                 "fallbacks", "rearms"):
        if c[name] < 1:
            problems.append(f"expected counter {name} >= 1, got {c[name]}")
    if c["stuck"] != 0:
        problems.append(f"watchdog flagged {c['stuck']} stuck dispatches")
    for site, n in c["retry_fail"].items():
        if n < 1:
            problems.append(f"no failed attempts recorded for fault site "
                            f"{site} (injection or retry bridge broken)")
    if sorted(result["attempt_log_sites"]) != \
            ["gen.decode", "gen.prefill", "gen.verify"]:
        problems.append("attempt_log missing records for some gen.* site: "
                        f"{result['attempt_log_sites']}")
    ev = set(result["events"])
    if "gen_spec_fallback" not in ev or "gen_spec_rearm" not in ev:
        problems.append(f"fallback/re-arm events missing from telemetry: "
                        f"{sorted(ev)}")
    d = result["drained"]
    if d["active"] or d["pending"]:
        problems.append(f"not drained: active={d['active']} "
                        f"pending={d['pending']}")
    if d["free_pages"] != d["num_pages"]:
        problems.append(f"page leak: {d['free_pages']}/{d['num_pages']} "
                        "free after drain")
    if d["reserved"]:
        problems.append(f"reservation leaked: {d['reserved']} pages")
    return problems


# ---------------------------------------------------------------------------
# --fleet: multi-replica chaos drill over mxnet_tpu.serving
# ---------------------------------------------------------------------------

#: (key, prompt seed, prompt len, max_new, priority class[, session])
FLEET_FIRST = [("fs0", 40, 5, 6, "interactive", "sessA"),
               ("fs1", 41, 6, 6, "normal"),
               ("fs2", 42, 7, 6, "normal"),
               ("fs3", 43, 5, 6, "batch"),
               ("fs4", 44, 6, 6, "batch"),
               ("fs5", 45, 7, 6, "normal")]
#: second burst lands mid-failure (one replica dead, one wedging)
FLEET_SECOND = [("fb0", 50, 5, 6, "normal"),
                ("fb1", 51, 6, 6, "interactive"),
                ("fb2", 52, 7, 6, "batch"),
                ("fb3", 53, 5, 6, "normal")]
#: second turn of sessA, submitted once fs0 completed — must land on the
#: replica holding its prefix pages while that replica is LIVE
FLEET_SESSION2 = ("fsA2", 46, 5, 6, "interactive", "sessA")
#: deliberately hopeless deadline: the one request ALLOWED to expire
FLEET_EXPIRE = ("expire", 60, 6, 8, "batch")

KILL_TICK, WEDGE_TICK, REPLACEMENT_RID = 3, 4, 3


def fleet_baseline():
    """Undisturbed single-engine run of every fleet prompt — the
    bit-identity reference a redistributed re-run must still match."""
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine

    eng = GenerationEngine(build_net(), batch_size=2, prefill_buckets=(8,),
                           eos_id=None, pad_id=PAD, paged=True, page_size=4,
                           num_pages=12)
    bat = ContinuousBatcher(eng)
    reqs = {}
    for spec in (FLEET_FIRST + FLEET_SECOND
                 + [FLEET_SESSION2, FLEET_EXPIRE]):
        key, seed, n, budget = spec[:4]
        reqs[key] = bat.submit(_prompt(n, seed), max_new_tokens=budget)
    bat.run_until_idle(max_steps=500)
    return {k: r.result() for k, r in reqs.items()}


def _drill_sampler():
    """Keep-everything tail sampler: the drill's gate needs a complete
    trace for EVERY terminal request, not a sample."""
    from mxnet_tpu.observability import tracing

    return tracing.TailSampler(sample=1.0, seed=0, slow_pct=100.0,
                               margin_floor=0.0)


def _fleet_replica(rid, net, fleet_dir, clock):
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
    from mxnet_tpu.observability import tracing
    from mxnet_tpu.serving import ServingReplica

    eng = GenerationEngine(net, batch_size=2, prefill_buckets=(8,),
                           eos_id=None, pad_id=PAD, paged=True, page_size=4,
                           num_pages=12)
    # watchdog disarmed while healthy: the first dispatches of a fresh
    # replica pay wall-clock jit compiles that a tight drill budget would
    # misread as stalls; the wedge arms it when the wedge starts
    bat = ContinuousBatcher(eng, max_queue=8, queue_policy="reject",
                            watchdog_s=0.0, clock=clock)
    tr = tracing.Tracer(
        os.path.join(fleet_dir, f"telemetry-h{rid}", "spans-g0.jsonl"),
        source=f"h{rid}", sampler=_drill_sampler(), clock=clock)
    return ServingReplica(rid, bat, fleet_dir, clock=clock, tracer=tr)


def run_fleet_drill(max_ticks=60, telemetry_dir=None, fleet_dir=None,
                    inject_orphan_span=False):
    """Run the multi-replica drill; returns the evidence dict
    ``validate_fleet`` judges. One tick = one fake second: the router
    schedules, then every still-running replica steps (the killed one
    stops stepping AND publishing; the wedged one publishes heartbeats
    but every dispatch trips its watchdog).

    Request tracing runs with a keep-everything tail sampler; after the
    drill the evidence includes, per terminal request, whether its
    assembled trace is gap-free with phase sums reconciling against the
    end-to-end latency (docs/OBSERVABILITY.md "Request tracing & SLO
    ledger"). ``inject_orphan_span`` appends a span with a trace id no
    request owns before assembly — the tested red path."""
    import tempfile

    import mxnet_tpu  # noqa: F401  (package init)
    from mxnet_tpu import observability as obs
    from mxnet_tpu.observability import tracing
    from mxnet_tpu.observability.fleet import FleetAggregator
    from mxnet_tpu.serving import DEAD, LIVE, FleetHealth, FleetRouter

    t_wall = time.perf_counter()
    base = fleet_baseline()

    before = {
        "redistributed": _counter("gen_requests_total",
                                  reason="redistributed"),
        "router_redistributions": _counter("router_redistributions_total"),
        "stuck": _counter("gen_stuck_dispatch_total"),
    }

    run_dir = telemetry_dir or os.path.join(
        "/tmp", f"fleetdrill-{os.getpid()}")
    fdir = fleet_dir or tempfile.mkdtemp(prefix="fleetdrill-fleet-")
    obs.enable(run_dir, run_id="fleetdrill")

    clock = FakeClock()
    net = build_net()
    replicas = {rid: _fleet_replica(rid, net, fdir, clock)
                for rid in (0, 1, 2)}
    health = FleetHealth(hb_timeout=2.5, drain_after=2.0, dead_grace=6.0)
    router = FleetRouter(fdir, health=health, queue_bound=3, affinity=True,
                         seed=0, clock=clock,
                         tracer=tracing.Tracer(
                             os.path.join(fdir, "router", "spans-g0.jsonl"),
                             source="router", sampler=_drill_sampler(),
                             owner=True, clock=clock))
    for rep in replicas.values():
        router.attach(rep)

    reqs = {}

    def sub(key, seed, n, budget, priority, session=None, deadline_s=500.0):
        reqs[key] = router.submit(_prompt(n, seed), max_new_tokens=budget,
                                  priority=priority, session=session,
                                  deadline_s=deadline_s)

    kill_rid = wedge_rid = None
    affinity = {}
    sess2_submitted = replacement_attached = False
    ticks = 0
    try:
        for spec in FLEET_FIRST:
            sub(*spec)
        while ticks < max_ticks:
            clock.advance(1.0)
            ticks += 1
            if ticks == KILL_TICK:
                # kill the replica holding the most in-flight work: its
                # loop AND its publisher stop — a dead process
                counts = router.assignments()
                kill_rid = max(replicas,
                               key=lambda r: (counts.get(r, 0), -r))
            if ticks == WEDGE_TICK:
                # wedge the busiest survivor: heartbeats continue, every
                # dispatch exceeds the watchdog budget
                counts = router.assignments()
                wedge_rid = max(
                    (r for r in replicas if r != kill_rid),
                    key=lambda r: (counts.get(r, 0), -r))
                for spec in FLEET_SECOND:  # burst into the failing fleet
                    sub(*spec)
                sub(*FLEET_EXPIRE, deadline_s=1.5)
            router.step()
            if not sess2_submitted and reqs["fs0"].done:
                first = (reqs["fs0"].replicas_tried[-1]
                         if reqs["fs0"].replicas_tried else None)
                affinity = {"first": first,
                            "first_state": None if first is None
                            else router.health.state(first)}
                sub(*FLEET_SESSION2)
                sess2_submitted = True
            if not replacement_attached and wedge_rid is not None \
                    and router.health.state(wedge_rid) == DEAD:
                replacement_attached = True
                replicas[REPLACEMENT_RID] = _fleet_replica(
                    REPLACEMENT_RID, net, fdir, clock)
                router.attach(replicas[REPLACEMENT_RID])
            for rid, rep in replicas.items():
                if router.health.state(rid) == DEAD:
                    continue
                if rid == kill_rid and ticks >= KILL_TICK:
                    continue
                if rid == wedge_rid and ticks >= WEDGE_TICK:
                    wd = rep.batcher.watchdog
                    wd.timeout_s = 0.05  # the wedge arms the watchdog
                    with wd.guard("decode", 0):
                        time.sleep(wd.timeout_s + 0.05)
                    rep.publish()
                    continue
                rep.step()
            if sess2_submitted and replacement_attached and router.idle \
                    and all(r.done for r in reqs.values()):
                break
        router.publish(generation=0)
        if sess2_submitted and reqs["fsA2"].replicas_tried:
            affinity["second"] = reqs["fsA2"].replicas_tried[-1]
        report = FleetAggregator(fdir).collect()
        router_summary = report.summary().get("router", {}) if report \
            else {}
        events = obs.read_events(run_dir)
    finally:
        obs.disable()

    # flush every tracer, then join the span files exactly like a
    # post-mortem would: by trace id from the shared fleet dir
    router.tracer.close()
    for rep in replicas.values():
        if rep.tracer is not None:
            rep.tracer.close()
    if inject_orphan_span:
        with open(os.path.join(fdir, "router", "spans-g0.jsonl"),
                  "a") as f:
            f.write(json.dumps({"kind": "span", "trace": "ghost-999",
                                "name": "router.backlog", "t0": 0.0,
                                "t1": 1.0, "src": "router"}) + "\n")
    assembled = tracing.assemble(tracing.collect_records(fdir))
    checks = {tid: tracing.check_trace(t) for tid, t in assembled.items()}
    id_of = {k: str(r.id) for k, r in reqs.items()}
    ends = [t["end"] for t in assembled.values() if t["end"] is not None]
    traces_ev = {
        "checked": len(ends),
        # terminal requests whose trace never assembled (no end record)
        "missing": sorted(k for k, tid in id_of.items()
                          if assembled.get(tid, {}).get("end") is None),
        "problems": {tid: c["problems"] for tid, c in checks.items()
                     if assembled[tid]["end"] is not None and not c["ok"]},
        "orphans": sorted(tid for tid, t in assembled.items()
                          if t["end"] is None and t["spans"]),
        "hops": sum(int(e.get("hops") or 0) for e in ends),
        "phase_err_max": max((checks[tid]["rel_err"]
                              for tid, t in assembled.items()
                              if t["end"] is not None
                              and checks[tid]["rel_err"] is not None),
                             default=0.0),
    }

    survivors = {rid: rep for rid, rep in replicas.items()
                 if router.health.state(rid) == LIVE}
    result = {
        "ticks": ticks,
        "max_ticks": max_ticks,
        "wall_s": time.perf_counter() - t_wall,
        "baseline": base,
        "kill_rid": kill_rid,
        "wedge_rid": wedge_rid,
        "replacement_attached": replacement_attached,
        "expected_deadline": ["expire"],
        "requests": {k: {"reason": r.finish_reason,
                         "output": list(r.output),
                         "redistributions": r.redistributions,
                         "replicas": list(r.replicas_tried),
                         "priority": r.priority}
                     for k, r in reqs.items()},
        "transitions": {rid: [{"to": t["to"], "cause": t["cause"]}
                              for t in rec.transitions]
                        for rid, rec in health.records.items()},
        "counters": {
            "redistributed": _counter("gen_requests_total",
                                      reason="redistributed")
            - before["redistributed"],
            "router_redistributions":
                _counter("router_redistributions_total")
                - before["router_redistributions"],
            "stuck": _counter("gen_stuck_dispatch_total") - before["stuck"],
        },
        "events": {
            "names": sorted({e["event"] for e in events
                             if e.get("event", "").startswith("replica_")}),
            "stuck_replicas": sorted(
                {e.get("replica") for e in events
                 if e.get("event") == "gen_stuck_dispatch"}),
        },
        "affinity": affinity,
        "router_state": {"backlog": router.backlog,
                         "in_flight": router.in_flight},
        "drained": {rid: {"active": rep.batcher.active,
                          "pending": rep.batcher.pending,
                          "free_pages": rep.engine.free_pages,
                          "num_pages": rep.engine.num_pages,
                          "reserved": rep.engine.reserved_pages}
                    for rid, rep in survivors.items()},
        "router_summary": router_summary,
        "traces": traces_ev,
        "fleet_dir": fdir,
    }
    return result


def validate_fleet(result):
    """Judge a fleet-drill result; returns violations (empty = OK)."""
    problems = []
    if result["ticks"] >= result["max_ticks"]:
        problems.append(f"fleet drill did not settle within "
                        f"{result['max_ticks']} ticks (possible hang)")
    base = result["baseline"]
    expected_deadline = set(result["expected_deadline"])
    for key, rec in result["requests"].items():
        reason, out = rec["reason"], rec["output"]
        if reason is None:
            problems.append(f"request {key} never terminated "
                            "(dropped in-deadline work)")
            continue
        want = base.get(key, [])
        if key in expected_deadline:
            if reason != "deadline":
                problems.append(f"request {key}: expected the hopeless "
                                f"deadline to expire, got {reason!r}")
            elif out != want[:len(out)]:
                problems.append(f"request {key}: expired tokens are not a "
                                "prefix of the baseline (corruption)")
            continue
        if reason != "length":
            # every in-deadline request must be SERVED to its budget —
            # a deadline/shed here is a dropped request
            problems.append(f"in-deadline request {key} finished "
                            f"{reason!r} instead of being served")
        elif out != want:
            problems.append(f"request {key}: tokens diverge from the "
                            "undisturbed baseline (corruption across "
                            "redistribution)")
    if result["kill_rid"] is None or result["wedge_rid"] is None:
        problems.append("drill never selected a kill/wedge replica")
        return problems
    tr = result["transitions"]
    wedged = [t["to"] for t in tr.get(result["wedge_rid"], [])]
    if wedged != ["degraded", "draining", "dead"]:
        problems.append(f"wedged replica walked {wedged}, expected "
                        "['degraded', 'draining', 'dead']")
    wcauses = [t["cause"] for t in tr.get(result["wedge_rid"], [])]
    if not wcauses or wcauses[0] != "stuck_dispatch":
        problems.append(f"wedged replica degraded for {wcauses[:1]}, "
                        "expected 'stuck_dispatch'")
    killed = tr.get(result["kill_rid"], [])
    if not killed or killed[-1]["to"] != "dead":
        problems.append(f"killed replica never reached DEAD: {killed}")
    elif killed[0]["cause"] != "heartbeat":
        problems.append(f"killed replica degraded for "
                        f"{killed[0]['cause']!r}, expected 'heartbeat'")
    if not result["replacement_attached"]:
        problems.append("replacement replica never joined the fleet")
    c = result["counters"]
    for name in ("redistributed", "router_redistributions", "stuck"):
        if c[name] < 1:
            problems.append(f"expected counter {name} >= 1, got {c[name]}")
    ev = set(result["events"]["names"])
    for name in ("replica_degraded", "replica_drain", "replica_dead"):
        if name not in ev:
            problems.append(f"event {name} missing from telemetry: "
                            f"{sorted(ev)}")
    if result["wedge_rid"] not in result["events"]["stuck_replicas"]:
        problems.append("gen_stuck_dispatch events do not attribute the "
                        f"wedged replica {result['wedge_rid']}: "
                        f"{result['events']['stuck_replicas']}")
    aff = result["affinity"]
    if aff.get("first") is not None and aff.get("first_state") == "live" \
            and aff.get("second") != aff["first"]:
        problems.append(f"session affinity broken: first turn on replica "
                        f"{aff['first']} (still LIVE), second landed on "
                        f"{aff.get('second')}")
    rs = result["router_state"]
    if rs["backlog"] or rs["in_flight"]:
        problems.append(f"router not idle: backlog={rs['backlog']} "
                        f"in_flight={rs['in_flight']}")
    if not result["drained"]:
        problems.append("no surviving LIVE replica at the end")
    for rid, d in result["drained"].items():
        if d["active"] or d["pending"]:
            problems.append(f"replica {rid} not drained: "
                            f"active={d['active']} pending={d['pending']}")
        if d["free_pages"] != d["num_pages"]:
            problems.append(f"replica {rid} page leak: "
                            f"{d['free_pages']}/{d['num_pages']} free")
        if d["reserved"]:
            problems.append(f"replica {rid} reservation leaked: "
                            f"{d['reserved']} pages")
    tre = result.get("traces") or {}
    if tre:
        # every terminal request must carry a complete, gap-free trace
        # whose router-level phase sums reconcile against its e2e latency
        if tre["missing"]:
            problems.append("requests with no assembled trace end record: "
                            f"{tre['missing']}")
        for tid, probs in sorted(tre["problems"].items()):
            problems.append(f"trace {tid} failed reconciliation: {probs}")
        if tre["orphans"]:
            problems.append(f"orphaned spans with no owning request: "
                            f"{tre['orphans']}")
        if tre["phase_err_max"] > 0.05:
            problems.append(f"worst trace phase-sum error "
                            f"{tre['phase_err_max']:.1%} exceeds 5%")
        if tre["hops"] != int(c["router_redistributions"]):
            problems.append(
                f"trace hop count {tre['hops']} does not match "
                f"router_redistributions_total "
                f"{c['router_redistributions']:.0f}")
    rsum = result["router_summary"].get("replicas", {})
    for rid in (result["kill_rid"], result["wedge_rid"]):
        if rsum.get(str(rid), {}).get("state") != "dead":
            problems.append(f"fleet report does not show replica {rid} "
                            f"dead: {rsum.get(str(rid))}")
    if not any(rec.get("state") == "live" for rec in rsum.values()):
        problems.append(f"fleet report shows no live replica: {rsum}")
    return problems


def main_fleet(args):
    result = run_fleet_drill(max_ticks=args.max_ticks,
                             inject_orphan_span=args.inject_orphan_span)
    if args.inject_drop:
        key = next(iter(result["requests"]))
        result["requests"][key]["reason"] = None
    problems = validate_fleet(result)

    c = result["counters"]
    print(f"fleetdrill: {len(result['requests'])} requests, "
          f"{result['ticks']} ticks, {result['wall_s']:.1f}s wall")
    print(f"  killed={result['kill_rid']} wedged={result['wedge_rid']} "
          f"replacement={'yes' if result['replacement_attached'] else 'NO'}")
    print(f"  transitions: " + "; ".join(
        f"r{rid}:" + "->".join(t['to'] for t in trs)
        for rid, trs in sorted(result["transitions"].items()) if trs))
    print(f"  redistributed={c['redistributed']:.0f} "
          f"(router pull-backs={c['router_redistributions']:.0f}) "
          f"stuck={c['stuck']:.0f}")
    reasons = sorted({v['reason'] or 'NONE'
                      for v in result['requests'].values()})
    print(f"  reasons: {', '.join(reasons)}")
    tre = result.get("traces") or {}
    if tre:
        print(f"  traces: checked={tre['checked']} "
              f"missing={len(tre['missing'])} "
              f"broken={len(tre['problems'])} orphans={len(tre['orphans'])} "
              f"hops={tre['hops']} "
              f"phase_err_max={tre['phase_err_max']:.2%} "
              f"(waterfalls: tools/tracereport.py {result['fleet_dir']})")
    print(f"  drained: {result['drained']}")
    if problems:
        for p in problems:
            print(f"fleetdrill: FAIL: {p}")
        return 1
    print("fleetdrill: OK — zero in-deadline drops, wedged replica "
          "degraded->drained->dead with work redistributed, gap-free "
          "traces reconciled, survivors drained clean")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--max-steps", type=int, default=250)
    ap.add_argument("--fleet", action="store_true",
                    help="run the multi-replica fleet drill "
                    "(make chaos-fleet) instead of the single-engine one")
    ap.add_argument("--max-ticks", type=int, default=60,
                    help="fleet drill tick budget (1 tick = 1 fake second)")
    ap.add_argument("--inject-leak", action="store_true",
                    help="failure-path test hook: corrupt the drained-state "
                    "evidence; the gate must fail")
    ap.add_argument("--inject-drop", action="store_true",
                    help="failure-path test hook (--fleet): erase one "
                    "request's finish reason; the gate must fail")
    ap.add_argument("--inject-orphan-span", action="store_true",
                    help="failure-path test hook (--fleet): append a span "
                    "owned by no request to the router span file; the "
                    "trace gate must fail")
    args = ap.parse_args(argv)

    if args.fleet:
        return main_fleet(args)

    result = run_drill(max_steps=args.max_steps)
    if args.inject_leak:
        result["drained"]["free_pages"] -= 1
    problems = validate(result)

    c = result["counters"]
    print(f"servedrill: {len(result['requests'])} requests, "
          f"{result['steps']} steps, {result['wall_s']:.1f}s wall")
    print(f"  reasons: "
          + ", ".join(sorted({v['reason'] or 'NONE'
                              for v in result['requests'].values()})))
    print(f"  deadline(queue/slot)={c['deadline_q']:.0f}/"
          f"{c['deadline_s']:.0f} cancelled={c['cancelled']:.0f} "
          f"shed={c['shed']:.0f}")
    print(f"  spec fallbacks={c['fallbacks']:.0f} rearms={c['rearms']:.0f} "
          f"stuck={c['stuck']:.0f}")
    print(f"  retry failures absorbed: "
          + ", ".join(f"{s}={n:.0f}"
                      for s, n in sorted(c["retry_fail"].items())))
    print(f"  drained: {result['drained']}")
    if problems:
        for p in problems:
            print(f"servedrill: FAIL: {p}")
        return 1
    print("servedrill: OK — explicit finish reasons, bit-identical "
          "survivors, fallback+re-arm observed, clean drain")
    return 0


if __name__ == "__main__":
    sys.exit(main())
