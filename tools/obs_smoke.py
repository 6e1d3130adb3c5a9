#!/usr/bin/env python
"""`make obs` gate: a tiny LeNet training run with full telemetry on, then
assert `tools/obs_report.py` renders a non-empty summary covering every
subsystem the ISSUE acceptance names — step/loss/throughput metrics, at
least one recompile event, KVStore byte/latency histograms, checkpoint
durations, and retry counters consistent with `resilience.retry.attempt_log`.

Also provides ``--chaos-check`` (used by `make chaos`): run one retried
operation under injected faults and assert the registry's retry counters
are non-zero and agree with the attempt log.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _fail(msg):
    print(f"obs_smoke: FAIL - {msg}", file=sys.stderr)
    sys.exit(1)


def chaos_check():
    """Assert retry counters flow into the metrics registry under injection."""
    import tempfile

    from mxnet_tpu import kv, nd, observability as obs, optimizer as opt
    from mxnet_tpu.resilience import faults, retry

    retry.clear_log("kv.save_states")
    store = kv.create("local")
    store.set_optimizer(opt.create("sgd"))
    store.init("w", nd.ones((2,)))
    before = obs.REGISTRY.counter("retry_attempts_total").total()
    with tempfile.TemporaryDirectory() as d:
        with faults.inject("kv.save_states", on=1):
            store.save_optimizer_states(os.path.join(d, "states"))
    attempts = retry.attempt_log("kv.save_states")
    delta = obs.REGISTRY.counter("retry_attempts_total").total() - before
    if not attempts:
        _fail("no retry attempts recorded under injected fault")
    if delta != len(attempts):
        _fail(f"registry retry counter delta {delta} != attempt_log "
              f"{len(attempts)}")
    failed = obs.REGISTRY.counter("retry_attempts_total").value(
        site="kv.save_states", ok="false")
    if failed < 1:
        _fail("no failed attempt counted for kv.save_states")
    print(f"obs_smoke: chaos-check OK ({len(attempts)} attempts, "
          f"{int(failed)} failed, counters match attempt_log)")


def main():
    if "--chaos-check" in sys.argv:
        chaos_check()
        return

    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, observability as obs, optimizer as opt
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep
    from mxnet_tpu.resilience import faults, retry

    run_dir = tempfile.mkdtemp(prefix="obs_smoke_")
    # fleet view (docs/OBSERVABILITY.md "Fleet view"): arm the single-rank
    # snapshot writer so the gate also exercises tools/fleetreport.py
    from mxnet_tpu import config

    fleet_dir = os.path.join(run_dir, "fleet")
    config.set("fleet_dir", fleet_dir)
    obs.enable(run_dir)
    mx.random.seed(0)

    # -- 2-step LeNet train under TrainStep (step/loss/gnorm/recompile) ------
    net = nn.HybridSequential()
    net.add(nn.Conv2D(6, 5, padding=2, activation="tanh"),
            nn.MaxPool2D(2, 2),
            nn.Flatten(),
            nn.Dense(32, activation="tanh"),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.rand(8, 1, 28, 28).astype(np.float32))
    y = nd.array(np.arange(8) % 10)
    _ = net(x)
    from mxnet_tpu import gluon

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = TrainStep(net, loss_fn, opt.create("adam", learning_rate=1e-3))
    for _i in range(2):
        step(x, y)

    # -- checkpoint save/restore metrics -------------------------------------
    step.save(os.path.join(run_dir, "ckpt"))
    step.restore(os.path.join(run_dir, "ckpt"))

    # -- KVStore collective metrics + retry counters -------------------------
    # single-host smoke: arming the fault registry forces the instrumented
    # DCN path (process_count==1 short-circuits otherwise), and an injected
    # transient on the psum exercises retry accounting end to end
    retry.clear_log("kv.dcn_psum")
    store = mx.kv.create("dist_sync")
    store.init("g", nd.zeros((16,)))
    with faults.inject("kv.dcn_psum", on=1):
        store.push("g", nd.ones((16,)))
    out = nd.zeros((16,))
    store.pull("g", out=out)
    attempts = retry.attempt_log("kv.dcn_psum")
    obs.shutdown()

    # -- assertions over the rendered report ---------------------------------
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import obs_report

    summary = obs_report.summarize(run_dir)
    if summary is None:
        _fail(f"empty telemetry dir {run_dir}")
    text = obs_report.render(summary)
    print(text)
    t = summary["train"]
    if t["steps"] < 2 or t["loss_last"] is None:
        _fail("missing step/loss metrics")
    if not t["samples_per_sec"] or not t["tokens_per_sec"]:
        _fail("missing throughput metrics")
    if t["recompiles"] < 1:
        _fail("no recompile events recorded")
    if "psum" not in summary["kv"] or summary["kv"]["psum"]["bytes"] <= 0:
        _fail("missing KVStore byte/latency metrics")
    if summary["checkpoint"]["saves"] < 1 or summary["checkpoint"]["loads"] < 1:
        _fail("missing checkpoint metrics")
    site = summary["retries"].get("kv.dcn_psum")
    if site is None:
        _fail("missing retry counters")
    if site["ok"] + site["failed"] != len(attempts):
        _fail(f"retry counters {site} disagree with attempt_log "
              f"({len(attempts)} records)")

    # -- fleet report over the single-rank snapshot --------------------------
    import fleetreport

    if fleetreport.main([fleet_dir]) != 0:
        _fail(f"fleetreport found no rank telemetry under {fleet_dir}")
    from mxnet_tpu.observability.fleet import FleetAggregator

    freport = FleetAggregator(fleet_dir).collect()
    if freport is None or 0 not in freport.ranks:
        _fail("fleet aggregator missing rank 0")
    if freport.ranks[0].step_hist["count"] < 2:
        _fail("fleet report missing the run's step timings")
    if freport.ranks[0].flops_per_step is None:
        _fail("fleet report missing the FLOPs/step gauge")
    if freport.goodput is None or freport.goodput.buckets["train"] <= 0:
        _fail("fleet goodput ledger missing productive train time")
    # the overhead guards below measure the record path in isolation —
    # the fleet snapshot cadence thread must not re-arm on re-enable
    config.set("fleet_dir", "")

    # -- telemetry-off overhead < 1% of a warm step --------------------------
    # the off-path adds exactly: the enabled() gate, the always-on step
    # record (five clock reads, five profiler annotations, one tuple), the
    # jitted function's program count read twice, and the (empty) monitor
    # loop. Time those extras in isolation against a warm compiled step.
    import time as _time

    from mxnet_tpu.parallel.train_step import _programs_held

    obs.disable()
    step(x, y)  # warm the telemetry-off program
    t0 = _time.perf_counter()
    for _i in range(5):
        step(x, y)
    jax.block_until_ready(step.params)
    step_s = (_time.perf_counter() - t0) / 5
    jitted = next(iter(step._compiled.values()))
    t0 = _time.perf_counter()
    for _i in range(1000):
        obs.enabled()
        with obs.step_record("obs_smoke", _i) as rec:
            for name in ("mx.train.input", "mx.train.args",
                         "mx.train.dispatch", "mx.train.after"):
                with obs.span(name):
                    pass
            rec.compiled = _programs_held(jitted) > _programs_held(jitted)
        for _m in step._monitors:
            pass
    extra_s = (_time.perf_counter() - t0) / 1000
    ratio = extra_s / step_s
    print(f"telemetry-off overhead: {extra_s * 1e6:.1f} us per step "
          f"({ratio * 100:.3f}% of a {step_s * 1e3:.2f} ms warm step)")
    if ratio >= 0.01:
        _fail(f"telemetry-off overhead {ratio * 100:.2f}% >= 1%")

    # -- telemetry-ON record-path budget (ISSUE 9 satellite) -----------------
    # the per-step extras when telemetry is on: holding the step's futures,
    # and, some dispatches later, publishing them = device fetch of ready
    # futures, ~8 registry ops, the FLOPs-memo lookup, one JSONL event
    # write. Budget (docs/OBSERVABILITY.md): <= 0.15% of a >=200 ms
    # production step, enforced here as a 300 us absolute ceiling (this
    # gate's LeNet step is ~10 ms, where the same absolute cost reads as
    # ~2-3%).
    import tempfile as _tf

    obs.enable(_tf.mkdtemp(prefix="obs_smoke_on_"))
    loss = step(x, y)  # telemetry-on program (adds the gnorm output)
    jax.block_until_ready(loss)
    obs.flush()
    raws_on = (x._data, y._data)
    key_on = step._step_cache_key(2, True)
    rec_s = None
    for _round in range(5):  # min-of-rounds: robust to CI load spikes
        t0 = _time.perf_counter()
        for _i in range(200):
            with obs.step_record("obs_smoke", _i) as rec:
                pass
            step._hold(rec, raws_on, loss, loss, key_on)
            step.flush_telemetry()
        d = (_time.perf_counter() - t0) / 200
        rec_s = d if rec_s is None or d < rec_s else rec_s
    budget = max(0.0015 * step_s, 300e-6)
    print(f"telemetry-on record path: {rec_s * 1e6:.1f} us per step "
          f"(budget {budget * 1e6:.0f} us = 0.15% of a >=200 ms step)")
    obs.disable()
    if rec_s > budget:
        _fail(f"telemetry-on record path {rec_s * 1e6:.1f} us exceeds the "
              f"{budget * 1e6:.0f} us budget")

    print(f"\nobs_smoke: OK (run dir {run_dir})")


if __name__ == "__main__":
    main()
