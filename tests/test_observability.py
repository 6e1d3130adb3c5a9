"""Observability subsystem (docs/OBSERVABILITY.md): metrics registry
semantics, JSONL event-log schema, and the step/comm/ckpt/retry
instrumentation contracts from the ISSUE acceptance criteria."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, observability as obs
from mxnet_tpu.observability import events as ev_mod
from mxnet_tpu.observability.metrics import Registry


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Tests arm telemetry per-case; never leak the gate (or an open event
    log) into the rest of the suite."""
    yield
    obs.disable()


# -- registry semantics ------------------------------------------------------

def test_counter_labels_and_totals():
    r = Registry()
    c = r.counter("reqs_total", "requests")
    c.inc(2, site="a")
    c.inc(site="a")
    c.inc(5, site="b")
    assert c.value(site="a") == 3
    assert c.value(site="b") == 5
    assert c.value(site="nope") == 0
    assert c.total() == 8
    with pytest.raises(ValueError):
        c.inc(-1)
    # re-registering the same name+kind returns the same object; kind clash raises
    assert r.counter("reqs_total") is c
    with pytest.raises(ValueError):
        r.gauge("reqs_total")


def test_gauge_set_and_value():
    r = Registry()
    g = r.gauge("temp")
    assert g.value() is None
    g.set(1.5)
    g.set(2.5, zone="hot")
    assert g.value() == 1.5
    assert g.value(zone="hot") == 2.5


def test_histogram_buckets_stats_percentile():
    r = Registry()
    h = r.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v, op="x")
    s = h.stats(op="x")
    assert s["count"] == 4
    assert s["min"] == 0.005 and s["max"] == 5.0
    assert abs(s["sum"] - 5.555) < 1e-9
    # one observation per bucket incl. the +Inf overflow
    assert s["buckets"] == [1, 1, 1, 1]
    assert h.percentile(0.5, op="x") == 0.1
    assert h.percentile(1.0, op="x") == 5.0  # max, not an edge
    assert h.total_count() == 4


def test_snapshot_reset_roundtrip():
    r = Registry()
    r.counter("c").inc(3, k="v")
    r.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = r.snapshot()
    assert snap["c"]["kind"] == "counter"
    assert snap["c"]["series"][0] == {"labels": {"k": "v"}, "value": 3.0}
    hseries = snap["h"]["series"][0]["value"]
    assert hseries["count"] == 1 and hseries["buckets"]["1.0"] == 1
    # snapshot is JSON-safe
    json.loads(r.to_json())
    r.reset("c")
    assert r.counter("c").total() == 0
    assert r.histogram("h").total_count() == 1
    r.reset()
    assert r.histogram("h").total_count() == 0


def test_prometheus_export_format():
    r = Registry()
    r.counter("n_total", "help text").inc(2, site="a")
    r.histogram("h_seconds", buckets=(0.1, 1.0)).observe(0.05, op="x")
    text = r.to_prometheus()
    assert '# TYPE n_total counter' in text
    assert 'n_total{site="a"} 2.0' in text
    # cumulative buckets + +Inf + sum/count
    assert 'h_seconds_bucket{le="0.1",op="x"} 1' in text
    assert 'h_seconds_bucket{le="+Inf",op="x"} 1' in text
    assert 'h_seconds_count{op="x"} 1' in text


# -- event log ---------------------------------------------------------------

def test_event_log_schema_roundtrip(tmp_path):
    log = ev_mod.EventLog()
    log.configure(str(tmp_path / "events.jsonl"), run_id="r1")
    log.set_step(7)
    assert log.emit("unit", foo=1, bar="baz")
    assert log.emit("unit2", step=9, val=2.5)
    log.close()
    recs = ev_mod.read_events(str(tmp_path / "events.jsonl"))
    assert len(recs) == 2
    for rec in recs:
        assert set(rec) >= {"ts", "run", "host", "step", "event"}
        assert rec["run"] == "r1"
    assert recs[0]["event"] == "unit" and recs[0]["step"] == 7 and recs[0]["foo"] == 1
    assert recs[1]["step"] == 9  # explicit step overrides the monotonic one


def test_event_log_rotation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = ev_mod.EventLog()
    log.configure(path, rotate_bytes=4096)  # exactly one rotation over 40 records
    for i in range(40):
        log.emit("tick", i=i, pad="x" * 64)
    log.close()
    # rotated segments are gzip-compressed, numbered oldest-first
    assert os.path.exists(path + ".1.gz"), "rotation never happened"
    recs = ev_mod.read_events(path)
    # nothing lost across a single rotation boundary, order preserved
    assert [r["i"] for r in recs] == list(range(40))
    # directory-mode read finds the same records (gz read transparently)
    assert len(ev_mod.read_events(str(tmp_path))) == 40
    # many rotations at the default keep_bytes=0: disk stays bounded at
    # the live file + exactly ONE rotated segment holding the tail
    log2 = ev_mod.EventLog()
    log2.configure(str(tmp_path / "e2.jsonl"), rotate_bytes=512)
    for i in range(64):
        log2.emit("tick", i=i, pad="x" * 64)
    log2.close()
    assert len(ev_mod.rotated_segments(str(tmp_path / "e2.jsonl"))) == 1
    tail = [r["i"] for r in ev_mod.read_events(str(tmp_path / "e2.jsonl"))]
    assert tail == list(range(tail[0], 64)) and len(tail) >= 2


def test_emit_noop_when_unconfigured():
    log = ev_mod.EventLog()
    assert log.emit("nope") is False


# -- TrainStep instrumentation ----------------------------------------------

def _tiny_train_step():
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep

    net = nn.Dense(4, in_units=3)
    net.initialize()
    _ = net(nd.ones((2, 3)))
    return TrainStep(net, lambda out, y: (out - y) ** 2,
                     opt.create("sgd", learning_rate=0.1))


def test_recompile_counter_increments_once_on_shape_change(tmp_path):
    obs.enable(str(tmp_path))
    rc = obs.counter("train_recompiles_total")
    step = _tiny_train_step()
    before = rc.total()
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    assert rc.total() == before + 1  # first lowering, steady state after
    step(nd.ones((6, 3)), nd.ones((6, 4)))  # batch-shape change
    assert rc.total() == before + 2
    assert rc.value(reason="shape") >= 1
    step(nd.ones((6, 3)), nd.ones((6, 4)))  # same shape: cached
    assert rc.total() == before + 2
    obs.shutdown()
    recs = [e for e in obs.read_events(str(tmp_path)) if e["event"] == "recompile"]
    assert len(recs) == 2
    assert recs[1]["reason"] == "shape" and recs[1]["shapes"][0] == [6, 3]


def test_train_step_metrics_and_events(tmp_path):
    obs.enable(str(tmp_path))
    step = _tiny_train_step()
    steps_c = obs.counter("train_steps_total")
    before = steps_c.value(loop="train_step")
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    # readings lag the dispatch (no step ever waits for the one just
    # queued): nothing is published before the lag or a flush
    assert steps_c.value(loop="train_step") == before
    obs.flush()
    assert steps_c.value(loop="train_step") == before + 2
    assert obs.REGISTRY.get("train_step_seconds").total_count() >= 2
    assert obs.gauge("train_loss").value() is not None
    assert obs.gauge("train_grad_norm").value() is not None
    obs.shutdown()
    recs = [e for e in obs.read_events(str(tmp_path)) if e["event"] == "train_step"]
    assert len(recs) == 2
    for r in recs:
        assert r["loss"] is not None and r["grad_norm"] is not None
        assert r["samples"] == 2 and r["tokens"] == 6
        assert r["step_seconds"] > 0


def test_telemetry_off_records_nothing(tmp_path):
    # off by default in the suite: the step loop must not touch step metrics
    h = obs.REGISTRY.get("train_step_seconds")
    before = h.total_count() if h else 0
    step = _tiny_train_step()
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    h = obs.REGISTRY.get("train_step_seconds")
    assert (h.total_count() if h else 0) == before
    assert not ev_mod.LOG.configured


# -- KVStore instrumentation -------------------------------------------------

def test_kv_psum_metrics_single_process(tmp_path):
    from mxnet_tpu.resilience import faults

    obs.enable(str(tmp_path))
    lat = obs.REGISTRY.histogram("kv_psum_seconds")
    byt = obs.counter("kv_psum_bytes_total")
    c0, b0 = lat.total_count(), byt.value(op="psum")
    # arming any site forces the instrumented DCN path at process_count==1
    faults.arm("obs.test.dummy", on=10 ** 9)
    try:
        store = mx.kv.create("dist_sync")
        store.init("w", nd.zeros((8,)))
        store.push("w", nd.ones((8,)))
        out = nd.zeros((8,))
        store.pull("w", out=out)
    finally:
        faults.disarm("obs.test.dummy")
    assert lat.total_count() == c0 + 1
    assert byt.value(op="psum") == b0 + 8 * 4  # 8 x f32
    assert obs.counter("kv_push_total").total() >= 1
    assert obs.counter("kv_pull_total").total() >= 1


def test_kv_psum_batch_dtype_buckets(tmp_path):
    from mxnet_tpu.resilience import faults

    obs.enable(str(tmp_path))
    buckets = obs.counter("kv_psum_dtype_buckets_total")
    f32_0 = buckets.value(dtype="float32")
    i32_0 = buckets.value(dtype="int32")
    faults.arm("obs.test.dummy", on=10 ** 9)
    try:
        store = mx.kv.create("dist_sync")
        vals = [nd.ones((4,)), nd.ones((2, 2)),
                nd.array(np.arange(3, dtype=np.int32), dtype="int32")]
        store.init(["a", "b", "c"], [v.copy() for v in vals])
        store.pushpull_batch(["a", "b", "c"], vals)
    finally:
        faults.disarm("obs.test.dummy")
    # two f32 leaves share one transfer bucket entry count; the int32 leaf
    # keeps its own dtype (no f32 funnel)
    assert buckets.value(dtype="float32") == f32_0 + 2
    assert buckets.value(dtype="int32") == i32_0 + 1
    assert obs.counter("kv_psum_bytes_total").value(op="psum_batch") > 0


# -- retry bridge ------------------------------------------------------------

def test_retry_counters_match_attempt_log():
    from mxnet_tpu.resilience import RetryPolicy, faults, retry

    site = "obs.test.retry"
    retry.clear_log(site)
    c = obs.counter("retry_attempts_total")
    ok0, fail0 = c.value(site=site, ok="true"), c.value(site=site, ok="false")
    with faults.inject(site, every=1, times=2):
        retry.retry_call(lambda: faults.fire(site), site=site,
                         policy=RetryPolicy(max_attempts=5, base_delay=0.001))
    log = retry.attempt_log(site)
    assert len(log) == 3  # 2 injected failures + 1 success
    assert c.value(site=site, ok="false") - fail0 == 2
    assert c.value(site=site, ok="true") - ok0 == 1
    assert (c.value(site=site, ok="true") + c.value(site=site, ok="false")
            - ok0 - fail0) == len(log)


@pytest.mark.chaos
def test_retry_counters_under_env_spec(tmp_path, monkeypatch):
    """MXNET_TPU_FAULTS-style arming (the make chaos path) also lands in the
    registry: counters, attempt log, and the report tool agree."""
    from mxnet_tpu.resilience import faults, retry

    retry.clear_log("ckpt.save")
    c = obs.counter("retry_attempts_total")
    before = (c.value(site="ckpt.save", ok="true")
              + c.value(site="ckpt.save", ok="false"))
    faults.load_spec("ckpt.save:on=1")
    try:
        from mxnet_tpu.checkpoint import save_train_state

        save_train_state(str(tmp_path), 1, {"w": np.ones((2,))}, {})
    finally:
        faults.disarm("ckpt.save")
    log = retry.attempt_log("ckpt.save")
    after = (c.value(site="ckpt.save", ok="true")
             + c.value(site="ckpt.save", ok="false"))
    assert after - before == len(log) >= 2


# -- DataLoader instrumentation ----------------------------------------------

def test_dataloader_wait_compute_metrics(tmp_path):
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    obs.enable(str(tmp_path))
    wait = obs.REGISTRY.histogram("data_batch_wait_seconds")
    w0 = wait.total_count()
    ds = ArrayDataset(nd.array(np.random.rand(32, 4).astype(np.float32)),
                      nd.array(np.arange(32, dtype=np.float32)))
    loader = DataLoader(ds, batch_size=8)
    n = sum(1 for _ in loader)
    assert n == 4
    assert wait.total_count() == w0 + 4
    comp = obs.REGISTRY.get("data_compute_seconds")
    assert comp is not None and comp.total_count() >= 1


# -- checkpoint instrumentation ----------------------------------------------

def test_checkpoint_metrics_and_events(tmp_path):
    from mxnet_tpu.checkpoint import load_train_state, save_train_state

    obs.enable(str(tmp_path / "tele"))
    saves = obs.counter("ckpt_saves_total")
    loads = obs.counter("ckpt_loads_total")
    s0, l0 = saves.total(), loads.total()
    params = {"w": np.ones((4, 4), np.float32)}
    opt_state = {"m": np.zeros((4, 4), np.float32)}
    path = save_train_state(str(tmp_path / "ck"), 3, params, opt_state)
    load_train_state(path, like=(params, opt_state))
    assert saves.total() == s0 + 1 and loads.total() == l0 + 1
    assert obs.counter("ckpt_bytes_total").value(op="save") > 0
    assert obs.REGISTRY.get("ckpt_save_seconds").total_count() >= 1
    assert obs.REGISTRY.get("ckpt_verify_seconds").total_count() >= 1
    obs.shutdown()
    kinds = {e["event"] for e in obs.read_events(str(tmp_path / "tele"))}
    assert {"checkpoint_save", "checkpoint_restore"} <= kinds


# -- wiring: Monitor / Trainer / Speedometer / span --------------------------

def test_monitor_wired_into_trainer(tmp_path):
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    obs.enable(str(tmp_path))
    net = nn.Dense(3, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    mon = mx.Monitor(interval=1).install(net, trainer=trainer)
    x = nd.ones((4, 2))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(4)  # tic/toc run inside step now — no manual driving
    assert mon.step == 1
    obs.shutdown()
    stats = [e for e in obs.read_events(str(tmp_path))
             if e["event"] == "monitor_stat"]
    names = {e["tensor"] for e in stats}
    assert any("weight" in n for n in names)
    assert any(n.endswith("_grad") for n in names)


def test_trainer_step_metrics_feed_speedometer(tmp_path):
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.gluon import nn

    obs.enable(str(tmp_path))
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    sp = Speedometer(batch_size=4, frequent=1)
    assert sp._registry_speed() is None  # primes the baseline
    for _ in range(2):
        x = nd.ones((4, 2))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(4)
    speed = sp._registry_speed()
    assert speed is not None and speed > 0  # registry path, not local clock
    assert obs.counter("train_samples_total").value(loop="trainer") >= 8


def test_span_times_and_labels(tmp_path):
    obs.enable(str(tmp_path))
    h = obs.REGISTRY.histogram("span_seconds")
    before = h.total_count()
    with obs.span("unit_region", phase="t"):
        nd.ones((4, 4)).sum().asnumpy()
    assert h.total_count() == before + 1
    s = h.stats(span="unit_region", phase="t")
    assert s is not None and s["count"] >= 1 and s["sum"] > 0
    # disabled -> no-op
    obs.disable()
    with obs.span("unit_region", phase="t"):
        pass
    assert h.stats(span="unit_region", phase="t")["count"] == s["count"]


# -- report tool -------------------------------------------------------------

def test_obs_report_renders_summary(tmp_path):
    import importlib.util

    obs.enable(str(tmp_path))
    step = _tiny_train_step()
    step(nd.ones((2, 3)), nd.ones((2, 4)))
    obs.shutdown()
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "obs_report.py"))
    obs_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_report)
    summary = obs_report.summarize(str(tmp_path))
    assert summary is not None
    assert summary["train"]["steps"] >= 1
    text = obs_report.render(summary)
    assert "telemetry report" in text and "training" in text
    assert obs_report.summarize(str(tmp_path / "empty_nonexistent")) is None
