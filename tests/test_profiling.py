"""Measured profiling layer (docs/OBSERVABILITY.md "Measured
profiling", ISSUE 14): XPlane parsing, MeasuredReport, capture, the
step-capture controller, and the event-log gz-rotation hardening it rides
with."""
import glob
import gzip
import json
import os

import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, nd, optimizer
from mxnet_tpu import observability as obs
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import events as ev_mod
from mxnet_tpu.observability import fleet as fleet_mod
from mxnet_tpu.observability import profiling as prof
from mxnet_tpu.parallel import TrainStep

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "xplane")


@pytest.fixture
def reset_controller():
    yield
    config.set("prof_every_n_steps", 0)
    config.set("fleet_dir", "")
    prof._reset_controller()


def _fixture_report():
    tl = prof.parse_trace(FIXTURE)
    assert tl.parse_errors == 0
    return prof.measured_report(tl)


# -- the wire parser over the committed fixture ------------------------------
def test_fixture_parses_planes_lines_events():
    tl = prof.parse_trace(FIXTURE)
    names = [p.name for p in tl.planes]
    assert names == ["/device:TPU:0", "/device:TPU:1", "/host:CPU"]
    tpu0 = tl.planes[0]
    assert tpu0.is_device
    assert [ln.name for ln in tpu0.lines] == ["XLA Ops", "Steps"]
    ev = tpu0.lines[0].events[0]
    # offsets are ps relative to the line's ns timestamp
    assert ev.name == "dot.1" and ev.start_ns == 1000.0 and ev.dur_ns == 10.0
    assert ev.stats["hlo_op"] == "dot.1"
    assert ev.stats["bytes accessed"] == 2048
    host = tl.planes[2].lines[0]
    steps = [e for e in host.events if e.name == "prof_step"]
    assert [e.stats["step"] for e in steps] == [0, 1]


def test_measured_report_multi_plane_rows_not_merged():
    r = _fixture_report()
    # device planes contribute their op lines; derived lines ("Steps")
    # and python frames are skipped; host rows need an hlo_op stat
    assert [(o.device, o.name) for o in r.op_rows] == [
        ("/device:TPU:0", "dot.1"), ("/device:TPU:0", "all-reduce.2"),
        ("/device:TPU:0", "fusion.3"),
        ("/device:TPU:1", "dot.1"), ("/device:TPU:1", "all-gather.7"),
        ("/host:CPU", "reduce.9")]
    # satellite 1 contract: the same op on two devices stays two rows
    hot = {(h["device"], h["name"]): h for h in r.hot_ops(10)}
    assert hot[("/device:TPU:0", "dot.1")]["self_ns"] == 10.0
    assert hot[("/device:TPU:1", "dot.1")]["self_ns"] == 8.0
    assert hot[("/device:TPU:0", "dot.1")]["bytes"] == 2048
    assert hot[("/device:TPU:1", "dot.1")]["bytes"] is None
    totals = r.per_device_totals()
    assert totals["/device:TPU:0"] == pytest.approx(26e-9)
    assert totals["/device:TPU:1"] == pytest.approx(12e-9)


def test_measured_overlap_hand_computed():
    r = _fixture_report()
    # TPU:0 — all-reduce spans 5..15ns; compute covers 0..10 + 12..18:
    # hidden = 5 + 3 = 8ns. TPU:1 — all-gather 8..12ns touches no
    # concurrent compute: fully exposed. Total collective 14ns.
    coll, hid, _comp = r.overlap()
    assert coll == pytest.approx(14e-9)
    assert hid == pytest.approx(8e-9)
    assert r.overlap_fraction == pytest.approx(8.0 / 14.0)
    cls = r.class_seconds()
    assert cls["all_reduce"] == pytest.approx(10e-9)
    assert cls["all_gather"] == pytest.approx(4e-9)
    assert cls["dot"] == pytest.approx(18e-9)
    assert cls["fusion"] == pytest.approx(6e-9)


def test_step_and_span_correlation():
    r = _fixture_report()
    assert [s.step for s in r.step_rows()] == [0, 1]
    assert r.step_seconds() == [pytest.approx(20e-9),
                                pytest.approx(18e-9)]
    spans = r.span_breakdown()
    assert spans["train_fwd"]["count"] == 1
    assert spans["train_fwd"]["steps"] == [0]
    assert spans["prof_step"]["steps"] == [0, 1]
    # and the whole thing serializes (what profile.json carries)
    s = r.summary()
    json.dumps(s)
    assert s["n_op_rows"] == 6 and s["steps"] == 2


def test_torn_and_empty_traces_counted_not_fatal(tmp_path):
    run = tmp_path / "plugins" / "profile" / "0001"
    run.mkdir(parents=True)
    with open(os.path.join(FIXTURE, "plugins", "profile",
                           "2026_01_01_00_00_00",
                           "synthetic.xplane.pb"), "rb") as f:
        good = f.read()
    (run / "torn.xplane.pb").write_bytes(good[:len(good) // 3])
    tl = prof.parse_trace(str(tmp_path))
    assert tl.parse_errors == 1 and tl.planes == []
    r = prof.measured_report(tl)
    assert r.op_rows == [] and r.parse_errors == 1
    # an empty / missing dir is an empty timeline, never a raise
    assert prof.parse_trace(str(tmp_path / "nope")).n_events == 0
    assert prof.latest_profile(str(tmp_path)) is None


def test_encoder_stat_value_kinds():
    data = prof.encode_xplane([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "timestamp_ns": 5, "events": [
            {"name": "x.1", "offset_ps": 1_000, "duration_ps": 2_000,
             "stats": {"i": 7, "f": 2.5, "s": "mod"}}]}]}])
    ev = prof.parse_xplane_bytes(data).planes[0].lines[0].events[0]
    assert ev.stats == {"i": 7, "f": 2.5, "s": "mod"}
    assert ev.start_ns == pytest.approx(6.0)


def test_op_class_vocabulary():
    assert prof.op_class("dot.12") == "dot"
    assert prof.op_class("dot_general") == "dot"
    assert prof.op_class("convolution.3") == "conv"
    assert prof.op_class("all-reduce-start.1") == "all_reduce"
    assert prof.op_class("all_gather") == "all_gather"
    assert prof.op_class("fusion.9") == "fusion"
    assert prof.op_class("broadcast_add_fusion") == "fusion"  # CPU thunks
    assert prof.op_class("reduce.1") == "other"


# -- live capture (CPU) -------------------------------------------------------
def _live_capture(tmp_path, steps=2):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    try:
        return prof.capture(lambda: f(x), steps=steps, warmup=1,
                            trace_dir=str(tmp_path / "trace"))
    except Exception as e:  # pragma: no cover - platform without tracing
        pytest.skip(f"jax trace capture unavailable here: {e}")


def test_live_cpu_capture_has_device_op_rows(tmp_path):
    cap = _live_capture(tmp_path, steps=2)
    r = cap.report
    assert r.op_rows, "no executed-op rows parsed from a live CPU trace"
    assert any(o.op_class == "dot" for o in r.op_rows)
    assert len(r.step_seconds()) == 2
    assert all(dt > 0 for dt in r.step_seconds())
    # capture telemetry (always-on, low-frequency site)
    assert obs.REGISTRY.counter("prof_captures_total").total() >= 1
    assert obs.REGISTRY.get("prof_capture_seconds").total_count() >= 1
    assert obs.REGISTRY.get("prof_overlap_measured") is not None


def test_trainstep_profile_shares_jit_cache(tmp_path):
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"),
            nn.Dense(4, in_units=16))
    net.initialize()
    x, y = nd.ones((4, 8)), nd.zeros((4, 4))
    _ = net(x)
    ts = TrainStep(net, lambda o, yy: ((o - yy) ** 2).mean(),
                   optimizer.SGD(learning_rate=0.1))
    ts(x, y)  # compile once
    n_programs = len(ts._compiled)
    try:
        cap = ts.profile(x, y, steps=2, warmup=1,
                         trace_dir=str(tmp_path / "t"))
    except RuntimeError as e:  # pragma: no cover
        pytest.skip(f"trace capture unavailable: {e}")
    # the traced dispatches reused the production program — no new entry
    assert len(ts._compiled) == n_programs
    assert cap.report.op_rows and len(cap.report.step_seconds()) == 2
    assert 0.0 <= cap.report.overlap_fraction <= 1.0


# -- the step-capture controller ---------------------------------------------
def _tiny_step():
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8))
    net.initialize()
    x, y = nd.ones((2, 8)), nd.zeros((2, 8))
    _ = net(x)
    ts = TrainStep(net, lambda o, yy: ((o - yy) ** 2).mean(),
                   optimizer.SGD(learning_rate=0.1))
    return ts, x, y


def test_periodic_capture_every_n_steps(tmp_path, reset_controller,
                                        monkeypatch):
    # an earlier test's obs.enable leaves telemetry_dir() set; pin it so
    # the controller resolves base_dir from profiler_dir deterministically
    monkeypatch.setattr(obs, "_dir", None)
    config.set("prof_every_n_steps", 3)
    config.set("profiler_dir", str(tmp_path))
    prof._reset_controller()
    ts, x, y = _tiny_step()
    for _ in range(7):
        ts(x, y)
    caps = sorted(os.path.basename(p) for p in
                  glob.glob(str(tmp_path / "prof" / "prof-*")))
    assert caps == ["prof-g0-s3-periodic", "prof-g0-s6-periodic"]
    snap = json.load(open(str(tmp_path / "prof" / caps[0]
                              / "profile.json")))
    assert snap["meta"]["trigger"] == "periodic"
    assert snap["report"]["n_op_rows"] > 0
    assert snap["report"]["steps"] == 1


def test_straggler_request_triggers_next_step_capture(tmp_path,
                                                      reset_controller):
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    config.set("fleet_dir", str(fleet))
    prof._reset_controller()
    ts, x, y = _tiny_step()
    ts(x, y)  # warm; also drains the first trigger probe window
    with open(prof.request_path(str(fleet), 0), "w") as f:
        json.dump({"reason": "straggler"}, f)
    # force the throttled probe to fire on the very next step
    ctl = prof._ensure_controller()
    ctl._next_probe = 0.0
    ts(x, y)
    snaps = glob.glob(str(fleet / "telemetry-h0" / "prof-*"
                          / "profile.json"))
    assert len(snaps) == 1, "the flagged rank's next step must be traced"
    snap = json.load(open(snaps[0]))
    assert snap["meta"]["trigger"] == "straggler"
    assert snap["report"]["n_op_rows"] > 0
    # the request was consumed exactly once
    assert not os.path.exists(prof.request_path(str(fleet), 0))


def test_retention_sweep_bounds_capture_bytes(tmp_path, reset_controller,
                                              monkeypatch):
    monkeypatch.setattr(obs, "_dir", None)
    config.set("prof_every_n_steps", 1)
    config.set("profiler_dir", str(tmp_path))
    config.set("prof_keep_bytes", 1)  # absurdly small: only newest survives
    prof._reset_controller()
    ts, x, y = _tiny_step()
    for _ in range(3):
        ts(x, y)
    config.set("prof_keep_bytes", 512 * 1024 * 1024)
    caps = glob.glob(str(tmp_path / "prof" / "prof-*"))
    assert len(caps) == 1, "retention must sweep all but the newest"
    assert os.path.basename(caps[0]) == "prof-g0-s3-periodic"


def test_step_capture_abort_releases_the_session(tmp_path,
                                                 reset_controller,
                                                 monkeypatch):
    """A traced step that raises must not leak the live trace session —
    abort closes it so later captures still work."""
    monkeypatch.setattr(obs, "_dir", None)
    config.set("prof_every_n_steps", 1)
    config.set("profiler_dir", str(tmp_path))
    prof._reset_controller()
    tok = prof.step_capture_begin(1)
    assert tok is not None  # a capture is live now
    prof.step_capture_abort(tok)
    # the session was released: an explicit capture succeeds afterwards
    cap = prof.capture(lambda: None, steps=1, warmup=0,
                       trace_dir=str(tmp_path / "after"))
    assert cap.steps == 1


def test_read_events_directory_orders_segments_numerically(tmp_path):
    for seq, tag in ((2, "old"), (10, "new")):
        with gzip.open(tmp_path / f"events-h0.jsonl.{seq}.gz", "wt") as f:
            f.write(json.dumps({"event": tag}) + "\n")
    with open(tmp_path / "events-h0.jsonl", "w") as f:
        f.write(json.dumps({"event": "live"}) + "\n")
    # lexically .10.gz sorts before .2.gz; the reader must not
    assert [r["event"] for r in ev_mod.read_events(str(tmp_path))] \
        == ["old", "new", "live"]


def test_aggregator_poll_writes_capture_request(tmp_path):
    finding = {"kind": "step", "rank": 2, "generation": 0, "step": 5,
               "seconds": 2.0, "median_seconds": 0.1, "ratio": 20.0}
    agg = fleet_mod.FleetAggregator(str(tmp_path))
    agg._request_capture(finding)
    path = prof.request_path(str(tmp_path), 2)
    req = json.load(open(path))
    assert req["reason"] == "straggler" and req["kind"] == "step"
    # one pending request per rank: a second finding does not clobber it
    before = os.path.getmtime(path)
    agg._request_capture(dict(finding, step=6))
    assert os.path.getmtime(path) == before


def test_aggregator_collects_newest_profile_snapshot(tmp_path):
    d = tmp_path / "telemetry-h0"
    (d / "prof-g0-s2-periodic").mkdir(parents=True)
    (d / "prof-g0-s9-straggler").mkdir()
    for sub, step, ts_ in (("prof-g0-s2-periodic", 2, 100.0),
                           ("prof-g0-s9-straggler", 9, 200.0)):
        with open(d / sub / "profile.json", "w") as f:
            json.dump({"meta": {"step": step, "ts": ts_},
                       "report": {"n_op_rows": 3, "hot_ops": []}}, f)
        os.utime(d / sub / "profile.json", (ts_, ts_))
    with open(d / "metrics-g0.json", "w") as f:
        json.dump({"meta": {"rank": 0}, "metrics": {}}, f)
    report = fleet_mod.FleetAggregator(str(tmp_path)).collect()
    assert report.profiles[0]["meta"]["step"] == 9  # newest wins
    assert "profiles" in report.summary()


# -- profiler.dumps() per-plane aggregation (satellite 1) ---------------------
def test_profiler_dumps_keys_by_plane(monkeypatch):
    from mxnet_tpu import profiler as mxprof

    monkeypatch.setitem(mxprof._state, "dir", FIXTURE)
    monkeypatch.setitem(mxprof._state, "ever_ran", True)
    stats = mxprof._aggregate_xplane(FIXTURE)
    # keyed (plane, op): dot.1 on two devices stays two aggregates
    assert ("/device:TPU:0", "dot.1") in stats
    assert ("/device:TPU:1", "dot.1") in stats
    assert stats[("/device:TPU:0", "dot.1")][1] == 10.0  # total ns
    assert stats[("/device:TPU:1", "dot.1")][1] == 8.0
    table = mxprof.dumps()
    assert "dot.1 [TPU:0]" in table and "dot.1 [TPU:1]" in table
    assert "Per-device totals" in table
    assert "/device:TPU:0" in table


def test_profiling_probe_is_registered_hot_path():
    from mxnet_tpu.analysis.astlint import EXTRA_HOT_PATHS

    quals = EXTRA_HOT_PATHS.get("observability/profiling.py")
    assert quals and "CaptureController.begin_if_due" in quals
    assert "step_capture_begin" in quals
    for q in quals:  # every registered qualname must actually exist
        target = prof
        for part in q.split("."):
            target = getattr(target, part)
        assert callable(target)


# -- event-log rotation hardening (satellite 2) -------------------------------
def test_event_log_keep_bytes_retains_multiple_segments(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = ev_mod.EventLog()
    log.configure(path, rotate_bytes=512, keep_bytes=64 * 1024)
    for i in range(100):
        log.emit("tick", i=i, pad="x" * 64)
    log.close()
    segs = ev_mod.rotated_segments(path)
    assert len(segs) > 1, "keep_bytes must retain more than one segment"
    assert all(s.endswith(".gz") for s in segs)
    # nothing lost across ALL rotations under the cap
    assert [r["i"] for r in ev_mod.read_events(path)] == list(range(100))
    # and a tiny cap sweeps down to one retained segment on next rotate
    log2 = ev_mod.EventLog()
    log2.configure(path, rotate_bytes=512, keep_bytes=1)
    for i in range(30):
        log2.emit("tock", i=i, pad="y" * 64)
    log2.close()
    assert len(ev_mod.rotated_segments(path)) == 1


def test_read_events_single_gz_segment(tmp_path):
    path = tmp_path / "events-g0.jsonl.gz"
    with gzip.open(path, "wt") as f:
        f.write(json.dumps({"event": "a", "ts": 1.0}) + "\n")
        f.write("torn{{{\n")
        f.write(json.dumps({"event": "b", "ts": 2.0}) + "\n")
    recs = ev_mod.read_events(str(path))
    assert [r["event"] for r in recs] == ["a", "b"]


def test_snapshotter_recovers_rotation_from_gz_segment(tmp_path):
    run = tmp_path / "run"
    fdir = tmp_path / "fleet"
    obs.REGISTRY.reset()
    try:
        obs.enable(str(run))
        # shrink the rotation threshold so the live file rotates (and
        # compresses) several times between two snapshots; keep_bytes
        # high enough that retention never outruns the snapshot cadence
        ev_mod.LOG._rotate_bytes = 2048
        ev_mod.LOG._keep_bytes = 64 * 1024
        snap = fleet_mod.FleetSnapshotter(str(fdir), rank=0, generation=0,
                                          interval=60.0)
        for i in range(10):
            obs.emit("pre", i=i, pad="x" * 64)
        snap.snapshot()
        for i in range(30):  # crosses the 2 KiB threshold repeatedly
            obs.emit("post", i=i, pad="x" * 64)
        assert ev_mod.rotated_segments(ev_mod.LOG.path or "")
        snap.snapshot()
        copied = ev_mod.read_events(
            str(fdir / "telemetry-h0" / "events-g0.jsonl"))
        names = [r["event"] for r in copied]
        # every record made it across the compressed rotation boundary
        assert names.count("pre") == 10 and names.count("post") == 30
    finally:
        obs.disable()
        obs.REGISTRY.reset()


def test_aggregator_reads_gzipped_event_segments(tmp_path):
    d = tmp_path / "telemetry-h0"
    d.mkdir(parents=True)
    with open(d / "metrics-g0.json", "w") as f:
        json.dump({"meta": {"rank": 0, "ts": 10.0}, "metrics": {}}, f)
    with gzip.open(d / "events-g0.jsonl.gz", "wt") as f:
        f.write(json.dumps({"ts": 1.0, "event": "train_step", "step": 1,
                            "step_seconds": 0.1, "host": 0}) + "\n")
    report = fleet_mod.FleetAggregator(str(tmp_path)).collect()
    assert report is not None
    assert [e["event"] for e in report.events] == ["train_step"]
    assert report.events[0]["_gen"] == 0
