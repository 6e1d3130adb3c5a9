"""Elastic multi-host training (docs/RESILIENCE.md "Elastic training"):
mesh re-formation plumbing, heartbeat peer-loss detection, retrying
``dist_init``, and world-size-agnostic checkpoints resharded on restore —
all on single-process CPU via deterministic injection (the real 4-process
kill-a-worker drill lives in test_launch_dist.py / ``make chaos-elastic``).
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, nd, observability as obs, optimizer
from mxnet_tpu.checkpoint import (CheckpointCorruptError, latest_checkpoint,
                                  load_train_state, save_train_state)
from mxnet_tpu.contrib.amp import Policy
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import MeshConfig, ShardingRules, TrainStep, make_mesh
from mxnet_tpu.parallel.mesh import refit_config
from mxnet_tpu.resilience import elastic, faults, retry
from mxnet_tpu.resilience.elastic import (ELASTIC_RESTART_EXIT,
                                          ElasticContext, HeartbeatMonitor,
                                          PeerLost, ReformExit)


@pytest.fixture(autouse=True)
def _isolated():
    """Clean injector/retry-log/elastic context per test; re-arm the env
    chaos spec on the way out (same contract as test_resilience)."""
    faults.reset()
    retry.clear_log()
    elastic._reset_context()
    yield
    elastic._reset_context()
    retry.clear_log()
    faults.reload_from_env()


@pytest.fixture
def _fast_retry():
    config.set("retry_base_delay", 0.002)
    config.set("retry_max_delay", 0.05)
    yield
    config._values.pop("retry_base_delay", None)
    config._values.pop("retry_max_delay", None)


# -- mesh re-fitting (refit_config) ------------------------------------------

def test_refit_scales_data_axes_only():
    # pure-dp world shrinks and grows along dp
    assert refit_config(MeshConfig(dp=4), 2) == MeshConfig(dp=2)
    assert refit_config(MeshConfig(dp=2), 8) == MeshConfig(dp=8)
    # fsdp layout is preserved at the new width
    assert refit_config(MeshConfig(fsdp=4), 2) == MeshConfig(dp=1, fsdp=2)
    assert refit_config(MeshConfig(fsdp=2), 8) == MeshConfig(dp=1, fsdp=8)
    # dp x fsdp keeps the fsdp width when it still divides
    assert refit_config(MeshConfig(dp=2, fsdp=2), 8) == \
        MeshConfig(dp=4, fsdp=2)
    # model axes survive unchanged; data capacity absorbs the change
    assert refit_config(MeshConfig(dp=2, tp=2), 8) == MeshConfig(dp=4, tp=2)


def test_refit_rejects_world_that_cannot_hold_model_axes():
    with pytest.raises(ValueError, match="model axes"):
        refit_config(MeshConfig(dp=2, tp=2), 3)


# -- heartbeat peer-loss detection -------------------------------------------

def test_heartbeat_beat_and_stale_detection(tmp_path):
    d = str(tmp_path)
    a = HeartbeatMonitor(d, rank=0, world=2, interval=0.03, timeout=0.25)
    b = HeartbeatMonitor(d, rank=1, world=2, interval=0.03, timeout=0.25)
    a.start()
    b.start()
    try:
        a.check()  # both beating: no peer loss
        b.stop()   # rank 1 "dies": its file goes stale
        deadline = time.time() + 5.0
        while time.time() < deadline:
            try:
                a.check()
            except PeerLost as e:
                assert e.ranks == [1]
                assert e.cause == "heartbeat_timeout"
                break
            time.sleep(0.05)
        else:
            pytest.fail("stale peer never detected")
    finally:
        a.stop()
        b.stop()


def test_heartbeat_missing_peer_gets_startup_grace(tmp_path):
    # world=2 but rank 1 never appears: inside the grace window (2x timeout
    # from monitor start) that's "still booting", after it it's dead.
    # timeout=0.5 -> a 1s grace budget: the pre-grace check below must not
    # flake when a loaded CI machine stalls between start() and check()
    m = HeartbeatMonitor(str(tmp_path), rank=0, world=2,
                         interval=0.05, timeout=0.5)
    m.start()
    try:
        m.check()  # within grace: no false positive
        deadline = time.time() + 5.0
        while time.time() < deadline:
            try:
                m.check()
            except PeerLost as e:
                assert e.ranks == [1]
                break
            time.sleep(0.05)
        else:
            pytest.fail("never-started peer never declared dead")
    finally:
        m.stop()


def test_heartbeat_fault_site_models_failed_probe(tmp_path):
    m = HeartbeatMonitor(str(tmp_path), rank=0, world=1,
                         interval=0.05, timeout=5.0)
    faults.arm("dist.heartbeat", on=1)
    with pytest.raises(PeerLost) as ei:
        m.check()
    assert ei.value.cause == "heartbeat_fault"
    m.check()  # one-shot trigger: the next probe is clean


# -- ElasticContext: the worker-side loop ------------------------------------

def test_context_built_from_supervisor_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_ELASTIC", "1")
    monkeypatch.setenv("MXNET_TPU_PROCID", "2")
    monkeypatch.setenv("MXNET_TPU_NPROC", "3")
    monkeypatch.setenv("MXNET_TPU_GENERATION", "1")
    monkeypatch.setenv("MXNET_TPU_ELASTIC_CAUSE", "worker_killed:sig9")
    monkeypatch.setenv("MXNET_TPU_PREV_WORLD", "4")
    monkeypatch.setenv("MXNET_TPU_HEARTBEAT_DIR", str(tmp_path / "hb"))
    elastic._reset_context()
    ctx = elastic.context()
    assert ctx is not None
    assert (ctx.rank, ctx.world, ctx.generation) == (2, 3, 1)
    assert ctx.prev_world == 4 and ctx.cause == "worker_killed:sig9"
    assert ctx.monitor is not None
    assert elastic.context() is ctx  # cached


def test_context_absent_outside_elastic_launch(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_ELASTIC", raising=False)
    elastic._reset_context()
    assert elastic.context() is None


def test_preemption_becomes_reform_request():
    ctx = ElasticContext(rank=0, world=2)
    guard = ctx.install_preemption()
    try:
        ctx.check()  # nothing pending
        guard.request(signum=15)
        with pytest.raises(ReformExit) as ei:
            ctx.check()
        assert ei.value.code == ELASTIC_RESTART_EXIT
        assert ei.value.cause == "preempted"
    finally:
        ctx.shutdown()


def test_peer_loss_becomes_reform_request(tmp_path):
    ctx = ElasticContext(rank=0, world=2, heartbeat_dir=str(tmp_path),
                         hb_interval=0.05, hb_timeout=0.1)
    ctx.start()
    try:
        # fabricate a peer that beat once, long ago
        stale = os.path.join(str(tmp_path), "hb-1")
        with open(stale, "w") as f:
            f.write("0")
        past = time.time() - 60
        os.utime(stale, (past, past))
        with pytest.raises(ReformExit) as ei:
            ctx.check()
        assert ei.value.code == ELASTIC_RESTART_EXIT
        assert ei.value.cause == "heartbeat_timeout"
    finally:
        ctx.shutdown()


def test_generation_start_and_resume_telemetry(tmp_path):
    obs.enable(str(tmp_path / "obs"))
    try:
        ctx = ElasticContext(rank=0, world=3, generation=1,
                             cause="worker_killed:sig9", prev_world=4)
        ctx.start()
        got = ctx.resume(lambda: 7, ckpt_step=7)
        assert got == 7
        assert obs.REGISTRY.get("mesh_reformations_total").value(
            cause="worker_killed:sig9") == 1
        assert obs.REGISTRY.get("elastic_world_size").value() == 3
        hist = obs.REGISTRY.get("elastic_restore_seconds")
        assert hist.stats()["count"] == 1
        ctx.shutdown()
    finally:
        obs.disable()
    events = obs.read_events(str(tmp_path / "obs"))
    reform = [e for e in events if e["event"] == "mesh_reformation"]
    restore = [e for e in events if e["event"] == "elastic_restore"]
    assert len(reform) == 1 and len(restore) == 1
    for e in reform + restore:  # the acceptance contract: cause + worlds
        assert e["cause"] == "worker_killed:sig9"
        assert (e["old_world"], e["new_world"]) == (4, 3)
    assert restore[0]["ckpt_step"] == 7


def test_exit_for_reform_carries_contract_exit_code(tmp_path):
    obs.enable(str(tmp_path / "obs"))
    try:
        with pytest.raises(ReformExit) as ei:
            elastic.exit_for_reform("peer_lost")
        assert ei.value.code == ELASTIC_RESTART_EXIT == 75
    finally:
        obs.disable()
    events = obs.read_events(str(tmp_path / "obs"))
    assert any(e["event"] == "elastic_reform_request" and
               e["cause"] == "peer_lost" for e in events)


# -- dist.init retry (replacement worker racing the coordinator port) --------

def test_dist_init_retries_with_backoff(monkeypatch, _fast_retry):
    from mxnet_tpu.parallel import distributed_trainer as dt

    calls = []
    monkeypatch.setattr(dt.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(dt.jax.distributed, "is_initialized", lambda: False)
    monkeypatch.setattr(dt, "_initialized", False)
    faults.arm("dist.init", on=1)  # first dial: coordinator not up yet
    dt.init("127.0.0.1:9", num_processes=2, process_id=1, retries=3)
    assert len(calls) == 1  # second attempt connected
    log = retry.attempt_log("dist.init")
    assert [a["ok"] for a in log] == [False, True]
    assert obs.REGISTRY.get("retry_attempts_total").value(
        site="dist.init", ok="false") >= 1


def test_dist_init_failed_attempt_does_not_poison_retry(monkeypatch,
                                                        _fast_retry):
    """jax's State.initialize registers global_state.client BEFORE
    client.connect(): a failed dial that *raises* must not leave the
    half-built client behind, or attempt 2 dies on "should only be called
    once" (and is_initialized() reports the failure as success)."""
    from jax._src import distributed as jdist

    from mxnet_tpu.parallel import distributed_trainer as dt

    calls = []

    def _initialize(**kw):
        if jdist.global_state.client is not None:
            raise RuntimeError(
                "distributed.initialize should only be called once.")
        jdist.global_state.client = object()  # assigned pre-connect...
        calls.append(kw)
        if len(calls) == 1:
            raise IOError("connect: coordinator not up")  # ...then the dial

    monkeypatch.setattr(dt.jax.distributed, "initialize", _initialize)
    monkeypatch.setattr(dt.jax.distributed, "is_initialized", lambda: False)
    monkeypatch.setattr(dt, "_initialized", False)
    monkeypatch.setattr(jdist.global_state, "client", None)
    monkeypatch.setattr(jdist.global_state, "service", None)
    try:
        dt.init("127.0.0.1:9", num_processes=2, process_id=1, retries=3)
    finally:
        jdist.global_state.client = None
    assert len(calls) == 2  # attempt 2 re-dialed instead of "called once"
    assert [a["ok"] for a in retry.attempt_log("dist.init")] == [False, True]


def test_dist_init_exhausted_retries_fail(monkeypatch, _fast_retry):
    from mxnet_tpu.parallel import distributed_trainer as dt

    monkeypatch.setattr(dt.jax.distributed, "initialize",
                        lambda **kw: None)
    monkeypatch.setattr(dt.jax.distributed, "is_initialized", lambda: False)
    monkeypatch.setattr(dt, "_initialized", False)
    faults.arm("dist.init", every=1)  # coordinator never comes up
    with pytest.raises(retry.RetryError):
        dt.init("127.0.0.1:9", num_processes=2, process_id=1, retries=2)
    assert not dt._initialized


def test_reform_tears_down_then_rejoins(monkeypatch, tmp_path):
    from mxnet_tpu.parallel import distributed_trainer as dt

    order = []
    monkeypatch.setattr(dt, "shutdown", lambda: order.append("shutdown"))
    monkeypatch.setattr(
        dt, "init",
        lambda coord, n, pid, timeout=None: order.append(("init", coord, n,
                                                          pid)))
    obs.enable(str(tmp_path / "obs"))
    try:
        got = elastic.reform("127.0.0.1:7", 3, 1)
        assert got is None  # no mesh_config
        assert order == ["shutdown", ("init", "127.0.0.1:7", 3, 1)]
        assert obs.REGISTRY.get("mesh_reformations_total").value(
            cause="reform_call") == 1
        assert obs.REGISTRY.get("elastic_world_size").value() == 3
    finally:
        obs.disable()
    events = obs.read_events(str(tmp_path / "obs"))
    assert any(e["event"] == "mesh_reformation" and e["new_world"] == 3
               for e in events)


# -- world-size-agnostic checkpoints + reshard-on-restore --------------------

def _fsdp_ts(mesh, seed=7):
    """Adam + f16 dynamic loss scaling on an fsdp-sharded MLP: the state a
    resharded restore must carry bit-exactly (params, Adam (mean, var) and
    t, the loss-scale carry)."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    # layers named inside the net's scope (dense0, dense1 under its prefix):
    # a restore pairs two fresh nets' parameters by sorted name, and the
    # process-wide counter's dense9 sorts AFTER its dense10
    with net.name_scope():
        net.add(nn.Dense(16, in_units=8, activation="relu"),
                nn.Dense(4, in_units=16))
    net.initialize()
    _ = net(nd.ones((8, 8)))
    rules = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return TrainStep(net, lambda o, y: loss_fn(o, y),
                     optimizer.Adam(learning_rate=1e-2), mesh=mesh,
                     rules=rules, amp=Policy("float16", loss_scale=8.0))


def _state_arrays(ts):
    """(sorted flat params, sorted flat opt leaves) as host numpy — names
    differ across fresh nets (gluon name counters) but sorted order
    corresponds structurally (same contract as test_resilience)."""
    import jax

    params = [np.asarray(ts.params[k]) for k in sorted(ts.params)]
    opt = [np.asarray(x)
           for k in sorted(ts.opt_state)
           for x in jax.tree_util.tree_leaves(ts.opt_state[k])]
    return params, opt


_XY = lambda: (nd.ones((8, 8)), nd.array([0, 1, 2, 3, 0, 1, 2, 3]))  # noqa: E731


@pytest.fixture
def _sharded_ckpt():
    config.set("ckpt_sharded", True)
    yield
    config._values.pop("ckpt_sharded", None)


@pytest.mark.parametrize("restore_world", [4, 2, 1])
def test_reshard_on_restore_bit_identical(tmp_path, _sharded_ckpt,
                                          restore_world):
    """Save at a world=4 fsdp layout; restore at world 4 / 2 / 1. The
    restored params and opt state (incl. Adam's t and the f16 loss-scale
    carry) must be bit-identical whatever the restoring world — elastic
    scale-down and scale-up change only the layout, never the numbers."""
    d = str(tmp_path / "ckpt")
    x, y = _XY()
    ts = _fsdp_ts(make_mesh(MeshConfig(fsdp=4)))
    for _ in range(3):
        ts(x, y)
    ts.save(d)
    want_params, want_opt = _state_arrays(ts)
    want_scale = ts.loss_scale

    # the manifest is the world-size-agnostic contract: global shape +
    # partition spec per array, per-shard index windows
    from mxnet_tpu.resilience import integrity
    mf = integrity.read_manifest(latest_checkpoint(d))
    assert mf["format"] == "npz-shards"
    recs = mf["arrays"].values()
    assert all("global_shape" in r and "spec" in r for r in recs)
    assert any(len(r["shards"]) > 1 for r in recs)  # actually sharded

    mesh = make_mesh(MeshConfig(fsdp=restore_world)) \
        if restore_world > 1 else None
    ts2 = _fsdp_ts(mesh, seed=23)  # different init: restore must overwrite
    assert ts2.restore(d)
    got_params, got_opt = _state_arrays(ts2)
    for a, b in zip(want_params, got_params):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(want_opt, got_opt):
        np.testing.assert_array_equal(a, b)
    # the schedule clock, Adam's applied-step t, and the amp carry
    assert ts2.optimizer.num_update == ts.optimizer.num_update == 3
    assert int(np.asarray(ts2.step_count)) == int(np.asarray(ts.step_count))
    assert ts2.loss_scale == want_scale
    if restore_world > 1:  # state actually landed in the new fsdp layout
        anyp = next(iter(ts2.params.values()))
        assert len(anyp.sharding.device_set) == restore_world
    ts2(x, y)  # the re-laid-out state trains


def test_scale_back_up_after_scale_down(tmp_path, _sharded_ckpt):
    """down (4 -> 2) then up (2 -> 4): both directions ride the same
    manifest; numbers never change."""
    d1, d2 = str(tmp_path / "c1"), str(tmp_path / "c2")
    x, y = _XY()
    ts4 = _fsdp_ts(make_mesh(MeshConfig(fsdp=4)))
    ts4(x, y)
    ts4.save(d1)
    ts2 = _fsdp_ts(make_mesh(MeshConfig(fsdp=2)), seed=23)
    assert ts2.restore(d1)
    ts2(x, y)
    ts2.save(d2)
    back4 = _fsdp_ts(make_mesh(MeshConfig(fsdp=4)), seed=31)
    assert back4.restore(d2)
    p2, o2 = _state_arrays(ts2)
    p4, o4 = _state_arrays(back4)
    for a, b in zip(p2 + o2, p4 + o4):
        np.testing.assert_array_equal(a, b)
    assert back4.optimizer.num_update == 2


def test_sharded_roundtrip_ml_dtypes_leaf(tmp_path, _sharded_ckpt):
    """np.savez degrades ml_dtypes leaves (bf16-stored weights are a
    supported AMP configuration) to raw void records — restore must
    reinterpret them against the manifest dtype, in both the npz-shards
    and flat-npz formats, not crash on 'no cast function'."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    w = np.arange(8, dtype=bf16)
    like = ({"w": np.zeros(8, bf16)}, {})
    for name, sharded in (("s", True), ("f", False)):
        d = str(tmp_path / name)
        save_train_state(d, 1, {"w": w}, {}, sharded=sharded)
        params, _, step = load_train_state(os.path.join(d, "ckpt-1"),
                                           like=like)
        assert step == 1
        assert params["w"].dtype == bf16, (name, params["w"].dtype)
        np.testing.assert_array_equal(params["w"], w)


def test_resume_flag_return_does_not_fake_ckpt_step(tmp_path):
    """A restore_fn returning a restored *flag* (TrainStep.restore does)
    must not put ``ckpt_step: true`` in the elastic_restore event."""
    obs.enable(str(tmp_path / "obs"))
    try:
        ctx = ElasticContext(rank=0, world=2, generation=1, cause="x")
        assert ctx.resume(lambda: True) is True
        ctx.shutdown()
    finally:
        obs.disable()
    events = obs.read_events(str(tmp_path / "obs"))
    restore = [e for e in events if e["event"] == "elastic_restore"]
    assert len(restore) == 1 and restore[0]["ckpt_step"] is None


def test_sharded_manifest_verifies_shards(tmp_path, _sharded_ckpt):
    """A tampered shard payload fails file-level validation (skipped by
    latest_checkpoint) and, read directly, per-shard sha256 verification."""
    d = str(tmp_path / "ckpt")
    x, y = _XY()
    ts = _fsdp_ts(make_mesh(MeshConfig(fsdp=4)))
    ts(x, y)
    path = ts.save(d)
    npz = os.path.join(path, "shards-h0.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(blob))
    assert latest_checkpoint(d) is None  # file sha mismatch: not a candidate
    with pytest.raises(CheckpointCorruptError):
        load_train_state(path, like=(ts.params, ts.opt_state))


def test_corruption_is_not_retried(tmp_path, _fast_retry):
    """CheckpointCorruptError.retryable = False: deterministic corruption
    surfaces unwrapped after ONE attempt instead of burning the backoff
    budget into a RetryError."""
    d = str(tmp_path / "c")
    save_train_state(d, 1, {"w": np.arange(8.0, dtype=np.float32)}, {})
    path = os.path.join(d, "ckpt-1")
    npz = os.path.join(path, "arrays.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(blob))
    retry.clear_log()
    with pytest.raises(CheckpointCorruptError):
        load_train_state(path, like=({"w": np.zeros(8, np.float32)}, {}))
    assert len(retry.attempt_log("ckpt.load")) == 1


def test_multihost_meta_written_last(tmp_path, _sharded_ckpt, monkeypatch):
    """The save-barrier ordering contract on one process: every barrier in
    the collective save runs in stage -> shards -> commit order, and
    ``meta.json`` does not exist until after the all-shards barrier — so a
    host that dies mid-save can never leave a checkpoint that
    ``latest_checkpoint`` would adopt."""
    from mxnet_tpu import checkpoint as ck

    seen = []

    def _spy(name):
        seen.append(name)
        if name == "ckpt.save.shards":
            # at the all-shards barrier the manifest/meta must NOT be
            # committed yet (rank 0 writes them after this barrier)
            assert not os.path.exists(
                os.path.join(str(tmp_path / "c"), "ckpt-1", "meta.json"))

    monkeypatch.setattr(ck, "_barrier", _spy)
    save_train_state(str(tmp_path / "c"), 1,
                     {"w": np.arange(8.0, dtype=np.float32)}, {})
    assert seen == ["ckpt.save.stage", "ckpt.save.shards", "ckpt.save.commit"]
    assert latest_checkpoint(str(tmp_path / "c")).endswith("ckpt-1")
