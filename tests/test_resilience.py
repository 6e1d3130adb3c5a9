"""Resilience subsystem (docs/RESILIENCE.md): fault injection, retry with
backoff, crash-safe checkpointing, graceful preemption — every recovery
path exercised on CPU via deterministic injected faults, no real signals
(except the one subprocess SIGTERM test, marked slow)."""
import logging
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, optimizer
from mxnet_tpu.checkpoint import (CheckpointCorruptError, latest_checkpoint,
                                  load_train_state, save_train_state)
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import TrainStep
from mxnet_tpu.resilience import (InjectedCrash, InjectedFault, Preempted,
                                  PreemptionGuard, RetryError, RetryPolicy,
                                  faults, retry)


@pytest.fixture(autouse=True)
def _isolated_faults():
    """Precise-count tests need a clean injector even under `make chaos`
    (env-armed triggers would skew attempt counts); re-arm the env spec on
    the way out so the rest of the suite keeps its chaos noise."""
    faults.reset()
    retry.clear_log()
    yield
    retry.clear_log()
    faults.reload_from_env()


@pytest.fixture
def _fast_retry():
    """Millisecond backoff so retry tests don't sleep for real."""
    from mxnet_tpu import config

    config.set("retry_base_delay", 0.002)
    config.set("retry_max_delay", 0.05)
    yield
    config._values.pop("retry_base_delay", None)
    config._values.pop("retry_max_delay", None)


def _net():
    mx.random.seed(11)
    net = nn.HybridSequential()
    # named inside the net's scope: a restore pairs two fresh nets by sorted
    # name, and the process-wide counter's dense9 sorts after its dense10
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    _ = net(nd.ones((4, 3)))
    return net


def _ts():
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return TrainStep(_net(), lambda o, y: loss_fn(o, y),
                     optimizer.Adam(learning_rate=1e-2))


_XY = lambda: (nd.ones((4, 3)), nd.array([0, 1, 0, 1]))  # noqa: E731


# -- crash-safe checkpointing (tentpole acceptance) --------------------------

@pytest.mark.chaos
def test_crash_during_save_resumes_from_previous_valid(tmp_path):
    """A kill mid-save (injected, no real signal) must leave the previous
    checkpoint authoritative: restart resumes from it with bit-identical
    params."""
    d = str(tmp_path / "ckpt")
    x, y = _XY()
    ts = _ts()
    ts(x, y)
    ts(x, y)
    ts.save(d)  # ckpt-2, valid
    at_2 = {k: np.asarray(v) for k, v in ts.params.items()}
    ts(x, y)
    faults.arm("ckpt.save", on=1, crash=True)
    with pytest.raises(InjectedCrash):
        ts.save(d)  # dies after arrays.npz, before manifest/commit
    # the torn stage dir exists but is never a restore candidate
    assert os.path.isdir(os.path.join(d, "ckpt-3.tmp"))
    assert not os.path.exists(os.path.join(d, "ckpt-3"))
    assert latest_checkpoint(d).endswith("ckpt-2")

    ts2 = _ts()
    assert ts2.restore(d)
    assert ts2.optimizer.num_update == 2
    # param names carry fresh gluon name-counter suffixes (dense2_* vs
    # dense0_*) but the pytree layout matches — compare in sorted-key order
    restored = [np.asarray(ts2.params[k]) for k in sorted(ts2.params)]
    expected = [at_2[k] for k in sorted(at_2)]
    assert len(restored) == len(expected)
    for r, e in zip(restored, expected):
        np.testing.assert_array_equal(r, e)


def test_corrupt_arrays_skipped_and_load_rejects(tmp_path):
    d = str(tmp_path / "c")
    save_train_state(d, 1, {"w": np.arange(4.0, dtype=np.float32)}, {})
    p2 = save_train_state(d, 2, {"w": np.ones(4, np.float32)}, {})
    blob = bytearray(open(os.path.join(p2, "arrays.npz"), "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # same size, different bytes
    with open(os.path.join(p2, "arrays.npz"), "wb") as f:
        f.write(bytes(blob))
    # newest is unverifiable -> falls back to the previous valid one
    assert latest_checkpoint(d).endswith("ckpt-1")
    like = ({"w": np.ones(4, np.float32)}, {})
    with pytest.raises((CheckpointCorruptError, RetryError)):
        load_train_state(p2, like=like)
    # and the fallback checkpoint round-trips
    params, _opt, step = load_train_state(latest_checkpoint(d), like=like)
    assert step == 1
    np.testing.assert_array_equal(params["w"], np.arange(4.0, dtype=np.float32))


def test_manifest_catches_rewritten_arrays(tmp_path):
    """A well-formed npz whose contents drifted from the manifest (bitrot,
    partial restore overwrite) is rejected at both selection and load."""
    d = str(tmp_path / "c")
    p = save_train_state(d, 7, {"w": np.ones(3, np.float32)}, {})
    np.savez(os.path.join(p, "arrays.npz"), **{"0": np.zeros(3, np.float32)})
    assert latest_checkpoint(d) is None  # file sha mismatch -> invalid
    with pytest.raises(CheckpointCorruptError):
        load_train_state(p, like=({"w": np.ones(3, np.float32)}, {}))


def test_latest_checkpoint_skips_meta_less_partial_dirs(tmp_path):
    d = str(tmp_path / "c")
    save_train_state(d, 3, {"w": np.ones(2, np.float32)}, {})
    os.makedirs(os.path.join(d, "ckpt-9"))  # partial write: no meta.json
    assert latest_checkpoint(d).endswith("ckpt-3")
    # pre-resilience behavior stays reachable for debugging
    assert latest_checkpoint(d, validate=False).endswith("ckpt-9")


def test_corrupt_manifest_json_skipped_not_raised(tmp_path):
    """A truncated manifest.json is the corruption class this subsystem
    tolerates — selection must fall back, not crash."""
    d = str(tmp_path / "c")
    save_train_state(d, 1, {"w": np.ones(2, np.float32)}, {})
    p2 = save_train_state(d, 2, {"w": np.ones(2, np.float32)}, {})
    with open(os.path.join(p2, "manifest.json"), "w") as f:
        f.write('{"format": "npz", "files"')  # torn mid-write
    assert latest_checkpoint(d).endswith("ckpt-1")
    with pytest.raises(CheckpointCorruptError):
        load_train_state(p2, like=({"w": np.ones(2, np.float32)}, {}))


def test_orphaned_stale_checkpoint_recovered(tmp_path):
    """Crash inside commit_dir's two-rename window (only ckpt-N.stale left):
    the next listing renames it back instead of treating it as debris."""
    d = str(tmp_path / "c")
    p = save_train_state(d, 5, {"w": np.ones(2, np.float32)}, {})
    os.replace(p, p + ".stale")  # simulate dying after the aside-rename
    assert latest_checkpoint(d).endswith("ckpt-5")  # recovered
    assert os.path.isdir(p) and not os.path.exists(p + ".stale")


def test_retention_sweep_keeps_last_n(tmp_path):
    d = str(tmp_path / "c")
    for s in range(1, 6):
        save_train_state(d, s, {"w": np.full(2, s, np.float32)}, {})
    os.makedirs(os.path.join(d, "ckpt-0.tmp"))  # stale interrupted stage
    save_train_state(d, 6, {"w": np.ones(2, np.float32)}, {}, keep_last=3)
    assert sorted(os.listdir(d)) == ["ckpt-4", "ckpt-5", "ckpt-6"]


# -- retry policy (ISSUE acceptance: observable attempts + backoff) ----------

@pytest.mark.chaos
def test_dcn_psum_double_failure_retried_and_logged(tmp_path, _fast_retry,
                                                    caplog):
    """Injected double-failure at the kv.dcn_psum site: the push must
    converge to the same psum result, and the attempt count + backoff
    schedule must be observable in both the attempt log and the logger."""
    from mxnet_tpu import config

    faults.arm("kv.dcn_psum", every=1, times=2)  # fail 1st and 2nd attempt
    kv = mx.kv.create("dist_sync")
    kv.init("w", nd.zeros((3,)))
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.resilience.retry"):
        kv.push("w", nd.ones((3,)) * 2)
    out = nd.zeros((3,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), 2 * np.ones(3))  # same psum

    log = retry.attempt_log("kv.dcn_psum")
    assert [r["ok"] for r in log] == [False, False, True]
    base = config.get("retry_base_delay")
    jit = config.get("retry_jitter")
    for k, rec in enumerate(log[:-1]):  # exponential backoff within jitter
        lo = base * 2.0 ** k
        assert lo <= rec["delay"] <= lo * (1.0 + jit) + 1e-9
    warns = [r.getMessage() for r in caplog.records
             if "retrying: site=kv.dcn_psum" in r.getMessage()]
    assert len(warns) == 2
    assert "attempt=1/3" in warns[0] and "attempt=2/3" in warns[1]


def test_retry_exhaustion_raises_retry_error(_fast_retry):
    faults.arm("kv.dcn_psum", every=1)  # unlimited failures
    kv = mx.kv.create("dist_sync")
    kv.init("w", nd.zeros((2,)))
    with pytest.raises(RetryError) as ei:
        kv.push("w", nd.ones((2,)))
    assert len(ei.value.attempts) == 3
    assert isinstance(ei.value.__cause__, InjectedFault)


def test_retry_policy_delay_schedule_deterministic_with_seed():
    p1 = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=10.0,
                     jitter=0.5, timeout=0.0, seed=42)
    p2 = RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=10.0,
                     jitter=0.5, timeout=0.0, seed=42)
    d1 = [p1.delay(k) for k in range(1, 5)]
    assert d1 == [p2.delay(k) for k in range(1, 5)]
    for k, d in enumerate(d1):  # exponential envelope
        assert 0.1 * 2.0 ** k <= d <= 0.1 * 2.0 ** k * 1.5


def test_injected_crash_is_not_absorbed_by_retry(_fast_retry):
    """InjectedCrash models process death — retry must NOT turn it into a
    successful-looking recovery."""
    kv = mx.kv.create("local")
    kv.set_optimizer(optimizer.SGD(learning_rate=0.1))
    kv.init("w", nd.ones((2,)))
    kv.push("w", nd.ones((2,)))
    faults.arm("kv.save_states", on=1, crash=True)
    with pytest.raises(InjectedCrash):
        kv.save_optimizer_states("/dev/null")
    assert retry.attempt_log("kv.save_states") == []  # never recorded as attempt


# -- fault injector semantics ------------------------------------------------

def test_fault_spec_grammar_and_counters():
    faults.load_spec("a.site:on=2;b.site:every=3:times=2:crash;seed=9")
    with pytest.raises(InjectedFault):
        for _ in range(5):
            faults.fire("a.site")
    assert faults.count("a.site") == 2  # fired on the 2nd invocation
    crashes = 0
    for _ in range(12):
        try:
            faults.fire("b.site")
        except InjectedCrash:
            crashes += 1
    assert crashes == 2  # every=3 but times=2 caps it
    with pytest.raises(ValueError):
        faults.load_spec("x:bogus=1")


def test_inject_context_manager_restores():
    with faults.inject("tmp.site", on=1):
        with pytest.raises(InjectedFault):
            faults.fire("tmp.site")
    faults.fire("tmp.site")  # disarmed again
    assert not faults.armed()


# -- satellite: atomic optimizer-state save ----------------------------------

def test_save_optimizer_states_crash_leaves_previous_file(tmp_path):
    f = str(tmp_path / "opt.states")
    kv = mx.kv.create("local")
    kv.set_optimizer(optimizer.SGD(learning_rate=0.1))
    kv.init("w", nd.ones((2,)))
    kv.push("w", nd.ones((2,)))
    kv.save_optimizer_states(f)
    orig = open(f, "rb").read()
    faults.arm("kv.save_states", on=1, crash=True)
    with pytest.raises(InjectedCrash):
        kv.save_optimizer_states(f)
    assert open(f, "rb").read() == orig  # old states intact, not truncated
    assert not os.path.exists(f + ".tmp")
    kv.load_optimizer_states(f)  # and still loadable


# -- satellite: dtype-bucketed batched psum ----------------------------------

def test_dcn_psum_batch_preserves_precision_per_dtype(monkeypatch):
    """The old funnel flattened everything through f32: an int32 gradient
    above 2^24 silently lost its low bits. Bucketing by dtype must keep the
    sum exact (simulated 2-process gather: each 'process' contributes the
    same value, so expected = 2x)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    from mxnet_tpu.kvstore import _dcn_psum_batch

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda b: jnp.stack([b, b]))
    big = np.int32(2 ** 24 + 1)  # not representable in f32
    raws = [jnp.asarray(np.full((3,), big, np.int32)),
            jnp.ones((2, 2), jnp.float32) * 0.5,
            jnp.asarray(np.full((4,), 2.0, np.float16)),
            jnp.asarray(np.array([7, 8], np.int32))]
    out = _dcn_psum_batch(raws)
    assert [o.dtype for o in out] == [r.dtype for r in raws]
    assert [o.shape for o in out] == [r.shape for r in raws]
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.full((3,), 2 * (2 ** 24 + 1), np.int64))
    np.testing.assert_allclose(np.asarray(out[1]), np.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  np.full((4,), 4.0, np.float16))
    np.testing.assert_array_equal(np.asarray(out[3]), np.array([14, 16], np.int32))


# -- graceful preemption -----------------------------------------------------

def test_trainstep_preemption_checkpoints_at_step_boundary(tmp_path):
    d = str(tmp_path / "ckpt")
    x, y = _XY()
    ts = _ts()
    guard = ts.install_preemption(d)
    try:
        ts(x, y)
        guard.request()  # no real signal needed
        with pytest.raises(Preempted) as ei:
            ts(x, y)  # completes the step, checkpoints, then unwinds
        assert ei.value.code == 0
        assert latest_checkpoint(d).endswith("ckpt-2")
    finally:
        guard.uninstall()


def test_trainer_preemption_runs_save_fn_then_exits(tmp_path):
    net = _net()
    x, y = _XY()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    saved = []
    guard = trainer.install_preemption(lambda: saved.append(True))
    try:
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        guard.request()
        with pytest.raises(Preempted):
            trainer.step(4)
        assert saved == [True]  # checkpoint action ran before the exit
    finally:
        guard.uninstall()


def test_estimator_preemption_handler_saves_and_stops(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import (BatchEnd, Estimator,
                                                   PreemptionHandler)

    net = _net()
    x, y = _XY()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    handler = PreemptionHandler(str(tmp_path), guard=PreemptionGuard(signals=()))

    class _RequestAtBatch1(BatchEnd):
        seen = 0

        def batch_end(self, estimator, **kwargs):
            self.seen += 1
            if self.seen == 1:
                handler.guard.request()

    req = _RequestAtBatch1()
    est = Estimator(net, loss_fn, train_metrics="acc")
    est.fit([(x, y)] * 6, epochs=1, event_handlers=[handler, req])
    assert req.seen == 2  # stopped right after the flagged boundary, not 6
    assert os.path.exists(os.path.join(str(tmp_path), "model-preempt.params"))
    assert os.path.exists(os.path.join(str(tmp_path), "model-preempt.states"))


@pytest.mark.slow
def test_sigterm_subprocess_checkpoints_and_exits_zero(tmp_path):
    """The real-signal contract end-to-end: SIGTERM -> checkpoint at the
    next step boundary -> exit code 0, resumable checkpoint on disk."""
    d = str(tmp_path / "ckpt")
    script = textwrap.dedent("""
        import os, sys, time
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd, optimizer
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.parallel import TrainStep

        net = nn.HybridSequential()
        with net.name_scope():   # as in _net: names that pair across nets
            net.add(nn.Dense(4, activation="relu"), nn.Dense(2))
        net.initialize()
        x = nd.ones((2, 3)); _ = net(x)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        ts = TrainStep(net, lambda o, y: loss_fn(o, y),
                       optimizer.SGD(learning_rate=0.1))
        ts.install_preemption(sys.argv[1])
        y = nd.array([0, 1])
        print("READY", flush=True)
        while True:
            ts(x, y)
            time.sleep(0.02)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", script, d],
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True, env=env)
    try:
        assert "READY" in proc.stdout.readline()
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0, proc.stdout.read()
    path = latest_checkpoint(d)
    assert path is not None  # a committed, manifest-valid checkpoint landed


# -- chaos smoke: transient fault storm absorbed end-to-end ------------------

@pytest.mark.chaos
def test_transient_fault_storm_absorbed(tmp_path, _fast_retry):
    """Periodic transient faults on every IO/DCN site at once: the training
    utilities keep working (this is the single-test version of the
    `make chaos` full-suite pass)."""
    faults.load_spec("ckpt.save:every=2;ckpt.load:every=2;"
                     "kv.dcn_psum:every=2;data.batch:every=3;seed=5")
    d = str(tmp_path / "c")
    for s in range(1, 4):
        save_train_state(d, s, {"w": np.full(2, s, np.float32)}, {})
    like = ({"w": np.ones(2, np.float32)}, {})
    params, _o, step = load_train_state(latest_checkpoint(d), like=like)
    assert step == 3
    np.testing.assert_array_equal(params["w"], np.full(2, 3, np.float32))

    kv = mx.kv.create("dist_sync")
    kv.init("w", nd.zeros((3,)))
    for _ in range(4):
        kv.push("w", nd.ones((3,)))
    out = nd.zeros((3,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(3))

    ds = gluon.data.ArrayDataset(np.arange(24, dtype=np.float32).reshape(12, 2),
                                 np.arange(12, dtype=np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=4)
    seen = sum(b.shape[0] for b, _l in loader)
    assert seen == 12  # every batch arrived despite injected fetch faults
