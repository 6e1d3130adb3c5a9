"""Compiled mixed-precision policy (ISSUE 5): the amp surface
(`contrib.amp.Policy` / `resolve_policy` / init/_reset), the in-graph bf16
cast against fp32 master weights, compiled fp16 dynamic loss scaling
(overflow -> skip-update -> scale-halving, window-compatible), and
activation rematerialization via ``hybridize(remat=...)``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, optimizer as opt
from mxnet_tpu.contrib import amp
from mxnet_tpu.contrib.amp import Policy, resolve_policy
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import TrainStep

IN, OUT = 6, 4


def _mlp(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():   # names that pair across fresh nets on restore
        net.add(nn.Dense(16, activation="relu"), nn.Dense(OUT))
    net.initialize()
    _ = net(nd.ones((2, IN)))
    return net


def _loss(out, *labels):
    return ((out - labels[0]) ** 2).mean()


def _batches(k, b=4, seed=123, scale=1.0):
    rs = np.random.RandomState(seed)
    return [(rs.normal(size=(b, IN)).astype(np.float32) * scale,
             rs.normal(size=(b, OUT)).astype(np.float32) * scale)
            for _ in range(k)]


def _params(ts):
    # natural sort: block counters are process-global, and two nets built
    # one after the other can straddle a digit (dense9, dense10)
    from conftest import natkey

    return [np.asarray(v) for _, v in sorted(ts.params.items(), key=natkey)]


def _tiny_gpt2_step(remat=None, amp=None, optimizer=None, seed=0, **cfg):
    """Seeded tiny-GPT-2 LM TrainStep + (ids, labels) batch — the one
    construction idiom shared by the remat tests (set remat BEFORE building
    the TrainStep; its program cache does not watch the flag)."""
    from mxnet_tpu.models import gpt2

    cfg = dict(dict(num_layers=2, units=32, num_heads=2, max_length=64,
                    vocab_size=64, batch=2, seq=32), **cfg)
    batch, seq = cfg.pop("batch"), cfg.pop("seq")
    mx.random.seed(seed)
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, **cfg)
    net.initialize()
    ids = nd.array(np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (batch, seq)), dtype="int32")
    _ = net(ids)
    if remat:
        net.hybridize(active=False, remat=remat)
    lbl = nd.array(np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (batch, seq)), dtype="int32")
    ts = TrainStep(net, gpt2.lm_loss,
                   optimizer or opt.Adam(learning_rate=1e-3), amp=amp)
    return ts, (ids, lbl)


# -- policy surface ----------------------------------------------------------
def test_init_and_reset_idempotent():
    try:
        amp.init("bfloat16")
        assert amp.amp_dtype() == "bfloat16"
        amp.init("bfloat16")  # second init: same state, no error
        assert amp.amp_dtype() == "bfloat16"
        amp.init("float16")
        assert amp.amp_dtype() == "float16"
    finally:
        amp._reset()
        assert amp.amp_dtype() is None
        amp._reset()  # idempotent
        assert amp.amp_dtype() is None


def test_resolve_policy_mapping():
    assert resolve_policy(None) is None
    assert resolve_policy(False) is None
    assert resolve_policy("bfloat16") == Policy("bfloat16")
    p = Policy("float16", loss_scale=128.0)
    assert resolve_policy(p) is p
    assert p.dynamic_scaling and not Policy("bfloat16").dynamic_scaling
    # 'auto' follows the global amp.init state
    assert resolve_policy("auto") is None
    try:
        amp.init("bfloat16")
        assert resolve_policy("auto") == Policy("bfloat16")
    finally:
        amp._reset()
    with pytest.raises(ValueError):
        Policy("float64")
    with pytest.raises(TypeError):
        resolve_policy(3.14)


def test_convert_model_roundtrip():
    net = _mlp()
    x = nd.ones((2, IN))
    ref = net(x).asnumpy()
    amp.convert_model(net, "bfloat16")
    assert "bfloat16" in str(net[0].weight.data()._data.dtype)
    out_bf16 = net(x.astype("bfloat16")).astype("float32").asnumpy()
    np.testing.assert_allclose(out_bf16, ref, rtol=2e-2, atol=1e-2)
    # round-trip back to f32: function preserved to bf16 rounding
    net.cast("float32")
    assert net[0].weight.data()._data.dtype == jnp.float32
    out_back = net(x).asnumpy()
    np.testing.assert_allclose(out_back, ref, rtol=2e-2, atol=1e-2)


# -- compiled bf16 policy ----------------------------------------------------
def test_bf16_policy_tracks_f32_trajectory():
    """fp32-vs-bf16 loss trajectory: identical init + data, the bf16-policy
    step must follow the f32 step within bf16 tolerance, with masters f32."""
    data = _batches(5)
    ts32 = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-2), amp=None)
    l32 = [float(np.asarray(jax.device_get(ts32(nd.array(x), nd.array(y)))))
           for x, y in data]
    ts16 = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-2),
                     amp="bfloat16")
    l16 = [float(np.asarray(jax.device_get(ts16(nd.array(x), nd.array(y)))))
           for x, y in data]
    np.testing.assert_allclose(l16, l32, rtol=2e-2, atol=1e-3)
    assert all(v.dtype == jnp.float32 for v in ts16.params.values())
    for a, b in zip(_params(ts32), _params(ts16)):
        np.testing.assert_allclose(b, a, rtol=2e-2, atol=1e-3)


def test_window_matches_singles_under_bf16():
    """ISSUE 5 satellite: the k-step scan window under the bf16 policy is
    numerically equivalent to k sequential compiled steps (same casts, same
    fp32 master update, same key stream)."""
    data = _batches(4)
    ts_seq = TrainStep(_mlp(), _loss, opt.Adam(learning_rate=1e-2),
                       amp="bfloat16")
    seq = [float(np.asarray(jax.device_get(ts_seq(nd.array(x), nd.array(y)))))
           for x, y in data]
    ts_win = TrainStep(_mlp(), _loss, opt.Adam(learning_rate=1e-2),
                       amp="bfloat16")
    losses = np.asarray(jax.device_get(ts_win.run(iter(data), steps=4,
                                                  window=4)))
    np.testing.assert_allclose(losses, seq, rtol=1e-3, atol=1e-4)
    assert int(ts_win.step_count) == 4 == int(ts_seq.step_count)
    for a, b in zip(_params(ts_seq), _params(ts_win)):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-4)


# -- compiled fp16 dynamic loss scaling --------------------------------------
def test_fp16_overflow_skips_update_and_halves_scale():
    """Overflowed grads (inf in the batch) must leave params, opt state and
    Adam's t untouched, halve the scale, and count the skip — all decided
    in-graph."""
    ts = TrainStep(_mlp(), _loss, opt.Adam(learning_rate=1e-2),
                   amp=Policy("float16", loss_scale=8.0, scale_window=1000))
    p0 = _params(ts)
    bad = np.ones((4, IN), np.float32)
    bad[0, 0] = np.inf
    loss = ts(nd.array(bad), nd.zeros((4, OUT)))
    assert not np.isfinite(float(np.asarray(jax.device_get(loss))))
    assert ts.loss_scale == 4.0
    assert ts.amp_skipped_steps == 1
    assert int(ts.step_count) == 0  # Adam's t frozen on the skipped step
    for a, b in zip(p0, _params(ts)):
        np.testing.assert_array_equal(a, b)
    # healthy step afterwards applies normally
    x, y = _batches(1)[0]
    ts(nd.array(x), nd.array(y))
    assert int(ts.step_count) == 1
    assert ts.amp_skipped_steps == 1
    assert any(not np.array_equal(a, b) for a, b in zip(p0, _params(ts)))


def test_fp16_scale_grows_after_window_of_good_steps():
    ts = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-3),
                   amp=Policy("float16", loss_scale=4.0, scale_factor=2.0,
                              scale_window=2))
    for x, y in _batches(4, scale=0.1):
        ts(nd.array(x), nd.array(y))
    # 4 good steps, window 2 -> two doublings: 4 -> 8 -> 16
    assert ts.loss_scale == 16.0
    assert ts.amp_skipped_steps == 0


def test_fp16_window_scaling_rides_the_carry():
    """The scan window threads (scale, good, skipped) through the carry:
    window results == sequential fp16 steps, and a poisoned in-window step
    is skipped without breaking the ones after it."""
    data = _batches(4, scale=0.1)
    pol = Policy("float16", loss_scale=8.0, scale_window=1000)
    ts_seq = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-2), amp=pol)
    seq = [float(np.asarray(jax.device_get(ts_seq(nd.array(x), nd.array(y)))))
           for x, y in data]
    ts_win = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-2), amp=pol)
    losses = np.asarray(jax.device_get(
        ts_win.run(iter(data), steps=4, window=4)))
    np.testing.assert_allclose(losses, seq, rtol=1e-3, atol=1e-4)
    for a, b in zip(_params(ts_seq), _params(ts_win)):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-4)
    assert ts_win.loss_scale == 8.0

    # poison step 2 of a fresh window: only that step is dropped
    data2 = _batches(4, seed=7, scale=0.1)
    data2[1][0][0, 0] = np.inf
    ts_bad = TrainStep(_mlp(), _loss, opt.SGD(learning_rate=1e-2), amp=pol)
    losses = np.asarray(jax.device_get(
        ts_bad.run(iter(data2), steps=4, window=4)))
    assert losses.shape == (4,)
    assert not np.isfinite(losses[1])
    assert np.isfinite(np.delete(losses, 1)).all()
    assert ts_bad.amp_skipped_steps == 1
    assert ts_bad.loss_scale == 4.0
    assert int(ts_bad.step_count) == 3  # 3 applied, 1 skipped


# -- rematerialization -------------------------------------------------------
def test_remat_preserves_numerics_and_validates_policy():
    def run_steps(remat):
        ts, (ids, lbl) = _tiny_gpt2_step(remat=remat)
        return [float(np.asarray(jax.device_get(ts(ids, lbl))))
                for _ in range(2)]

    base = run_steps(False)
    # remat is a pure recompute: bit-identical ops, only scheduling changes
    np.testing.assert_allclose(run_steps(True), base, rtol=1e-6)
    np.testing.assert_allclose(run_steps("dots_saveable"), base, rtol=1e-6)

    net = _mlp()
    with pytest.raises(ValueError):
        net.hybridize(remat="not_a_policy")
    # remat=False clears the flag
    net.hybridize(remat=True)
    assert net._remat is True
    net.hybridize(remat=False)
    assert net._remat is None


def test_remat_composes_with_bf16_policy():
    """remat + bf16 policy in one program (the long-context configuration):
    trains, loss finite and decreasing, masters f32."""
    ts, (ids, lbl) = _tiny_gpt2_step(
        remat=True, amp="bfloat16", optimizer=opt.Adam(learning_rate=1e-2))
    losses = [float(np.asarray(jax.device_get(ts(ids, lbl))))
              for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert all(v.dtype == jnp.float32 for v in ts.params.values())


def test_fp16_checkpoint_preserves_applied_t_and_scale(tmp_path):
    """ISSUE 5 review regression: save/restore must keep the APPLIED step
    (Adam's t, frozen on skips) and the dynamic loss-scale carry — a
    preemption restart must not inflate t by the skipped count nor reset
    the scale to its 2^16 init."""
    pol = Policy("float16", loss_scale=8.0, scale_window=1000)
    ts = TrainStep(_mlp(), _loss, opt.Adam(learning_rate=1e-2), amp=pol)
    bad = np.ones((4, IN), np.float32)
    bad[0, 0] = np.inf
    ts(nd.array(bad), nd.zeros((4, OUT)))        # skipped: scale 8 -> 4
    x, y = _batches(1, scale=0.1)[0]
    ts(nd.array(x), nd.array(y))                 # applied
    assert int(ts.step_count) == 1 and ts.optimizer.num_update == 2
    ts.save(str(tmp_path))

    ts2 = TrainStep(_mlp(seed=1), _loss, opt.Adam(learning_rate=1e-2),
                    amp=pol)
    assert ts2.restore(str(tmp_path))
    assert int(ts2.step_count) == 1              # applied t, not attempted
    assert ts2.optimizer.num_update == 2         # schedule clock: attempted
    assert ts2.loss_scale == 4.0                 # carry survives, not 2^16
    assert ts2.amp_skipped_steps == 1
    for a, b in zip(_params(ts), _params(ts2)):
        np.testing.assert_array_equal(a, b)


# -- review regressions ------------------------------------------------------
def test_plain_states_adopted_when_multi_precision_flips(tmp_path):
    """States created (or checkpoint-restored) in the PLAIN layout before
    multi_precision flips must be ADOPTED as the base of the
    self-describing {"master", "base"} layout — Adam's (mean, var) must
    never be misread as a master tuple, in-process or across
    save_states/load_states."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer

    try:
        mx.random.seed(0)
        net = nn.Dense(2, in_units=3)
        net.initialize()
        _ = net(nd.ones((2, 3)))
        net.cast("float16")
        tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
        x = nd.ones((2, 3)).astype("float16")

        def one_step(t):
            with autograd.record():
                loss = (net(x).astype("float32") ** 2).sum()
            loss.backward()
            t.step(2)

        one_step(tr)  # states created in the PLAIN (mean, var) layout
        mean_before = np.asarray(tr._states[0][0])
        fname = str(tmp_path / "opt.states")
        tr.save_states(fname)

        amp.init("float16")
        amp.init_trainer(tr)  # flips multi_precision on existing states
        assert tr._optimizer.multi_precision
        one_step(tr)
        st = tr._states[0]
        assert isinstance(st, dict) and set(st) == {"master", "base"}
        assert st["master"].dtype == jnp.float32
        assert st["master"].shape == tuple(net.weight.data().shape)
        assert isinstance(st["base"], tuple) and len(st["base"]) == 2
        assert np.isfinite(np.asarray(st["master"])).all()

        # the checkpoint-restore path: plain-layout states loaded AFTER the
        # flip are adopted too (momentum preserved, not misread/discarded)
        tr2 = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
        amp.init_trainer(tr2)
        tr2.load_states(fname)
        one_step(tr2)
        st2 = tr2._states[0]
        assert isinstance(st2, dict) and st2["master"].dtype == jnp.float32
        # adopted base evolved FROM the restored mean, not from zeros
        assert not np.allclose(np.asarray(st2["base"][0]), 0.0)
        assert np.isfinite(np.asarray(st2["base"][0])).all()
        assert mean_before.shape == np.asarray(st2["base"][0]).shape
    finally:
        amp._reset()


def test_trainer_run_keeps_adam_t_frozen_across_runs_with_skips():
    """A cached fused TrainStep whose first run() skipped a step must not
    have Adam's t bumped past the applied count by the next run()'s
    num_update reseed."""
    from mxnet_tpu.gluon import Trainer

    net = _mlp()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 1e-2})
    pol = Policy("float16", loss_scale=8.0, scale_window=1000)
    data = _batches(4, scale=0.1)
    data[1][0][0, 0] = np.inf  # one in-window overflow
    tr.run(net, _loss, iter(data), steps=4, window=4, amp=pol)
    ts = tr._fused[1]
    assert ts.amp_skipped_steps == 1
    assert int(ts.step_count) == 3  # 3 applied
    # second run on the SAME cached TrainStep: t resumes from 3, not 4
    tr.run(net, _loss, iter(_batches(4, seed=9, scale=0.1)), steps=4,
           window=4, amp=pol)
    assert tr._fused[1] is ts
    assert int(ts.step_count) == 7  # 3 + 4 applied, skip never re-counted

    # third run with a DIFFERENT loss_fn: fused-cache miss builds a fresh
    # TrainStep — the trainer-level skip count must still seed t = applied
    # (8 attempted - 1 historical skip = 7), not num_update
    other_loss = lambda out, *l: ((out - l[0]) ** 2).sum()  # noqa: E731
    tr.run(net, other_loss, iter(_batches(4, seed=11, scale=0.1)), steps=4,
           window=4, amp=pol)
    ts2 = tr._fused[1]
    assert ts2 is not ts
    assert int(ts2.step_count) == 11  # 7 seeded + 4 applied this run

    # interleaved imperative step(): num_update's max() maintenance absorbs
    # it (stays 12 while counts reach 12), so a num_update-only reseed
    # would hand out a t already consumed — the counts-based seed must not
    from mxnet_tpu import autograd
    x, y = _batches(1, seed=13, scale=0.1)[0]
    with autograd.record():
        out = net(nd.array(x))
        loss = ((out - nd.array(y)) ** 2).mean()
    loss.backward()
    tr.step(4)
    assert max(tr._optimizer._index_update_count.values()) == 12
    tr.run(net, other_loss, iter(_batches(4, seed=17, scale=0.1)), steps=4,
           window=4, amp=pol)
    assert int(tr._fused[1].step_count) == 16  # 12 seeded + 4, no reuse of t
