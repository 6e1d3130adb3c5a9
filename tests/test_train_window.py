"""Fused multi-step training: the compiled k-step scan window
(``TrainStep.run``) and the async device-prefetch queue (``io.prefetch``).

The contract under test (ISSUE 3 acceptance):
  - a k-step window is numerically equivalent to k sequential ``__call__``s
    (params, opt-state, step-count, losses, fixed RNG stream), including a
    gradient-accumulation case;
  - ``run(steps=K)`` with ``window=K`` issues exactly ONE compiled program
    per (window, shapes) signature and one dispatch per window
    (``train_recompiles_total{reason="window"}`` + dispatch counter);
  - the prefetch queue preserves order, propagates errors, and shuts down
    cleanly mid-stream;
  - the window path runs on the virtual 8-way mesh with params staying in
    the storage layout.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, observability as obs, optimizer as opt
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.io.prefetch import DevicePrefetcher
from mxnet_tpu.parallel import MeshConfig, TrainStep, make_mesh

IN, OUT = 6, 4


def _mlp(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(OUT))
    net.initialize()
    _ = net(nd.ones((2, IN)))
    return net


def _loss(out, *labels):
    return ((out - labels[0]) ** 2).mean()


def _make_step(optimizer=None, mesh=None, seed=0):
    return TrainStep(_mlp(seed), _loss,
                     optimizer or opt.Adam(learning_rate=1e-2), mesh=mesh)


def _batches(k, b=4, seed=123):
    rs = np.random.RandomState(seed)
    return [(rs.normal(size=(b, IN)).astype(np.float32),
             rs.normal(size=(b, OUT)).astype(np.float32)) for _ in range(k)]


def _param_values(ts):
    # the Dense name counter is process-global, so two structurally
    # identical nets carry different param names — compare by NATURAL
    # sorted order (two nets can straddle a digit: dense9, dense10)
    from conftest import natkey

    return [np.asarray(v) for _, v in sorted(ts.params.items(), key=natkey)]


def _state_leaves(ts):
    # in the same natural order (a list: tree_leaves sorts a dict's keys
    # lexicographically, dense10 before dense9)
    from conftest import natkey

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        [ts.opt_state[k] for k in sorted(
            ts.opt_state, key=lambda name: natkey((name,)))])]


# -- numerical equivalence ---------------------------------------------------
def test_window_matches_sequential_steps():
    data = _batches(4)
    ts_seq = _make_step()
    seq_losses = [float(ts_seq(nd.array(x), nd.array(y))) for x, y in data]

    ts_win = _make_step()  # reseeded: identical init + identical key stream
    losses = ts_win.run(iter(data), steps=4, window=4)
    losses = np.asarray(jax.device_get(losses))

    assert losses.shape == (4,)
    np.testing.assert_allclose(losses, seq_losses, rtol=2e-5, atol=1e-6)
    assert int(ts_win.step_count) == 4 == int(ts_seq.step_count)
    assert ts_win.optimizer.num_update == 4
    for a, b in zip(_param_values(ts_seq), _param_values(ts_win)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)
    for a, b in zip(_state_leaves(ts_seq), _state_leaves(ts_win)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)


def test_window_accum_matches_full_batch_steps():
    # 2 steps x accum=2 over microbatches of 4 == 2 plain steps over the
    # concatenated batches of 8 (mean-of-microbatch-grads == full-batch grad)
    micro = _batches(4, b=4)
    full = [(np.concatenate([micro[2 * i][0], micro[2 * i + 1][0]]),
             np.concatenate([micro[2 * i][1], micro[2 * i + 1][1]]))
            for i in range(2)]

    ts_seq = _make_step()
    seq_losses = [float(ts_seq(nd.array(x), nd.array(y))) for x, y in full]

    ts_win = _make_step()
    losses = np.asarray(jax.device_get(
        ts_win.run(iter(micro), steps=2, window=2, accum=2)))

    np.testing.assert_allclose(losses, seq_losses, rtol=5e-5, atol=1e-6)
    assert int(ts_win.step_count) == 2
    for a, b in zip(_param_values(ts_seq), _param_values(ts_win)):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6)


def test_partial_tail_with_accum_stays_accumulated():
    # 3 steps, window=2, accum=2: one full window (2 steps) + a k=1 window
    # for the tail — NEVER un-accumulated singles (which would train at a
    # different effective batch size)
    ts = _make_step()
    losses = np.asarray(jax.device_get(
        ts.run(iter(_batches(6)), steps=3, window=2, accum=2)))
    assert losses.shape == (3,)
    assert ts._window_dispatches == 2 and int(ts.step_count) == 3

    # a sub-group remainder is dropped (and counted), not mis-trained
    from mxnet_tpu import observability as obs2
    dropped = obs2.counter("prefetch_dropped_batches_total")
    before = dropped.total()
    ts2 = _make_step()
    losses2 = np.asarray(jax.device_get(
        ts2.run(iter(_batches(5)), window=2, accum=2)))  # steps=None
    assert losses2.shape == (2,) and int(ts2.step_count) == 2
    assert dropped.total() == before + 1


def test_partial_tail_falls_back_to_single_steps():
    ts = _make_step()
    losses = np.asarray(jax.device_get(
        ts.run(iter(_batches(5)), steps=5, window=2)))
    assert losses.shape == (5,)
    assert ts._window_dispatches == 2  # 2 full windows + 1 single tail
    assert int(ts.step_count) == 5 and ts.optimizer.num_update == 5


# -- one program per signature, one dispatch per window ----------------------
def test_one_program_per_window_signature(tmp_path):
    obs.enable(str(tmp_path))
    try:
        rc = obs.counter("train_recompiles_total")
        before = rc.value(reason="window")
        ts = _make_step()
        ts.run(iter(_batches(8)), steps=8, window=4)
        wkeys = [k for k in ts._compiled if k[0] == "window"]
        assert len(wkeys) == 1, "window=4 x2 must lower exactly one program"
        assert ts._window_dispatches == 2  # one dispatch per window
        assert rc.value(reason="window") == before + 1

        # same (window, shapes) signature again: fully cached
        ts.run(iter(_batches(4)), steps=4, window=4)
        assert len([k for k in ts._compiled if k[0] == "window"]) == 1
        assert rc.value(reason="window") == before + 1
        assert ts._window_dispatches == 3

        # a NEW window size lowers a new program, counted reason="window"
        ts.run(iter(_batches(4)), steps=4, window=2)
        assert len([k for k in ts._compiled if k[0] == "window"]) == 2
        assert rc.value(reason="window") == before + 2
    finally:
        obs.shutdown()


def test_window_telemetry_records_run_window_loop(tmp_path):
    obs.enable(str(tmp_path))
    try:
        # the registry is process-global: count deltas, not absolutes
        h = obs.histogram("train_step_seconds")
        s0 = h.stats(loop="run_window")
        h_before = s0["count"] if s0 else 0
        c_before = obs.counter("train_steps_total").value(loop="run_window")
        ts = _make_step()
        ts.run(iter(_batches(4)), steps=4, window=2)
        obs.flush()  # a window's readings are published some dispatches on
        assert h.stats(loop="run_window")["count"] == h_before + 2
        assert obs.counter("train_steps_total").value(
            loop="run_window") == c_before + 4
        assert obs.gauge("train_loss").value() is not None
        assert obs.gauge("train_grad_norm").value() is not None
    finally:
        obs.shutdown()
    recs = [e for e in obs.read_events(str(tmp_path))
            if e["event"] == "train_window"]
    assert len(recs) == 2
    for r in recs:
        assert r["window"] == 2 and r["window_seconds"] > 0
        assert r["step_seconds_amortized"] < r["window_seconds"]


def test_window_matches_sequential_with_lr_scheduler():
    from mxnet_tpu import lr_scheduler

    def sched_opt():
        return opt.SGD(learning_rate=0.1,
                       lr_scheduler=lr_scheduler.FactorScheduler(
                           step=2, factor=0.5))

    data = _batches(4)
    ts_seq = _make_step(optimizer=sched_opt())
    seq_losses = [float(ts_seq(nd.array(x), nd.array(y))) for x, y in data]
    ts_win = _make_step(optimizer=sched_opt())
    losses = np.asarray(jax.device_get(ts_win.run(iter(data), steps=4, window=4)))
    # each window step i must read the scheduler at num_update + i, exactly
    # like i sequential __call__s (the lr decays INSIDE the window)
    np.testing.assert_allclose(losses, seq_losses, rtol=2e-5, atol=1e-6)
    for a, b in zip(_param_values(ts_seq), _param_values(ts_win)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)


# -- device prefetch queue ---------------------------------------------------
def test_prefetcher_handles_ragged_tail_batch():
    # DataLoader last_batch="keep" tails are smaller: a ragged batch inside
    # a would-be-full group must flush the group, not crash np.stack
    data = _batches(4, b=4) + _batches(1, b=2)
    pf = DevicePrefetcher(iter(data), window=2)
    kinds = []
    while True:
        kind, payload, n = pf.next_group()
        if kind is None:
            break
        kinds.append((kind, n, np.asarray(payload[0]).shape[-3:]
                      if kind == "window" else np.asarray(payload[0]).shape))
    assert [(k, n) for k, n, _ in kinds] == \
        [("window", 2), ("window", 2), ("single", 1)]
    assert kinds[-1][2][0] == 2  # the ragged 2-sample tail survived intact
    pf.close()


def test_run_rejects_mismatched_prefetcher_config():
    ts = _make_step()
    pf = DevicePrefetcher(iter(_batches(4)), train_step=ts, window=2)
    with pytest.raises(ValueError, match="window=4"):
        ts.run(pf, steps=4, window=4)
    with pytest.raises(ValueError, match="accum=2"):
        ts.run(pf, steps=4, accum=2)
    pf.close()
def test_prefetcher_orders_windows_and_tail():
    data = _batches(5, b=2)
    pf = DevicePrefetcher(iter(data), window=2)
    groups = []
    while True:
        kind, payload, n = pf.next_group()
        if kind is None:
            break
        groups.append((kind, payload, n))
    assert [(k, n) for k, _, n in groups] == \
        [("window", 2), ("window", 2), ("single", 1)]
    # stacking preserves source order: window i holds batches 2i, 2i+1
    np.testing.assert_allclose(np.asarray(groups[0][1][0][0]), data[0][0])
    np.testing.assert_allclose(np.asarray(groups[0][1][0][1]), data[1][0])
    np.testing.assert_allclose(np.asarray(groups[1][1][1][0]), data[2][1])
    np.testing.assert_allclose(np.asarray(groups[2][1][0]), data[4][0])
    # exhausted: stays exhausted, and the iterator protocol agrees
    assert pf.next_group()[0] is None
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()
    pf.close()  # idempotent


def test_prefetcher_propagates_source_error():
    def bad():
        yield (np.ones((2, 3), np.float32),)
        raise ValueError("boom")

    pf = DevicePrefetcher(bad(), window=2)
    with pytest.raises(ValueError, match="boom"):
        while pf.next_group()[0] is not None:
            pass
    pf.close()


def test_prefetcher_close_mid_stream_joins_producer():
    pf = DevicePrefetcher(iter(_batches(64, b=2)), window=2, depth=2)
    kind, _payload, _n = pf.next_group()
    assert kind == "window"
    pf.close()  # must unblock the producer's put and join without hanging
    assert not pf._thread.is_alive()
    assert pf.next_group()[0] is None


def test_dataloader_prefetch_to_device_adapter():
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset

    x = np.arange(32, dtype=np.float32).reshape(16, 2)
    y = np.arange(16, dtype=np.float32)
    loader = DataLoader(ArrayDataset(x, y), batch_size=4)
    pf = loader.prefetch_to_device(window=2)
    wins = list(pf)
    assert len(wins) == 2  # 4 batches -> 2 stacked windows
    assert tuple(np.asarray(wins[0][0]).shape) == (2, 4, 2)
    np.testing.assert_allclose(np.asarray(wins[0][0][0]), x[:4])
    np.testing.assert_allclose(np.asarray(wins[1][1][1]), y[12:])
    pf.close()


def test_ndarrayiter_prefetch_to_device_flattens_databatch():
    x = np.arange(24, dtype=np.float32).reshape(8, 3)
    y = np.arange(8, dtype=np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=4)
    pf = it.prefetch_to_device(window=2)
    wins = list(pf)
    assert len(wins) == 1
    assert tuple(np.asarray(wins[0][0]).shape) == (2, 4, 3)  # data
    assert tuple(np.asarray(wins[0][1]).shape) == (2, 4)     # label
    pf.close()


def test_run_with_attached_prefetcher_skips_caller_device_put():
    mesh = make_mesh(MeshConfig(dp=8))
    ts = _make_step(mesh=mesh)
    x, y = _batches(1, b=8)[0]
    placed = (jax.device_put(x, ts.batch_sharding),
              jax.device_put(y, ts.batch_sharding))
    calls = {"n": 0}
    orig = jax.device_put

    def counting(arr, *a, **kw):
        if any(arr is p for p in placed):
            calls["n"] += 1
        return orig(arr, *a, **kw)

    jax.device_put = counting
    try:
        ts.attach_prefetcher(object())  # batches marked device-resident
        ts(placed[0], placed[1])
        assert calls["n"] == 0, "device_put ran despite attached prefetcher"
        ts._prefetcher = None
        ts(placed[0], placed[1])
        assert calls["n"] == 2  # detached: per-call placement is back
    finally:
        jax.device_put = orig


# -- multichip (virtual 8-way mesh) ------------------------------------------
def test_run_window_on_virtual_mesh():
    mesh = make_mesh(MeshConfig(dp=8))
    ts = _make_step(mesh=mesh)
    losses = np.asarray(jax.device_get(
        ts.run(iter(_batches(4, b=8)), steps=4, window=2)))
    assert losses.shape == (4,) and np.isfinite(losses).all()
    # params stayed pinned to the storage layout across windows
    for v in ts.params.values():
        assert v.sharding.mesh.shape == mesh.shape


def test_window_matches_sequential_on_mesh():
    data = _batches(4, b=8)
    mesh = make_mesh(MeshConfig(dp=8))
    ts_seq = _make_step(mesh=mesh)
    seq_losses = [float(ts_seq(nd.array(x), nd.array(y))) for x, y in data]
    ts_win = _make_step(mesh=mesh)
    losses = np.asarray(jax.device_get(ts_win.run(iter(data), steps=4, window=4)))
    np.testing.assert_allclose(losses, seq_losses, rtol=2e-5, atol=1e-6)
    for a, b in zip(_param_values(ts_seq), _param_values(ts_win)):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)


# -- lower_hlo shares the __call__ program (satellite bugfix) ----------------
def test_lower_hlo_shares_call_cache():
    ts = _make_step()
    x, y = _batches(1)[0]
    lowered = ts.lower_hlo(nd.array(x), nd.array(y))
    assert len(ts._compiled) == 1, "lower_hlo must populate the jit cache"
    assert "hlo" in lowered.as_text().lower() or lowered.compile()
    ts(nd.array(x), nd.array(y))
    assert len(ts._compiled) == 1, "__call__ compiled a second program"


def test_lower_hlo_applies_mesh_shardings():
    mesh = make_mesh(MeshConfig(dp=8))
    ts = _make_step(mesh=mesh)
    x, y = _batches(1, b=8)[0]
    text = ts.lower_hlo(nd.array(x), nd.array(y)).compile().as_text()
    assert "all-reduce" in text, "dp grad all-reduce missing from lowering"


# -- Trainer.run -------------------------------------------------------------
def test_trainer_run_matches_train_step_and_refreshes_states():
    net = _mlp()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    data = _batches(4)
    losses = np.asarray(jax.device_get(
        trainer.run(net, _loss, iter(data), steps=4, window=2)))
    assert losses.shape == (4,) and np.isfinite(losses).all()
    assert trainer.optimizer.num_update == 4
    assert all(trainer._states_created)

    # same training as a plain TrainStep sequence — and run() synced the
    # updated params back into the Gluon block
    ts = _make_step(optimizer=opt.SGD(learning_rate=0.1))
    seq_losses = [float(ts(nd.array(x), nd.array(y))) for x, y in data]
    np.testing.assert_allclose(losses, seq_losses, rtol=2e-5, atol=1e-6)
    net_vals = [p.data().asnumpy() for _, p in sorted(net.collect_params().items())]
    for a, b in zip(_param_values(ts), net_vals):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)


def test_trainer_run_reseeds_from_net_between_runs():
    # params replaced between run() calls (what an interleaved imperative
    # step() does) must be picked up by the cached TrainStep, not clobbered
    # by its stale device copies
    data = _batches(2)
    net = _mlp()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    trainer.run(net, _loss, iter(data), steps=2, window=2)
    ts_cached = trainer._fused[1]

    plist = [p for _, p in sorted(net.collect_params().items())]
    snap = []
    for i, p in enumerate(plist):
        new = np.random.RandomState(50 + i).normal(
            0, 0.1, p._nd._data.shape).astype(np.float32)
        p._nd._data = jnp.asarray(new)
        snap.append(new)
    trainer.run(net, _loss, iter(data), steps=2, window=2)
    assert trainer._fused[1] is ts_cached  # same signature: cache hit

    # reference: a fresh TrainStep started from the same snapshot
    net2 = _mlp()
    plist2 = [p for _, p in sorted(net2.collect_params().items())]
    for p, v in zip(plist2, snap):
        p._nd._data = jnp.asarray(v)
    ts_ref = TrainStep(net2, _loss, opt.SGD(learning_rate=0.1))
    for x, y in data:
        ts_ref(nd.array(x), nd.array(y))
    ref_vals = _param_values(ts_ref)
    got_vals = [p.data().asnumpy()
                for _, p in sorted(net.collect_params().items())]
    for a, b in zip(ref_vals, got_vals):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-6)

    # a different loss_fn is a different program family: cache rebuilds
    trainer.run(net, lambda o, *l: ((o - l[0]) ** 2).sum(), iter(data),
                steps=2, window=2)
    assert trainer._fused[1] is not ts_cached
