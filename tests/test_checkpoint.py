"""Checkpoint/resume of full training state (SURVEY §5.4)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, optimizer
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import TrainStep


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


def test_trainstep_save_restore_resumes_identically(tmp_path):
    d = str(tmp_path / "ckpt")
    x, y = nd.ones((4, 3)), nd.array([0, 1, 0, 1])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    ts = TrainStep(_net(), lambda o, y: loss_fn(o, y), optimizer.Adam(learning_rate=1e-2))
    for _ in range(3):
        ts(x, y)
    ts.save(d)
    expected = [float(ts(x, y)) for _ in range(2)]

    ts2 = TrainStep(_net(), lambda o, y: loss_fn(o, y), optimizer.Adam(learning_rate=1e-2))
    assert ts2.restore(d)
    assert ts2.optimizer.num_update == 3
    resumed = [float(ts2(x, y)) for _ in range(2)]
    np.testing.assert_allclose(expected, resumed, rtol=1e-5)


def test_latest_checkpoint_selection(tmp_path):
    from mxnet_tpu.checkpoint import latest_checkpoint, save_train_state

    d = str(tmp_path / "c")
    save_train_state(d, 5, {"w": np.ones(2)}, {})
    save_train_state(d, 12, {"w": np.ones(2)}, {})
    assert latest_checkpoint(d).endswith("ckpt-12")
    assert latest_checkpoint(str(tmp_path / "missing")) is None
