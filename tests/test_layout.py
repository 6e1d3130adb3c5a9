"""The declarative parallelism layout (docs/PARALLELISM.md): Layout
serialization/identity/validation, elastic refit and declared-vs-restored
checkpoint compatibility, the mesh/rules back-compat bridge, and the
layout-equivalence contract — ONE spec driving TrainStep, the k-step
window, batch placement and reshard-on-restore, with equivalent specs
(however constructed) producing identical compiled programs and sharing
one fused-TrainStep cache entry."""
import json
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (Layout, MeshConfig, ShardingRules, TrainStep,
                                make_mesh, reshard_tree)
from mxnet_tpu.parallel.layout import AXES
from jax.sharding import PartitionSpec as P


# -- identity / serialization ------------------------------------------------
def test_layout_roundtrip_and_identity():
    lay = Layout(dp=2, fsdp=4, rules=[(r"dense\d*_weight$", ("fsdp", None))],
                 fsdp_axis="fsdp", min_fsdp_size=1)
    back = Layout.from_dict(lay.to_dict())
    assert back == lay and hash(back) == hash(lay)
    assert Layout.from_json(lay.to_json()) == lay
    # canonical is constructor-order independent and list/tuple agnostic
    same = Layout.from_dict(json.loads(json.dumps(lay.to_dict())))
    assert same.canonical() == lay.canonical()
    assert Layout(dp=2, fsdp=4) != lay
    # unused axes stay out of the serialized record
    assert set(lay.to_dict()["axes"]) == {"dp", "fsdp"}
    assert lay.total == 8 and lay.sizes() == (2, 4, 1, 1, 1, 1)


def test_layout_validation():
    with pytest.raises(ValueError):
        Layout(dp=0)
    with pytest.raises(ValueError):
        Layout(dp=2, rules=[("w$", ("nope", None))])  # unknown rule axis
    with pytest.raises(ValueError):
        Layout(dp=2, batch_axes=("nope",))
    with pytest.raises(Exception):
        Layout(dp=2, rules=[("(w$", ("dp",))])  # bad regex fails fast
    with pytest.raises(ValueError):
        Layout.from_dict({"axes": {"zz": 2}})


def test_layout_batch_spec():
    # default batch axes = data axes with size > 1
    assert Layout(dp=8).batch_spec() == P("dp")
    assert Layout(dp=2, fsdp=4).batch_spec() == P(("dp", "fsdp"))
    assert Layout(pp=8).batch_spec() == P()
    # the window stacks [window(, accum)] in front of the batch dim
    assert Layout(dp=8).batch_spec(extra_leading=2) == P(None, None, "dp")
    # explicit batch axes override (the fused dp==ep MoE layout)
    assert Layout(ep=4, fsdp=2, batch_axes=("ep",)).batch_spec() == P("ep")
    assert Layout().batch_sharding() is None


def test_layout_mesh_cached_and_shared():
    a = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    b = Layout(fsdp=4, dp=2, fsdp_axis="fsdp", min_fsdp_size=1)
    assert a == b
    assert a.mesh() is b.mesh()  # equivalent specs share ONE Mesh object
    assert dict(a.mesh().shape) == {ax: s for ax, s in
                                    zip(AXES, (2, 4, 1, 1, 1, 1))}


# -- elastic refit / checkpoint compatibility --------------------------------
def test_layout_refit():
    # fsdp width survives when divisible; dp absorbs the rest
    lay = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    assert lay.refit(8).axes == lay.axes
    r = lay.refit(4)
    assert r.axes["fsdp"] == 4 and r.axes["dp"] == 1
    # pure dp scales freely
    assert Layout(dp=8).refit(2).axes["dp"] == 2
    # model axes must survive unchanged — or it is an error, not a repartition
    lay_pp = Layout(pp=4, dp=2)
    assert lay_pp.refit(8).axes["pp"] == 4
    with pytest.raises(ValueError):
        lay_pp.refit(6)
    # default batch axes are recomputed for the new data axes
    assert Layout(dp=2, fsdp=4).refit(4).batch_axes == ("fsdp",)


def test_layout_compatible_restore():
    lay = Layout(dp=2, fsdp=4, rules=[("w$", ("fsdp", None))],
                 fsdp_axis="fsdp", min_fsdp_size=1)
    rec = lay.to_dict()
    assert lay.compatible_restore(rec) is None
    # data-axis changes are the elastic contract — compatible
    rec2 = dict(rec, axes={"dp": 8})
    assert lay.compatible_restore(rec2) is None
    # model-axis changes are a different program — refused, with the reason
    rec3 = dict(rec, axes={"dp": 1, "tp": 8})
    why = lay.compatible_restore(rec3)
    assert why is not None and "tp" in why
    # rule drift is refused too
    rec4 = dict(rec, rules=[["w$", [["dp"], None]]])
    assert lay.compatible_restore(rec4) is not None
    assert lay.compatible_restore({"axes": {"zz": 3}}) is not None


def test_from_mesh_bridge():
    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    rules = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    bridged = Layout.from_mesh(mesh, rules)
    explicit = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    assert bridged.canonical() == explicit.canonical()
    # a mesh outside the vocabulary cannot be bridged
    from jax.sharding import Mesh

    alien = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))
    with pytest.raises(ValueError):
        Layout.from_mesh(alien)


# -- layout equivalence: one spec drives the whole stack ---------------------
def _tiny_net():
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():   # names that pair across fresh nets on restore
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    return net, x, nd.zeros((8, 8))


def test_layout_equivalence_trainstep_window_prefetch():
    """The same spec via layout= and via legacy mesh=/rules= produces the
    SAME placement and the SAME compiled step/window programs, and the
    prefetcher-facing batch shardings all derive from the layout."""
    lay = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    net, x, y = _tiny_net()
    loss = lambda out, *l: ((out - l[0]) ** 2).mean()  # noqa: E731
    ts1 = TrainStep(net, loss, opt.Adam(learning_rate=1e-3), layout=lay)
    ts2 = TrainStep(net, loss, opt.Adam(learning_rate=1e-3),
                    mesh=make_mesh(MeshConfig(dp=2, fsdp=4)),
                    rules=ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1))
    # the legacy convention is bridged INTO an equivalent layout
    assert ts2.layout is not None
    assert ts2.layout.canonical() == lay.canonical()
    assert ts1.mesh == ts2.mesh
    assert ts1.batch_sharding == ts2.batch_sharding
    assert ts1.batch_sharding == lay.batch_sharding(ts1.mesh)
    assert ts1.window_batch_sharding(2) == \
        jax.sharding.NamedSharding(ts1.mesh, lay.batch_spec(extra_leading=2))
    assert {k: s.spec for k, s in ts1.param_sharding.items()} == \
        {k: s.spec for k, s in ts2.param_sharding.items()}
    # identical compiled programs: step AND window, clean contract
    for kwargs in ({}, {"window": 2}):
        a1 = ts1.audit(x, y, **kwargs)
        a2 = ts2.audit(x, y, **kwargs)
        assert a1.contract == [] and a2.contract == []
        assert [i for i in a1.lowered.inputs] == \
            [i for i in a2.lowered.inputs]
        assert a1.compiled.op_census() == a2.compiled.op_census()
        # overlap policy defaults on through either construction path
        assert a1.lowered.count("optimization_barrier") == \
            a2.lowered.count("optimization_barrier") > 0


def test_overlap_policy_is_in_the_program_and_changes_no_result():
    """What the program shows of ``Layout(overlap=)``: with it the lowered
    step chains each gradient bucket behind the next one's with
    ``optimization_barrier`` (four parameters in four buckets: three),
    without it there is none, and the barrier being the identity on
    values, the two steps train alike."""
    loss = lambda out, *l: ((out - l[0]) ** 2).mean()  # noqa: E731
    steps = {}
    for overlap in (True, False):
        net, x, y = _tiny_net()
        ts = TrainStep(net, loss, opt.Adam(learning_rate=1e-3),
                       layout=Layout(dp=2, fsdp=4, fsdp_axis="fsdp",
                                     min_fsdp_size=1, overlap=overlap,
                                     overlap_buckets=4))
        barriers = ts.audit(x, y, compile=False).lowered.count(
            "optimization_barrier")
        losses = [float(ts(x, y)) for _ in range(3)]
        ts.sync()
        steps[overlap] = (barriers, losses, {
            k: v.data().asnumpy() for k, v in net.collect_params().items()})
    assert steps[True][0] == 3 and steps[False][0] == 0
    assert steps[True][1] == steps[False][1]
    for (_, a), (_, b) in zip(sorted(steps[True][2].items()),
                              sorted(steps[False][2].items())):
        np.testing.assert_array_equal(a, b)


def test_trainer_run_cache_keys_on_canonical_layout():
    """Equivalent specs — layout= objects rebuilt each call, or the
    legacy mesh=/rules= pair — share ONE fused TrainStep cache entry."""
    from mxnet_tpu.gluon import Trainer

    net, x, y = _tiny_net()
    loss = lambda out, *l: ((out - l[0]) ** 2).mean()  # noqa: E731
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-3})
    data = [(x, y)]
    tr.run(net, loss, iter(data), steps=1, window=1,
           layout=Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1))
    ts_first = tr._fused[1]
    # a NEW but equivalent Layout object: same canonical -> same entry
    tr.run(net, loss, iter(data), steps=1, window=1,
           layout=Layout(fsdp=4, dp=2, fsdp_axis="fsdp", min_fsdp_size=1))
    assert tr._fused[1] is ts_first
    # the legacy convention bridges to the same canonical key
    tr.run(net, loss, iter(data), steps=1, window=1,
           mesh=make_mesh(MeshConfig(dp=2, fsdp=4)),
           rules=ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1))
    assert tr._fused[1] is ts_first
    with pytest.raises(ValueError):
        tr.run(net, loss, iter(data), steps=1, layout=Layout(dp=8),
               mesh=make_mesh(MeshConfig(dp=8)))


def test_layout_checkpoint_roundtrip_and_validation(tmp_path):
    """save() records the layout in the manifest; restore validates the
    declared layout (model axes + rules) and reshards through it."""
    from mxnet_tpu.checkpoint import checkpoint_layout

    lay = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    net, x, y = _tiny_net()
    loss = lambda out, *l: ((out - l[0]) ** 2).mean()  # noqa: E731
    ts = TrainStep(net, loss, opt.Adam(learning_rate=1e-3), layout=lay)
    ts(x, y)
    path = ts.save(str(tmp_path))
    rec = checkpoint_layout(path)
    assert rec is not None and rec["axes"] == {"dp": 2, "fsdp": 4}
    assert lay.compatible_restore(rec) is None
    assert ts.restore(str(tmp_path))
    # restored state lands back on the layout's storage shardings
    for k, v in ts.params.items():
        assert v.sharding.spec == ts.param_sharding[k].spec
    # a model-axis mismatch in the recorded layout refuses the restore
    from mxnet_tpu.resilience import integrity

    mf_path = os.path.join(path, integrity.MANIFEST_NAME)
    with open(mf_path) as f:
        mf = json.load(f)
    mf["layout"]["axes"] = {"dp": 1, "tp": 8}
    with open(mf_path, "w") as f:
        json.dump(mf, f)
    with pytest.raises(ValueError, match="tp"):
        ts.restore(str(tmp_path))


def test_reshard_tree_layout_path():
    lay = Layout(dp=2, fsdp=4, fsdp_axis="fsdp", min_fsdp_size=1)
    tree = {"dense0_weight": np.ones((32, 16), np.float32)}
    out = reshard_tree({k: jax.numpy.asarray(v) for k, v in tree.items()},
                       layout=lay)
    assert out["dense0_weight"].sharding.spec == \
        lay.spec_for("dense0_weight", (32, 16), lay.mesh())
    with pytest.raises(ValueError):
        reshard_tree(tree, shardings={}, layout=lay)
