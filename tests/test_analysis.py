"""Static-analysis subsystem (ISSUES 6 + 8, docs/ANALYSIS.md): the HLO
auditor (ProgramReport parsing over both text dialects, donation coverage,
program fingerprints + recompile causes), the sharding-and-communication
layer (ShardingInfo parsing, the declared-vs-compiled contract checker,
the comm cost model + accidental-reshard detector), and the AST jit-hazard
linter (rule engine, suppressions, and the package-is-clean regression
that backs ``make lint``).
"""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, nd, optimizer as opt
from mxnet_tpu import observability as obs
from mxnet_tpu.analysis import astlint
from mxnet_tpu.analysis.hlo_audit import Fingerprint, fingerprint_diff
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import TrainStep

PKG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mxnet_tpu")


# -- ProgramReport parsing ---------------------------------------------------
def _bf16_cond_program():
    def f(p, x):
        y = (p["w"].astype(jnp.bfloat16) @ x.astype(jnp.bfloat16)).astype(
            jnp.float32)
        z = jax.lax.cond(y.sum() > 0, lambda v: v + 1, lambda v: v - 1, y)
        return {"w": p["w"] - 0.1 * z.sum()}, z.sum()

    return jax.jit(f, donate_argnums=(0,)).lower(
        {"w": jnp.ones((4, 8))}, jnp.ones((8, 2)))


def test_stablehlo_report_census_dots_and_donation():
    rep = analysis.audit_lowered(_bf16_cond_program())
    assert rep.dialect == "stablehlo"
    assert rep.dot_dtypes() == {"bf16": 1}
    assert rep.count("case") == 1          # the lax.cond branch
    assert rep.has("dot_general") and not rep.has("nonexistent_op")
    assert not rep.ops_with_dtype("f64")   # no f64 promotion leak
    assert "bf16" in rep.dtype_census() and "f32" in rep.dtype_census()
    # donation: arg0 (the donated dict leaf) aliased, arg1 (batch) not
    assert rep.donation.aliased == {0: "may-alias"}
    assert rep.donation.n_inputs == 2
    assert rep.donation.coverage([0]) == 1.0
    assert rep.donation.coverage([0, 1]) == 0.5
    assert rep.donation.missing([0, 1]) == [1]
    assert rep.inputs[0] == ("f32", (4, 8))
    assert not rep.host_transfers()


def test_hlo_report_compiled_dialect_and_alias_header():
    low = _bf16_cond_program()
    rep = analysis.audit_compiled(low.compile())
    assert rep.dialect == "hlo"
    # nested-brace input_output_alias header parses (the regex trap)
    assert rep.donation.aliased == {0: "may-alias"}
    assert rep.count("fusion") >= 1 or rep.count("dot") >= 1


def test_report_collectives_replica_groups():
    """GSPMD-inserted collectives with both replica-group spellings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=8))

    def g(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())).sum() + x.mean()

    jg = jax.jit(g, in_shardings=NamedSharding(mesh, P("dp")),
                 out_shardings=NamedSharding(mesh, P()))
    xs = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("dp")))
    rep = analysis.audit_compiled(jg.lower(xs).compile())
    counts = rep.collective_counts()
    assert counts.get("all_reduce", 0) >= 1
    for c in rep.collectives:
        assert c.groups is not None and c.group_size == 8, \
            (c.name, c.raw_groups)
    assert len(rep.replica_group_specs()) == 1


def _sharding_dialect(text):
    """The attribute the installed jax lays tensors out with: Shardy's
    ``sdy.sharding`` or GSPMD's ``mhlo.sharding``. Each parser is also held
    to a committed text of the OTHER dialect (``_LOWERED_FIXTURES``)."""
    found = [d for d in ("sdy.sharding", "mhlo.sharding") if d in text]
    assert len(found) == 1, found
    return found[0]


def test_stablehlo_donation_survives_sharding_attrs():
    """Arg attrs like ``mhlo.sharding = "{replicated}"`` or ``sdy.sharding =
    #sdy.sharding<@mesh, [{}]>`` hold a ``}`` inside their value — the
    lowered-dialect alias scan must not stop there and drop
    tf.aliasing_output (the compile=False audit path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=8))

    def f(p, x):
        return p + x.sum()

    lowered = jax.jit(f, donate_argnums=(0,),
                      in_shardings=(NamedSharding(mesh, P()),
                                    NamedSharding(mesh, P("dp"))),
                      out_shardings=NamedSharding(mesh, P())).lower(
        jnp.ones((4,)), jnp.ones((8, 4)))
    rep = analysis.audit_lowered(lowered)
    _sharding_dialect(lowered.as_text())  # the trap is present
    assert rep.donation.aliased == {0: "may-alias"}
    assert rep.donation.coverage([0]) == 1.0


# jax's own lowering of one function (a donated replicated vector, a matrix
# split over both mesh axes and constrained to one inside) under the Shardy
# and under the GSPMD partitioner, mesh dp=2 x fsdp=4; a third argument was
# added by hand to both: a buffer donor split over fsdp, the LAST argument,
# so that its attributes end where the results' begin
_LOWERED_FIXTURES = ("lowered_sdy.mlir", "lowered_mhlo.mlir")


@pytest.fixture(params=_LOWERED_FIXTURES)
def lowered_fixture(request):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", request.param)
    with open(path) as f:
        return analysis.audit_text(f.read())


def test_lowered_fixture_donation_in_either_sharding_dialect(lowered_fixture):
    """``tf.aliasing_output`` and ``jax.buffer_donor`` both survive the
    layout attributes beside them, whichever partitioner wrote the text."""
    don = lowered_fixture.donation
    assert don.aliased == {0: "may-alias", 2: "buffer-donor"}
    assert don.out_alias == {0: 0}
    assert don.coverage([0, 2]) == 1.0 and don.missing([0, 1, 2]) == [1]


def test_lowered_fixture_layouts_in_either_sharding_dialect(lowered_fixture):
    """Arguments and the constraint inside read the same from either
    dialect; the last argument's layout is its own, not a result's."""
    rep = lowered_fixture
    assert rep.inputs == [("f32", (4,)), ("f32", (8, 4)), ("f32", (4,))]
    assert rep.arg_sharding(0).is_replicated
    assert rep.arg_sharding(1).tile_dims == (8, 1)
    assert not rep.arg_sharding(1).replicate_last
    assert rep.arg_sharding(2).tile_dims == (4,)
    assert rep.arg_sharding(2).replicate_last
    assert rep.sharded_inputs() == [1, 2]
    inner = [o.sharding for o in rep.ops if o.sharding is not None]
    assert [(s.tile_dims, s.replicate_last) for s in inner] == [((4, 1), True)]
    # the value the constraint defines is in the value table under either
    # spelling (a custom_call @Sharding or Shardy's own op)
    assert any(v.vid == "2" and v.bytes == 8 * 4 * 4 for v in rep.values)


def test_sdy_layout_over_an_undeclared_mesh_or_axis_is_unknown():
    from mxnet_tpu.analysis.hlo_audit import (parse_sdy_meshes,
                                              parse_sdy_shardings)

    meshes = parse_sdy_meshes('sdy.mesh @mesh = <["dp"=2, "fsdp"=4]>')
    assert meshes == {"mesh": {"dp": 2, "fsdp": 4}}
    kinds = [s.kind for s in parse_sdy_shardings(
        '<@mesh, [{"tp"}, {}]> <@other, [{}]> <@mesh, [{"fsdp":(1)2, ?}, {}]>'
        ' <@mesh, []>', meshes)]
    assert kinds == ["unknown", "unknown", "tiled", "replicated"]


def test_async_collective_pair_counts_once():
    """all-reduce-start/-done is ONE collective (TPU/GPU backends emit the
    async pair — with a TUPLE result type on the start op — and combined
    gradient all-reduces are variadic; the -done op carries no
    replica_groups and must not dilute the spanning check)."""
    text = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p0: f32[4], p1: f32[2]) -> f32[4] {
          %p0 = f32[4]{0} parameter(0)
          %p1 = f32[2]{0} parameter(1)
          %ars = (f32[4]{0}, u32[], u32[]) all-reduce-start(f32[4]{0} %p0), replica_groups={{0,1,2,3}}, to_apply=%add
          %ard = f32[4]{0} all-reduce-done((f32[4]{0}, u32[], u32[]) %ars)
          %var = (f32[4]{0}, f32[2]{0}) all-reduce(f32[4]{0} %ard, f32[2]{0} %p1), replica_groups={{0,1,2,3}}, to_apply=%add
          %inf = ((f32[4]{0}), token[]) infeed(token[] %tok)
          ROOT %r = f32[4]{0} add(f32[4]{0} %ard, f32[4]{0} %ard)
        }
        """)
    rep = analysis.audit_text(text)
    # the start/done pair counts once; the variadic (tuple-result)
    # all-reduce is seen too
    assert rep.collective_counts() == {"all_reduce": 2}
    for ar in rep.collectives_named("all_reduce"):
        assert ar.groups == ((0, 1, 2, 3),) and ar.group_size == 4
    assert not rep.has("all_reduce_done")
    # tuple-result host transfers are not invisible to the serving gate
    assert [o.name for o in rep.host_transfers()] == ["infeed"]


def test_audit_text_synthetic_hlo_inventories():
    """Explicit-list replica groups, custom-call targets and host-transfer
    ops — exercised on synthetic HLO so every branch of the parser is
    pinned without needing a TPU-only lowering."""
    text = textwrap.dedent("""\
        HloModule m, input_output_alias={ {0}: (1, {}, must-alias) }

        ENTRY %main (p0: f32[4], p1: f32[4]) -> f32[4] {
          %p0 = f32[4]{0} parameter(0)
          %p1 = f32[4]{0} parameter(1)
          %ar = f32[4]{0} all-reduce(f32[4]{0} %p0), replica_groups={{0,1},{2,3}}, to_apply=%add
          %cc = f32[4]{0} custom-call(f32[4]{0} %ar), custom_call_target="my_kernel"
          %of = token[] outfeed(f32[4]{0} %cc)
          ROOT %r = f32[4]{0} add(f32[4]{0} %cc, f32[4]{0} %p1)
        }
        """)
    rep = analysis.audit_text(text)
    assert rep.dialect == "hlo"
    assert rep.donation.aliased == {1: "must-alias"}
    (ar,) = rep.collectives_named("all-reduce")
    assert ar.groups == ((0, 1), (2, 3)) and ar.group_size == 2
    assert rep.custom_calls == ["my_kernel"]
    assert [o.name for o in rep.host_transfers()] == ["outfeed"]
    assert rep.has_tensor((4,), dtype="f32")
    assert not rep.has_tensor((5,))


# -- fingerprints & recompile causes -----------------------------------------
def test_fingerprint_diff_distinct_causes():
    """ISSUE 6 satellite: shape-change vs dtype-change vs static-arg-change
    each produce a DISTINCT cause, with a detail naming the change."""
    base = Fingerprint.of([jnp.ones((2, 3)), jnp.ones((2, 4))], lr=0.1)
    shape = Fingerprint.of([jnp.ones((6, 3)), jnp.ones((2, 4))], lr=0.1)
    dtype = Fingerprint.of([jnp.ones((2, 3), jnp.bfloat16),
                            jnp.ones((2, 4))], lr=0.1)
    static = Fingerprint.of([jnp.ones((2, 3)), jnp.ones((2, 4))], lr=0.5)
    arity = Fingerprint.of([jnp.ones((2, 3))], lr=0.1)

    assert fingerprint_diff(base, shape) == ("shape", "arg0: [2, 3] -> [6, 3]")
    cause, detail = fingerprint_diff(base, dtype)
    assert cause == "dtype" and "float32 -> bfloat16" in detail
    cause, detail = fingerprint_diff(base, static)
    assert cause == "static" and "lr" in detail
    assert fingerprint_diff(base, arity)[0] == "arity"
    assert fingerprint_diff(base, base) == ("identical", "")


def test_recompile_guard_counts_and_explains(tmp_path):
    obs.enable(str(tmp_path))
    try:
        guard = analysis.RecompileGuard(
            "analysis_test_recompiles_total",
            label_map={"static": "hyperparams"})
        f1 = Fingerprint.of([jnp.ones((2, 3))], k=1)
        f2 = Fingerprint.of([jnp.ones((6, 3))], k=1)
        f3 = Fingerprint.of([jnp.ones((6, 3))], k=2)
        assert guard.observe(f1) == "first"
        assert guard.observe(f1) is None          # seen: no double count
        assert guard.observe(f2) == "shape"
        assert guard.observe(f3) == "hyperparams"  # label_map applied
        assert guard.observe(f1, reason="forced") is None  # f1 already seen
        assert len(guard) == 3
        c = obs.REGISTRY.get("analysis_test_recompiles_total")
        assert c.value(reason="first") == 1
        assert c.value(reason="shape") == 1
        assert c.value(reason="hyperparams") == 1
        obs.shutdown()
        recs = [e for e in obs.read_events(str(tmp_path))
                if e["event"] == "recompile"]
        assert len(recs) == 3
        shape_ev = next(e for e in recs if e["reason"] == "shape")
        assert shape_ev["cause"] == "shape"
        assert "arg0" in shape_ev["detail"]        # explained, not counted
        assert shape_ev["shapes"] == [[6, 3]]
    finally:
        obs.disable()
        obs.REGISTRY.reset("analysis_test_recompiles_total")


def test_recompile_guard_groups_diff_separately(tmp_path):
    """Program families never cross-diff: the first step program after a
    window run is cause 'first', NOT a phantom shape change vs the
    window's stacked-batch fingerprint."""
    obs.enable(str(tmp_path))
    try:
        guard = analysis.RecompileGuard("analysis_test_group_recompiles")
        window_fp = Fingerprint.of([jnp.ones((4, 8, 16))], key="w")
        step_fp = Fingerprint.of([jnp.ones((8, 16))], key="s")
        assert guard.observe(window_fp, reason="window",
                             group="window") == "window"
        assert guard.observe(step_fp, group="step") == "first"
        assert len(guard) == 2
        # within a family the diff still explains
        step2 = Fingerprint.of([jnp.ones((2, 16))], key="s")
        assert guard.observe(step2, group="step") == "shape"
    finally:
        obs.disable()
        obs.REGISTRY.reset("analysis_test_group_recompiles")


def test_train_step_recompile_causes_shape_dtype_hyperparams(tmp_path):
    """The live TrainStep path: a batch-shape change, a label-dtype change
    and an lr-multiplier edit each land in the event log with their own
    cause (acceptance: the shape recompile is *logged* with cause
    "shape")."""
    obs.enable(str(tmp_path))
    try:
        mx.random.seed(0)
        net = nn.Dense(4, in_units=3)
        net.initialize()
        _ = net(nd.ones((2, 3)))
        sgd = opt.SGD(learning_rate=0.1)
        ts = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), sgd)
        rc = obs.counter("train_recompiles_total")
        base = {k: rc.value(reason=k)
                for k in ("first", "shape", "dtype", "hyperparams")}
        ts(nd.ones((2, 3)), nd.ones((2, 4)))                  # first
        ts(nd.ones((6, 3)), nd.ones((6, 4)))                  # shape
        ts(nd.ones((6, 3)), nd.ones((6, 4), dtype="int32"))   # dtype
        w = net.weight.name
        sgd.set_lr_mult({w: 0.5})
        ts(nd.ones((6, 3)), nd.ones((6, 4), dtype="int32"))   # hyperparams
        assert rc.value(reason="first") == base["first"] + 1
        assert rc.value(reason="shape") == base["shape"] + 1
        assert rc.value(reason="dtype") == base["dtype"] + 1
        assert rc.value(reason="hyperparams") == base["hyperparams"] + 1
        obs.shutdown()
        recs = [e for e in obs.read_events(str(tmp_path))
                if e["event"] == "recompile"]
        by_reason = {e["reason"]: e for e in recs}
        assert by_reason["shape"]["cause"] == "shape"
        assert "[2, 3] -> [6, 3]" in by_reason["shape"]["detail"]
        assert "float32 -> int32" in by_reason["dtype"]["detail"]
    finally:
        obs.disable()


# -- audit(): donation coverage ----------------------------------------------
def _tiny_mlp_step(amp=None, optimizer=None):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    x = nd.ones((4, 6))
    _ = net(x)
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   optimizer or opt.Adam(learning_rate=1e-3), amp=amp)
    return ts, (x, nd.zeros((4, 4)))


def test_train_step_audit_step_carry_fully_donated():
    ts, batch = _tiny_mlp_step(amp="bfloat16")
    audit = ts.audit(*batch)
    # 4 params + 8 adam slots ride the donated carry
    assert len(audit.carry_indices) == 12
    assert audit.carry_donation() == 1.0, audit.carry_missing()
    # acceptance: zero f64 ops in the compiled bf16 program's lowering
    assert not audit.lowered.ops_with_dtype("f64")
    assert audit.lowered.dot_dtypes().get("bf16", 0) >= 2
    assert audit.summary()["carry"]["donation_coverage"] == 1.0


def test_train_step_audit_window_carry_fully_donated():
    """ISSUE 6 satellite: 100% donation coverage for the k-step window
    carry (params + opt state through the lax.scan program)."""
    ts, batch = _tiny_mlp_step()
    audit = ts.audit(*batch, window=3)
    assert audit.lowered.count("while") >= 1   # the scan compiled in
    assert audit.carry_donation() == 1.0, audit.carry_missing()


@pytest.mark.slow
def test_generation_engine_audit_cache_carry_fully_donated():
    """ISSUE 6 satellite: 100% donation coverage for the decode-engine
    KV-cache carry (and the prefill program's cache donation)."""
    from mxnet_tpu.inference import GenerationEngine
    from mxnet_tpu.models import gpt2

    mx.random.seed(0)
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, num_layers=2, units=32,
                        num_heads=2, max_length=64, vocab_size=64)
    net.initialize()
    _ = net(nd.array(np.zeros((1, 4), np.int32)))
    eng = GenerationEngine(net, batch_size=2, max_length=64,
                           prefill_buckets=(8, 16))
    audit = eng.audit()
    assert len(audit.carry_indices) == 4       # 2 layers x (k_buf, v_buf)
    assert audit.carry_donation() == 1.0, audit.carry_missing()
    assert eng.audit(bucket=8).carry_donation() == 1.0


def test_audit_does_not_consume_training_rng():
    """lower()/audit() must not draw from the live key stream — an audit
    mid-run would otherwise perturb every later step's dropout keys and
    break fixed-seed reproducibility."""
    from mxnet_tpu import random as mxrandom

    ts, batch = _tiny_mlp_step()
    mx.random.seed(42)
    ref = np.asarray(jax.random.key_data(mxrandom.next_key()))
    mx.random.seed(42)
    ts.audit(*batch, compile=False)
    ts.audit(*batch, window=2, compile=False)
    got = np.asarray(jax.random.key_data(mxrandom.next_key()))
    assert (ref == got).all(), "audit() advanced the global key stream"


# -- sharding annotations (ISSUE 8) ------------------------------------------
def test_parse_sharding_spellings():
    """Every GSPMD annotation form normalizes into ShardingInfo — both the
    compiled ``sharding={...}`` body and the lowered quoted-attr value."""
    p = analysis.parse_sharding
    assert p("{replicated}").is_replicated
    assert p('"{replicated}"').kind == "replicated"   # lowered quoting
    s = p("{devices=[4,1]<=[4]}")
    assert s.kind == "tiled" and s.tile_dims == (4, 1)
    assert not s.is_replicated
    assert s.describe() == "sharded devices=[4, 1]"
    # subgroup replication: the trailing tile dim partitions nothing
    s = p("{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}")
    assert s.tile_dims == (4, 1) and s.replicate_last
    assert p("{maximal device=0}").is_replicated     # one device holds all
    assert p("{manual}").kind == "manual"
    assert p("{devices=[1,1]<=[1]}").is_replicated   # all-ones tiling
    # tuple shardings (per-element layouts) are not a single-tensor form
    t = p("{{replicated}, {devices=[2]<=[2]}}")
    assert t.kind == "unknown" and t.raw


def test_hlo_parameter_shardings_parsed():
    """Compiled-dialect parameter shardings land in arg_shardings, with
    the balanced-brace scan surviving nested/annotated bodies."""
    text = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p0: f32[8,8], p1: f32[4], p2: f32[2,2]) -> f32[8,8] {
          %p0 = f32[8,8]{1,0} parameter(0), sharding={devices=[4,1]<=[8] last_tile_dim_replicate}
          %p1 = f32[4]{0} parameter(1), sharding={replicated}
          %p2 = f32[2,2]{1,0} parameter(2)
          ROOT %r = f32[8,8]{1,0} add(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p0)
        }
        """)
    rep = analysis.audit_text(text)
    assert rep.arg_sharding(0).tile_dims == (4,)
    assert rep.arg_sharding(1).is_replicated
    assert rep.arg_sharding(2) is None       # unannotated -> None
    assert rep.sharded_inputs() == [0]
    assert rep.summary()["sharded_inputs"] == 1


def test_compiled_input_layouts_come_from_the_executable():
    """``audit_compiled`` takes ``Compiled.input_shardings`` over the text's
    ``sharding={...}``, keyed like the text's parameters: an argument the
    compiler dropped has neither, and the text's own answer is the same."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))

    def f(p, unused, x):
        return p * x.sum()

    compiled = jax.jit(
        f, in_shardings=(NamedSharding(mesh, P()), None,
                         NamedSharding(mesh, P("fsdp", "dp"))),
        out_shardings=NamedSharding(mesh, P())).lower(
            jnp.ones((4,)), jnp.ones((3,)), jnp.ones((8, 4))).compile()
    rep = analysis.audit_compiled(compiled)
    text = analysis.audit_text(compiled.as_text())
    assert len(rep.inputs) == 2
    assert rep.arg_sharding(0).is_replicated
    assert rep.arg_sharding(1).tile_dims == (4, 2)
    assert "NamedSharding" in rep.arg_sharding(1).raw
    assert text.arg_sharding(1).tile_dims == (4, 2)
    assert "devices=" in text.arg_sharding(1).raw
    assert rep.sharded_inputs() == text.sharded_inputs() == [1]


def test_stablehlo_arg_and_op_shardings_parsed():
    """Lowered-dialect layout attrs (``sdy.sharding`` or ``mhlo.sharding``,
    whichever the installed jax writes): per-arg annotations on a live
    mesh lowering parse into arg_shardings (and per-op attrs onto Op)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=8))

    def f(p, x):
        return p * x.sum()

    lowered = jax.jit(
        f, in_shardings=(NamedSharding(mesh, P()),
                         NamedSharding(mesh, P("dp"))),
        out_shardings=NamedSharding(mesh, P())).lower(
            jnp.ones((4,)), jnp.ones((8, 4)))
    rep = analysis.audit_lowered(lowered)
    _sharding_dialect(lowered.as_text())
    assert rep.arg_sharding(0) is not None
    assert rep.arg_sharding(0).is_replicated
    assert rep.arg_sharding(1) is not None
    assert not rep.arg_sharding(1).is_replicated
    assert rep.arg_sharding(1).tile_dims[0] == 8
    assert rep.sharded_inputs() == [1]


def test_replica_groups_transposed_iota():
    """The V2 iota form GSPMD emits for a NON-trailing mesh axis:
    ``[4,2]<=[2,4]T(1,0)`` groups device ids column-major — the dp-axis
    groups of a dp=2 x fsdp=4 mesh, not 4 consecutive pairs."""
    from mxnet_tpu.analysis.hlo_audit import _parse_groups

    assert _parse_groups("[4,2]<=[2,4]T(1,0)") == \
        ((0, 4), (1, 5), (2, 6), (3, 7))
    assert _parse_groups("[2,4]<=[8]") == ((0, 1, 2, 3), (4, 5, 6, 7))
    # malformed forms stay unparsed (raw preserved), never throw
    assert _parse_groups("[2,4]<=[9]") is None
    assert _parse_groups("[2,2,2]<=[8]") is None


# -- communication cost model (ISSUE 8) ---------------------------------------
_COMM_HLO = textwrap.dedent("""\
    HloModule m

    ENTRY %main (p0: f32[100], p1: f32[2,8], p2: f32[4,8]) -> f32[100] {
      %p0 = f32[100]{0} parameter(0)
      %p1 = f32[2,8]{1,0} parameter(1)
      %p2 = f32[4,8]{1,0} parameter(2)
      %ar = f32[100]{0} all-reduce(f32[100]{0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
      %ag = f32[8,8]{1,0} all-gather(f32[2,8]{1,0} %p1), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
      %rs = f32[1,8]{1,0} reduce-scatter(f32[4,8]{1,0} %p2), replica_groups={{0,1,2,3}}, to_apply=%add
      ROOT %r = f32[100]{0} add(f32[100]{0} %ar, f32[100]{0} %ar)
    }
    """)


def test_comm_report_prices_collectives():
    """The documented cost convention: all-reduce 2x tensor bytes,
    all-gather shard x group span, reduce-scatter 1x the input."""
    rep = analysis.audit_text(_COMM_HLO)
    comm = analysis.comm_report(rep)          # no mesh: all axes "?"
    by = {c.kind: c for c in comm.costs}
    assert by["all_reduce"].payload_bytes == 400      # 100 x f32
    assert by["all_reduce"].bytes == 800              # 2x factor
    # (2,8) shard x span 4 == the full (8,8) gathered tensor
    assert by["all_gather"].payload_bytes == 256
    assert by["all_gather"].bytes == 256
    assert by["reduce_scatter"].bytes == 128          # the (4,8) input
    assert by["reduce_scatter"].payload_bytes == 128
    assert comm.total_bytes() == 800 + 256 + 128
    assert comm.by_axis() == {"?": comm.total_bytes()}
    assert comm.by_kind()["all_reduce"] == 800
    assert comm.kind_counts() == {"all_reduce": 1, "all_gather": 1,
                                  "reduce_scatter": 1}
    assert bool(comm)
    assert comm.summary()["n_collectives"] == 3


def test_stablehlo_collective_payload_ignores_group_table():
    """The lowered dialect's ``replica_groups = dense<..> : tensor<NxMxi64>``
    attribute carries its own tensor type — payload sizing must price the
    operands, never the group table; the region form (types on the closing
    line) prices 0 rather than garbage."""
    text = textwrap.dedent("""\
        module @m {
          func.func public @main(%arg0: tensor<2x8xf32>) -> tensor<8x8xf32> {
            %0 = "stablehlo.all_gather"(%arg0) {all_gather_dim = 0 : i64, replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>} : (tensor<2x8xf32>) -> tensor<8x8xf32>
            %1 = "stablehlo.all_reduce"(%0) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> ({
            ^bb0(%a: tensor<f32>, %b: tensor<f32>):
              "stablehlo.return"(%a) : (tensor<f32>) -> ()
            }) : (tensor<8x8xf32>) -> tensor<8x8xf32>
            return %1 : tensor<8x8xf32>
          }
        }
        """)
    rep = analysis.audit_text(text)
    ag, ar = rep.collectives
    assert ag.name == "all_gather" and ag.group_size == 4
    assert ag.operand_info == (("f32", (2, 8)),)
    assert "i64" not in ag.dtypes                 # the table is not a tensor
    comm = analysis.comm_report(rep)
    by = {c.kind: c for c in comm.costs}
    assert by["all_gather"].payload_bytes == 256  # (2,8) f32 shard x 4
    # region form: groups still parse, payload best-effort 0 — NOT the
    # 32-byte i64 table priced as an all-reduce
    assert ar.groups == ((0, 1, 2, 3, 4, 5, 6, 7),)
    assert by["all_reduce"].payload_bytes == 0


def test_comm_report_axis_attribution():
    """Replica groups resolve onto mesh axes: groups whose device
    coordinates vary along dp land under "dp", groups varying along fsdp
    under "fsdp" — so per-axis byte budgets are structural."""
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    text = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p0: f32[16], p1: f32[16]) -> f32[16] {
          %p0 = f32[16]{0} parameter(0)
          %p1 = f32[16]{0} parameter(1)
          %a = f32[16]{0} all-reduce(f32[16]{0} %p0), replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add
          %b = f32[16]{0} all-reduce(f32[16]{0} %p1), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
          ROOT %r = f32[16]{0} add(f32[16]{0} %a, f32[16]{0} %b)
        }
        """)
    comm = analysis.comm_report(analysis.audit_text(text), mesh)
    assert [c.axes for c in comm.costs] == [("dp",), ("fsdp",)]
    assert comm.by_axis() == {"dp": 128, "fsdp": 128}   # 2 x 64 bytes each


def test_accidental_reshard_detector():
    """An all-gather whose full result matches a declared-sharded tensor's
    global shape is flagged — unless it is an intended compute gather."""
    from jax.sharding import PartitionSpec as P

    rep = analysis.audit_text(_COMM_HLO)
    declared = {"w": P("fsdp", None), "b": P(None)}
    shapes = {"w": (8, 8), "b": (100,)}
    flagged = analysis.detect_accidental_reshards(rep, declared, shapes)
    assert len(flagged) == 1 and flagged[0].param == "w"
    assert "fully materializes" in str(flagged[0])
    assert flagged[0].bytes == 256
    # the intended ZeRO compute gathers are exempt
    assert analysis.detect_accidental_reshards(
        rep, declared, shapes, intended={"w"}) == []
    # a replicated declaration is never a reshard (nothing to preserve)
    assert analysis.detect_accidental_reshards(
        rep, {"b": P(None)}, {"b": (8, 8)}) == []
    # shape shared between an intended and a non-intended tensor is
    # ambiguous: skipped, so the intended gather never flags its twin
    twin = {"w": P("fsdp", None), "w2": P("tp", None)}
    tshapes = {"w": (8, 8), "w2": (8, 8)}
    assert analysis.detect_accidental_reshards(
        rep, twin, tshapes, intended={"w"}) == []
    # with a mesh the gather's OPERAND must be the declared shard shape:
    # P('fsdp', None) on fsdp=4 shards (8,8) into (2,8) — matches the
    # program's gather, still flagged...
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    hit = analysis.detect_accidental_reshards(
        rep, declared, shapes, mesh=mesh)
    assert [r.param for r in hit] == ["w"]
    # ...but a declaration whose shard shape is (4,8) does NOT own this
    # gather (a same-result-shape coincidence, e.g. an activation)
    assert analysis.detect_accidental_reshards(
        rep, {"w": P("dp", None)}, shapes, mesh=mesh) == []


# -- sharding contract checker (ISSUE 8) --------------------------------------
def test_expected_tiles():
    from jax.sharding import PartitionSpec as P

    shape = {"dp": 2, "fsdp": 4, "tp": 1}
    assert analysis.expected_tiles(P("fsdp", None), 2, shape) == (4, 1)
    assert analysis.expected_tiles(P(None, ("dp", "fsdp")), 2, shape) == \
        (1, 8)
    # spec shorter than rank pads with 1s; size-1 axes partition nothing
    assert analysis.expected_tiles(P("tp"), 3, shape) == (1, 1, 1)
    # an axis the mesh does not have: un-realizable intent
    assert analysis.expected_tiles(P("ghost"), 1, shape) is None


def test_check_contract_synthetic():
    """Declared-vs-compiled diffs over a synthetic compiled program: a
    matching tiled layout passes, a replicated-where-declared-sharded
    param is reported in the ``declared → compiled`` rendering."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, fsdp=4))
    text = textwrap.dedent("""\
        HloModule m

        ENTRY %main (p0: f32[8,8], p1: f32[4]) -> f32[8,8] {
          %p0 = f32[8,8]{1,0} parameter(0), sharding={devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}
          %p1 = f32[4]{0} parameter(1)
          ROOT %r = f32[8,8]{1,0} add(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %p0)
        }
        """)
    rep = analysis.audit_text(text)
    shapes = {"w": (8, 8), "b": (4,)}
    order = {"w": 0, "b": 1}
    # intent matches the compiled layout: no violations
    ok = analysis.check_contract(
        rep, {"w": P("fsdp", None), "b": P(None)}, shapes, order, mesh)
    assert ok == []
    # w declared on dp (2 shards) but compiled with 4; b fine
    vs = analysis.check_contract(
        rep, {"w": P("dp", None), "b": P(None)}, shapes, order, mesh)
    assert len(vs) == 1
    assert str(vs[0]) == \
        "w: declared P('dp', None) → compiled sharded devices=[4, 1]"
    # b declared sharded but compiled without any annotation (replicated)
    vs = analysis.check_contract(
        rep, {"b": P("fsdp")}, shapes, {"b": 1}, mesh)
    assert str(vs[0]) == "b: declared P('fsdp') → compiled replicated"
    # declaring a size-1 axis legitimately compiles replicated: no report
    assert analysis.check_contract(
        rep, {"b": P("tp")}, shapes, {"b": 1}, mesh) == []
    # an axis the mesh lacks is ALWAYS a violation, even vs replicated
    vs = analysis.check_contract(
        rep, {"b": P("ghost")}, shapes, {"b": 1}, mesh)
    assert len(vs) == 1 and "P('ghost')" in vs[0].declared


def test_train_step_audit_fsdp_contract_and_comm():
    """ISSUE 8 acceptance: on a 4-device fsdp mesh the audit reports ZERO
    sharding-contract violations, a non-empty CommReport with the ZeRO
    traffic attributed to mesh axes, and no accidental reshards."""
    from mxnet_tpu.parallel import MeshConfig, ShardingRules, make_mesh

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    mesh = make_mesh(MeshConfig(fsdp=4))
    rules = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   opt.Adam(learning_rate=1e-3), mesh=mesh, rules=rules)
    audit = ts.audit(x, nd.zeros((8, 8)))
    assert audit.contract == [], [str(v) for v in audit.contract]
    comm = audit.comm
    assert comm is not None and bool(comm), "empty CommReport on a mesh"
    assert comm.reshards == [], [str(r) for r in comm.reshards]
    # the ZeRO pattern: compute all-gathers + grad reductions, every
    # priced byte attributed to a real mesh axis (nothing under "?")
    assert comm.kind_counts().get("all_gather", 0) >= 1
    assert comm.kind_counts().get("all_reduce", 0) >= 1
    assert "fsdp" in comm.by_axis() and "?" not in comm.by_axis()
    assert audit.summary()["comm"]["total_bytes"] == comm.total_bytes()
    assert audit.summary()["contract"] == []


def test_train_step_audit_catches_misspecced_rule():
    """ISSUE 8 acceptance: a deliberately mis-specced rule (typo'd axis
    name — spec_for silently falls back to replicated) is caught with the
    ``declared → compiled`` diff."""
    from mxnet_tpu.parallel import MeshConfig, ShardingRules, make_mesh

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    mesh = make_mesh(MeshConfig(fsdp=4))
    bad = ShardingRules(rules=[("weight", ("fsdq", None))])   # typo'd axis
    ts = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                   opt.Adam(learning_rate=1e-3), mesh=mesh, rules=bad)
    audit = ts.audit(x, nd.zeros((8, 8)))
    msgs = [str(v) for v in audit.contract]
    assert len(msgs) == 2, msgs                    # both dense weights
    for m in msgs:
        assert "declared P('fsdq', None) → compiled replicated" in m
    # the rules= override audits an alternative declaration against the
    # SAME compiled program (what shardcheck uses for what-if checks)
    good = ShardingRules(fsdp_axis="fsdp", min_fsdp_size=1)
    ts2 = TrainStep(net, lambda out, *l: ((out - l[0]) ** 2).mean(),
                    opt.Adam(learning_rate=1e-3), mesh=mesh, rules=good)
    vs = ts2.audit(x, nd.zeros((8, 8)), rules=bad).contract
    # every param diffs: the weights' typo'd intent vs the compiled fsdp
    # layout, and the biases' implied-replicated intent vs their compiled
    # fsdp-fallback sharding
    weight_vs = [v for v in vs if "weight" in v.param]
    assert weight_vs and all(
        "declared P('fsdq', None) → compiled sharded" in str(v)
        for v in weight_vs)


# -- astlint: rules ----------------------------------------------------------
HOT_SRC = textwrap.dedent("""\
    import time
    import numpy as np
    import jax

    def make_step():
        def step(params, batch):
            if params > 0:                    # JH002
                pass
            x = float(batch)                  # JH001
            v = np.asarray(batch)             # JH001
            y = batch.item()                  # JH001
            t = time.time()                   # JH003
            return params
        fn = step
        return jax.jit(fn, donate_argnums=(0,))
    """)


def _rules(violations):
    return sorted(v.rule for v in violations)


def test_lint_hot_path_rules_fire_through_alias():
    vs = astlint.lint_source(HOT_SRC, "mxnet_tpu/x.py")
    assert _rules(vs) == ["JH001", "JH001", "JH001", "JH002", "JH003"]
    lines = {v.rule + ":" + str(v.line) for v in vs}
    assert "JH002:7" in lines and "JH003:12" in lines


def test_lint_structural_idioms_not_flagged():
    """`x is None` and `name in container` are static under tracing; casts
    of static op params are trace-time specialization — none may fire."""
    src = textwrap.dedent("""\
        import jax

        def make(topk):
            def step(params, state):
                if params is not None:        # structural: ok
                    pass
                for name in state:
                    if name not in state:     # structural: ok
                        pass
                k = int(topk)                 # static param: ok
                return params
            return jax.jit(step)
        """)
    assert astlint.lint_source(src, "mxnet_tpu/x.py") == []


def test_lint_decorated_and_method_hot_paths():
    src = textwrap.dedent("""\
        import numpy as np
        import jax

        @jax.jit
        def decorated(x):
            return np.asarray(x)              # JH001

        class Engine:
            def __init__(self):
                self._fn = jax.jit(self._decode)

            def _decode(self, x):
                return x.item()               # JH001 (method via self.)
        """)
    assert _rules(astlint.lint_source(src, "m.py")) == ["JH001", "JH001"]


def test_lint_mutable_defaults_and_global_mutation():
    src = textwrap.dedent("""\
        import threading

        _REG = {}
        _lock = threading.Lock()

        def bad(x=[], y={}):                  # JH004 x2
            return x

        def put(k, v):
            _REG[k] = v                       # JH005

        def put_locked(k, v):
            with _lock:
                _REG[k] = v                   # ok

        def rhs_mutation(site):
            h = _REG.setdefault(site, [])     # JH005: mutates via RHS
            return h

        def aug(k):
            _REG[k] += 1                      # JH005: read-modify-write

        def local_only(k, v):
            reg = {}
            reg[k] = v                        # ok: not module-global
            return reg

        def deferred(k, v):
            with _lock:
                def cb():
                    _REG[k] = v               # JH005: cb runs later,
                return cb                     # NOT under the lock
        """)
    assert _rules(astlint.lint_source(src, "m.py")) == \
        ["JH004", "JH004", "JH005", "JH005", "JH005", "JH005"]


def test_lint_nondeterminism_in_op_modules():
    src = textwrap.dedent("""\
        import numpy as np

        def my_op(x):
            noise = np.random.normal(size=x.shape)     # JH003
            rs = np.random.RandomState(0)              # ok: explicit seed
            return x + noise + rs.normal(size=x.shape)
        """)
    vs = astlint.lint_source(src, "mxnet_tpu/ops/myop.py")
    assert _rules(vs) == ["JH003"]
    # same source outside op scope and outside hot paths: clean
    assert astlint.lint_source(src, "mxnet_tpu/io/loader.py") == []


def test_lint_suppressions_inline_above_def_and_file():
    src = textwrap.dedent("""\
        import numpy as np
        import jax

        def make():
            def step(p):
                a = np.asarray(p)  # lint: disable=JH001
                # lint: disable=JH001
                b = np.asarray(p)
                c = np.asarray(p)               # still flagged
                return a, b, c
            return jax.jit(step)

        def make2():
            def step2(p):  # lint: disable=all
                return np.asarray(p)
            return jax.jit(step2)
        """)
    vs = astlint.lint_source(src, "m.py")
    assert len(vs) == 1 and vs[0].line == 9
    assert astlint.lint_source(
        "# lint: disable-file=JH004\ndef f(x=[]):\n    return x\n",
        "m.py") == []


def test_lint_suppression_in_string_literal_is_inert():
    """A docstring that merely QUOTES the suppression syntax (as the rule
    catalog and astlint's own module docstring do) must not activate it —
    only real comment tokens count."""
    src = textwrap.dedent('''\
        """Docs quoting the syntax: # lint: disable-file=JH004"""

        def f(x=[]):
            return x
        ''')
    assert _rules(astlint.lint_source(src, "m.py")) == ["JH004"]


def test_lint_registered_extra_hot_paths():
    """EXTRA_HOT_PATHS reaches helpers called from jitted closures — the
    registered TrainStep._loss_of is hot even with no jit call in sight."""
    src = textwrap.dedent("""\
        class TrainStep:
            def _loss_of(self, params, batch, key):
                return float(batch)           # JH001 via registration
        """)
    vs = astlint.lint_source(src, "mxnet_tpu/parallel/train_step.py")
    assert _rules(vs) == ["JH001"]
    assert astlint.lint_source(src, "mxnet_tpu/parallel/other.py") == []


def test_lint_unknown_mesh_axis_jh006():
    """ISSUE 8 satellite: axis-name literals outside the MeshConfig
    vocabulary at PartitionSpec/named_sharding call sites — a typo'd axis
    silently replicates the tensor."""
    src = textwrap.dedent("""\
        from jax.sharding import PartitionSpec as P

        def specs(mesh):
            a = P("fsdq", None)               # JH006: typo'd axis
            b = P("dp", "fsdp")               # ok
            c = P(("dp", "fsdpp"))            # JH006: inside a tuple entry
            d = named_sharding(mesh, "tpp")   # JH006 (mesh arg skipped)
            e = PartitionSpec(None, "ep")     # ok
            f = P(axis)                       # ok: not a literal
            return a, b, c, d, e, f
        """)
    vs = astlint.lint_source(src, "mxnet_tpu/x.py")
    assert _rules(vs) == ["JH006", "JH006", "JH006"]
    assert sorted(v.line for v in vs) == [4, 6, 7]
    assert "fsdq" in [v for v in vs if v.line == 4][0].message
    # inline-suppressible like JH001-JH005
    sup = 'P("fsdq")  # lint: disable=JH006\n'
    assert astlint.lint_source(sup, "mxnet_tpu/x.py") == []
    # the vocabulary pins to parallel.layout.AXES (the declarative spec
    # owns it; parallel.mesh re-exports) — update both together
    from mxnet_tpu.parallel.layout import AXES

    assert astlint._MESH_AXES == frozenset(AXES)


def test_lint_traced_constant_capture_jh007():
    """ISSUE 12 satellite: a jitted/scanned closure reading a name bound
    to a host np.ndarray (module global or enclosing-function local) —
    the trace bakes it into the program as a constant. Shadowing and
    inline suppression are respected."""
    src = textwrap.dedent("""\
        import jax
        import numpy as np

        TABLE = np.arange(1000).reshape(10, 100)

        def build():
            scale = np.ones((64,))
            def step(x):
                return x @ TABLE + scale      # JH007 x2
            return jax.jit(step)

        def cold(x):
            return x @ TABLE                  # ok: not a hot path

        def shadowed():
            def step(x, TABLE):
                return x @ TABLE              # ok: parameter shadows
            return jax.jit(step)
        """)
    vs = astlint.lint_source(src, "mxnet_tpu/x.py")
    assert _rules(vs) == ["JH007", "JH007"]
    assert {"TABLE", "scale"} == {v.message.split("'")[1] for v in vs}
    sup = src.replace("return x @ TABLE + scale",
                      "return x @ TABLE + scale  # lint: disable=JH007")
    assert astlint.lint_source(sup, "mxnet_tpu/x.py") == []
    # jnp arrays are device values, not baked host constants
    ok = textwrap.dedent("""\
        import jax
        import jax.numpy as jnp

        TABLE = jnp.arange(1000)

        def f(x):
            return x + TABLE
        g = jax.jit(f)
        """)
    assert astlint.lint_source(ok, "mxnet_tpu/x.py") == []
    # the build-then-transfer idiom: a later rebinding to a non-host
    # expression clears the hazard (module level AND function level)
    rebound = textwrap.dedent("""\
        import jax
        import jax.numpy as jnp
        import numpy as np

        X = np.arange(100000)
        X = jnp.asarray(X)

        def build():
            y = np.ones((64,))
            y = jnp.asarray(y)
            def step(v):
                return v + X + y
            return jax.jit(step)
        """)
    assert astlint.lint_source(rebound, "mxnet_tpu/x.py") == []


def test_lint_sync_per_dispatch_jh008():
    """ISSUE 13 satellite: a driver loop that dispatches a compiled
    callable and immediately materializes its result blocks the host
    every step — async dispatch pipelining is gone. Recognized compiled
    callees: jax.jit(...) assignment targets (name or attribute) and the
    *_jit naming convention; materializers: block_until_ready/.item()/
    float()/np.asarray/device_get. Deferred materialization after the
    loop is the fix and stays clean; inline suppression is honored."""
    src = textwrap.dedent("""\
        import jax
        import numpy as np

        step = jax.jit(lambda x: x + 1)

        def drive(xs):
            out = []
            for x in xs:
                y = step(x)
                out.append(float(y))           # JH008
            return out

        def drive_direct(xs):
            while xs:
                step(xs.pop()).block_until_ready()   # JH008
            return 1

        class Engine:
            def __init__(self):
                self._decode_jit = jax.jit(lambda x: x)

            def loop(self, xs):
                for x in xs:
                    r = self._decode_jit(x)
                    np.asarray(r)              # JH008
        """)
    vs = astlint.lint_source(src, "mxnet_tpu/driver.py")
    assert _rules(vs) == ["JH008", "JH008", "JH008"]
    assert "defeating async dispatch" in vs[0].message
    # the fix: keep device futures, materialize ONCE after the loop
    ok = textwrap.dedent("""\
        import jax

        step = jax.jit(lambda x: x + 1)

        def drive(xs):
            futs = [ ]
            for x in xs:
                futs.append(step(x))
            last = futs[-1]
            last.block_until_ready()
            return [float(f) for f in futs]

        def plain(xs):
            for x in xs:
                y = helper(x)     # not a compiled callee
                float(y)
        """)
    assert astlint.lint_source(ok, "mxnet_tpu/driver.py") == []
    # inside a jitted hot path the rule stays quiet (that's JH001's turf)
    hot = textwrap.dedent("""\
        import jax

        inner = jax.jit(lambda x: x)

        def traced(xs):
            for x in xs:
                y = inner(x)
            return y
        g = jax.jit(traced)
        """)
    assert "JH008" not in _rules(astlint.lint_source(
        hot, "mxnet_tpu/driver.py"))
    sup = src.replace("out.append(float(y))           # JH008",
                      "out.append(float(y))  # lint: disable=JH008")
    assert _rules(astlint.lint_source(sup, "mxnet_tpu/driver.py")) == \
        ["JH008", "JH008"]


def test_lint_changed_diffs_merge_base(tmp_path):
    """ISSUE 8 satellite: --changed diffs against the merge-base of main,
    so a pre-commit run late in a branch still sees the files committed
    earlier ON that branch (the old vs-HEAD diff saw only the dirty
    tree)."""
    import importlib.util
    import subprocess

    spec = importlib.util.spec_from_file_location(
        "lintcli", os.path.join(os.path.dirname(PKG_DIR), "tools",
                                "lint.py"))
    lintcli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lintcli)

    repo = tmp_path / "r"
    (repo / "mxnet_tpu").mkdir(parents=True)

    def git(*args):
        subprocess.run(["git", *args], cwd=repo, check=True,
                       capture_output=True, text=True)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    git("checkout", "-q", "-b", "main")
    (repo / "mxnet_tpu" / "old.py").write_text("def f(x=()):\n    return x\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    git("checkout", "-q", "-b", "feature")
    (repo / "mxnet_tpu" / "committed.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "branch work")
    (repo / "mxnet_tpu" / "untracked.py").write_text("y = 2\n")
    (repo / "elsewhere.py").write_text("z = 3\n")   # outside linted trees

    names = {os.path.basename(f)
             for f in lintcli._changed_files(repo=str(repo))}
    # the branch's committed file IS seen (the fix), untracked still is,
    # the unchanged seed file and out-of-tree files are not
    assert names == {"committed.py", "untracked.py"}
    # on main itself the merge-base degrades to HEAD: nothing changed
    (repo / "mxnet_tpu" / "untracked.py").unlink()
    git("checkout", "-q", "main")
    assert lintcli._changed_files(repo=str(repo)) == []


def test_package_is_lint_clean():
    """The `make lint` contract, as a regression test: the package carries
    no unsuppressed jit hazards. Any new violation fails here AND in CI."""
    vs = astlint.lint_paths([PKG_DIR])
    assert vs == [], "\n".join(str(v) for v in vs)


def test_lint_cli_smoke(tmp_path):
    import subprocess
    import sys

    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    tools = os.path.join(os.path.dirname(PKG_DIR), "tools", "lint.py")
    r = subprocess.run([sys.executable, tools, str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "JH004" in r.stdout
    good = tmp_path / "good.py"
    good.write_text("def f(x=()):\n    return x\n")
    r = subprocess.run([sys.executable, tools, str(good)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, tools, "--list-rules"],
                       capture_output=True, text=True)
    assert r.returncode == 0 and "JH005" in r.stdout
