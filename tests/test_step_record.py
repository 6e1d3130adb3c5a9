"""The train step measured from inside (docs/OBSERVABILITY.md "The step
record", "Named scopes", "The lag of telemetry-on readings"): the always-on
record of ``TrainStep.__call__``'s host phases, the named scopes on its
device operations, and telemetry that reads step n some dispatches later."""
import contextlib
import gc
import re

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, observability as obs, optimizer as opt
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import profiling, scopes
from mxnet_tpu.parallel import TrainStep, train_step as ts_mod

PHASES = ("mx.train.input", "mx.train.args", "mx.train.dispatch",
          "mx.train.after")


def _step(amp=None, optimizer="sgd"):
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="tiny_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=3, activation="relu", prefix="hidden_"))
        net.add(nn.Dense(4, in_units=8, prefix="out_"))
    net.initialize()
    _ = net(nd.ones((2, 3)))
    return TrainStep(net, lambda out, y: (out - y) ** 2,
                     opt.create(optimizer, learning_rate=0.1), amp=amp)


def _batch(rows=2):
    return nd.ones((rows, 3)), nd.ones((rows, 4))


def _mine(before):
    """The records written since ``before`` was read (the ring belongs to
    the process, other tests of this worker write to it too, and once it is
    full its length says nothing)."""
    recs = obs.step_records()
    for i in range(len(recs) - 1, -1, -1):
        if before and recs[i] is before[-1]:
            return recs[i + 1:]
    return recs if not before else []


# -- the record ---------------------------------------------------------------
def test_phases_are_ordered_nest_in_the_step_and_sum_to_it():
    before = obs.step_records()
    ts = _step()
    for _ in range(3):
        ts(*_batch())
    recs = _mine(before)
    assert [r.step for r in recs] == [1, 2, 3]
    for r in recs:
        assert r.loop == "train_step"
        assert tuple(name for name, _ in r.marks) == PHASES
        ends = [end for _, end in r.marks]
        assert r.t0_ns <= ends[0] and ends == sorted(ends)
        phases = r.phase_ns()
        assert all(v >= 0 for v in phases.values())
        # contiguous by construction: they sum to the step exactly
        assert sum(phases.values()) == r.duration_ns == ends[-1] - r.t0_ns


def test_records_are_written_with_telemetry_off_and_touch_no_device_value(
        monkeypatch):
    assert not obs.enabled()
    ts = _step()
    ts(*_batch())  # compiled outside the guarded call

    def refuse(*a, **k):
        raise AssertionError("the hot path read the device")

    monkeypatch.setattr(jax, "device_get", refuse)
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    before = obs.step_records()
    loss = ts(*_batch())
    monkeypatch.undo()
    assert len(_mine(before)) == 1
    assert np.isfinite(float(loss))
    h = obs.REGISTRY.get("train_step_seconds")
    assert not ts._held and (h is None or not h.stats(loop="none"))


def test_the_ring_is_bounded_and_survives_the_step_object(monkeypatch):
    before = obs.step_records()
    ts = _step()
    ts(*_batch())
    del ts
    gc.collect()
    assert len(_mine(before)) == 1  # read after the TrainStep is gone
    # a ring a loop name (PR 38), each of STEP_RECORDS_KEPT
    assert obs._records["train_step"].maxlen == obs.STEP_RECORDS_KEPT >= 1024
    monkeypatch.setattr(obs, "_records", {})
    monkeypatch.setattr(obs, "STEP_RECORDS_KEPT", 8)
    for i in range(13):
        with obs.step_record("ring_test", i):
            pass
    assert [r.step for r in obs.step_records()] == list(range(5, 13))
    assert obs.step_records("train_step") == []


def test_a_changed_batch_shape_sets_the_compile_flag_on_that_step_only():
    before = obs.step_records()
    rc = obs.counter("train_recompiles_total")
    counted = rc.total()
    ts = _step()
    for rows in (2, 2, 2, 6, 6, 2):
        ts(*_batch(rows))
    flags = [r.compiled for r in _mine(before)]
    # the first lowering, steady, then the new shape once; the old shape
    # again is cached
    assert flags == [True, False, False, True, False, False]
    # counted with telemetry off too, with the cause the guard diffed
    assert not obs.enabled() and rc.total() == counted + 2
    assert rc.value(reason="shape") >= 1


def test_window_dispatch_writes_one_record_per_window():
    before = obs.step_records()
    ts = _step()
    batches = [(np.ones((2, 3), "float32"), np.ones((2, 4), "float32"))] * 4
    ts.run(iter(batches), steps=4, window=2)
    recs = _mine(before)
    assert [(r.loop, r.step, r.compiled) for r in recs] == [
        ("run_window", 2, True), ("run_window", 4, False)]
    assert [n for n, _ in recs[0].marks] == list(PHASES[1:])


def test_spans_outside_a_record_stay_a_no_op_when_telemetry_is_off():
    h = obs.REGISTRY.get("span_seconds")
    n = h.total_count() if h else 0
    before = obs.step_records()
    with obs.span("unit_region_off"):
        pass
    h = obs.REGISTRY.get("span_seconds")
    assert (h.total_count() if h else 0) == n and not _mine(before)


def test_spans_in_a_record_feed_span_seconds_when_telemetry_is_on(tmp_path):
    obs.enable(str(tmp_path))
    try:
        ts = _step()
        ts(*_batch())
        h = obs.REGISTRY.get("span_seconds")
        assert all(h.stats(span=name)["count"] >= 1 for name in PHASES)
    finally:
        obs.shutdown()
        obs.disable()


def test_the_phases_are_annotations_on_the_host_plane_of_a_trace(tmp_path):
    ts = _step()
    ts(*_batch())
    cap = profiling.capture(lambda: ts(*_batch()), steps=2, warmup=0,
                            trace_dir=str(tmp_path))
    spans = cap.report.span_breakdown()
    for name in ("mx.train.step",) + PHASES:
        assert spans[name]["count"] == 2, sorted(spans)
    # each carries the step's number, and the phases lie inside the step
    assert spans["mx.train.step"]["steps"] == spans["mx.train.dispatch"]["steps"]
    steps = [s for s in cap.report.spans if s.name == "mx.train.step"]
    inner = [s for s in cap.report.spans if s.name in PHASES]
    assert all(any(o.start_ns <= s.start_ns and s.end_ns <= o.end_ns
                   for o in steps) for s in inner)


# -- named scopes -------------------------------------------------------------
def _op_names(lowered):
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


def test_forward_optimizer_and_a_blocks_name_are_in_the_op_names():
    ts = _step()
    names = _op_names(ts.lower_hlo(*_batch()))
    assert any("/jvp(forward)/tiny/hidden/" in n for n in names), names
    assert any("/transpose(jvp(forward))/tiny/out/" in n for n in names)
    assert any("/optimizer/" in n for n in names)
    assert any("(loss)" in n for n in names)
    table = ts.op_scopes(*_batch())
    tops = {p.split("/")[0] for p in table.values()}
    assert {"forward", "backward", "optimizer"} <= tops
    assert tops <= set(scopes.SCOPES) | {scopes.BACKWARD, scopes.MIXED}
    assert any(p.startswith("forward/tiny/hidden") for p in table.values())


def test_amp_and_grad_norm_scopes_under_float16_with_telemetry(tmp_path):
    obs.enable(str(tmp_path))
    try:
        ts = _step(amp="float16")
        names = _op_names(ts.lower_hlo(*_batch()))
    finally:
        obs.shutdown()
        obs.disable()
    assert any("/amp/" in n or "(amp)" in n for n in names)
    assert any("/grad_norm/" in n for n in names)
    # the update sits in a cond under its own scope, not under amp's
    assert any("optimizer" in n and "/amp/" not in n for n in names)


def test_the_accumulate_scope_leaves_forward_and_backward_their_own():
    ts = _step()
    lowered = ts.lower_window_hlo(*_batch(), window=2, accum=2)
    table = scopes.op_scopes_from_hlo(lowered.compile().as_text())
    tops = {p.split("/")[0] for p in table.values()}
    assert {"accumulate", "forward", "backward", "optimizer"} <= tops


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The lowered program with and without scopes: the same text (no
    locations), the same cost, and bit-equal loss and parameters."""
    def run():
        ts = _step(optimizer="adam")
        lowered = ts.lower_hlo(*_batch())
        cost = lowered.compile().cost_analysis()
        losses = [np.asarray(ts(*_batch())) for _ in range(3)]
        return (lowered.as_text(), cost, losses,
                {k: np.asarray(v) for k, v in ts.params.items()})

    text, cost, losses, params = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    text0, cost0, losses0, params0 = run()
    monkeypatch.undo()
    # block-name counters differ between the two builds: compare by order
    assert text == text0
    assert cost == cost0
    assert all(np.array_equal(a, b) for a, b in zip(losses, losses0))
    assert all(np.array_equal(a, b) for a, b in
               zip(params.values(), params0.values()))
    assert len(params) == len(params0) == 4


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/jvp(forward)/bert0/enc/layer3/attn/dot_general",
     "forward/bert0/enc/layer3/attn"),
    ("jit(step)/transpose(jvp(forward))/bert0/enc/layer3/attn/dot_general",
     "backward/bert0/enc/layer3/attn"),
    ("jit(step)/transpose(jvp(loss))/mul", "backward/loss"),
    ("jit(step)/jvp(loss)/reduce_sum", "loss"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(window_fn)/while/body/jvp(forward)/net/jit(relu)/max",
     "forward/net"),
    ("jit(step)/jvp(forward)/net/checkpoint/rematted_computation/l0/tanh",
     "forward/net/l0"),
    ("jit(step)/cond/branch_1_fun/optimizer/add", "optimizer"),
    ("jit(step)/mul", None),
    ("p['w0']", None),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert scopes.scope_path(op_name) == path


HLO = """HloModule jit_step

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0), metadata={op_name="p['w']"}
  %a = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/jvp(forward)/net/attn/mul"}
  %b = f32[4]{0} add(%a, %a), metadata={op_name="jit(step)/jvp(forward)/net/ffn/add"}
  ROOT %c = f32[4]{0} tanh(%b), metadata={op_name="jit(step)/jvp(forward)/net/ffn/tanh"}
}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %d = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %e = f32[4]{0} add(%d, %d), metadata={op_name="jit(step)/transpose(jvp(forward))/net/add_any"}
}

%fused_computation.2 (param_0.2: f32[4,4]) -> f32[4,4] {
  %param_0.2 = f32[4,4]{1,0} parameter(0)
  %f = f32[4,4]{1,0} tanh(%param_0.2), metadata={op_name="jit(step)/jvp(forward)/net/ffn/tanh"}
  %g = f32[4,4]{1,0} multiply(%f, %f), metadata={op_name="jit(step)/jvp(forward)/net/ffn/mul"}
  %h = f32[4,4]{1,0} add(%g, %f), metadata={op_name="jit(step)/jvp(forward)/net/ffn/add"}
  ROOT %i = f32[4,4]{1,0} convolution(%h, %param_0.2), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(forward))/net/ffn/dot_general"}
}

ENTRY %main.5 (w: f32[4]) -> f32[4] {
  %m = f32[4,4]{1,0} parameter(1), metadata={op_name="m"}
  %fusion.9 = f32[4,4]{1,0} fusion(%m), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp(forward))/net/ffn/dot_general"}
  %w = f32[4]{0} parameter(0), metadata={op_name="w"}
  %fusion = f32[4]{0} fusion(%w), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(forward)/net/ffn/tanh"}
  %fusion.1 = f32[4]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/mul"}
  %copy.2 = f32[4]{0} copy(%fusion.1)
  ROOT %dot.3 = f32[4]{0} multiply(%copy.2, %w), metadata={op_name="jit(step)/optimizer/mul"}
}
"""


def test_a_fusion_goes_by_the_scope_that_holds_most_of_its_operations():
    table = scopes.op_scopes_from_hlo(HLO)
    # two of three under net/ffn, all three under forward/net
    assert table["fusion"] == "forward/net/ffn"
    # one optimizer, one backward: no scope holds most
    assert table["fusion.1"] == scopes.MIXED
    assert table["dot.3"] == "optimizer"
    # a fusion that holds a product goes by the product, whatever cheap
    # operations of the forward pass were pulled in beside it
    assert table["fusion.9"] == "backward/net/ffn"
    # and what else such a fusion holds is kept beside the path
    assert table.shared == {"fusion.1": {"optimizer": 1, "backward": 1},
                            "fusion.9": {"forward": 3, "backward": 1}}
    assert "copy.2" not in table and "w" not in table


def test_scope_seconds_rolls_own_time_up_by_depth():
    rows = [profiling.OpRow("/device:TPU:0", "XLA Ops", n, s, d) for n, s, d in (
        ("%fusion = f32[4]{0} fusion(f32[4]{0} %w), kind=kLoop", 0.0, 4e9),
        ("%fusion.1 = f32[4]{0} fusion(%fusion)", 4e9, 2e9),
        ("%copy.2 = f32[4]{0} copy(%fusion.1)", 6e9, 1e9),
        ("%dot.3 = f32[4]{0} multiply(%copy.2, %w)", 7e9, 3e9))]
    report = profiling.MeasuredReport(op_rows=rows, spans=[])
    table = scopes.op_scopes_from_hlo(HLO)
    assert report.scope_seconds(table) == {
        "forward": 4.0, "mixed": 2.0, "unscoped": 1.0, "optimizer": 3.0}
    assert report.scope_seconds(table, depth=None)["forward/net/ffn"] == 4.0
    assert report.scope_seconds(table, depth=2)["forward/net"] == 4.0


def test_a_traced_step_rolls_up_under_its_scopes(tmp_path):
    ts = _step()
    ts(*_batch())
    cap = ts.profile(*_batch(), steps=2, warmup=0, trace_dir=str(tmp_path))
    by_scope = cap.report.scope_seconds(ts.op_scopes(*_batch()))
    assert by_scope and set(by_scope) - {"unscoped", "mixed"}
    assert set(by_scope) <= (set(scopes.SCOPES)
                             | {"backward", "unscoped", "mixed"})


# -- telemetry on does not stop the pipeline ---------------------------------
def test_with_telemetry_on_no_call_reads_the_newest_step(tmp_path, monkeypatch):
    obs.enable(str(tmp_path))
    try:
        ts = _step(optimizer="adam")
        steps_c = obs.counter("train_steps_total")
        before = steps_c.value(loop="train_step")
        read = []
        real = jax.device_get

        def spy(tree):
            read.append(tree)
            return real(tree)

        monkeypatch.setattr(jax, "device_get", spy)
        n, lag = 13, ts_mod.TELEMETRY_LAG
        newest = []
        for i in range(n):
            loss = ts(*_batch())
            newest.append(loss)
            published = steps_c.value(loop="train_step") - before
            # the registry lags by exactly the fixed lag, never less
            assert published == max(0, i + 1 - lag)
            assert len(ts._held) == min(i + 1, lag)
            # and what was read is never the step just dispatched
            assert all(loss is not leaf for tree in read
                       for leaf in jax.tree_util.tree_leaves(tree))
        monkeypatch.undo()
        obs.flush()
        assert steps_c.value(loop="train_step") - before == n
        assert not ts._held
        assert obs.gauge("train_grad_norm").value() is not None
    finally:
        obs.shutdown()
        obs.disable()
    recs = [e for e in obs.read_events(str(tmp_path))
            if e["event"] == "train_step"]
    # every step published once, under its own number, in order
    assert [e["step"] for e in recs] == list(range(1, n + 1))
    assert all(e["step_seconds"] > 0 and e["loss"] is not None for e in recs)
    want = [float(x) for x in newest]
    assert [e["loss"] for e in recs] == pytest.approx(want)


def test_shutdown_publishes_what_is_held(tmp_path):
    obs.enable(str(tmp_path))
    try:
        ts = _step()
        for _ in range(3):
            ts(*_batch())
        assert len(ts._held) == 3
    finally:
        obs.shutdown()
        obs.disable()
    assert not ts._held
    recs = [e for e in obs.read_events(str(tmp_path))
            if e["event"] == "train_step"]
    assert [e["step"] for e in recs] == [1, 2, 3]
    # the seconds of steps seen done at one look are shared equally, and
    # add up to the time from the first submit to the look
    assert len({e["step_seconds"] for e in recs}) == 1
