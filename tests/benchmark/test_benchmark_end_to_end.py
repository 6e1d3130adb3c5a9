"""A tiny run of each runner on the CPU, through the harness's own ``main``
(the test-only entry of ``benchmark_tiny``): the last line's keys, the
metrics each cell reports, and ``correct`` coming out false when the timed
path is broken underneath."""
import json

import numpy as np
import pytest

from benchmark import harness

from benchmark_tiny import CELLS, make_root, run_cell

LAST_LINE = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_benchmark"))


@pytest.fixture(scope="module")
def traced(root):
    """One traced run of each tiny cell: {cell: (run, last line)}."""
    out = {}
    for cell in CELLS.values():
        run, stdout = run_cell(root, cell, trace=1)
        out[cell] = (run, json.loads(stdout.strip().splitlines()[-1]), stdout)
    return out


@pytest.mark.parametrize("cell", CELLS.values())
def test_a_tiny_run_is_correct_and_prints_the_contracts_last_line(traced, cell):
    run, line, stdout = traced[cell]
    assert run["correct"] is True and line["correct"] is True, stdout
    assert set(line) == LAST_LINE | {"breakdown"}
    assert set(line["device"]) == DEVICE | {"busy_s", "window_s"}
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert line["device"]["count"] == (4 if cell.endswith("zero4") else 1)
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert all(isinstance(v["value"], float) and v["unit"]
               for v in line["metrics"].values())
    # every number compared is printed beside its limit, and is the result
    # line's last key: short plain names, each with its number and its limit
    assert stdout.count("[benchmark] check ") == len(run["check"]) >= 4
    assert list(line)[-1] == "compared" and len(line["compared"]) == len(run["check"])
    assert all(set(v) == {"value", "limit", "ok"} and v["ok"] and " " not in k
               for k, v in line["compared"].items())
    assert ("widest_gap" if run["kind"] == "serve" else "loss_rel.first") in \
        line["compared"]
    assert "setup by phase" in stdout


@pytest.mark.parametrize("cell", CELLS.values())
def test_each_cell_reports_its_metrics_and_no_others(traced, root, cell):
    run, line, _ = traced[cell]
    bench = harness.load_benchmark(root)
    entry = harness.find_cell(bench, cell)
    declared = {m["name"] for m in harness.metrics_of(bench, entry, "per_layer")}
    # what the CPU has nothing to read for: the decode program's device
    # time (a TPU trace's module line), the packed attention kernels (the
    # CPU takes the einsum path) and the device's peak memory
    assert declared - set(line["metrics"]) <= {
        "decode_hbm_roofline_pct.serve", "hbm_peak_gb.serve", "hbm_peak_gb.train",
        "packed_attention_roofline_pct.train"}
    assert set(line["metrics"]) <= declared
    # the same run's end-to-end line, as --trace 0 prints it
    devices = [type("D", (), {"platform": "cpu", "device_kind": "cpu"})()]
    e2e = json.loads(harness.result_line(bench, entry, run, False, devices, root))
    assert set(e2e) == LAST_LINE and set(e2e["device"]) == DEVICE
    want = {m["name"] for m in harness.metrics_of(bench, entry, "end_to_end")}
    assert set(e2e["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(v["value"] > 0 for v in e2e["metrics"].values())


def test_the_serving_window_counts_what_ended_and_what_was_served_in_it(traced):
    run, line, _ = traced["tiny_serve"]
    lo, hi = run["window"]
    ended = [r for r in run["requests"] if lo <= r.get("end_t", -1) < hi]
    assert line["attempted"] == len(ended) > 10 and line["failed"] == 0
    assert all(r["req"].finish_reason == "length"
               and len(r["req"].output) == r["max_new_tokens"] for r in ended)
    due = [r for r in run["requests"] if r["in_window"]]
    assert len(due) == 20  # rate x seconds, for every seed
    assert all(r["handoff_t"] >= run["origin"] + r["due"] for r in due)
    assert all(r["token_times"] == sorted(r["token_times"]) for r in ended)
    served = sum(lo <= t < hi for r in run["requests"]
                 for t in r.get("token_times", ()))
    # the judged rate is every token served in the window over its length
    assert run["end_to_end"]["serve_tokens_per_s"] == pytest.approx(
        served / run["window_s"])
    # and the window holds whole steps: it opens where the lead-in's last
    # step ended and closes where the step under way at --seconds ended
    assert run["window_s"] == hi - lo
    opens = run["origin"] + run["mix"]["lead_in_s"]
    assert opens <= lo < opens + 0.5 and opens + 1.0 <= hi < opens + 1.5
    assert [s for s in run["steps"] if lo <= s["t1"] < hi]
    assert not any(s["t0"] < edge <= s["t1"] for s in run["steps"]
                   for edge in (lo, hi))
    assert line["metrics"]["window_compiles.serve"]["value"] == 0.0
    assert run["end_to_end"]["setup_s"] > run["mix"]["lead_in_s"]
    assert len(run["sample"]) == run["mix"]["check_requests"]
    longest = max(len(r["prompt"]) + r["max_new_tokens"] for r in ended)
    assert len(run["sample"][0][0]) + len(run["sample"][0][1]) == longest


def test_a_mix_that_drains_counts_the_requests_due_in_the_window(root):
    import json
    import os

    path = os.path.join(root, "benchmark", "traffic", "tiny_serve.json")
    with open(path) as f:
        mix = json.load(f)
    try:
        with open(path, "w") as f:
            json.dump(dict(mix, drain=True), f)
        run, _ = run_cell(root, "tiny_serve", seconds=1.0)
    finally:
        with open(path, "w") as f:
            json.dump(mix, f)
    due = [r for r in run["requests"] if r["in_window"]]
    assert run["correct"] and run["attempted"] == len(due) == 20
    assert all(r["req"].done for r in due)  # served past the window's end


def test_the_training_window_runs_the_object_that_setup_checked(traced):
    run, _, _ = traced["tiny_train"]
    assert run["attempted"] == run["steps"] > 10
    got, want = run["readings"]["program"], run["readings"]["reference"]
    assert len(got["loss"]) == len(want["loss"]) == 3
    assert set(got["grad_norm"]) == set(want["grad_norm"]) == set(got["change_norm"])
    assert all(v > 0 for v in want["change_norm"].values())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    import jax

    from benchmark.systems import bert as system

    real = system.build_train

    class Frozen:
        """The real step, except that it hands back the state it was given."""

        def __init__(self, ts):
            self.ts, self.optimizer = ts, ts.optimizer
            self.params, self.opt_state = ts.params, ts.opt_state

        def __call__(self, *batch):
            keep = jax.tree_util.tree_map(jax.numpy.copy,
                                          (self.ts.params, self.ts.opt_state,
                                           self.ts.step_count))
            loss = self.ts(*batch)
            self.ts.params, self.ts.opt_state, self.ts.step_count = keep
            self.params, self.opt_state = keep[0], keep[1]
            return loss

    def broken(config, mix, weights):
        ts, names = real(config, mix, weights)
        return Frozen(ts), names

    monkeypatch.setattr(system, "build_train", broken)
    run, stdout = run_cell(root, "tiny_train", seconds=0.3)
    assert run["correct"] is False
    failed = [r["name"] for r in run["check"] if not r["ok"]]
    assert any(n.startswith("change_norm_rel") for n in failed), stdout
    assert json.loads(stdout.strip().splitlines()[-1])["correct"] is False


def test_a_batch_with_rows_left_out_is_not_correct(root, monkeypatch):
    from benchmark.systems import bert as system

    real = system.build_train

    def broken(config, mix, weights):
        ts, names = real(config, mix, weights)

        class HalfBatch:
            optimizer = ts.optimizer
            params = property(lambda self: ts.params)
            opt_state = property(lambda self: ts.opt_state)

            def __call__(self, *batch):
                half = batch[0].shape[0] // 2
                return ts(*(np.concatenate([a[:half], a[:half]]) for a in batch))

        return HalfBatch(), names

    monkeypatch.setattr(system, "build_train", broken)
    run, _ = run_cell(root, "tiny_train", seconds=0.3)
    assert run["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from benchmark.systems import gpt2 as system

    real = system.build_serve

    def broken(config, weights):
        engine, batcher = real(config, weights)
        step = engine.decode_step

        def altered():
            tok, done, logits = step()
            return (tok + 1) % config["n_vocab"], done, logits

        engine.decode_step = altered
        return engine, batcher

    monkeypatch.setattr(system, "build_serve", broken)
    run, stdout = run_cell(root, "tiny_serve", seconds=1.0)
    assert run["correct"] is False and run["failed"] == 0
    assert not run["check"][0]["ok"], stdout
    assert json.loads(stdout.strip().splitlines()[-1])["correct"] is False


def test_a_program_compiled_inside_the_window_is_not_correct(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import serve

    real = serve.Server._step
    seen = []

    def compiling(self):
        if not seen and self.steps:  # once, after the lead-in has begun
            late = self.steps[-1]["t1"] - self.gen.origin
            if late > self.gen.requests[0]["due"] + 0.45:
                seen.append(jax.jit(lambda x: x * 3 + len(seen))(jnp.ones(7)))
        return real(self)

    monkeypatch.setattr(serve.Server, "_step", compiling)
    run, stdout = run_cell(root, "tiny_serve", seconds=1.0)
    assert seen and run["window_compiles"] >= 1 and run["correct"] is False
