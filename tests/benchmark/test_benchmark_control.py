"""The control: the reference computed in the precision below the
configuration's, put in the program's place, has to come out as not correct.

For training the separation shows only at the cell's own widths and depth
(at toy sizes fp8 operands and the bfloat16 program read alike), so the test
holds the committed limits against readings taken on the chip at the cell's
own size (``python3 -m benchmark.control --dump 1``, PR 23; three seeds kept
in ``chip_readings/``). For serving the control also runs live, at a size a
test run can hold. PERF.md has the full tables."""
import json
import math
import os

import pytest

from benchmark import control, harness, train
from benchmark.harness import Check

from benchmark_tiny import REPO, cpu_settings, make_root

READINGS = os.path.join(REPO, "tests", "benchmark", "chip_readings")


@pytest.fixture(scope="module")
def bert():
    config = harness.load_json(os.path.join(REPO, "benchmark/configs/bert_large.json"))
    specs = harness.reference_for(config).param_specs(config)
    return config, train.matrices_of(specs)


def rows(config, matrices, got, want):
    check = Check()
    train.compare(check, got, want, config["check"], matrices)
    return {r["name"].split(" ")[0]: r for r in check.rows}, check.correct


@pytest.mark.parametrize("seed", [105, 106, 107])
def test_the_limits_pass_the_program_and_fail_the_fp8_control(bert, seed):
    config, matrices = bert
    with open(os.path.join(READINGS, f"bert_large_train_s128_{seed}.json")) as f:
        r = json.load(f)
    assert len(r["reference"]["grad_norm"]) == 303 and len(matrices) == 103
    got, ok = rows(config, matrices, r["program"], r["reference"])
    assert ok, got
    low, ok = rows(config, matrices, r["fp8"], r["reference"])
    assert not ok
    assert not low["grad_norm_rel.matrices"]["ok"]
    # the number that separates them does so by a wide margin on every seed
    assert low["grad_norm_rel.matrices"]["value"] > \
        5 * got["grad_norm_rel.matrices"]["value"]
    # and the reference in the program's place, unrounded, reads exactly 0
    same, ok = rows(config, matrices, r["reference"], r["reference"])
    assert ok and all(v["value"] == 0 for v in same.values())


def test_each_number_is_held_against_the_fault_it_is_there_to_catch(bert):
    config, matrices = bert
    with open(os.path.join(READINGS, "bert_large_train_s128_105.json")) as f:
        r = json.load(f)
    frozen = dict(r["program"], change_norm={k: 0.0 for k in r["program"]["change_norm"]})
    got, ok = rows(config, matrices, frozen, r["reference"])
    assert not ok and got["change_norm_rel"]["value"] == pytest.approx(1.0)
    shifted = dict(r["program"], loss=[x * 1.02 for x in r["program"]["loss"]])
    got, ok = rows(config, matrices, shifted, r["reference"])
    assert not ok and not got["loss_rel.first"]["ok"]
    # seed 4123456789 on the chip: Adam's first steps sent the program's third
    # loss apart from the reference's (5.6%), the widest sound reading so far
    apart = ([11.237384796142578, 11.19614315032959, 11.922656059265137],
             [11.239174842834473, 11.196002006530762, 11.28818130493164])
    got, ok = rows(config, matrices, dict(r["program"], loss=apart[0]),
                   dict(r["reference"], loss=apart[1]))
    assert ok and got["loss_rel.later"]["value"] == pytest.approx(0.0562, abs=1e-4)
    assert got["loss_rel.first"]["value"] < config["check"]["loss_rel.first"] / 3
    no_bias = dict(r["program"], grad_norm=dict(r["program"]["grad_norm"],
                                                **{"layer3.ffn1.b": 0.0}))
    got, ok = rows(config, matrices, no_bias, r["reference"])
    assert not ok and not got["grad_norm_rel.vectors"]["ok"]


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The tiny tree with wider and deeper models: the smallest at which a
    lower precision shows in the served tokens."""
    root = make_root(tmp_path_factory.mktemp("small_benchmark"))
    path = os.path.join(root, "benchmark", "configs", "gpt2_tiny.json")
    config = harness.load_json(path)
    config.update(n_embd=256, n_head=4, n_layer=4, n_vocab=2000)
    # the CPU program is float32 as the reference is: its gaps are near-ties
    # that two orders of summation settle differently
    config["check"] = {"widest_gap": LIMIT, "mean_gap": LIMIT / 10}
    with open(path, "w") as f:
        json.dump(config, f)
    # which requests END in a two-second window on the CPU goes by the clock,
    # and four short answers may hold no position at which fp8 differs: the
    # requests DUE in it (a mix that drains), and more of them compared
    path = os.path.join(root, "benchmark", "traffic", "tiny_serve.json")
    mix = harness.load_json(path)
    with open(path, "w") as f:
        json.dump(dict(mix, check_requests=24, drain=True), f)
    return root


# between the two readings over seeds 3, 5, 7, 11, 13 at this size: the
# program's largest 0.00047, the fp8 control's smallest 0.060
LIMIT = 0.003


def test_the_serving_control_comes_out_not_correct_at_test_size(small_root):
    with cpu_settings(small_root):
        out = control.main(["--workload", "tiny_serve", "--seeds", "3,5,7",
                            "--seconds", "2", "--control", "fp8,bfloat16,kv8"],
                           platform="cpu", root=small_root)
    for row in out:
        assert row["correct"] and row["program"]["widest_gap"] <= LIMIT / 3
        assert row["control_fp8"]["widest_gap"] > 3 * LIMIT
        for milder in ("bfloat16", "kv8"):  # kv8: the cache alone in 8 bits
            assert 0 <= row["control_" + milder]["widest_gap"] <= \
                row["control_fp8"]["widest_gap"]
        assert row["program"]["mean_gap"] <= LIMIT / 30  # tokens of 24 requests
        assert row["control_fp8"]["mean_gap"] > 3 * LIMIT / 10
    assert max(row["control_kv8"]["widest_gap"] for row in out) > LIMIT


def test_the_training_control_runs_at_test_size(small_root):
    with cpu_settings(small_root):
        out = control.main(["--workload", "tiny_train", "--seeds", "4",
                            "--control", "fp8"], platform="cpu", root=small_root)
    (row,) = out
    assert row["correct"]
    assert set(row["control_fp8"]) == {"loss_rel.first", "loss_rel.later",
                                       "grad_norm_rel.matrices",
                                       "grad_norm_rel.vectors", "change_norm_rel"}
    assert all(math.isfinite(v) and v > 0 for v in row["control_fp8"].values())
