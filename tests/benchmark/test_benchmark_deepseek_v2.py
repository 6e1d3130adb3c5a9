"""The DeepSeek-V2 serving cell at a toy size on the CPU, through the
harness's own ``main``: ``correct`` comes out true for what the engine
served and false for a token altered, the cell's readers return numbers
(the two that read the program's own records among them), the bytes a
decode step must read follow the shapes, and the entries this cell added to
``BENCHMARK.json`` keep to the contract's form."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, serve
from benchmark.reference import deepseek_v2 as ref
from benchmark.weights import make_weights

import benchmark_tiny
from benchmark_tiny import REPO, run_cell

CELL, TINY = "deepseek_v2_serve_reason", "tiny_reason"
NEW_READERS = ("moe_load_imbalance.serve", "decode_ahead_pct.serve")


def tiny_deepseek():
    cfg = benchmark_tiny.load("benchmark/configs/deepseek_v2.json")
    cfg.update(name="deepseek_v2_tiny", hidden_size=64, num_attention_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               q_lora_rank=24, kv_lora_rank=32, intermediate_size=96,
               moe_intermediate_size=24, n_routed_experts=16, n_group=4,
               topk_group=2, num_experts_per_tok=3, n_layer=3, n_vocab=300,
               held_experts=[0, 1, 5, 9], n_routed_experts_held=4)
    cfg["precision"]["weights"] = "float32"
    cfg["engine"].update(batch_size=4, page_size=8, num_pages=48,
                         max_length=128, cache_dtype="float32")
    # float32 on the CPU: the engine and the reference differ by rounding of
    # the last place only; a wrong token lies a logit's spread (~0.1) away
    cfg["check"] = {"widest_gap": 1e-3, "mean_gap": 1e-4}
    return cfg


def tiny_reason_mix():
    mix = benchmark_tiny.load("benchmark/traffic/reason_saturate.json")
    mix.update(rate_per_s=16.0, lead_in_s=0.5, tail_s=0.2, trace_s=0.4,
               check_requests=4,
               prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.6,
                           "min": 4, "max": 40},
               answer_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                           "min": 3, "max": 14})
    return mix


def make_root(tmp):
    """``benchmark_tiny``'s tree plus this cell on its toy configuration,
    reporting whatever the real cell reports in ``BENCHMARK.json``."""
    root = benchmark_tiny.make_root(tmp)
    real = harness.load_benchmark(REPO)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    entry = dict(next(c for c in real["configs"] if c["name"] == "deepseek_v2"),
                 name="deepseek_v2_tiny",
                 file="benchmark/configs/deepseek_v2_tiny.json")
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != "deepseek_v2"] + [entry]
    bench["workloads"].append(dict(harness.find_cell(real, CELL), name=TINY,
                                   config="deepseek_v2_tiny", traffic=TINY))
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in real[group] if CELL in m.get("workloads", ())}
        for m in bench[group]:
            if m["name"] in mine:
                m["workloads"].append(TINY)
    for rel, data in (("benchmark/configs/deepseek_v2_tiny.json", tiny_deepseek()),
                      (f"benchmark/traffic/{TINY}.json", tiny_reason_mix()),
                      ("BENCHMARK.json", bench)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_deepseek"))


@pytest.fixture(scope="module")
def traced(root):
    run, stdout = run_cell(root, TINY, seconds=1.5, trace=1)
    return run, json.loads(stdout.strip().splitlines()[-1]), stdout


def test_a_tiny_run_of_the_cell_is_correct(traced):
    run, line, stdout = traced
    assert run["correct"] is True and line["correct"] is True, stdout
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["widest_gap"]["ok"] and line["compared"]["mean_gap"]["ok"]
    assert line["compared"]["window_compiles"]["value"] == 0
    assert "read path xla_gather_latent" in stdout
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_traced_run_reports_the_serving_readers_and_the_two_new_ones(traced):
    run, line, _ = traced
    bench = harness.load_benchmark(REPO)
    want = {m["name"] for m in harness.metrics_of(
        bench, harness.find_cell(bench, CELL), "per_layer")}
    assert len(want) == 13 + len(NEW_READERS) and set(NEW_READERS) <= want
    # the CPU keeps no memory peak, and its trace may hold no decode module
    assert want - set(line["metrics"]) <= {"decode_hbm_roofline_pct.serve",
                                           "hbm_peak_gb.serve"}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 1.0 <= got["moe_load_imbalance.serve"] <= 4.0
    # the toy engine outruns its arrivals, so its slots are seldom all full
    assert 0.0 <= got["decode_ahead_pct.serve"] < 100.0
    assert line["metrics"]["decode_ahead_pct.serve"]["unit"] == "%"


def test_the_share_of_steps_dispatched_ahead_is_read_from_the_step_records():
    """Four slots full of answers of 12 tokens (one from the prefill, eleven
    decode steps): every step but the one on which the requests end
    dispatches the next ahead, and the reader counts them by their mark."""
    import time

    from benchmark.systems import deepseek_v2 as adaptor

    cfg = tiny_deepseek()
    engine, batcher = adaptor.build_serve(
        cfg, make_weights(ref.param_specs(cfg), 5))
    t0 = time.perf_counter()
    for i in range(4):
        batcher.submit([7 + i, 9, 11], max_new_tokens=12)
    batcher.run_until_idle()
    run = {"kind": "serve", "config": cfg, "window": (t0, time.perf_counter())}
    share = harness.load_reader("decode_ahead_pct.serve", REPO).read(run)
    assert share == pytest.approx(100.0 * 10 / 11)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_where_the_program_keeps_no_record(name):
    """The parent of this change has neither the decode step record nor the
    gauge: the reader then returns None and raises nothing."""
    reader = harness.load_reader(name, REPO)
    cfg = benchmark_tiny.load("benchmark/configs/deepseek_v2.json")
    empty = {"kind": "serve", "config": cfg, "window": (-2.0, -1.0)}
    assert reader.read(empty) is None
    assert reader.read(dict(empty, kind="train")) is None


def test_a_token_altered_is_not_correct(traced):
    """The comparison that decides ``correct``, on the run's own sample: the
    served tokens pass both limits; one token swapped for another fails."""
    run, _, _ = traced
    cfg, mix = run["config"], run["mix"]
    weights = make_weights(ref.param_specs(cfg), 4294967301)
    gaps, n = serve.logit_gaps(ref, weights, cfg, run["sample"],
                               serve.check_shape(mix))
    assert n >= 4 and all(gaps[k] <= cfg["check"][k] for k in cfg["check"])
    prompt, output = run["sample"][0]
    wrong = list(output)
    wrong[1] = (wrong[1] + 7) % cfg["n_vocab"]
    bad, _ = serve.logit_gaps(ref, weights, cfg, [(prompt, wrong)],
                              serve.check_shape(mix))
    assert bad["widest_gap"] > 10 * cfg["check"]["widest_gap"]
    assert bad["mean_gap"] > cfg["check"]["mean_gap"]


@pytest.mark.parametrize("precision,moves", [("fp8", True), ("kv8", True),
                                             ("float32", False)])
def test_the_controls_move_the_logits_and_float32_does_not(precision, moves):
    cfg = tiny_deepseek()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 30).tolist()
    want = ref.next_token_logits(weights, cfg, tokens, 5, 20, pad_to=8, out_pad=8)
    got = ref.next_token_logits(weights, cfg, tokens, 5, 20, precision=precision,
                                pad_to=8, out_pad=8)
    assert bool(np.abs(got - want).max() > 1e-3) is moves


def test_decode_step_bytes_follow_the_shapes():
    cfg = benchmark_tiny.load("benchmark/configs/deepseek_v2.json")
    specs = {name: shape for name, shape, _ in ref.param_specs(cfg)}
    assert specs["layer1.experts.gate.w"] == (8, 1536, 5120)
    assert specs["layer1.router.w"] == (160, 5120)       # the published width
    assert specs["layer0.kv_a.w"] == (576, 5120) and "layer0.router.w" not in specs
    total = sum(int(np.prod(s)) for s in specs.values())
    assert round(total / 1e6, 1) == 2013.0
    embed = 12800 * 5120
    assert ref.decode_step_bytes(cfg, 0) == 2 * (total - embed)
    assert ref.latent_bytes_per_token(cfg) == 5760
    assert ref.decode_step_bytes(cfg, 1000) - ref.decode_step_bytes(cfg, 0) \
        == 5_760_000
    # a share of other experts has the same bytes; twice the experts has more
    more = dict(cfg, held_experts=list(range(16)))
    assert ref.decode_step_bytes(more, 0) - ref.decode_step_bytes(cfg, 0) \
        == 2 * 4 * 8 * 3 * 5120 * 1536


def test_the_configuration_holds_every_published_number_and_states_the_cut():
    cfg = benchmark_tiny.load("benchmark/configs/deepseek_v2.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert row["source_url"] in cfg["source"]
    assert cfg["reduced"] == ["n_layer", "n_routed_experts_held", "n_vocab"]
    assert (cfg["n_layer"], cfg["n_routed_experts_held"], cfg["n_vocab"]) == \
        (5, 8, 12800)
    # the model-configs guide's floors: four expert layers after the dense
    # one, eight routed experts a layer, an eighth of the vocabulary
    assert cfg["n_layer"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["held_experts"] == list(range(cfg["n_routed_experts_held"]))
    assert cfg["n_vocab"] * 8 >= cfg["vocab_size"]
    assert "20 chips" in cfg["deployment"] and cfg["assumed"]
    # each limit is written with the reason for it, beside it
    assert set(cfg["check"]) == {"widest_gap", "mean_gap"} <= set(cfg["check_why"])
    assert 0 < cfg["check"]["mean_gap"] < cfg["check"]["widest_gap"] / 100


def test_the_cell_and_its_entries_keep_to_the_contracts_form():
    bench = harness.load_benchmark(REPO)
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("deepseek_v2", "reason_saturate", 1)
    mix = harness.load_mix(cell, REPO)
    assert mix["rate_per_s"] == pytest.approx(1.5 * mix["knee_per_s"], rel=0.02)
    assert (mix["prompt_len"]["median"], mix["answer_len"]["median"]) == (256, 512)
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] < \
        harness.load_config(bench, cell, REPO)["engine"]["max_length"]
    e2e = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    # entries are appended, never put before what was there
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "deepseek_v2"
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW_READERS)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            assert m["workloads"][-1] == CELL
