"""The dots3-note-prev serving cell at a toy size on the CPU, through the
harness's own ``main``: ``correct`` comes out true for what the engine
served through both pool groups and false for a token altered, the cell's
readers return numbers (the three new ones among them), the two controls of
the mathematics move the logits, the bytes a decode step must read follow
the shapes, and the entries this cell added to ``BENCHMARK.json`` keep to
the contract's form."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, serve
from benchmark.reference import dots3_note as ref
from benchmark.weights import make_weights

import benchmark_tiny
from benchmark_tiny import REPO, run_cell

CELL, TINY = "dots3_note_serve_longctx", "tiny_longctx"
NEW_READERS = ("index_scores_roofline_pct.serve", "dsa_selected_share_pct.serve",
               "window_pages_held_pct.serve")


def tiny_dots3():
    cfg = benchmark_tiny.load("benchmark/configs/dots3_note.json")
    cfg.update(name="dots3_note_tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=24, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, swa_num_attention_heads=2, swa_q_lora_rank=24,
               swa_kv_lora_rank=40, swa_qk_nope_head_dim=24,
               swa_qk_rope_head_dim=8, swa_v_head_dim=16, sliding_window_size=5,
               index_n_heads=4, index_head_dim=16, index_topk=8,
               n_routed_experts=16, num_experts_per_tok=3, n_layer=4,
               n_vocab=300, held_experts=[0, 1, 5, 9], n_routed_experts_held=4,
               layer_types=["full_attention", "full_attention",
                            "sliding_attention", "sliding_attention"])
    cfg["precision"]["weights"] = "float32"
    cfg["engine"].update(batch_size=4, page_size=2, max_length=64,
                         num_pages={"all": 128, "window": 24},
                         cache_dtype="float32", prefill_buckets=[8, 16, 32])
    # float32 on the CPU: the engine and the reference differ by rounding of
    # the last place only; a wrong token lies a logit's spread (~0.1) away
    cfg["check"] = {"widest_gap": 1e-3, "mean_gap": 1e-4}
    return cfg


def tiny_longctx_mix():
    mix = benchmark_tiny.load("benchmark/traffic/longctx_mixed_saturate.json")
    mix.update(rate_per_s=16.0, lead_in_s=0.5, tail_s=0.2, trace_s=0.4,
               check_requests=4,
               prompt_len={"dist": "lognormal", "median": 14, "sigma": 0.6,
                           "min": 6, "max": 32},
               answer_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 4, "max": 16})
    return mix


def make_root(tmp):
    """``benchmark_tiny``'s tree plus this cell on its toy configuration,
    reporting whatever the real cell reports in ``BENCHMARK.json``."""
    root = benchmark_tiny.make_root(tmp)
    real = harness.load_benchmark(REPO)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    entry = dict(next(c for c in real["configs"] if c["name"] == "dots3_note"),
                 name="dots3_note_tiny",
                 file="benchmark/configs/dots3_note_tiny.json")
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != "dots3_note"] + [entry]
    bench["workloads"].append(dict(harness.find_cell(real, CELL), name=TINY,
                                   config="dots3_note_tiny", traffic=TINY))
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in real[group] if CELL in m.get("workloads", ())}
        for m in bench[group]:
            if m["name"] in mine:
                m["workloads"].append(TINY)
    for rel, data in (("benchmark/configs/dots3_note_tiny.json", tiny_dots3()),
                      (f"benchmark/traffic/{TINY}.json", tiny_longctx_mix()),
                      ("BENCHMARK.json", bench)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_dots3"))


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
    assert "xla_gather_rows" in stdout and "xla_gather_ring" in stdout
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_traced_run_reports_the_serving_readers_and_the_new_ones(traced):
    run, line, _ = traced
    bench = harness.load_benchmark(REPO)
    want = {m["name"] for m in harness.metrics_of(
        bench, harness.find_cell(bench, CELL), "per_layer")}
    assert len(want) == 15 + len(NEW_READERS) and set(NEW_READERS) <= want
    # the CPU keeps no memory peak, and its trace holds no decode module and
    # none of the operations the configuration names
    assert want - set(line["metrics"]) <= {
        "decode_hbm_roofline_pct.serve", "hbm_peak_gb.serve",
        "index_scores_roofline_pct.serve"}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # rows of 6 to 48 positions read 8 at most: well under all they hold
    assert 10.0 < got["dsa_selected_share_pct.serve"] < 100.0
    # four rows hold three or four window pages each of a pool of 24
    assert 0.0 < got["window_pages_held_pct.serve"] <= 100.0 * 16 / 24
    assert 1.0 <= got["moe_load_imbalance.serve"] <= 4.0
    assert 0.0 < got["kv_pages_held_pct.serve"] < 100.0    # the all group


def test_the_index_scores_roofline_share_follows_the_named_operations(traced):
    """The reader on a trace that holds the operations the configuration
    names: the held positions' index keys of the traced steps over the peak
    bandwidth, as a share of those operations' device time, and of no
    other's (the selection's ``sort`` stands beside it in the trace)."""
    run, _, _ = traced
    reader = harness.load_reader("index_scores_roofline_pct.serve", REPO)
    cfg = dict(run["config"], trace_names={"decode_module": "decode",
                                           "index_scores": ["index_scores"]})
    steps = [{"t1": 1.0, "decoded_rows": 4, "held_positions": 80}]
    made = dict(run, config=cfg, steps=steps, trace_span=(0.0, 2.0),
                peaks={"hbm_bytes_per_s": 1e9},
                trace={"ops": {"sort": 2e-3, "index_scores": 3e-3, "fusion": 1.0},
                       "modules": {"jit_paged_decode_fn": (10, 0.5)}})
    need = 10 * ref.index_score_bytes(cfg, 80)
    assert need == 10 * 2 * 80 * cfg["index_head_dim"] * 2  # 2 full layers
    assert reader.read(made) == pytest.approx(100.0 * need / 1e9 / 3e-3)
    assert reader.read(dict(made, trace=dict(made["trace"], ops={}))) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_where_the_program_lacks_the_mechanism(name):
    """The parent of this change has neither the counts nor the operations,
    and the older serving configurations name none: the reader then returns
    None and raises nothing."""
    reader = harness.load_reader(name, REPO)
    for path in ("benchmark/configs/deepseek_v2.json",
                 "benchmark/configs/gpt2_345m.json",
                 "benchmark/configs/dots3_note.json"):
        cfg = benchmark_tiny.load(path)
        empty = {"kind": "serve", "config": cfg, "window": (-2.0, -1.0),
                 "trace": {"ops": {"fusion": 1.0}, "modules": {}}, "steps": []}
        assert reader.read(empty) is None
        assert reader.read(dict(empty, kind="train")) is None


def test_a_token_altered_is_not_correct(traced):
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


@pytest.mark.parametrize("precision,moves", [
    ("no_selection", True), ("window_512", True), ("fp8", True),
    ("bfloat16", True), ("float32", False)])
def test_the_controls_move_the_logits_and_float32_does_not(precision, moves):
    cfg = tiny_dots3()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 30).tolist()
    want = ref.next_token_logits(weights, cfg, tokens, 5, 20, pad_to=8, out_pad=8)
    got = ref.next_token_logits(weights, cfg, tokens, 5, 20, precision=precision,
                                pad_to=8, out_pad=8)
    assert bool(np.abs(got - want).max() > 1e-3) is moves


def test_a_bfloat16_indexer_moves_the_logits_beyond_bfloat16_products():
    """``bfloat16_index`` is ``bfloat16`` with the indexer's operands rounded
    too: where the selection binds, the two differ (near-ties at rank
    ``index_topk`` fall the other way); while every position is selected
    they are the same forward."""
    cfg = tiny_dots3()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 40).tolist()

    def logits(precision, first, count):
        return ref.next_token_logits(weights, cfg, tokens, first, count,
                                     precision=precision, pad_to=8, out_pad=8)

    short = cfg["index_topk"] - 2  # every position still selected
    assert np.array_equal(logits("bfloat16_index", 0, short)[:short - 1],
                          logits("bfloat16", 0, short)[:short - 1])
    assert np.abs(logits("bfloat16_index", 8, 32)
                  - logits("bfloat16", 8, 32)).max() > 1e-4


def test_the_controls_of_the_mathematics_fail_the_tiny_cells_limits(traced):
    """What ``benchmark.control`` computes on the chip, here on the toy
    cell's own sample: each control's tokens lie past a limit of ``check``."""
    run, _, _ = traced
    for control in ("no_selection", "window_512"):
        gaps = serve.control(run, run["config"], run["mix"], 4294967301, None,
                             control)
        assert any(gaps[k] > run["config"]["check"][k] for k in gaps), control


def test_the_bytes_of_a_decode_step_follow_the_shapes():
    cfg = benchmark_tiny.load("benchmark/configs/dots3_note.json")
    specs = {name: shape for name, shape, _ in ref.param_specs(cfg)}
    assert specs["layer1.experts.gate.w"] == (8, 1536, 5120)
    assert specs["layer1.router.w"] == (256, 5120)       # the published width
    assert specs["layer1.router.bias"] == (256,)
    assert specs["layer0.kv_a.w"] == (576, 5120)
    assert specs["layer2.kv_a.w"] == (1088, 5120)        # a window layer's own
    assert specs["layer0.index.q_b.w"] == (64 * 128, 1024)
    assert "layer2.index.k.w" not in specs and "layer0.router.w" not in specs
    assert specs["layer0.attn_gate.w"] == (128, 5120)
    assert specs["layer2.attn_gate.w"] == (64, 5120)
    total = sum(int(np.prod(s)) for s in specs.values())
    assert round(total / 1e6, 1) == 1822.2
    embed = 19008 * 5120
    assert ref.decode_step_bytes(cfg, 0) == 2 * (total - embed)
    rows = cfg["engine"]["batch_size"]
    # rows of 100 positions: everything held is read, in both kinds of layer
    short = ref.decode_step_bytes(cfg, 100 * rows) - ref.decode_step_bytes(cfg, 0)
    assert short == rows * 100 * (2 * (256 + 1152) + 3 * 2176)
    assert ref.sparse_read_bytes(cfg, 100 * rows) == rows * 100 * 2 * 1152
    assert ref.index_score_bytes(cfg, 100 * rows) == rows * 100 * 2 * 256
    # rows of 10,000: the indexer's keys of all of them, 2,048 latents, and
    # a window's 513
    long = ref.decode_step_bytes(cfg, 10000 * rows) - ref.decode_step_bytes(cfg, 0)
    assert long == rows * (2 * (10000 * 256 + 2048 * 1152) + 3 * 513 * 2176)
    assert ref.sparse_read_bytes(cfg, 10000 * rows) == rows * 2 * 2048 * 1152
    assert ref.sparse_read_bytes(cfg, 10000 * 7, rows=7) == 7 * 2 * 2048 * 1152


def test_the_configuration_holds_every_published_number_and_states_the_cut():
    cfg = benchmark_tiny.load("benchmark/configs/dots3_note.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert row["source_url"] in cfg["source"]
    assert cfg["reduced"] == ["n_layer", "n_routed_experts_held", "n_vocab"]
    assert (cfg["n_layer"], cfg["n_routed_experts_held"], cfg["n_vocab"]) == \
        (5, 8, 19008)
    # the model-configs guide's floors: four expert layers after the dense
    # one, a whole period of full and window layers among them, eight routed
    # experts a layer, an eighth of the vocabulary
    assert cfg["n_layer"] - cfg["first_k_dense_replace"] >= 4
    kinds = cfg["layer_types"][:cfg["n_layer"]]
    assert kinds.count("full_attention") == 2 and kinds.count("sliding_attention") == 3
    assert cfg["held_experts"] == list(range(cfg["n_routed_experts_held"]))
    assert cfg["n_vocab"] * 8 >= cfg["vocab_size"]
    assert "32 chips" in cfg["deployment"]
    assert {"apply_mla_qkv_lora_rescale", "sliding_window_size"} <= set(cfg["assumed"])
    assert {"towers", "mtp", "hadamard", "indexer_8bit"} == set(cfg["not_run"])
    # window_512 cannot fail a limit on the chip (check_why says so): it is
    # asked for by name, and the toy cell's limits hold it (above)
    assert cfg["precision"]["control"].split(",") == ["no_selection", "fp8"]
    # each limit is written with the reason for it, beside it
    assert set(cfg["check"]) == {"widest_gap", "mean_gap"} <= set(cfg["check_why"])
    assert 0 < cfg["check"]["mean_gap"] < cfg["check"]["widest_gap"] / 10
    # what the chip's readings said of the two controls no limit can fail
    assert {"window_512", "bfloat16_index"} <= set(cfg["check_why"])
    engine = cfg["engine"]
    assert engine["num_pages"] == {"all": 36864, "window": 2048}
    assert engine["num_pages"]["window"] >= engine["batch_size"] * (513 // 16 + 3)


def test_the_cell_and_its_entries_keep_to_the_contracts_form():
    bench = harness.load_benchmark(REPO)
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("dots3_note", "longctx_mixed_saturate", 1)
    assert len(cell["why"]) <= 200
    mix = harness.load_mix(cell, REPO)
    # ISSUE 31's 1.5 times the knee, the knee by benchmark.sweep on the
    # finished change (PERF.md, Findings, PR 31)
    assert mix["rate_per_s"] == 1.5 * mix["knee_per_s"] == 1.5
    assert "window: CPU tests" in cell["why"]
    assert (mix["prompt_len"]["median"], mix["answer_len"]["median"]) == (4096, 1024)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (1024, 16384)
    assert (mix["answer_len"]["min"], mix["answer_len"]["max"]) == (256, 4096)
    assert (mix["lead_in_s"], mix["check_requests"], mix["drain"]) == (30.0, 6, False)
    engine = harness.load_config(bench, cell, REPO)["engine"]
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= engine["max_length"]
    assert mix["prompt_len"]["max"] <= max(engine["prefill_buckets"])
    e2e = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    # entries are appended, never put before what was there: this cell came
    # fourth, its configuration fourth, its three readers after the
    # twenty-five. Pinned by place from the front, so that a later cell
    # appended behind them leaves this test green (a pin on the LAST entry
    # goes red with the next cell, and the file is then no PR's to repair
    # but a `benchmark` issue's)
    assert bench["workloads"][3]["name"] == CELL
    assert bench["configs"][3]["name"] == "dots3_note"
    assert len(bench["configs"][3]["source"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert at == 25 and names[at:at + 3] == list(NEW_READERS)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            before = m["workloads"][:m["workloads"].index(CELL)]
            assert before in ([], ["deepseek_v2_serve_reason"],
                              ["gpt2_345m_serve_saturate",
                               "deepseek_v2_serve_reason"])
    for m in bench["per_layer"][at:at + 3]:
        assert m["workloads"][0] == CELL and m["unit"] == "%"
