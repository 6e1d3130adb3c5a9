"""The SmallThinker serving cell at a toy size on the CPU, through the
harness's own ``main``: ``correct`` comes out true for what the engine
served through both page groups and false for a token altered, the cell's
readers return numbers (the two new ones among them), every listed control
fails the toy limits that the program passes, the bytes a decode step must
read follow the shapes, and the entries this cell added to
``BENCHMARK.json`` are pinned BY NAME."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, serve
from benchmark.reference import smallthinker as ref
from benchmark.weights import make_weights

import benchmark_tiny
from benchmark_tiny import REPO, run_cell

CELL, CONFIG, MIX = ("smallthinker_21b_serve_mixed", "smallthinker_21b",
                     "mixed_len_saturate")
TINY = "tiny_mixed_len"
NEW_READERS = ("gqa_decode_roofline_pct.serve", "moe_pairs_per_expert.serve")
SERVING_READERS = (
    "gen_lateness_p99_ms", "backlog_growth_per_s", "slot_occupancy_pct.serve",
    "ttft_p90_ms.obs", "decode_step_mean_ms", "itl_p90_ms.obs",
    "prefill_share_pct.serve", "window_compiles.serve",
    "kv_pages_held_pct.serve", "decode_hbm_roofline_pct.serve",
    "custom_call_share_pct.serve", "device_idle_pct.serve", "hbm_peak_gb.serve",
    "moe_load_imbalance.serve", "decode_ahead_pct.serve",
    "window_pages_held_pct.serve")


def tiny_smallthinker():
    cfg = benchmark_tiny.load("benchmark/configs/smallthinker_21b.json")
    cfg.update(name="smallthinker_tiny", hidden_size=64, num_attention_heads=6,
               num_key_value_heads=2, head_dim=16, sliding_window_size=5,
               moe_ffn_hidden_size=24, moe_num_primary_experts=8,
               moe_num_active_primary_experts=3, n_layer=4, n_vocab=300,
               # widths 40 times under the published ones: the scores'
               # spread, and so what a position or a window moves, is the
               # published widths' at sqrt(40) times their 0.02
               initializer_range=0.1,
               held_experts=list(range(8)), n_routed_experts_held=8)
    cfg["precision"]["weights"] = "float32"
    cfg["engine"].update(batch_size=4, page_size=4, max_length=64,
                         num_pages={"all": 64, "window": 16},
                         cache_dtype="float32", prefill_buckets=[8, 16, 32])
    # float32 on the CPU: the engine and the reference differ by rounding of
    # the last place only; a wrong token lies a logit's spread (~0.1) away
    cfg["check"] = {"widest_gap": 1e-3, "mean_gap": 1e-4}
    return cfg


def tiny_mix():
    mix = benchmark_tiny.load(f"benchmark/traffic/{MIX}.json")
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
    entry = dict(next(c for c in real["configs"] if c["name"] == CONFIG),
                 name="smallthinker_tiny",
                 file="benchmark/configs/smallthinker_tiny.json")
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != CONFIG] + [entry]
    bench["workloads"].append(dict(harness.find_cell(real, CELL), name=TINY,
                                   config="smallthinker_tiny", traffic=TINY))
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in real[group] if CELL in m.get("workloads", ())}
        for m in bench[group]:
            if m["name"] in mine:
                m["workloads"].append(TINY)
    for rel, data in (("benchmark/configs/smallthinker_tiny.json",
                       tiny_smallthinker()),
                      (f"benchmark/traffic/{TINY}.json", tiny_mix()),
                      ("BENCHMARK.json", bench)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_smallthinker"))


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
    assert "full layers: xla_gather" in stdout and "window layers: xla_gather" in stdout
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_traced_run_reports_the_serving_readers_and_the_new_ones(traced):
    run, line, _ = traced
    bench = harness.load_benchmark(REPO)
    want = {m["name"] for m in harness.metrics_of(
        bench, harness.find_cell(bench, CELL), "per_layer")}
    assert want == set(SERVING_READERS) | set(NEW_READERS)
    # the CPU keeps no memory peak, and its trace holds no decode module and
    # none of the operations the configuration names
    assert want - set(line["metrics"]) <= {
        "decode_hbm_roofline_pct.serve", "hbm_peak_gb.serve",
        "gqa_decode_roofline_pct.serve"}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # every expert is held: a step's pairs are rows x 3 over 8 experts
    assert 0.0 < got["moe_pairs_per_expert.serve"] <= 4 * 3 / 8
    assert 0.0 < got["window_pages_held_pct.serve"] <= 100.0 * 4 * 3 / 16
    assert 1.0 <= got["moe_load_imbalance.serve"] <= 8.0
    assert 0.0 < got["kv_pages_held_pct.serve"] < 100.0    # the all group


def test_the_gqa_roofline_share_follows_the_counts_and_the_named_operations(traced):
    """The reader on a trace that holds the operation the configuration
    names: the keys and values the traced steps' softmaxes read (by the
    program's own counts) over the peak bandwidth, as a share of that
    operation's device time, and of no other's."""
    from mxnet_tpu import observability as obs

    run, _, _ = traced
    reader = harness.load_reader(NEW_READERS[0], REPO)
    records = [r for r in obs.step_records("decode_step")
               if r.counts and "attn_read_full" in r.counts][-3:]
    span = (1e-9 * records[0].t0_ns - 1e-6, 1e-9 * records[-1].t0_ns + 1e-6)
    cfg = dict(run["config"], trace_names={"decode_module": "decode",
                                           "gqa_decode": ["paged_gqa_decode"]})
    made = dict(run, config=cfg, trace_span=span,
                peaks={"hbm_bytes_per_s": 1e9},
                trace={"ops": {"fusion": 1.0, "paged_gqa_decode": 3e-3},
                       "modules": {"jit_paged_decode_fn": (10, 0.5)}})
    reads = [sum(r.counts["attn_read_full"]) + sum(r.counts["attn_read_window"])
             for r in obs.step_records("decode_step")
             if span[0] <= 1e-9 * r.t0_ns < span[1]]
    need = 10 * ref.gqa_read_bytes(cfg, sum(reads) / len(reads))
    assert need == 10 * (sum(reads) / len(reads)) * 2 * 2 * 16 * 2
    assert reader.read(made) == pytest.approx(100.0 * need / 1e9 / 3e-3)
    assert reader.read(dict(made, trace=dict(made["trace"], ops={}))) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_where_the_program_lacks_the_mechanism(name):
    """The parent of this change has neither the counts nor the operation,
    and the older serving configurations name none: the reader then returns
    None and raises nothing."""
    reader = harness.load_reader(name, REPO)
    for path in ("benchmark/configs/deepseek_v2.json",
                 "benchmark/configs/gpt2_345m.json",
                 "benchmark/configs/dots3_note.json",
                 "benchmark/configs/smallthinker_21b.json"):
        cfg = benchmark_tiny.load(path)
        empty = {"kind": "serve", "config": cfg, "window": (-2.0, -1.0),
                 "trace_span": (-2.0, -1.0),
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
    ("no_window", True), ("rope_everywhere", True), ("rope_nowhere", True),
    ("window_minus_1", True), ("router_reads_u", True), ("fp8", True),
    ("bfloat16", True), ("float32", False)])
def test_the_controls_move_the_logits_and_float32_does_not(precision, moves):
    cfg = tiny_smallthinker()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 30).tolist()
    want = ref.next_token_logits(weights, cfg, tokens, 5, 20, pad_to=8, out_pad=8)
    got = ref.next_token_logits(weights, cfg, tokens, 5, 20, precision=precision,
                                pad_to=8, out_pad=8)
    assert bool(np.abs(got - want).max() > 2e-4) is moves


def test_every_listed_control_fails_the_tiny_cells_limits(traced):
    """What ``benchmark.control`` computes on the chip, here on the toy
    cell's own sample: each control the configuration lists puts tokens
    first that lie past a limit of ``check``, which the program's pass."""
    run, _, _ = traced
    listed = run["config"]["precision"]["control"].split(",")
    assert listed and set(listed) <= {"fp8", "no_window", "rope_everywhere"}
    for control in listed + ["window_minus_1"]:
        gaps = serve.control(run, run["config"], run["mix"], 4294967301, None,
                             control)
        assert any(gaps[k] > run["config"]["check"][k] for k in gaps), \
            (control, gaps)


def test_the_bytes_of_a_decode_step_follow_the_shapes():
    cfg = benchmark_tiny.load("benchmark/configs/smallthinker_21b.json")
    specs = {name: shape for name, shape, _ in ref.param_specs(cfg)}
    assert specs["layer0.q.w"] == (28 * 128, 2560)
    assert specs["layer0.k.w"] == specs["layer3.v.w"] == (4 * 128, 2560)
    assert specs["layer1.router.w"] == (64, 2560)
    assert specs["layer1.experts.gate.w"] == (64, 768, 2560)   # every expert
    assert specs["layer1.experts.down.w"] == (64, 2560, 768)
    assert specs["head.w"] == specs["embed.word"] == (37984, 2560)
    total = sum(int(np.prod(s)) for s in specs.values())
    assert round(total / 1e6, 1) == 1789.0
    embed = 37984 * 2560
    assert ref.decode_step_bytes(cfg, 0) == 2 * (total - embed)
    rows = cfg["engine"]["batch_size"]
    token = 2 * 4 * 128 * 2                 # a key and a value, bfloat16
    assert ref.gqa_read_bytes(cfg, 1) == token == 2048
    # rows of 100 positions: all four layers read everything held
    short = ref.decode_step_bytes(cfg, 100 * rows) - ref.decode_step_bytes(cfg, 0)
    assert short == rows * 100 * 4 * token
    # rows of 6,000: the full layer reads them all, a window layer 4,096
    long = ref.decode_step_bytes(cfg, 6000 * rows) - ref.decode_step_bytes(cfg, 0)
    assert long == rows * (6000 + 3 * 4096) * token
    assert ref.decode_step_bytes(cfg, 6000 * 7, rows=7) \
        - ref.decode_step_bytes(cfg, 0) == 7 * (6000 + 3 * 4096) * token


def test_the_configuration_holds_every_published_number_and_states_the_cut():
    cfg = benchmark_tiny.load("benchmark/configs/smallthinker_21b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert row["source_url"] in cfg["source"]
    assert cfg["reduced"] == ["n_layer", "n_vocab"]
    assert (cfg["n_layer"], cfg["n_vocab"]) == (4, 37984)
    # the model-configs guide's floors: a whole period (full, window, window,
    # window), at least eight routed experts (here all 64), at least an
    # eighth of the vocabulary (here a quarter)
    assert cfg["sliding_window_layout"][:4] == cfg["rope_layout"][:4] == [0, 1, 1, 1]
    assert cfg["held_experts"] == list(range(64)) == \
        list(range(cfg["moe_num_primary_experts"]))
    assert cfg["n_vocab"] * 4 == cfg["vocab_size"]
    assert "all 64 experts" in cfg["deployment"]
    assert {"rotary_layout", "sliding_window_size", "attention_bias",
            "hidden_act", "router_input"} <= set(cfg["assumed"])
    assert {"lm_head_predictor", "expert_prefetch", "secondary_experts"} == \
        set(cfg["not_run"])
    assert set(cfg["precision"]["control"].split(",")) <= \
        {"fp8", "no_window", "rope_everywhere"}
    # each limit is written with the reason for it, beside it
    assert set(cfg["check"]) == {"widest_gap", "mean_gap"} <= set(cfg["check_why"])
    assert 0 < cfg["check"]["mean_gap"] < cfg["check"]["widest_gap"] / 10
    assert set(cfg["trace_names"]) == {"decode_module", "gqa_decode"}
    engine = cfg["engine"]
    assert engine["num_pages"]["window"] >= engine["batch_size"] * (4096 // 16 + 2)
    assert engine["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]
    # the measured GB before any program runs stands beside the arithmetic's
    assert cfg["memory"]["arithmetic_gb"] == 12.76
    assert 10.0 < cfg["memory"]["measured_gb"] < 14.5


def test_the_cell_and_its_entries_are_pinned_by_name():
    """By NAME, never by place from the end: a later cell appended behind
    these leaves this test green."""
    bench = harness.load_benchmark(REPO)
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "window: CPU tests" in cell["why"]
    mix = harness.load_mix(cell, REPO)
    # ISSUE 35's 1.5 times the knee, the knee by benchmark.sweep on the
    # finished change (PERF.md, Findings, PR 35)
    assert mix["rate_per_s"] == 1.5 * mix["knee_per_s"]
    assert f"{mix['rate_per_s']:g}/s" in cell["why"]
    assert (mix["prompt_len"]["median"], mix["answer_len"]["median"]) == (4096, 512)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (512, 8192)
    assert (mix["answer_len"]["min"], mix["answer_len"]["max"]) == (128, 2048)
    assert (mix["prompt_len"]["sigma"], mix["answer_len"]["sigma"]) == (0.7, 0.6)
    assert (mix["lead_in_s"], mix["check_requests"], mix["trace_s"],
            mix["drain"]) == (20.0, 6, 6.0, False)
    config = harness.load_config(bench, cell, REPO)
    engine = config["engine"]
    assert engine["batch_size"] == 48
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= engine["max_length"]
    assert mix["prompt_len"]["max"] <= max(engine["prefill_buckets"])
    assert serve.check_shape(mix) == (10240, 2048)
    e2e = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/smallthinker_21b.json"
    assert entry["reduced"] == config["reduced"] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert (by_name[NEW_READERS[0]]["layer"], by_name[NEW_READERS[0]]["unit"],
            by_name[NEW_READERS[0]]["source"]) == ("kernels", "%", "device_trace")
    assert (by_name[NEW_READERS[1]]["layer"],
            by_name[NEW_READERS[1]]["source"]) == ("expert layer",
                                                   "program_counter")
    for name in SERVING_READERS:
        assert CELL in by_name[name]["workloads"], name
    for name in ("index_scores_roofline_pct.serve",
                 "dsa_selected_share_pct.serve"):
        assert CELL not in by_name[name]["workloads"]
    for name in NEW_READERS:
        reader = harness.load_reader(name, REPO)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            by_name[name]["layer"], by_name[name]["unit"], by_name[name]["moves"])
