"""The Olmo-Hybrid serving cell at a toy size on the CPU, through the
harness's own ``main``: ``correct`` comes out true for what the engine
served through page pools and slot state and false for a token altered, the
cell's readers return numbers (the two new ones among them), every listed
control fails the toy limits that the program passes, the bytes a decode
step must move follow the shapes, and the entries this cell added to
``BENCHMARK.json`` are pinned BY NAME."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, serve
from benchmark.reference import olmo_hybrid as ref
from benchmark.weights import make_weights

import benchmark_tiny
from benchmark_tiny import REPO, run_cell

CELL, CONFIG, MIX = ("olmo_hybrid_7b_serve_longgen", "olmo_hybrid_7b",
                     "longgen_saturate")
TINY = "tiny_longgen"
NEW_READERS = ("gdn_decode_roofline_pct.serve", "gdn_state_share_pct.serve")
SERVING_READERS = (
    "gen_lateness_p99_ms", "backlog_growth_per_s", "slot_occupancy_pct.serve",
    "ttft_p90_ms.obs", "decode_step_mean_ms", "itl_p90_ms.obs",
    "prefill_share_pct.serve", "window_compiles.serve",
    "kv_pages_held_pct.serve", "decode_hbm_roofline_pct.serve",
    "custom_call_share_pct.serve", "device_idle_pct.serve", "hbm_peak_gb.serve",
    "decode_ahead_pct.serve", "step_host_ms.serve", "step_outside_ms.serve",
    "prefill_host_ms.serve", "prefill_pad_pct.serve", "decode_pages_ms.serve")
OTHERS = ("moe_load_imbalance.serve", "index_scores_roofline_pct.serve",
          "dsa_selected_share_pct.serve", "window_pages_held_pct.serve",
          "gqa_decode_roofline_pct.serve", "moe_pairs_per_expert.serve",
          "host_turnround_ms.serve")
CONTROLS = {"fp8", "no_decay", "beta_le_1"}   # those the chip fails


def tiny_olmo_hybrid():
    cfg = benchmark_tiny.load("benchmark/configs/olmo_hybrid_7b.json")
    cfg.update(name="olmo_hybrid_tiny", hidden_size=32, intermediate_size=48,
               num_attention_heads=2, num_key_value_heads=2, head_dim=16,
               linear_num_key_heads=2, linear_num_value_heads=2,
               linear_key_head_dim=8, linear_value_head_dim=64, n_layer=4,
               n_vocab=300,
               # widths a hundred times under the published ones: at five
               # times their 0.02 the logits spread as the published widths'
               # do; decays of 0.8-0.98 a position, so a state lives through
               # a toy answer
               initializer_range=0.1,
               decay_init={"A_log_mean": 0.0, "A_log_std": 0.5,
                           "dt_bias_mean": -3.0, "dt_bias_std": 0.7,
                           "a_proj_std": 0.02, "conv_std": 0.3})
    cfg["precision"]["weights"] = "float32"
    cfg["engine"].update(batch_size=4, page_size=4, max_length=64,
                         num_pages={"all": 64}, cache_dtype="float32",
                         prefill_buckets=[8, 16, 32])
    # float32 on the CPU: the engine and the reference differ by rounding of
    # the last place only; a wrong token lies a logit's spread (~0.5) away
    cfg["check"] = {"widest_gap": 1e-3, "mean_gap": 1e-4}
    return cfg


def tiny_mix():
    mix = benchmark_tiny.load(f"benchmark/traffic/{MIX}.json")
    mix.update(rate_per_s=16.0, lead_in_s=0.5, tail_s=0.2, trace_s=0.4,
               check_requests=4,
               # answers long enough that a state kept in bfloat16 drifts
               # past a toy limit
               prompt_len={"dist": "lognormal", "median": 8, "sigma": 0.6,
                           "min": 3, "max": 24},
               answer_len={"dist": "lognormal", "median": 28, "sigma": 0.3,
                           "min": 16, "max": 40})
    return mix


def make_root(tmp):
    """``benchmark_tiny``'s tree plus this cell on its toy configuration,
    reporting whatever the real cell reports in ``BENCHMARK.json``."""
    root = benchmark_tiny.make_root(tmp)
    real = harness.load_benchmark(REPO)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    entry = dict(next(c for c in real["configs"] if c["name"] == CONFIG),
                 name="olmo_hybrid_tiny",
                 file="benchmark/configs/olmo_hybrid_tiny.json")
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != CONFIG] + [entry]
    bench["workloads"].append(dict(harness.find_cell(real, CELL), name=TINY,
                                   config="olmo_hybrid_tiny", traffic=TINY))
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in real[group] if CELL in m.get("workloads", ())}
        for m in bench[group]:
            if m["name"] in mine:
                m["workloads"].append(TINY)
    for rel, data in (("benchmark/configs/olmo_hybrid_tiny.json",
                       tiny_olmo_hybrid()),
                      (f"benchmark/traffic/{TINY}.json", tiny_mix()),
                      ("BENCHMARK.json", bench)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_olmo_hybrid"))


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
    assert "full layers: xla_gather" in stdout and "linear layers: gdn_xla" in stdout
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_traced_run_reports_the_serving_readers_and_the_new_ones(traced):
    run, line, _ = traced
    bench = harness.load_benchmark(REPO)
    listed = {m["name"] for m in harness.metrics_of(
        bench, harness.find_cell(bench, CELL), "per_layer")}
    # by name: a reader a later PR lists this cell under leaves this green
    assert set(SERVING_READERS) | set(NEW_READERS) <= listed
    assert not listed & set(OTHERS)
    # the CPU keeps no memory peak, and its trace holds no decode module and
    # none of the operations the configuration names
    assert (set(SERVING_READERS) | set(NEW_READERS)) - set(line["metrics"]) <= {
        "decode_hbm_roofline_pct.serve", "hbm_peak_gb.serve",
        "gdn_decode_roofline_pct.serve"}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < got["gdn_state_share_pct.serve"] < 100.0
    assert 0.0 < got["kv_pages_held_pct.serve"] < 100.0
    assert 0.0 <= got["decode_ahead_pct.serve"] <= 100.0


def test_the_state_share_follows_the_counts(traced):
    """The reader against its own arithmetic: the state of the rows the
    window's decode steps advanced over a step's bytes at the positions the
    rows held."""
    from benchmark.decoderecords import decode_counts
    from benchmark.records import window_steps

    run, line, _ = traced
    counts = decode_counts(run, "state_rows")
    assert counts and all(len(c) == 3 and len(set(c)) == 1 for c in counts)
    rows = sum(c[0] for c in counts) / len(counts)
    assert 0.0 < rows <= 4.0
    steps = [s for s in window_steps(run) if s["decoded_rows"]]
    held = sum(s["held_positions"] for s in steps) / len(steps)
    cfg = run["config"]
    state = rows * 3 * 2 * (2 * 8 * 64) * 4     # read and written, float32
    assert ref.gdn_state_bytes(cfg, rows) == state
    want = 100.0 * state / ref.decode_step_bytes(cfg, held, rows=rows)
    assert line["metrics"]["gdn_state_share_pct.serve"]["value"] == \
        pytest.approx(want)


def test_the_gdn_roofline_share_follows_the_counts_and_the_named_operations(traced):
    """The reader on a trace that holds the operation the configuration
    names: the state the traced steps advanced (by the program's own count)
    over the peak bandwidth, as a share of that operation's device time, and
    of no other's."""
    from mxnet_tpu import observability as obs

    run, _, _ = traced
    reader = harness.load_reader(NEW_READERS[0], REPO)
    records = [r for r in obs.step_records("decode_step")
               if r.counts and "state_rows" in r.counts][-3:]
    span = (1e-9 * records[0].t0_ns - 1e-6, 1e-9 * records[-1].t0_ns + 1e-6)
    cfg = dict(run["config"], trace_names={"decode_module": "decode",
                                           "gdn_decode": ["gdn_decode_step"]})
    made = dict(run, config=cfg, trace_span=span,
                peaks={"hbm_bytes_per_s": 1e9},
                trace={"ops": {"fusion": 1.0, "gdn_decode_step": 3e-3},
                       "modules": {"jit_paged_decode_fn": (10, 0.5)}})
    rows = [r.counts["state_rows"][0]
            for r in obs.step_records("decode_step")
            if span[0] <= 1e-9 * r.t0_ns < span[1]]
    need = 10 * ref.gdn_state_bytes(cfg, sum(rows) / len(rows))
    assert reader.read(made) == pytest.approx(100.0 * need / 1e9 / 3e-3)
    assert reader.read(dict(made, trace=dict(made["trace"], ops={}))) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_where_the_program_lacks_the_mechanism(name):
    """The parent of this change has neither the counts nor the operation,
    and the older serving configurations' references count no such bytes:
    the reader then returns None and raises nothing."""
    reader = harness.load_reader(name, REPO)
    for path in ("benchmark/configs/deepseek_v2.json",
                 "benchmark/configs/gpt2_345m.json",
                 "benchmark/configs/dots3_note.json",
                 "benchmark/configs/smallthinker_21b.json",
                 "benchmark/configs/olmo_hybrid_7b.json"):
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
    ("fp8", True), ("bfloat16", True), ("state_bf16", True),
    ("no_decay", True), ("beta_le_1", True), ("no_conv", True),
    ("pad_writes_state", True), ("float32", False)])
def test_the_controls_move_the_logits_and_float32_does_not(precision, moves):
    cfg = tiny_olmo_hybrid()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 30).tolist()
    want = ref.next_token_logits(weights, cfg, tokens, 5, 20, pad_to=8, out_pad=8)
    got = ref.next_token_logits(weights, cfg, tokens, 5, 20, precision=precision,
                                pad_to=8, out_pad=8)
    assert bool(np.abs(got - want).max() > 2e-4) is moves
    if precision == "pad_writes_state":
        # the logits behind the prompt's last token stand before any padding
        np.testing.assert_allclose(got[0], want[0], atol=1e-6)


def test_every_listed_control_fails_the_tiny_cells_limits(traced):
    """What ``benchmark.control`` computes on the chip, here on the toy
    cell's own sample: each control the configuration lists puts tokens
    first that lie past a limit of ``check``, which the program's pass; and
    the three the CPU tests keep (a state in bfloat16 hides in the chip's
    own bfloat16 noise: ``check_why.state_bf16``)."""
    run, _, _ = traced
    listed = run["config"]["precision"]["control"].split(",")
    assert set(listed) == CONTROLS
    for control in listed + ["state_bf16", "no_conv", "pad_writes_state"]:
        gaps = serve.control(run, run["config"], run["mix"], 4294967301, None,
                             control)
        assert any(gaps[k] > run["config"]["check"][k] for k in gaps), \
            (control, gaps)


def test_the_bytes_of_a_decode_step_follow_the_shapes():
    cfg = benchmark_tiny.load("benchmark/configs/olmo_hybrid_7b.json")
    specs = {name: shape for name, shape, _ in ref.param_specs(cfg)}
    assert specs["layer0.gdn.q.w"] == specs["layer2.gdn.k.w"] == (2880, 3840)
    assert specs["layer0.gdn.v.w"] == specs["layer1.gdn.g.w"] == (5760, 3840)
    assert specs["layer0.gdn.o.w"] == (3840, 5760)
    assert specs["layer0.gdn.a.w"] == specs["layer0.gdn.b.w"] == (30, 3840)
    assert specs["layer0.gdn.conv.w"] == (2880 + 2880 + 5760, 4)
    assert specs["layer0.gdn.A_log"] == specs["layer0.gdn.dt_bias"] == (30,)
    assert specs["layer0.gdn.o_norm.gamma"] == (192,)
    assert specs["layer3.attn.q.w"] == specs["layer7.attn.o.w"] == (3840, 3840)
    assert specs["layer3.attn.q_norm.gamma"] == (3840,)
    assert specs["layer0.ffn.gate.w"] == (11008, 3840)
    assert specs["layer7.ffn.down.w"] == (3840, 11008)
    assert specs["head.w"] == specs["embed.word"] == (25088, 3840)
    assert "layer3.gdn.q.w" not in specs and "layer0.attn.q.w" not in specs
    count = lambda i: sum(int(np.prod(s)) for n, s in specs.items()  # noqa: E731
                          if n.startswith(f"layer{i}."))
    # ISSUE 40's count: 88.7M + 126.8M a linear layer, 185.8M a full one
    assert 215.5 < count(0) / 1e6 < 215.6
    assert 185.8 < count(3) / 1e6 < 185.9
    total = sum(int(np.prod(s)) for s in specs.values())
    assert round(total / 1e6, 1) == 1857.7   # ISSUE 40 reckons 1,857.3
    embed = 25088 * 3840
    rows = cfg["engine"]["batch_size"]
    state = 6 * 2 * 30 * 96 * 192 * 4           # a row: read and written
    assert ref.gdn_state_bytes(cfg, 1) == state == 26542080
    assert ref.decode_step_bytes(cfg, 0) == 2 * (total - embed) + rows * state
    assert ref.decode_step_bytes(cfg, 0, rows=0) == 2 * (total - embed)
    token = 2 * 2 * 30 * 128 * 2          # two full layers' key and value
    assert ref.kv_read_bytes(cfg, 1) == token == 30720
    assert ref.decode_step_bytes(cfg, 1000, rows=7) \
        - ref.decode_step_bytes(cfg, 0, rows=7) == 1000 * token
    # the state overtakes the keys and values under 864 positions a row
    assert state / token == 864.0


def test_the_configuration_holds_every_published_number_and_states_the_cut():
    cfg = benchmark_tiny.load("benchmark/configs/olmo_hybrid_7b.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert row["source_url"] in cfg["source"]
    assert cfg["reduced"] == ["n_layer", "n_vocab"]
    assert (cfg["n_layer"], cfg["n_vocab"]) == (8, 25088)
    # the model-configs guide's floors: whole periods (here two), at least
    # an eighth of the vocabulary (here a quarter)
    assert cfg["layer_types"][:8] == (["linear_attention"] * 3
                                      + ["full_attention"]) * 2
    assert cfg["n_vocab"] * 4 == cfg["vocab_size"]
    assert (cfg["hidden_size"], cfg["intermediate_size"]) == (3840, 11008)
    assert (cfg["num_attention_heads"], cfg["head_dim"]) == (30, 128)
    assert (cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"]) == (96, 192, 4)
    assert "every layer WHOLE on its chip" in cfg["deployment"]
    assert {"head_dim", "positions", "block", "linear_layer",
            "state_precision", "decays", "engine"} <= set(cfg["assumed"])
    assert set(cfg["decay_init"]) == {"A_log_mean", "A_log_std", "dt_bias_mean",
                                      "dt_bias_std", "a_proj_std", "conv_std"}
    assert set(cfg["precision"]["control"].split(",")) == CONTROLS
    # each limit is written with the reason for it, beside it
    assert set(cfg["check"]) == {"widest_gap", "mean_gap"} <= set(cfg["check_why"])
    assert "NOT in precision.control" in cfg["check_why"]["state_bf16"]
    assert 0 < cfg["check"]["mean_gap"] < cfg["check"]["widest_gap"] / 10
    assert set(cfg["trace_names"]) == {"decode_module", "gdn_decode"}
    engine = cfg["engine"]
    assert 32 <= engine["batch_size"] <= 48
    assert engine["num_pages"] == {"all": 5120}
    assert engine["prefill_buckets"] == [128, 256, 512, 1024, 2048]
    # the measured GB before any program runs stands beside the arithmetic's
    assert 12.0 < cfg["memory"]["measured_gb"] < 15.0
    assert abs(cfg["memory"]["arithmetic_gb"] - cfg["memory"]["measured_gb"]) < 0.5


def test_the_cell_and_its_entries_are_pinned_by_name():
    """By NAME, never by place from the end: a later cell appended behind
    these leaves this test green."""
    bench = harness.load_benchmark(REPO)
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "host share 4x" in cell["why"]
    mix = harness.load_mix(cell, REPO)
    # ISSUE 40's 1.5 times the knee, the knee by benchmark.sweep on the
    # finished change (PERF.md, Findings, PR 40)
    assert mix["rate_per_s"] == 1.5 * mix["knee_per_s"]
    assert f"{mix['rate_per_s']:g}/s" in cell["why"]
    assert (mix["prompt_len"]["median"], mix["answer_len"]["median"]) == (512, 768)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (128, 2048)
    assert (mix["answer_len"]["min"], mix["answer_len"]["max"]) == (256, 2048)
    assert (mix["prompt_len"]["sigma"], mix["answer_len"]["sigma"]) == (0.7, 0.6)
    assert (mix["lead_in_s"], mix["check_requests"], mix["trace_s"],
            mix["drain"]) == (20.0, 6, 6.0, False)
    config = harness.load_config(bench, cell, REPO)
    engine = config["engine"]
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= engine["max_length"]
    assert mix["prompt_len"]["max"] <= max(engine["prefill_buckets"])
    assert serve.check_shape(mix) == (4096, 2048)
    e2e = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/olmo_hybrid_7b.json"
    assert entry["reduced"] == config["reduced"] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert (by_name[NEW_READERS[0]]["layer"], by_name[NEW_READERS[0]]["unit"],
            by_name[NEW_READERS[0]]["source"]) == ("kernels", "%", "device_trace")
    assert (by_name[NEW_READERS[1]]["layer"], by_name[NEW_READERS[1]]["unit"],
            by_name[NEW_READERS[1]]["source"]) == ("engine", "%",
                                                   "program_counter")
    for name in SERVING_READERS:
        assert CELL in by_name[name]["workloads"], name
    for name in OTHERS:
        assert CELL not in by_name[name]["workloads"]
    for name in NEW_READERS:
        reader = harness.load_reader(name, REPO)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            by_name[name]["layer"], by_name[name]["unit"], by_name[name]["moves"])
