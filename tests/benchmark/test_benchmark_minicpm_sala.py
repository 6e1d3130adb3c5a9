"""The MiniCPM-SALA serving cell at a toy size on the CPU, through the
harness's own ``main``: ``correct`` comes out true for what the engine
served through page pools, compressed keys and slot state, and false for a
token altered; the cell's readers return numbers (the four new ones among
them); every control fails the toy limits that the program passes; the
bytes a decode step must move follow the shapes; and the entries this cell
added to ``BENCHMARK.json`` are pinned BY NAME, never by place or by a
list's whole content."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, serve
from benchmark.reference import minicpm_sala as ref
from benchmark.weights import make_weights

import benchmark_tiny
from benchmark_tiny import REPO, run_cell

CELL, CONFIG, MIX = ("minicpm_sala_serve_longdoc", "minicpm_sala",
                     "longdoc_saturate")
TINY = "tiny_longdoc"
NEW_READERS = ("block_select_roofline_pct.serve",
               "block_read_roofline_pct.serve",
               "lightning_decode_roofline_pct.serve",
               "blocks_read_share_pct.serve")
SERVING_READERS = (
    "gen_lateness_p99_ms", "backlog_growth_per_s", "slot_occupancy_pct.serve",
    "ttft_p90_ms.obs", "decode_step_mean_ms", "itl_p90_ms.obs",
    "prefill_share_pct.serve", "window_compiles.serve",
    "kv_pages_held_pct.serve", "decode_hbm_roofline_pct.serve",
    "custom_call_share_pct.serve", "device_idle_pct.serve", "hbm_peak_gb.serve",
    "decode_ahead_pct.serve", "step_host_ms.serve", "step_outside_ms.serve",
    "prefill_host_ms.serve", "prefill_pad_pct.serve", "decode_pages_ms.serve")
# other cells' own readers, whose lists their tests pin whole
OTHERS = ("moe_load_imbalance.serve", "index_scores_roofline_pct.serve",
          "window_pages_held_pct.serve", "moe_pairs_per_expert.serve",
          "host_turnround_ms.serve", "gdn_decode_roofline_pct.serve",
          "gdn_state_share_pct.serve", "gqa_decode_roofline_pct.serve",
          "dsa_selected_share_pct.serve")
# a trace of the CPU holds no decode module and none of the named kernels
DEVICE_ONLY = {"decode_hbm_roofline_pct.serve", "hbm_peak_gb.serve",
               *NEW_READERS[:3]}
# tests/test_minicpm_sala.py's toy: a block of 4, 5 selected, 3 forced
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, topk=5,
              init_blocks=1, window_size=4, dense_len=8)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"]


def tiny_minicpm_sala():
    cfg = benchmark_tiny.load("benchmark/configs/minicpm_sala.json")
    cfg.update(name="minicpm_sala_tiny", hidden_size=32, intermediate_size=48,
               num_attention_heads=4, num_key_value_heads=1, head_dim=8,
               lightning_nh=2, lightning_nkv=2, lightning_head_dim=8,
               n_layer=4, n_vocab=300, sparse_config=dict(SPARSE),
               mixer_types=MIXERS,
               # tests/test_minicpm_sala.py says why each of these three
               scale_emb=1, dim_model_base=32, initializer_range=0.1)
    cfg["precision"]["weights"] = "float32"
    cfg["engine"].update(batch_size=4, page_size=4, max_length=128,
                         num_pages={"all": 160}, cache_dtype="float32",
                         prefill_buckets=[16, 32, 64])
    # float32 on the CPU: the engine and the reference differ by rounding of
    # the last place only; a wrong token lies a logit's spread away
    cfg["check"] = {"widest_gap": 1e-3, "mean_gap": 1e-4}
    return cfg


def tiny_mix():
    mix = benchmark_tiny.load(f"benchmark/traffic/{MIX}.json")
    mix.update(rate_per_s=12.0, lead_in_s=0.6, tail_s=0.2, trace_s=0.4,
               check_requests=4,
               # the real mix's shape at a toy size: every prompt at or
               # past the dense length of 8, as every real one is
               prompt_len={"dist": "lognormal", "median": 32, "sigma": 0.5,
                           "min": 8, "max": 64},
               answer_len={"dist": "lognormal", "median": 20, "sigma": 0.4,
                           "min": 8, "max": 40})
    return mix


def make_root(tmp):
    """``benchmark_tiny``'s tree plus this cell on its toy configuration,
    reporting whatever the real cell reports in ``BENCHMARK.json``."""
    root = benchmark_tiny.make_root(tmp)
    real = harness.load_benchmark(REPO)
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    entry = dict(next(c for c in real["configs"] if c["name"] == CONFIG),
                 name="minicpm_sala_tiny",
                 file="benchmark/configs/minicpm_sala_tiny.json")
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != CONFIG] + [entry]
    bench["workloads"].append(dict(harness.find_cell(real, CELL), name=TINY,
                                   config="minicpm_sala_tiny", traffic=TINY))
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in real[group] if CELL in m.get("workloads", ())}
        for m in bench[group]:
            if m["name"] in mine:
                m["workloads"].append(TINY)
    for rel, data in (("benchmark/configs/minicpm_sala_tiny.json",
                       tiny_minicpm_sala()),
                      (f"benchmark/traffic/{TINY}.json", tiny_mix()),
                      ("BENCHMARK.json", bench)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_minicpm_sala"))


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
    assert "sparse layers: selected_pages_xla" in stdout
    assert "selector: block_scores_xla" in stdout
    assert "lightning layers: lightning_xla" in stdout
    assert line["device"]["platform"] == "cpu"  # named for what it ran on
    assert run["end_to_end"]["serve_tokens_per_s"] > 0


def test_the_traced_run_reports_the_readers_the_cell_is_listed_in(traced):
    run, line, _ = traced
    bench = harness.load_benchmark(REPO)
    listed = {m["name"] for m in harness.metrics_of(
        bench, harness.find_cell(bench, CELL), "per_layer")}
    # by name: a reader a later PR lists this cell under leaves this green
    wanted = set(SERVING_READERS) | set(NEW_READERS)
    assert wanted <= listed
    assert not listed & set(OTHERS)
    assert wanted - set(line["metrics"]) <= DEVICE_ONLY
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 10.0 < got["blocks_read_share_pct.serve"] < 100.0
    assert 0.0 < got["kv_pages_held_pct.serve"] < 100.0


def test_the_blocks_read_share_follows_the_counts(traced):
    from benchmark.decoderecords import decode_counts

    run, line, _ = traced
    reads = decode_counts(run, "blocks_read")
    helds = decode_counts(run, "blocks_held")
    assert reads and all(len(c) == 2 for c in reads + helds)   # two sparse layers
    assert all(0 < r[0] <= h[0] and r == [r[0]] * 2 for r, h in zip(reads, helds))
    shares = [100.0 * sum(r) / sum(h) for r, h in zip(reads, helds)]
    assert line["metrics"]["blocks_read_share_pct.serve"]["value"] == \
        pytest.approx(sum(shares) / len(shares))
    # a row past the dense length reads 5 blocks of those it holds
    assert min(shares) < 60.0
    # and a prefill's record holds the same counts behind its first token
    from mxnet_tpu import observability as obs
    fills = [r.counts for r in obs.step_records("prefill")
             if r.counts and "blocks_read" in r.counts]
    assert fills and all(
        f["compressed_written"] == [f["prompt"] - 1] * 2
        and f["state_rows"] == [1, 1]
        and 0 < f["blocks_read"][0] <= f["blocks_held"][0] for f in fills)


def test_the_three_roofline_shares_follow_the_counts_and_the_named_operations(
        traced):
    """The readers on a trace that holds the operations the configuration
    names, each over that operation's device time and no other's: the
    compressed keys of the blocks held, the blocks the tables list, the state
    the steps advanced, all by the program's own counts."""
    from mxnet_tpu import observability as obs

    run, _, _ = traced
    records = [r for r in obs.step_records("decode_step")
               if r.counts and "blocks_read" in r.counts][-3:]
    span = (1e-9 * records[0].t0_ns - 1e-6, 1e-9 * records[-1].t0_ns + 1e-6)
    inside = [r for r in obs.step_records("decode_step")
              if span[0] <= 1e-9 * r.t0_ns < span[1]]
    cfg = dict(run["config"], trace_names={
        "decode_module": "decode",
        "lightning_decode": ["lightning_decode_step"],
        "block_read": ["paged_gqa_decode_selected"],
        "block_select": ["paged_block_scores"]})
    made = dict(run, config=cfg, trace_span=span,
                peaks={"hbm_bytes_per_s": 1e9},
                trace={"ops": {"fusion": 1.0, "paged_block_scores": 2e-3,
                               "paged_gqa_decode_selected": 5e-3,
                               "lightning_decode_step": 3e-3},
                       "modules": {"jit_paged_decode_fn": (10, 0.5)}})
    mean = lambda name, of: sum(  # noqa: E731
        sum(of(cfg, layer) for layer in r.counts[name])
        for r in inside) / len(inside)
    select, read, state = (harness.load_reader(n, REPO) for n in NEW_READERS[:3])
    # 4 compressed keys a block of 1 key-value head of 8, float32 counted at
    # the cache's stated 2 bytes
    assert ref.block_select_bytes(cfg, 10) == 10 * 4 * 1 * 8 * 2
    assert ref.block_read_bytes(cfg, 10) == 10 * 1 * 4 * 2 * 8 * 2
    need = 10 * mean("blocks_held", ref.block_select_bytes)
    assert select.read(made) == pytest.approx(100.0 * need / 1e9 / 2e-3)
    need = 10 * mean("blocks_read", ref.block_read_bytes)
    assert read.read(made) == pytest.approx(100.0 * need / 1e9 / 5e-3)
    rows = [r.counts["state_rows"][0] for r in inside]
    need = 10 * ref.lightning_state_bytes(cfg, sum(rows) / len(rows))
    assert state.read(made) == pytest.approx(100.0 * need / 1e9 / 3e-3)
    for reader in (select, read, state):
        assert reader.read(dict(made, trace=dict(made["trace"], ops={}))) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_nothing_where_the_program_lacks_the_mechanism(name):
    """The parent of this change has neither the counts nor the operation,
    and the older serving configurations name no such operations: the reader
    then returns None and raises nothing."""
    reader = harness.load_reader(name, REPO)
    for path in ("benchmark/configs/deepseek_v2.json",
                 "benchmark/configs/gpt2_345m.json",
                 "benchmark/configs/dots3_note.json",
                 "benchmark/configs/smallthinker_21b.json",
                 "benchmark/configs/olmo_hybrid_7b.json",
                 "benchmark/configs/minicpm_sala.json"):
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
    ("fp8", True), ("bfloat16", True), ("no_selection", True),
    ("forced_only", True), ("no_decay", True), ("early_key", True),
    ("float32", False)])
def test_the_controls_move_the_logits_and_float32_does_not(precision, moves):
    cfg = tiny_minicpm_sala()
    weights = make_weights(ref.param_specs(cfg), 11)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 120).tolist()
    args = (weights, cfg, tokens, 70, 40)
    want = ref.next_token_logits(*args, pad_to=8, out_pad=8)
    got = ref.next_token_logits(*args, precision=precision, pad_to=8, out_pad=8)
    assert bool(np.abs(got - want).max() > 2e-4) is moves


def test_every_control_fails_the_tiny_cells_limits(traced):
    """What ``benchmark.control`` computes on the chip, here on the toy
    cell's own sample: every control the configuration lists puts tokens
    first that lie past a limit of ``check``, which the program's pass (the
    two that only a toy can tell apart are tests/test_minicpm_sala.py's)."""
    run, _, _ = traced
    listed = run["config"]["precision"]["control"].split(",")
    assert listed == ["fp8", "no_selection", "forced_only", "no_decay"]
    assert set(listed) <= {"fp8", *ref.MATH_CONTROLS}
    for control in listed:
        gaps = serve.control(run, run["config"], run["mix"], 4294967301, None,
                             control)
        assert any(gaps[k] > run["config"]["check"][k] for k in gaps), \
            (control, gaps)


def test_the_bytes_of_a_decode_step_follow_the_shapes():
    cfg = benchmark_tiny.load("benchmark/configs/minicpm_sala.json")
    specs = {name: shape for name, shape, _ in ref.param_specs(cfg)}
    assert specs["layer0.attn.q.w"] == specs["layer0.attn.g.w"] == (4096, 4096)
    assert specs["layer0.attn.k.w"] == specs["layer0.attn.v.w"] == (256, 4096)
    assert specs["layer0.attn.q_norm.gamma"] == (128,)
    assert specs["layer1.lin.q.w"] == specs["layer3.lin.g.w"] == (4096, 4096)
    assert specs["layer2.lin.k_norm.gamma"] == (128,)
    assert specs["layer2.lin.o_norm.gamma"] == (128,)
    assert specs["layer0.ffn.gate.w"] == (16384, 4096)
    assert specs["head.w"] == specs["embed.word"] == (36724, 4096)
    assert "layer0.lin.q.w" not in specs and "layer1.attn.q.w" not in specs
    count = lambda i: sum(int(np.prod(s)) for n, s in specs.items()  # noqa: E731
                          if n.startswith(f"layer{i}."))
    # ISSUE 43's count: 253.7M a sparse layer, 285.2M a lightning layer
    assert 253.7 < count(0) / 1e6 < 253.8
    assert 285.2 < count(1) / 1e6 < 285.3
    total = sum(int(np.prod(s)) for s in specs.values())
    # ISSUE 43's 1,109M a period and 300.8M of embedding and head
    assert round(total / 1e6, 1) == 1410.3
    embed, rows = 36724 * 4096, cfg["engine"]["batch_size"]
    state = 3 * 2 * 32 * 128 * 128 * 4          # a row: read and written
    assert ref.lightning_state_bytes(cfg, 1) == state == 12582912
    # a block a key-value head lists: both heads' key and value of 64 x 128
    assert ref.block_read_bytes(cfg, 1) == 2 * 64 * 2 * 128 * 2
    # a block held: 4 compressed keys of both heads
    assert ref.block_select_bytes(cfg, 1) == 4 * 2 * 128 * 2
    # a row under the dense length reads what it holds, past it 64 blocks
    assert ref.blocks_a_row_reads(cfg, 5000) == 79
    assert ref.blocks_a_row_reads(cfg, 8192) == 128
    assert ref.blocks_a_row_reads(cfg, 8193) == 64
    assert ref.blocks_a_row_reads(cfg, 65536) == 64
    assert ref.decode_step_bytes(cfg, 0, rows=0) == 2 * (total - embed)
    long_rows = ref.decode_step_bytes(cfg, rows * 22016) \
        - ref.decode_step_bytes(cfg, 0, rows=0)
    assert long_rows == rows * state \
        + ref.block_select_bytes(cfg, rows * 344) \
        + ref.block_read_bytes(cfg, rows * 64)
    # ISSUE 43's reckoning of a step at 48 rows of 22k: state 0.60 GB,
    # selected keys and values 0.20, compressed keys 0.03
    assert round(rows * state / 1e9, 2) == 0.60
    assert round(ref.block_read_bytes(cfg, rows * 64) / 1e9, 2) == 0.20
    assert round(ref.block_select_bytes(cfg, rows * 344) / 1e9, 2) == 0.03
    np.testing.assert_allclose(ref.decays(cfg, 0)[[0, 31]],
                               np.exp(-np.array([2 ** -0.25, 2 ** -8.0])
                                      * (1 + 1e-5)), rtol=1e-6)
    assert ref.residual_scale(cfg) == pytest.approx(1.4 / 32 ** 0.5)


def test_the_configuration_holds_every_published_number_and_states_the_cut():
    cfg = benchmark_tiny.load("benchmark/configs/minicpm_sala.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert row["source_url"] in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["n_layer", "n_vocab"]
    assert (cfg["n_layer"], cfg["n_vocab"]) == (4, 36724)
    # one whole period (the published one to three), half the vocabulary
    assert cfg["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    assert [i for i, m in enumerate(cfg["mixer_types"]) if m == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg["n_vocab"] * 2 == cfg["vocab_size"]
    assert (cfg["hidden_size"], cfg["intermediate_size"]) == (4096, 16384)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (32, 2, 128)
    assert cfg["sparse_config"] == dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048, dense_len=8192)
    assert "every layer WHOLE on its chip" in cfg["deployment"]
    assert {"block", "selection", "decay", "gates_input", "norms_shapes",
            "initialisation", "engine", "published_counts"} \
        <= set(cfg["assumed"])
    assert {"selection_normaliser", "exchange"} <= set(cfg["not_run"])
    assert cfg["precision"]["control"] == "fp8,no_selection,forced_only,no_decay"
    # each limit is written with the reason for it, beside it
    assert set(cfg["check"]) == {"widest_gap", "mean_gap"} <= set(cfg["check_why"])
    assert 0 < cfg["check"]["mean_gap"] < cfg["check"]["widest_gap"]
    assert set(cfg["trace_names"]) == {"decode_module", "block_select",
                                       "block_read", "lightning_decode"}
    engine = cfg["engine"]
    assert engine["batch_size"] == 48 and engine["page_size"] == 64
    assert engine["max_length"] == 69632
    assert engine["num_pages"] == {"all": 24576}
    assert (engine["prefill_buckets"][0], engine["prefill_buckets"][-1]) \
        == (8192, 65536)


def test_the_arithmetic_of_memory_adds_up():
    """What the device holds before any program runs, from the shapes: the
    harness's float32 weights and the engine's bfloat16 copy (6 bytes a
    parameter), the sparse layer's three pools, the lightning layers' state;
    the builder's measured GB stands beside it, over a quarter of the chip."""
    cfg = benchmark_tiny.load("benchmark/configs/minicpm_sala.json")
    engine, sparse = cfg["engine"], cfg["sparse_config"]
    params = sum(int(np.prod(s)) for _, s, _ in ref.param_specs(cfg))
    pages = engine["num_pages"]["all"] + 1
    kv = cfg["num_key_value_heads"] * cfg["head_dim"] * 2       # bfloat16
    pools = pages * engine["page_size"] * kv * 2
    compressed = pages * (engine["page_size"] // sparse["kernel_stride"]) * kv
    state = 3 * engine["batch_size"] * (32 * 128 * 128 * 4 + 4)
    total = (params * 6 + pools + compressed + state) / 1e9
    assert round(params * 6 / 1e9, 2) == 8.46 and round(pools / 1e9, 2) == 1.61
    assert round(compressed / 1e9, 2) == 0.05 and round(state / 1e9, 2) == 0.30
    assert cfg["memory"]["arithmetic_gb"] == pytest.approx(total, abs=0.002)
    assert abs(cfg["memory"]["arithmetic_gb"] - cfg["memory"]["measured_gb"]) < 0.5
    assert cfg["memory"]["measured_gb"] > 0.25 * 16.0


def test_the_cell_and_its_entries_are_pinned_by_name():
    """By NAME, never by place from the end and never by a list's whole
    content: a later cell appended behind these, or listed under these
    readers too, leaves this test green."""
    bench = harness.load_benchmark(REPO)
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "4 of 32 layers" in cell["why"]
    mix = harness.load_mix(cell, REPO)
    # ISSUE 43's 1.5 times the knee, the knee by benchmark.sweep on the
    # finished change (PERF.md, Findings, PR 43)
    assert mix["rate_per_s"] == pytest.approx(1.5 * mix["knee_per_s"])
    assert (mix["prompt_len"]["median"], mix["answer_len"]["median"]) == (16384, 1024)
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (8192, 65536)
    assert (mix["answer_len"]["min"], mix["answer_len"]["max"]) == (256, 4096)
    assert (mix["prompt_len"]["sigma"], mix["answer_len"]["sigma"]) == (0.6, 0.6)
    assert (mix["lead_in_s"], mix["tail_s"], mix["check_requests"],
            mix["trace_s"], mix["drain"]) == (30.0, 1.0, 6, 6.0, False)
    # every prompt is at or past the dense length: every decode step selects
    config = harness.load_config(bench, cell, REPO)
    assert mix["prompt_len"]["min"] >= config["sparse_config"]["dense_len"]
    engine = config["engine"]
    assert mix["prompt_len"]["max"] + mix["answer_len"]["max"] <= engine["max_length"]
    assert mix["prompt_len"]["max"] <= max(engine["prefill_buckets"])
    assert serve.check_shape(mix) == (69632, 4096)
    e2e = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "benchmark/configs/minicpm_sala.json"
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 0
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS + SERVING_READERS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    for name in OTHERS:
        assert CELL not in by_name[name]["workloads"]
    for name in NEW_READERS:
        want = ("engine", "%", "program_counter") \
            if name == "blocks_read_share_pct.serve" \
            else ("kernels", "%", "device_trace")
        assert (by_name[name]["layer"], by_name[name]["unit"],
                by_name[name]["source"]) == want
        assert by_name[name]["workloads"][0] == CELL
        reader = harness.load_reader(name, REPO)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            by_name[name]["layer"], by_name[name]["unit"], by_name[name]["moves"])
