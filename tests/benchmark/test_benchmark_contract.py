"""``BENCHMARK.json`` against the contract and against the files it names,
and the harness's lookup on files a later change would add."""
import json
import os
import re

import pytest

from benchmark import harness

from benchmark_tiny import REPO

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|expan|"
                   r"experts_per|n_embd|_dim$|_rank$)")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(REPO)


def cells_of(bench, metric):
    return set(metric.get("workloads", [w["name"] for w in bench["workloads"]]))


def test_the_file_has_exactly_the_contracts_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert bench["command"] == ["python3", "-m", "benchmark.run"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    # a full check of 24 cells at this length fits the driver's 43200 s
    assert ((2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_lines_use_only_what_is_allowed(bench):
    names = []
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
                        ("per_layer", {"name", "unit", "better", "source", "layer",
                                       "moves"})):
        for entry in bench[group]:
            assert set(entry) - {"workloads"} == keys, entry
            assert NAME.fullmatch(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                text = entry.get(key, "x")
                assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in bench["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_configuration_is_a_file_of_its_own_used_by_a_cell(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        cfg = harness.load_json(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"] and cfg["kind"] in harness.RUNNERS
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)], "a width was cut"
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        # the references and the adaptor it names exist
        assert harness.reference_for(cfg).param_specs(cfg)
        assert harness.system_for(cfg)
        assert all(isinstance(v, (int, float)) and v > 0
                   for v in cfg["check"].values()), "a limit is not set"


def test_every_cell_finds_its_configuration_mix_and_readers(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in bench["workloads"]:
        config = harness.load_config(bench, cell, REPO)
        mix = harness.load_mix(cell, REPO)
        assert mix["kind"] == config["kind"]
        mine = [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layer = harness.metrics_of(bench, cell, "per_layer")
        assert layer
        assert harness.reference_for(config).param_specs(config)
        assert harness.runner_for(config).run and harness.system_for(config)
        for m in layer:
            # a cell that reports a layer metric reports what it moves
            assert m["moves"] in mine, (cell["name"], m["name"])


def test_the_two_cells_report_what_the_issue_of_pr_26_counts(bench):
    by_cell = {c["name"]: {m["name"] for m in harness.metrics_of(
        bench, c, "per_layer")} for c in bench["workloads"]}
    assert len(by_cell["gpt2_345m_serve_saturate"]) == 13  # moved with it
    assert len(by_cell["bert_large_train_s128"]) == 10
    assert "packed_attention_roofline_pct.train" in by_cell["bert_large_train_s128"]
    assert not by_cell["bert_large_train_s128"] & by_cell["gpt2_345m_serve_saturate"]
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"] == ["gpt2_345m_serve_saturate"]
    assert 0.01 <= serve["bound"] <= 0.05
    # the serving cell compares the worst position and the mean over all: the
    # cache alone in 8 bits passes the first on one seed in three, not the second
    check = harness.load_config(bench, harness.find_cell(
        bench, "gpt2_345m_serve_saturate"), REPO)["check"]
    assert set(check) == {"widest_gap", "mean_gap"}
    assert 0 < check["mean_gap"] < check["widest_gap"] / 100


def test_every_layer_metric_has_a_reader_that_declares_the_same(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        reader = harness.load_reader(m["name"], REPO)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"]), m["name"]
        assert m["moves"] in e2e and callable(reader.read)
        assert cells_of(bench, m) <= {w["name"] for w in bench["workloads"]}
        layers.setdefault(m["layer"], []).append(m["name"])
    on_disk = {f[:-3] for f in os.listdir(os.path.join(REPO, "benchmark", "metrics"))
               if f.endswith(".py")}
    # every metric has its reader, and every reader its entry: in
    # BENCHMARK.json, or parked with its cell
    assert {m["name"] for m in bench["per_layer"]} <= on_disk
    parked = harness.load_benchmark(REPO, parked=True)
    assert {m["name"] for m in parked["per_layer"]} == on_disk
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_parked_entries_join_the_file_and_find_their_files(bench):
    """``benchmark/parked.json``: what a later change moves into
    BENCHMARK.json as it stands. The command never sees it."""
    parked = harness.load_json(os.path.join(REPO, "benchmark", "parked.json"))
    both = harness.load_benchmark(REPO, parked=True)
    mine = {w["name"] for w in bench["workloads"]}
    theirs = {w["name"] for w in parked["workloads"]}
    assert not mine & theirs
    # PR 26 moved the saturated serving cell out; these two wait
    assert mine == {"bert_large_train_s128", "gpt2_345m_serve_saturate"}
    assert theirs == {"gpt2_345m_serve_short", "bert_large_train_s128_zero4"}
    assert "gpt2_345m_serve_saturate" not in json.dumps(
        {k: v for k, v in parked.items() if k != "what"})
    assert [w["name"] for w in both["workloads"]] == \
        [w["name"] for w in bench["workloads"] + parked["workloads"]]
    with pytest.raises(KeyError):
        harness.find_cell(bench, sorted(theirs)[0])
    for cell in parked["workloads"]:
        config = harness.load_config(both, cell, REPO)
        assert harness.load_mix(cell, REPO)["kind"] == config["kind"]
        assert 1 <= len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    for m in parked["per_layer"]:
        reader = harness.load_reader(m["name"], REPO)
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"]), m["name"]
        assert set(m["workloads"]) <= theirs
    # a metric both have keeps its own cells and gains the parked ones
    step = next(m for m in both["per_layer"] if m["name"] == "step_ms.train")
    assert step["workloads"] == ["bert_large_train_s128",
                                 "bert_large_train_s128_zero4"]
    for cell in parked["workloads"]:
        got = [m["name"] for m in harness.metrics_of(both, cell, "end_to_end")]
        layer = harness.metrics_of(both, cell, "per_layer")
        assert "setup_s" in got and all(m["moves"] in got for m in layer)
    assert bench == harness.load_benchmark(REPO)  # joining edits no entry


def test_files_under_paths_have_names_of_allowed_characters(bench):
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", rel), rel


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_raises():
    peaks = harness.peaks_for("TPU v5 lite", REPO)
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary", REPO)


def test_no_tpu_is_an_exception_not_a_fallback():
    with pytest.raises(RuntimeError, match="needs 1 tpu"):
        harness.require_devices(1)  # the tests run on the CPU


def test_a_later_change_adds_files_and_entries_and_edits_nothing(tmp_path):
    """A configuration, a mix and a per-layer metric added as new files plus
    entries: the harness's lookup finds each by the name in BENCHMARK.json."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        (root / "benchmark" / sub).mkdir(parents=True)
    (root / "benchmark" / "configs" / "new_model.json").write_text(json.dumps(
        {"name": "new_model", "kind": "serve", "model": "gpt2", "n_layer": 3}))
    (root / "benchmark" / "traffic" / "new_mix.json").write_text(json.dumps(
        {"kind": "serve", "rate_per_s": 3.0}))
    (root / "benchmark" / "metrics" / "new_share_pct.itl.py").write_text(
        'LAYER, UNIT, MOVES = "engine", "%", "itl_p90_ms"\n\n\n'
        'def read(run):\n    return run.get("new_share")\n')
    bench = {"configs": [{"name": "new_model",
                          "file": "benchmark/configs/new_model.json"}],
             "workloads": [{"name": "new_model.new_mix", "config": "new_model",
                            "traffic": "new_mix", "chips": 1}],
             "end_to_end": [{"name": "itl_p90_ms", "unit": "ms"},
                            {"name": "other", "unit": "s", "workloads": ["x"]}],
             "per_layer": [{"name": "new_share_pct.itl", "unit": "%",
                            "workloads": ["new_model.new_mix"]}]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = harness.load_benchmark(str(root))
    cell = harness.find_cell(bench, "new_model.new_mix")
    config = harness.load_config(bench, cell, str(root))
    assert config["n_layer"] == 3
    assert harness.load_mix(cell, str(root))["rate_per_s"] == 3.0
    assert harness.runner_for(config).__name__ == "benchmark.serve"
    assert harness.reference_for(config).__name__ == "benchmark.reference.gpt2"
    reader = harness.load_reader("new_share_pct.itl", str(root))
    assert reader.read({"new_share": 12.5}) == 12.5 and reader.read({}) is None
    assert [m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")] == \
        ["itl_p90_ms"]
    with pytest.raises(KeyError):
        harness.find_cell(bench, "absent")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("absent_metric", str(root))
