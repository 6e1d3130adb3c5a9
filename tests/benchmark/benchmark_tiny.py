"""A tiny copy of the benchmark's tree for the CPU tests: the real
``BENCHMARK.json``, joined by the parked entries (``benchmark/parked.json``),
with the cells renamed onto toy configurations and mixes, the real metric
readers and peaks. ``run_cell`` is the test-only entry: the
harness's own ``main`` with the platform check told to expect the CPU, so
the command itself needs no option for it."""
from __future__ import annotations

import contextlib
import io
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the four-chip ZeRO cell is parked (PERF.md, Open questions): its mix, its
# readers and the mesh path are in the tree, and the CPU (four virtual
# devices) drives it here beside the two cells of BENCHMARK.json
ZERO4 = "tiny_train_zero4"
CELLS = {"bert_large_train_s128": "tiny_train",
         "gpt2_345m_serve_saturate": "tiny_serve",
         "bert_large_train_s128_zero4": ZERO4}


def load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def tiny_bert():
    cfg = load("benchmark/configs/bert_large.json")
    cfg.update(name="bert_tiny", hidden_size=64, intermediate_size=256,
               num_attention_heads=2, num_hidden_layers=2, vocab_size=500,
               max_position_embeddings=32)
    # toy widths, a toy batch: rounding is a far larger share than at size
    cfg["check"] = {"loss_rel.first": 0.02, "loss_rel.later": 0.02,
                    "grad_norm_rel.matrices": 0.3,
                    "grad_norm_rel.vectors": 0.3, "change_norm_rel": 0.3}
    return cfg


def tiny_gpt2():
    cfg = load("benchmark/configs/gpt2_345m.json")
    cfg.update(name="gpt2_tiny", n_vocab=500, n_ctx=128, n_embd=64, n_head=2,
               n_layer=2)
    cfg["engine"].update(batch_size=4, page_size=8)
    cfg["check"] = {"widest_gap": 0.05}
    return cfg


def tiny_train_mix(layout=None):
    mix = load("benchmark/traffic/pretrain_s128_b64.json")
    mix.update(seq_length=16, masked_per_seq=3, global_batch=8,
               valid_length_min=8, pool_batches=6, trace_steps=3,
               reference_block_rows=1 if layout else 4, layout=layout)
    return mix


def tiny_serve_mix():
    mix = load("benchmark/traffic/short_saturate.json")
    mix.update(rate_per_s=20.0, lead_in_s=0.4, tail_s=0.2, trace_s=0.4,
               check_requests=4,
               prompt_len={"dist": "lognormal", "median": 12, "sigma": 0.6,
                           "min": 4, "max": 40},
               answer_len={"dist": "lognormal", "median": 5, "sigma": 0.5,
                           "min": 2, "max": 12})
    return mix


def make_root(root):
    """Write the tiny tree under ``root`` (a path) and return it as str."""
    root = str(root)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(root, "benchmark", sub), exist_ok=True)
    os.symlink(os.path.join(REPO, "benchmark", "metrics"),
               os.path.join(root, "benchmark", "metrics"))
    os.symlink(os.path.join(REPO, "benchmark", "peaks.json"),
               os.path.join(root, "benchmark", "peaks.json"))
    files = {
        "benchmark/configs/bert_tiny.json": tiny_bert(),
        "benchmark/configs/gpt2_tiny.json": tiny_gpt2(),
        "benchmark/traffic/tiny_train.json": tiny_train_mix(),
        "benchmark/traffic/tiny_train_zero4.json": tiny_train_mix(
            {"fsdp": 4, "fsdp_axis": "fsdp", "min_fsdp_size": 1}),
        "benchmark/traffic/tiny_serve.json": tiny_serve_mix()}
    from benchmark import harness

    def tiny(name):
        return name.replace("large", "tiny").replace("345m", "tiny")

    bench = harness.load_benchmark(REPO, parked=True)
    bench["configs"] = [dict(c, name=tiny(c["name"]),
                             file=f"benchmark/configs/{tiny(c['name'])}.json")
                        for c in bench["configs"]]
    bench["workloads"] = [
        dict(w, name=CELLS[w["name"]], traffic=CELLS[w["name"]],
             config=tiny(w["config"]))
        for w in bench["workloads"] if w["name"] in CELLS]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELLS[w] for w in m["workloads"] if w in CELLS]
    files["BENCHMARK.json"] = bench
    files["benchmark/parked.json"] = {"what": "nothing is parked here"}
    for rel, data in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(data, f)
    return root


@contextlib.contextmanager
def cpu_settings(root):
    """What ``harness.main`` sets for a run, put back afterwards: the
    compile cache's place in the environment and jax's cache thresholds."""
    import jax
    import pytest

    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_cache"))
        try:
            yield
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)


def run_cell(root, workload, seconds=1.0, seed=4294967301, trace=0):
    """(run record, stdout) of one run through ``harness.main`` on the CPU."""
    from benchmark import harness

    out = io.StringIO()
    with cpu_settings(root), contextlib.redirect_stdout(out):
        run = harness.main(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace)],
                           platform="cpu", root=root)
    return run, out.getvalue()
