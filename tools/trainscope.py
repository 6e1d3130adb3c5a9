"""The train step of the benchmark's cell, looked at with the program's own
tracing (docs/OBSERVABILITY.md "The step record", "Named scopes"). A
builder's instrument, not the benchmark: it builds the cell's ``TrainStep``
through the benchmark's own adaptor, weights and batches, and then

``pace``    runs the cell's free-running loop (8 steps in flight) for
            ``--seconds`` with telemetry off or on (``--obs 1`` =
            ``obs.enable()``), and prints tokens/s, the median gap between
            step completions and the medians of the host phases from the
            step record. Run from a checkout of an older commit it prints
            the same but for the phases, so that telemetry's cost can be
            compared commit against commit;
``scopes``  traces ``--steps`` steps and prints device seconds of own time
            by named scope (depth 1, and the largest block-level scopes with
            layer numbers folded), the share no scope claims, and the share
            in fusions that no one scope holds most of.

    python tools/trainscope.py pace --obs 1 --seconds 20 --seed 7
    python tools/trainscope.py scopes --steps 12 --seed 7
    JAX_PLATFORMS=cpu python tools/trainscope.py scopes --tiny   # rehearsal

Each run ends in one JSON line, also appended to
``chiprun_out/trainscope.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def build(tiny, seed):
    """(TrainStep, pool of host batches, mix, tokens per step) of the cell,
    or of the CPU tests' toy copy of it."""
    from benchmark import harness, traffic
    from benchmark.weights import make_weights

    root, cell_name, platform = ROOT, "bert_large_train_s128", "tpu"
    if tiny:
        import tempfile

        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import benchmark_tiny

        root = benchmark_tiny.make_root(tempfile.mkdtemp(prefix="trainscope-"))
        cell_name, platform = "tiny_train", "cpu"
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, cell_name)
    config, mix = harness.load_config(bench, cell, root), harness.load_mix(cell, root)
    harness.place_compile_cache(ROOT)
    harness.require_devices(cell["chips"], platform)
    specs = harness.reference_for(config).param_specs(config)
    pool = traffic.train_batches(mix, config["vocab_size"],
                                 config["type_vocab_size"], seed)
    ts, _ = harness.system_for(config).build_train(
        config, mix, make_weights(specs, seed))
    return ts, pool, mix, mix["global_batch"] * mix["seq_length"]


def free_run(ts, pool, ahead, seconds=None, steps=None, start=0):
    """The cell's loop: at most ``ahead`` steps in flight. Returns (steps
    run, window seconds, times each step was seen done)."""
    import jax

    losses, done_t, i = [], [], start
    t0 = time.perf_counter()
    while True:
        losses.append(ts(*pool[i % len(pool)]))
        i += 1
        if len(losses) > ahead:
            jax.block_until_ready(losses[-1 - ahead])
            done_t.append(time.perf_counter() - t0)
        if steps is not None and len(losses) >= steps:
            break
        if seconds is not None and time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(losses[-1])
    return len(losses), time.perf_counter() - t0, done_t


def phase_medians(since):
    """Medians (ms) of the step record's phases over the records written
    after the first ``since``; {} for a program that keeps no record."""
    from mxnet_tpu import observability as obs

    if not hasattr(obs, "step_records"):
        return {}
    recs = obs.step_records("train_step")
    phases = [r.phase_ns() for r in recs[since:]]
    if not phases:
        return {}
    values = {"mx.train.step": [r.duration_ns for r in recs[since:]]}
    for name in phases[0]:
        values[name] = [p[name] for p in phases]
    ms = lambda v: round(v * 1e-6, 4)  # noqa: E731
    # the least and the lowest tenth beside the median: while the device is
    # the bottleneck the host waits inside the call for room in the
    # runtime's queue, and the low end is what the phase costs without it
    return {"records": len(phases),
            "compiled_after_first": sum(r.compiled for r in recs[1:]),
            **{k: ms(statistics.median(v)) for k, v in values.items()},
            "min_p10": {k: [ms(min(v)), ms(statistics.quantiles(v, n=10)[0])]
                        for k, v in values.items()}}


def idle_costs(ts, n_batch, repeats=50):
    """Host ms of each part of ``mx.train.args`` with the device idle (the
    mean of ``repeats`` calls each): issuing them costs this much when
    nothing is queued ahead."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import random as rng

    jax.block_until_ready(ts.params)
    out = {}
    for name, call in (
            ("cache_key", lambda: ts._step_cache_key(n_batch, False)),
            ("next_key", rng.next_key),
            ("lr_wd_scalars", lambda: (jnp.float32(1e-4), jnp.float32(0.0)))):
        t0 = time.perf_counter()
        for _ in range(repeats):
            last = call()
        out[name] = round(1e3 * (time.perf_counter() - t0) / repeats, 4)
        jax.block_until_ready(last)
    return out


def pace(args):
    from mxnet_tpu import observability as obs

    if args.obs:
        obs.enable(os.path.join(ROOT, ".benchmark_trace", "trainscope-obs"))
    ts, pool, mix, tokens = build(args.tiny, args.seed)
    ahead = mix["run_ahead"]
    free_run(ts, pool, ahead, steps=3 + ahead)  # compile, warm up
    since = len(obs.step_records("train_step")) \
        if hasattr(obs, "step_records") else 0
    steps, window_s, done_t = free_run(ts, pool, ahead, seconds=args.seconds,
                                       start=3 + ahead)
    gaps = [b - a for a, b in zip(done_t, done_t[1:])]
    out = {"mode": "pace", "obs": bool(args.obs), "seed": args.seed,
           "steps": steps, "window_s": round(window_s, 4),
           "tokens_per_s": round(steps * tokens / window_s, 1),
           "median_step_ms": round(1e3 * statistics.median(gaps), 3),
           "phases_ms": phase_medians(since),
           "args_idle_ms": idle_costs(ts, len(pool[0]))}
    if args.obs:
        obs.shutdown()
        h = obs.REGISTRY.get("train_step_seconds")
        stats = h.stats(loop="train_step") if h is not None else None
        if stats:
            out["train_step_seconds"] = {
                "count": stats["count"],
                "mean_ms": round(1e3 * stats["sum"] / stats["count"], 3)}
    return out


def fold(path):
    """``.../enc/layer17/attn`` -> ``.../enc/layerN/attn``: one row for the
    same block of every layer."""
    return re.sub(r"\d+", "N", path)


def scopes(args):
    import jax

    from mxnet_tpu.observability import profiling
    from mxnet_tpu.observability.scopes import (MIXED, UNSCOPED, at_depth,
                                                instruction_name)

    ts, pool, mix, _ = build(args.tiny, args.seed)
    ahead = mix["run_ahead"]
    free_run(ts, pool, ahead, steps=3 + ahead)
    t0 = time.perf_counter()
    table = ts.op_scopes(*pool[0])
    table_s = time.perf_counter() - t0
    directory = os.path.join(ROOT, ".benchmark_trace", "trainscope")
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark traces
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        free_run(ts, pool, ahead, steps=args.steps, start=3 + ahead)
    finally:
        jax.profiler.stop_trace()
    report = profiling.measured_report(profiling.parse_trace(directory))
    shutil.rmtree(directory, ignore_errors=True)
    # the device's operations as the benchmark's reducer takes them: the
    # "XLA Ops" line of a TPU plane (the CPU writes them on host threads)
    on_tpu = any(r.lane.startswith("XLA Ops") for r in report.op_rows)
    report.op_rows = [r for r in report.op_rows
                      if r.lane.startswith("XLA Ops") or not on_tpu]
    top = report.scope_seconds(table, depth=1)
    total = sum(top.values())
    blocks = {}
    for path, s in report.scope_seconds(table, depth=None).items():
        blocks[fold(path)] = blocks.get(fold(path), 0.0) + s
    spans = report.span_breakdown()
    per_step = lambda s: round(1e3 * s / args.steps, 3)  # noqa: E731
    # the benchmark's breakdown names operations by kind (``fusion``,
    # ``divide_subtract_fusion``): which scopes own each kind's time
    from benchmark.trace.reduce import op_base

    kinds, shared = {}, {}
    for r, own in zip(report.op_rows, report._self_times()):
        name = instruction_name(r.name)
        split = kinds.setdefault(op_base(r.name), {})
        scope = at_depth(table.get(name, UNSCOPED), 1)
        split[scope] = split.get(scope, 0.0) + own * 1e-9
        if name in table.shared:  # counted under `scope`, holds these too
            key = scope + " also holding " + "+".join(sorted(
                k for k in table.shared[name] if k != scope))
            shared[key] = shared.get(key, 0.0) + own * 1e-9
    return {
        "mode": "scopes", "seed": args.seed, "steps": args.steps,
        "instructions_scoped": len(table), "op_scopes_s": round(table_s, 1),
        "device_busy_s": round(total, 4),
        "ms_per_step": {k: per_step(v) for k, v in
                        sorted(top.items(), key=lambda kv: -kv[1])},
        "share_pct": {k: round(100 * v / total, 2) for k, v in
                      sorted(top.items(), key=lambda kv: -kv[1])},
        "unscoped_pct": round(100 * top.get(UNSCOPED, 0.0) / total, 2),
        "mixed_pct": round(100 * top.get(MIXED, 0.0) / total, 2),
        "blocks_ms_per_step": [[k, per_step(v)] for k, v in sorted(
            blocks.items(), key=lambda kv: -kv[1])[:args.top]],
        "kinds_ms_per_step": {
            k: {scope: per_step(v) for scope, v in
                sorted(split.items(), key=lambda kv: -kv[1])}
            for k, split in sorted(kinds.items(),
                                   key=lambda kv: -sum(kv[1].values()))[:10]},
        "shared_fusions_ms_per_step": {k: per_step(v) for k, v in sorted(
            shared.items(), key=lambda kv: -kv[1])[:8]},
        "unscoped_ops_ms_per_step": [[d["name"].split(" ")[0][:60],
                                      per_step(d["self_ns"] * 1e-9)]
                                     for d in unscoped_ops(report, table)[:8]],
        "host_spans_ms": {k: round(1e3 * v["mean_seconds"], 4)
                          for k, v in sorted(spans.items())
                          if k.startswith("mx.")},
    }


def unscoped_ops(report, table):
    """The hot operations that no scope claims, most own time first."""
    from mxnet_tpu.observability.scopes import instruction_name

    return [d for d in report.hot_ops(10 ** 6)
            if instruction_name(d["name"]) not in table]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("pace", "scopes"))
    ap.add_argument("--seed", type=int, default=4294967301)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=0)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--tiny", action="store_true",
                    help="the CPU tests' toy copy of the cell (a rehearsal)")
    args = ap.parse_args()
    out = {"pace": pace, "scopes": scopes}[args.mode](args)
    import jax

    out["device"] = jax.devices()[0].device_kind
    line = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "trainscope.jsonl"), "a") as f:
        f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
