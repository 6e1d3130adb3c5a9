"""A serving cell's decode step and prefill, looked at with the program's
own named scopes (docs/OBSERVABILITY.md "Named scopes"). A builder's
instrument, not the benchmark: it builds the cell's engine through the
benchmark's own adaptor and weights, fills every slot with a prompt of the
mix's lengths, decodes ``--ahead`` steps so that the rows hold what they
hold in a window, then traces ``--steps`` decode steps and ``--prefills``
prefills of the mix's median prompt, and prints the device's own time of
ONE decode step and ONE prefill by scope, layer numbers folded, and by
operation the time of every operation whose result has a page pool's shape
(``pool_shaped_ms``: an in-place scatter is some 0.01 ms; a layout change
or a copy of the pool is 0.8 ms for GPT-2 345M's 134 MB, and 86 of them
were a prefill's 72 ms until PR 28; no CPU test can see them), and the time of
the paged kernels by name (``paged_kernel_ms``; they also stand under their
scope, ``layerN/attn`` or ``layerN/mla/core``).

    python tools/servescope.py --workload deepseek_v2_serve_reason --seed 7
    python tools/servescope.py --workload dots3_note_serve_longctx --depth 4
    python tools/servescope.py --workload smallthinker_21b_serve_mixed --prefill-lens 8192
    JAX_PLATFORMS=cpu python tools/servescope.py --tiny     # rehearsal

``--depth 4`` tells ``layerN/mla/dsa/index`` from ``layerN/mla/dsa/select``
(a full layer's scoring of the held index keys and its selection).
``--prefill-lens 16384,8192`` traces one prefill of each length besides and
prints ``prefill_programs``: the program's device time and each scope's as
the UNION of its operations' intervals (``benchmark/trace/reduce.py``: an
asynchronous copy's whole span is then counted once, where it covers nothing
else, and not added to every operation it overlaps).

Ends in one JSON line, also appended to ``chiprun_out/servescope.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def build(workload, tiny, seed):
    """(engine, mix, config) of the cell, or of the CPU tests' toy copy."""
    from benchmark import harness
    from benchmark.weights import make_weights

    root, platform = ROOT, "tpu"
    if tiny:
        import importlib
        import tempfile

        # the toy copy lives with the CPU tests of the cell's configuration
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        bench = harness.load_benchmark(ROOT)
        cell = harness.find_cell(bench, workload)
        try:
            toy = importlib.import_module(f"test_benchmark_{cell['config']}")
        except ModuleNotFoundError:   # named for the model, not its size
            model = harness.load_config(bench, cell, ROOT)["model"]
            toy = importlib.import_module(f"test_benchmark_{model}")
        root = toy.make_root(tempfile.mkdtemp(prefix="servescope-"))
        workload, platform = toy.TINY, "cpu"
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, workload)
    config, mix = harness.load_config(bench, cell, root), harness.load_mix(cell, root)
    harness.place_compile_cache(ROOT)
    harness.require_devices(cell["chips"], platform)
    weights = make_weights(harness.reference_for(config).param_specs(config), seed)
    engine, _ = harness.system_for(config).build_serve(config, weights)
    del weights
    return engine, mix, config


def by_scope(report, table, per, depth=3):
    """{scope path, layers folded, model name dropped, ``depth`` components
    deep: ms of own time per ``per`` calls}, largest first."""
    out = {}
    for path, s in report.scope_seconds(table, depth=None).items():
        path = re.sub(r"layer\d+", "layerN", path.split("/", 1)[-1])
        path = "/".join(path.split("/")[:depth])  # layerN/mla/core, no deeper
        out[path] = out.get(path, 0.0) + 1e3 * s / per
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def program_by_scope(report, table, per, depth=3):
    """One program's traced calls as ``benchmark/trace/reduce.py`` reads a
    trace: {"busy_ms": the union of every operation's interval, "scope_ms":
    {scope path: the union of the intervals of the scope's operations that
    cover no other}, "op_ms": the ten kinds of operation with most own
    time}, per ``per`` calls."""
    from benchmark.trace import reduce as red
    from mxnet_tpu.observability.scopes import instruction_name

    rows = [r for r in report.op_rows if r.lane.startswith("XLA Ops")]
    events = [(r.hlo_op or r.name, r.start_ns * 1e-9, r.dur_ns * 1e-9)
              for r in rows or report.op_rows]   # the CPU has no such line
    lo = min(a for _, a, _ in events)
    hi = max(a + d for _, a, d in events)
    spans = {}
    for name, a, b in red.leaves(events, lo, hi):
        path = table.get(instruction_name(name), "unscoped")
        path = re.sub(r"layer\d+", "layerN", path.split("/", 1)[-1])
        spans.setdefault("/".join(path.split("/")[:depth]), []).append((a, b))
    ms = lambda s: round(1e3 * s / per, 3)  # noqa: E731
    scope_ms = {k: ms(red.measure(v)) for k, v in spans.items()}
    return {"busy_ms": ms(red.measure([(a, b) for _, a, b
                                       in red.clip(events, lo, hi)])),
            "scope_ms": dict(sorted(scope_ms.items(), key=lambda kv: -kv[1])),
            "op_ms": {k: ms(v) for k, v in
                      red.top(red.per_op_seconds(events, lo, hi))}}


def scopes_and_pool_ops(engine, report, lowered, per, depth=3):
    """(ms by scope, {opcode: ms} of the traced operations whose result has
    the shape of one of the engine's page pools, {kernel: ms} of the paged
    Pallas kernels), per ``per`` calls, from one more compile of ``lowered``
    (``scopes.scoped_text`` says why)."""
    from mxnet_tpu.observability.scopes import (instruction_name,
                                                op_scopes_from_hlo,
                                                scoped_text)

    text = scoped_text(lowered)
    table = op_scopes_from_hlo(text, scopes=(engine.net._scope_label(None),))
    pools = {f"[{','.join(map(str, p.shape))}]"
             for layer in engine.pools for p in layer}
    opcode = {}
    for name, shape, op in re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+(\[[\d,]*\])\S* ([\w\-]+)\(",
            text, re.M):
        if shape in pools:
            opcode[name] = op
    ms, kernels = {}, {}
    for row, own in zip(report.op_rows, report._self_times()):
        name = instruction_name(row.hlo_op or row.name)
        op = opcode.get(name)
        if op:
            ms[op] = ms.get(op, 0.0) + own / 1e6 / per
        kernel = re.match(r"paged_\w*(?:attention|scores|gqa)\w*?(?=[.\d]*$)",
                          name)
        if kernel:
            kernels[kernel[0]] = kernels.get(kernel[0], 0.0) + own / 1e6 / per
    rounded = lambda d: {k: round(v, 4) for k, v in d.items()}  # noqa: E731
    return by_scope(report, table, per, depth), rounded(ms), rounded(kernels)


def main(argv):
    from benchmark import traffic
    from mxnet_tpu.observability import profiling
    from mxnet_tpu.observability.scopes import op_scopes_from_hlo, scoped_text

    ap = argparse.ArgumentParser(prog="servescope")
    ap.add_argument("--workload", default="deepseek_v2_serve_reason")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--prefills", type=int, default=4)
    ap.add_argument("--ahead", type=int, default=300)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--prefill-lens", default="",
                    help="prompt lengths whose prefill programs are traced "
                         "besides, each by scope as the union of intervals")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    engine, mix, config = build(args.workload, args.tiny, args.seed)
    rng = traffic.rng_for(args.seed, 5)
    lengths = traffic.quantile_lengths(mix["prompt_len"], engine.batch_size)
    for slot, n in enumerate(rng.permutation(lengths)[1:], start=1):
        engine.prefill(rng.integers(1, config["n_vocab"], int(n)).tolist(), slot)
    for _ in range(min(args.ahead, mix["answer_len"]["min"] - 1)):
        engine.decode_step()
    held = int(engine.positions[~engine.done].sum())
    out = {"workload": args.workload, "rows": int((~engine.done).sum()),
           "held_positions": held, "read_path": engine.read_path}
    cap = profiling.capture(engine.decode_step, steps=args.steps, warmup=2)
    out["decode_ms_by_scope"], pool_ms, kernel_ms = scopes_and_pool_ops(
        engine, cap.report, engine.lower_decode(), args.steps, args.depth)
    out["pool_shaped_ms"] = {"decode": pool_ms}
    out["paged_kernel_ms"] = {"decode": kernel_ms}
    median = mix["prompt_len"]["median"]
    prompt = rng.integers(1, config["n_vocab"], median).tolist()
    cap = profiling.capture(lambda: engine.prefill(prompt, 0),
                            steps=args.prefills, warmup=1)
    out["prefill_bucket"] = engine.bucket_for(median)
    (out["prefill_ms_by_scope"], out["pool_shaped_ms"]["prefill"],
     out["paged_kernel_ms"]["prefill"]) = scopes_and_pool_ops(
        engine, cap.report, engine.lower_prefill(median), args.prefills,
        args.depth)
    out["prefill_programs"] = {}
    for n in [int(x) for x in args.prefill_lens.split(",") if x]:
        long_prompt = rng.integers(1, config["n_vocab"], n).tolist()
        cap = profiling.capture(lambda: engine.prefill(long_prompt, 0),
                                steps=2, warmup=1)
        table = op_scopes_from_hlo(
            scoped_text(engine.lower_prefill(n)),
            scopes=(engine.net._scope_label(None),))
        found = program_by_scope(cap.report, table, 2, args.depth)
        out["prefill_programs"][str(engine.bucket_for(n))] = found
        print(f"[servescope] prefill of {n} tokens: busy "
              f"{found['busy_ms']:.1f} ms; by operation {found['op_ms']}")
        for path, ms in found["scope_ms"].items():
            print(f"[servescope]   {ms:9.3f}  {path}")
    for key in ("decode_ms_by_scope", "prefill_ms_by_scope"):
        print(f"[servescope] {key} (sum {sum(out[key].values()):.3f} ms):")
        for path, ms in out[key].items():
            print(f"[servescope]   {ms:9.3f}  {path}")
    print(f"[servescope] pool_shaped_ms: {out['pool_shaped_ms']}")
    print(f"[servescope] paged_kernel_ms: {out['paged_kernel_ms']}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "servescope.jsonl"), "a") as f:
        f.write(json.dumps(out) + "\n")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
