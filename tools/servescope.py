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
prints ``prefill_programs`` (by bucket; a prompt that ends short of its
bucket, as 36864 in 65,536, under ``"65536:36864"``: a program that runs only
the stretches its prompt reaches costs what the prompt's length costs): the
program's device time and each scope's as
the UNION of its operations' intervals (``benchmark/trace/reduce.py``: an
asynchronous copy's whole span is then counted once, where it covers nothing
else, and not added to every operation it overlaps).

``--idle`` (PR 38) looks at the HOST's side instead: it serves the cell's own
open-loop traffic through the benchmark's ``Server`` (warm-up, the mix's
lead-in, then a traced slice of the mix's ``trace_s``, as a traced run of
the benchmark does behind its window), keeps the program's
``mx.*`` host spans of the trace (``benchmark/trace/reduce.py`` keeps
``bench.*`` alone) and gives every instant of every idle gap of the device to
the innermost span over it: ``mx.gen.step.tokens``, ``mx.gen.decode.dispatch``,
... (``idle_by_span_s``; what no ``mx.*`` span covers goes to the ``bench.*``
span over it, or to ``outside batcher.step()``), and prints, of the slice's
``serve_step``, ``prefill`` and ``decode_step`` records, the mean
milliseconds of every phase, the mean of every count a record (``queued``,
``steady``, ``pages``, ... : the counts no benchmark metric reads are read
here) and how many of the calls compiled a program (``record_phase_ms``),
in any cell.

    python tools/servescope.py --workload gpt2_345m_serve_saturate --idle

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
    """(engine, batcher, mix, config) of the cell, or of the CPU tests' toy
    copy."""
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
    engine, batcher = harness.system_for(config).build_serve(config, weights)
    del weights
    return engine, batcher, mix, config


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


def innermost_segments(spans):
    """Disjoint ``(start, end, name)`` in order: at every instant that some
    span of ``spans`` (``(name, start, end)``, one thread's, so they nest)
    covers, the one that began last."""
    out, stack, at = [], [], 0.0

    def close(until):
        nonlocal at
        while stack and stack[-1][2] <= until:
            name, _, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(a)
        if stack and a > at:
            out.append((at, a, stack[-1][0]))
        at = max(at, a) if stack else a
        stack.append((name, a, b))
    close(float("inf"))
    return out


def idle_by_span(ops, host_spans, lo, hi, between_ops_s=2e-6):
    """{label: idle seconds} over [lo, hi]: every instant in which no device
    operation ran, under the innermost ``mx.*`` host span over it; else the
    ``bench.*`` span over it; else ``outside batcher.step()``. A gap too
    short for the host to matter is ``device.between_ops``, as in
    ``benchmark/trace/reduce.py:idle_gaps``, whose arithmetic this is."""
    from benchmark.trace import reduce as red

    busy = red.merge([(a, b) for _, a, b in red.clip(ops, lo, hi)])
    gaps = red.subtract([(lo, hi)], busy)
    out = {"device.between_ops": sum(b - a for a, b in gaps
                                     if b - a <= between_ops_s)}
    rest = [(a, b) for a, b in gaps if b - a > between_ops_s]
    for prefix in ("mx.", "bench."):
        spans = [s for s in red.clip(host_spans, lo, hi)
                 if s[0].startswith(prefix)]
        at = 0  # both lists are sorted and disjoint: one walk over the two
        for a, b, name in innermost_segments(spans):
            while at < len(rest) and rest[at][1] <= a:
                at += 1
            k = at
            while k < len(rest) and rest[k][0] < b:
                out[name] = out.get(name, 0.0) \
                    + min(b, rest[k][1]) - max(a, rest[k][0])
                k += 1
        rest = red.subtract(rest, [(a, b) for _, a, b in spans])
    out["outside batcher.step()"] = red.measure(rest)
    return {k: v for k, v in sorted(out.items(), key=lambda kv: -kv[1]) if v}


def host_spans_of(path, prefixes=("mx.", "bench.")):
    """The host plane's spans whose names begin with one of ``prefixes``, as
    (name, start_s, duration_s); a ``TraceAnnotation``'s arguments
    (``#step=7#``) are cut off the name."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(prefixes) and e.duration_ns > 0:
                    out.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def record_phase_ms(lo_s, hi_s):
    """{loop: {"calls", "call_ms", phase: mean ms, "compiled": calls that
    compiled a program, "counts": {count: mean a call}}} of the records of
    the serving loops that began in [lo_s, hi_s) of the host's clock (the
    decode record's ``counts`` are the program's own, a list a layer, and
    have readers in ``benchmark/metrics``)."""
    from mxnet_tpu import observability as obs

    out = {}
    for loop in ("serve_step", "prefill", "decode_step"):
        recs = [r for r in obs.step_records(loop)
                if lo_s <= 1e-9 * r.t0_ns < hi_s]
        if not recs:
            continue
        total, counts = {}, {}
        for r in recs:
            for name, ns in r.phase_ns().items():
                total[name] = total.get(name, 0) + ns
            if loop != "decode_step":  # one host integer a count
                for name, n in (r.counts or {}).items():
                    counts[name] = counts.get(name, 0) + n
        out[loop] = {"calls": len(recs), "call_ms": round(
            1e-6 * sum(r.duration_ns for r in recs) / len(recs), 4),
            **{k: round(1e-6 * v / len(recs), 4) for k, v in total.items()},
            "compiled": sum(bool(r.compiled) for r in recs)}
        if counts:
            out[loop]["counts"] = {k: round(v / len(recs), 4)
                                   for k, v in counts.items()}
    return out


def idle(args):
    """``--idle``: the cell's traffic, a traced slice, the device's idle
    time by what the host was doing."""
    import shutil

    import jax

    from benchmark import harness, serve, tracing, traffic
    from benchmark.trace import reduce as red

    engine, batcher, mix, config = build(args.workload, args.tiny, args.seed)
    longest = mix["prompt_len"]["max"]
    rng = traffic.rng_for(args.seed, 4)
    for b in engine.prefill_buckets:  # every program the mix can reach
        if b <= engine.bucket_for(longest):
            batcher.submit(rng.integers(1, config["n_vocab"],
                                        min(b, longest)).tolist(),
                           max_new_tokens=3)
    batcher.run_until_idle()
    # the benchmark's own period of traffic; the slice lies in its first
    # seconds, where a traced run's lies behind its last
    schedule = traffic.serve_schedule(
        mix, config["n_vocab"], args.seed,
        harness.load_benchmark(ROOT)["run_seconds"])
    origin = serve.clock()
    gen = serve.Generator(schedule["requests"], origin)
    server = serve.Server(engine, batcher, gen)
    directory = os.path.join(ROOT, ".benchmark_trace")
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host
    options.host_tracer_level = 1
    gen.start()
    try:
        server.until(origin + mix["lead_in_s"])
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            server.until(serve.clock() + 1.0)  # the profiler's start is past
            t_a = serve.clock()
            with tracing.span(tracing.WINDOW):
                server.until(t_a + mix["trace_s"])
            t_b = serve.clock()
        finally:
            jax.profiler.stop_trace()
    finally:
        gen.stop.set()
        gen.join(timeout=10.0)
    path = red.find_trace(directory)
    platform = jax.devices()[0].platform
    ops = next(iter(red.load(path, platform)["devices"].values()))["ops"]
    spans = host_spans_of(path)
    shutil.rmtree(directory, ignore_errors=True)
    lo, hi = next((a, a + d) for n, a, d in spans if n == tracing.WINDOW)
    spans = [s for s in spans if s[0] != tracing.WINDOW]
    table = idle_by_span(ops, spans, lo, hi)
    idle_s = sum(table.values())
    named = sum(v for k, v in table.items() if k.startswith("mx.gen."))
    steps = [s for s in server.steps if t_a <= s["t1"] < t_b]
    out = {"workload": args.workload, "idle": True, "window_s": hi - lo,
           "idle_s": idle_s, "idle_pct": 100.0 * idle_s / (hi - lo),
           "idle_under_mx_gen_pct": 100.0 * named / idle_s if idle_s else None,
           "idle_by_span_s": {k: round(v, 6) for k, v in table.items()},
           "steps": len(steps),
           "bench_step_ms": 1e3 * sum(s["t1"] - s["t0"] for s in steps)
           / max(len(steps), 1),
           "record_phase_ms": record_phase_ms(t_a, t_b)}
    print(f"[servescope] {args.workload}: traced {hi - lo:.3f} s, "
          f"{len(steps)} steps; the device idle {idle_s:.3f} s "
          f"({out['idle_pct']:.2f}%), of which under mx.gen.* spans "
          f"{named:.3f} s")
    for name, s in table.items():
        print(f"[servescope]   {s:9.4f} s  {100.0 * s / idle_s:6.2f}%  {name}")
    for loop, row in out["record_phase_ms"].items():
        print(f"[servescope] {loop}: {row}")
    return out


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
    ap.add_argument("--idle", action="store_true",
                    help="serve the cell's traffic and give the device's "
                         "idle time to the mx.* host span over it")
    args = ap.parse_args(argv)
    if args.idle:
        return emit(idle(args))
    engine, _, mix, config = build(args.workload, args.tiny, args.seed)
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
        bucket = engine.bucket_for(n)  # a prompt short of it: "<bucket>:<n>"
        out["prefill_programs"][str(bucket) if n == bucket
                                else f"{bucket}:{n}"] = found
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
    return emit(out)


def emit(out):
    """The run's one JSON line, printed and kept."""
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "servescope.jsonl"), "a") as f:
        f.write(json.dumps(out) + "\n")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
