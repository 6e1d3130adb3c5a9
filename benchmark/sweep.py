"""The sweep that finds a serving cell's knee, once, on the chip:
``python3 -m benchmark.sweep --workload <cell> --rates 4,6,8,10,12
--seconds 30``. One process; each rate is the cell's mix with only
``rate_per_s`` changed and a seed of its own. The knee is the highest rate
at which the backlog at the window's end is no larger than at its start
and nothing failed; the cell's rate is four fifths of it, written into the
mix file by hand with the table in PERF.md."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from . import harness
from .records import window_steps


def main(argv, platform="tpu", root=harness.ROOT):
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1000)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark(root, parked=True)
    cell = harness.find_cell(bench, args.workload)
    config = harness.load_config(bench, cell, root)
    base = harness.load_mix(cell, root)
    harness.place_compile_cache(root)
    devices = harness.require_devices(cell["chips"], platform)
    runner = harness.runner_for(config)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(base, rate_per_s=rate)
        run = runner.run(cell=cell, config=config, mix=mix, seed=args.seed + i,
                         seconds=args.seconds, trace_on=False, devices=devices,
                         peaks={}, t_start=time.perf_counter(), root=root)
        steps = window_steps(run)
        decode = [s["decode_s"] for s in steps if s["decoded_rows"]]
        row = {"rate_per_s": rate, "seed": args.seed + i,
               "attempted": run["attempted"], "failed": run["failed"],
               "correct": run["correct"],
               "backlog_start": steps[0]["pending"] if steps else None,
               "backlog_end": steps[-1]["pending"] if steps else None,
               "backlog_max": max((s["pending"] for s in steps), default=None),
               "rows_mean": sum(s["decoded_rows"] for s in steps) / max(len(steps), 1),
               "decode_step_ms": 1e3 * sum(decode) / max(len(decode), 1),
               "tokens_per_s": run["end_to_end"]["serve_tokens_per_s"],
               "widest_gap": run["check"][0]["value"],
               **{k: run["end_to_end"][k] for k in ("ttft_p90_ms", "itl_p90_ms",
                                                    "setup_s")}}
        del run
        gc.collect()
        print(json.dumps(row), flush=True)
        rows.append(row)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", f"sweep_{cell['name']}.jsonl"),
              "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
