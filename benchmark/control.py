"""Readings for the limits of ``correct``, on the chip, several seeds in one
process: ``python3 -m benchmark.control --workload <cell> --seeds 1,2,3
[--seconds s] [--control fp8,...|none]``. For each seed one line of JSON:
the numbers a run compares (the program against the reference) and the same
numbers with the reference computed in the precision below the
configuration's (or those named) put in the program's place. The benchmark's own runs never
call this; PERF.md has the readings the limits were set from."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from . import harness


def main(argv, platform="tpu", root=harness.ROOT):
    ap = argparse.ArgumentParser(prog="benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", default=None,
                    help="precisions to put in the program's place, comma "
                         "separated; default the configuration's; 'none'")
    ap.add_argument("--dump", type=int, choices=(0, 1), default=0,
                    help="1: every leaf's readings to chiprun_out/")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark(root, parked=True)
    cell = harness.find_cell(bench, args.workload)
    config = harness.load_config(bench, cell, root)
    mix = harness.load_mix(cell, root)
    harness.place_compile_cache(root)
    devices = harness.require_devices(cell["chips"], platform)
    runner = harness.runner_for(config)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = runner.run(cell=cell, config=config, mix=mix, seed=seed,
                         seconds=args.seconds, trace_on=False, devices=devices,
                         peaks={}, t_start=time.perf_counter(), root=root)
        row = {"workload": cell["name"], "seed": seed,
               "correct": run["correct"],
               "program": {r["name"].split(" ")[0]: r["value"]
                           for r in run["check"]},
               "end_to_end": run["end_to_end"]}
        wanted = (args.control or config["precision"]["control"]).split(",")
        full = {k: run.get("readings", {}).get(k)
                for k in ("program", "reference")}
        for precision in (p for p in wanted if p != "none"):
            low = runner.control(run, config, mix, seed, devices, precision)
            full[precision] = low.pop("readings", None)
            row["control_" + precision] = low
        if args.dump:
            os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
            with open(os.path.join(root, "chiprun_out",
                                   f"readings_{cell['name']}_{seed}.json"),
                      "w") as f:
                json.dump(full, f)
        del run
        gc.collect()
        print(json.dumps(row), flush=True)
        out.append(row)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out",
                           f"control_{cell['name']}.jsonl"), "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in out)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
