"""A serving cell's window, simulated on the host: what ``serve_tokens_per_s``
reads for a given cost of a prefill, WITHOUT the chip. A builder's
instrument, not the benchmark, and no device number comes out of it: it
takes the arrivals, prompt lengths and answer lengths of the cell's own
schedule (``benchmark.traffic.serve_schedule``), a batcher that fills every
free slot at a step's boundary and then decodes one step
(``inference/batcher.py:_admit``), a decode step of ``--decode-ms`` and a
prefill that costs what ``--prefill-ms`` says for its prompt's length, and
counts the tokens whose time falls in the window, as ``benchmark/serve.py``
does.

Why it exists (PERF.md, Findings, PR 46): ``minicpm_sala_serve_longdoc``'s
window of 50 s opens in the wake of the lead-in's first fill of 48 empty
slots, and its tokens/s is not monotonic in a prefill's cost. With the costs
``tools/servescope.py --prefill-lens`` read on the chip for PR 46's parent
and change the simulation reproduces both trees' readings to 1.5%::

    python tools/windowsim.py --workload minicpm_sala_serve_longdoc \\
        --prefill-ms 8192:133.7,16384:286.8,32768:658.0,65536:1637.7 --by bucket
    python tools/windowsim.py --workload minicpm_sala_serve_longdoc \\
        --prefill-ms 8192:132.7,12288:209.0,16384:286.8,20480:379.9,32768:658.0,36864:790.5,65536:1627.7

``--prefill-ms`` is ``length:ms`` pairs: ``--by bucket`` charges a prompt the
smallest listed length that holds it, the default interpolates between the
listed lengths of the prompt's length rounded up to ``--stretch``.
``--scale 1.15,1.0,0.9`` scales the costs; ``--seconds`` and ``--lead-in``
move the window. Ends in one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def prefill_cost(table, by_bucket=False, stretch=4096, host_s=0.004):
    """``cost(prompt length) -> seconds`` of ``table`` {length: ms}."""
    lengths = sorted(table)

    def cost(n):
        if by_bucket:
            return 1e-3 * table[min(b for b in lengths if b >= n)] + host_s
        n = -(-n // stretch) * stretch
        lo = max([b for b in lengths if b <= n] or lengths[:1])
        hi = min([b for b in lengths if b >= n] or lengths[-1:])
        ms = table[lo] if hi == lo else table[lo] + (
            table[hi] - table[lo]) * (n - lo) / (hi - lo)
        return 1e-3 * ms + host_s

    return cost


def simulate(requests, window, cost, decode_s, slots):
    """{"tokens_per_s", "finished", "admitted", "queued"} of the window
    ``(t0, t1)``: ``requests`` [(due s, prompt length, answer length)] in the
    order they fall due; a prefill gives the first token, a decode step one
    token to every row."""
    t0, t1 = window
    t, due, queue, rows = 0.0, 0, [], []
    tokens = finished = admitted = 0
    inside = lambda at: t0 <= at < t1  # noqa: E731
    while t < t1:
        while due < len(requests) and requests[due][0] <= t:
            queue.append(requests[due])
            due += 1
        if not rows and not queue:
            if due == len(requests):
                break
            t = requests[due][0]
            continue
        while queue and len(rows) < slots:   # every free slot, FIFO
            _, prompt, answer = queue.pop(0)
            t += cost(prompt)
            tokens, admitted = tokens + inside(t), admitted + inside(t)
            rows.append(answer - 1)
        t += decode_s
        tokens += len(rows) * inside(t)
        finished += sum(left == 1 for left in rows) * inside(t)
        rows = [left - 1 for left in rows if left > 1]
    return {"tokens_per_s": tokens / (t1 - t0), "finished": finished,
            "admitted": admitted, "queued": len(queue)}


def main(argv):
    from benchmark import harness, traffic

    ap = argparse.ArgumentParser(prog="windowsim")
    ap.add_argument("--workload", default="minicpm_sala_serve_longdoc")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (BENCHMARK.json's run_seconds)")
    ap.add_argument("--lead-in", type=float, default=None,
                    help="where the window opens (the mix's lead_in_s)")
    ap.add_argument("--prefill-ms", required=True,
                    help="length:ms pairs, comma separated")
    ap.add_argument("--by", choices=("stretch", "bucket"), default="stretch")
    ap.add_argument("--stretch", type=int, default=4096)
    ap.add_argument("--decode-ms", type=float, default=6.87)
    ap.add_argument("--scale", default="1.0")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    mix = harness.load_mix(cell, ROOT)
    config = harness.load_config(bench, cell, ROOT)
    seconds = args.seconds or bench["run_seconds"]
    lead = mix["lead_in_s"] if args.lead_in is None else args.lead_in
    # the schedule runs to the window's end wherever the window is put
    schedule = traffic.serve_schedule(
        mix, config["n_vocab"], args.seed, lead + seconds - mix["lead_in_s"])
    requests = [(r["due"], len(r["prompt"]), r["max_new_tokens"])
                for r in schedule["requests"]]
    table = {int(k): float(v) for k, v in
             (pair.split(":") for pair in args.prefill_ms.split(","))}
    out = {"workload": args.workload, "window": [lead, lead + seconds],
           "requests": len(requests), "by": args.by, "runs": {}}
    for scale in [float(x) for x in args.scale.split(",")]:
        cost = prefill_cost({k: v * scale for k, v in table.items()},
                            args.by == "bucket", args.stretch)
        found = simulate(requests, (lead, lead + seconds), cost,
                         1e-3 * args.decode_ms, config["engine"]["batch_size"])
        out["runs"][str(scale)] = found
        print(f"[windowsim] x{scale:g}: {found['tokens_per_s']:.0f} tokens/s, "
              f"{found['finished']} finished, {found['admitted']} admitted, "
              f"{found['queued']} queued at the window's end")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
