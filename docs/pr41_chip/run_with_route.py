"""PR 41: one run of a cell as `python3 -m benchmark.run` makes it, from the
tree it is started in, then what the expert layers' route read: the counter
`moe_route_total{path}` over the whole process and `moe_whole_path` over the
window's prefill and decode records (a tree without them prints none)."""
import json
import os
import sys

sys.path.insert(0, os.getcwd())
from benchmark import harness  # noqa: E402
from benchmark.serverecords import window_records  # noqa: E402

run = harness.main(sys.argv[1:])
from mxnet_tpu import observability as obs  # noqa: E402
import numpy as np  # noqa: E402

route = obs.counter("moe_route_total")
out = {"moe_route_total": {p: route.value(path=p) for p in ("prefix", "whole")}}
for loop in ("prefill", "decode_step"):
    recs = [r for r in window_records(run, loop)
            if r.counts and "moe_whole_path" in r.counts]
    out[loop] = {"records": len(recs), "moe_whole_path": int(sum(
        np.sum(r.counts["moe_whole_path"]) for r in recs))}
    if loop == "decode_step" and recs:
        out[loop]["calls"] = int(sum(np.size(r.counts["moe_whole_path"]) for r in recs))
        out[loop]["most_pairs_held"] = int(max(np.max(r.counts["moe_pairs_held"]) for r in recs))
print("route " + json.dumps(out), file=sys.stderr, flush=True)
