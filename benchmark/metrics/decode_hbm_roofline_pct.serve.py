"""The least time the chip's memory could take to feed one decode step (the
bytes it has to read: every weight once, plus the keys and values of the
positions the rows hold, by ``decode_step_bytes`` of the configuration's
reference, over the peak bandwidth) as a share of the decode program's
device time per step in the trace. Bound by bytes, not by operations."""
from benchmark.harness import reference_for
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if run["kind"] != "serve" or not trace:
        return None
    want = run["config"]["trace_names"]["decode_module"]
    calls = [v for k, v in trace["modules"].items() if want in k]
    steps = [s for s in window_steps(run, "trace_span") if s["decoded_rows"]]
    if not calls or not steps:
        return None
    device_s = sum(s for _, s in calls) / sum(n for n, _ in calls)
    held = sum(s["held_positions"] for s in steps) / len(steps)
    need = reference_for(run["config"]).decode_step_bytes(run["config"], held)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
