"""The least time the chip's memory could take to feed the indexer's scoring
of the traced decode steps (``index_score_bytes`` of the configuration's
reference: in every full layer the index key of every position the rows hold,
read once; over the peak bandwidth) as a share of the device time of the
operations the configuration names under ``trace_names.index_scores``: for
``dots3_note`` the one kernel ``paged_index_scores``, which reads those keys
and nothing else of the cache. Both halves are the scoring read's own. The
selection (``sort``) and the sparse read of the selected latents (an XLA
gather, which a device trace calls ``fusion`` like every other fusion) are
not in it: ``tools/servescope.py`` has them by scope, and a share of the
sparse read's own roofline waits for a reduction that keys operations by scope
(PERF.md section 7). Nothing to read where the configuration names none or
the trace holds none of them (a program without the mechanism)."""
from benchmark.harness import reference_for
from benchmark.records import window_steps

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    names = run["config"].get("trace_names", {}).get("index_scores")
    ref = reference_for(run["config"]) if names else None
    if run["kind"] != "serve" or not trace or not hasattr(
            ref, "index_score_bytes"):
        return None
    device_s = sum(s for op, s in trace["ops"].items() if op in names)
    want = run["config"]["trace_names"]["decode_module"]
    calls = sum(n for k, (n, _) in trace["modules"].items() if want in k)
    steps = [s for s in window_steps(run, "trace_span") if s["decoded_rows"]]
    if not device_s or not calls or not steps:
        return None
    held = sum(s["held_positions"] for s in steps) / len(steps)
    need = calls * ref.index_score_bytes(run["config"], held)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
