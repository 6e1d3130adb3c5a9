"""The least time the chip's memory could take to feed the grouped-heads
decode reads of the traced decode steps (``gqa_read_bytes`` of the
configuration's reference: the key and the value of every position the
layers' softmaxes READ, by the program's own counts in its decode step
records, ``attn_read_full`` and ``attn_read_window``, an entry per layer:
every position a row holds in a full layer, the last window's worth in a
window layer; over the peak bandwidth) as a share of the device time of the
operations the configuration names under ``trace_names.gqa_decode``: for
``smallthinker_21b`` the one kernel ``paged_gqa_decode``, which reads those
keys and values and nothing else of the cache. Both halves are the kernel's
own. Nothing to read where the configuration names none, the program keeps
no such counts, or the trace holds none of the operations (a program without
the mechanism)."""
from benchmark.decoderecords import decode_counts
from benchmark.harness import reference_for

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    names = run["config"].get("trace_names", {}).get("gqa_decode")
    ref = reference_for(run["config"]) if names else None
    if run["kind"] != "serve" or not trace or not run.get("trace_span") \
            or not hasattr(ref, "gqa_read_bytes"):
        return None
    device_s = sum(s for op, s in trace["ops"].items() if op in names)
    want = run["config"]["trace_names"]["decode_module"]
    calls = sum(n for k, (n, _) in trace["modules"].items() if want in k)
    traced = dict(run, window=run["trace_span"])
    reads = [sum(a) + sum(b) for a, b in zip(
        decode_counts(traced, "attn_read_full"),
        decode_counts(traced, "attn_read_window"))]
    if not device_s or not calls or not reads:
        return None
    need = calls * ref.gqa_read_bytes(run["config"], sum(reads) / len(reads))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
