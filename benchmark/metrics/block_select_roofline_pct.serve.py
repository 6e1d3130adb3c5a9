"""The least time the chip's memory could take to feed the selection's scoring
of the traced decode steps (``block_select_bytes`` of the configuration's
reference: in every sparse layer the compressed keys, every key-value
head's, of the blocks the rows hold, read once, by the program's own count in
its decode step records, ``blocks_held``, an entry per sparse layer; over the
peak bandwidth) as a share of the device time of the operations the
configuration names under ``trace_names.block_select``: for ``minicpm_sala``
the one kernel ``paged_block_scores``, which weighs a row's compressed keys
as its page table gathered them and leaves a group's summed softmax weights.
The kernel reads the table's whole width (what a row does not hold weighs
nothing), so the share cannot pass held over capacity: the gather that would
walk the pool by the table inside the kernel is PERF.md section 7's. XLA's
gather of the compressed-key pages before it, the pooling to blocks and the
``top_k`` after it are fusions and a sort, which a device trace does not tell
from any other: ``tools/servescope.py`` has them by scope
(``sparse/select``). Nothing to read where the configuration names none, the
reference counts no such bytes, the program keeps no such count, or the trace
holds none of the operations (a program without the mechanism)."""
from benchmark.decoderecords import decode_counts
from benchmark.harness import reference_for

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    names = run["config"].get("trace_names", {}).get("block_select")
    ref = reference_for(run["config"]) if names else None
    if run["kind"] != "serve" or not trace or not run.get("trace_span") \
            or not hasattr(ref, "block_select_bytes"):
        return None
    device_s = sum(s for op, s in trace["ops"].items() if op in names)
    want = run["config"]["trace_names"]["decode_module"]
    calls = sum(n for k, (n, _) in trace["modules"].items() if want in k)
    held = decode_counts(dict(run, window=run["trace_span"]), "blocks_held")
    if not device_s or not calls or not held:
        return None
    need = calls * sum(ref.block_select_bytes(run["config"], layer)
                       for step in held for layer in step) / len(held)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
