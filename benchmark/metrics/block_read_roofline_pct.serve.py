"""The least time the chip's memory could take to feed the selected pages'
reads of the traced decode steps (``block_read_bytes`` of the configuration's
reference: for every block a key-value head's table lists, that head's key
and value of the block's positions, by the program's own count in its decode
step records, ``blocks_read``, an entry per sparse layer; over the peak
bandwidth) as a share of the device time of the operations the configuration
names under ``trace_names.block_read``: for ``minicpm_sala`` the one kernel
``paged_gqa_decode_selected``, the grouped-heads decode read walking a table
of selected pages, which reads those keys and values and nothing else of the
cache. Both halves are the kernel's own. (The accepted
``gqa_decode_roofline_pct.serve`` reads the kernel that walks every page a
row holds, ``paged_gqa_decode``, which this configuration's programs do not
hold: one of the two is kept a cell, not both.) Nothing to read where the
configuration names none, the reference counts no such bytes, the program
keeps no such count, or the trace holds none of the operations (a program
without the mechanism)."""
from benchmark.decoderecords import decode_counts
from benchmark.harness import reference_for

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    names = run["config"].get("trace_names", {}).get("block_read")
    ref = reference_for(run["config"]) if names else None
    if run["kind"] != "serve" or not trace or not run.get("trace_span") \
            or not hasattr(ref, "block_read_bytes"):
        return None
    device_s = sum(s for op, s in trace["ops"].items() if op in names)
    want = run["config"]["trace_names"]["decode_module"]
    calls = sum(n for k, (n, _) in trace["modules"].items() if want in k)
    reads = decode_counts(dict(run, window=run["trace_span"]), "blocks_read")
    if not device_s or not calls or not reads:
        return None
    need = calls * sum(ref.block_read_bytes(run["config"], layer)
                       for step in reads for layer in step) / len(reads)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
