"""The least time the chip's memory could take to move the Lightning
Attention state that the traced decode steps advanced
(``lightning_state_bytes`` of the configuration's reference: every lightning
layer's state matrix of a row read once and written once, for the rows the
program itself counts in its decode step records, ``state_rows``, an entry
per lightning layer; over the peak bandwidth) as a share of the device time
of the operations the configuration names under
``trace_names.lightning_decode``: for ``minicpm_sala`` the one kernel
``lightning_decode_step``, which moves that state and nothing else of it.
Both halves are the kernel's own. Nothing to read where the configuration
names none, the reference counts no such bytes, the program keeps no such
count, or the trace holds none of the operations (a program without the
mechanism)."""
from benchmark.decoderecords import decode_counts
from benchmark.harness import reference_for

LAYER, UNIT, MOVES = "kernels", "%", "serve_tokens_per_s"


def read(run):
    trace = run.get("trace")
    names = run["config"].get("trace_names", {}).get("lightning_decode")
    ref = reference_for(run["config"]) if names else None
    if run["kind"] != "serve" or not trace or not run.get("trace_span") \
            or not hasattr(ref, "lightning_state_bytes"):
        return None
    device_s = sum(s for op, s in trace["ops"].items() if op in names)
    want = run["config"]["trace_names"]["decode_module"]
    calls = sum(n for k, (n, _) in trace["modules"].items() if want in k)
    rows = [sum(c) / len(c) for c in decode_counts(
        dict(run, window=run["trace_span"]), "state_rows") if c]
    if not device_s or not calls or not rows:
        return None
    need = calls * ref.lightning_state_bytes(run["config"],
                                             sum(rows) / len(rows))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
