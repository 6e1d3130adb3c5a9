"""The least time the chip's memory could take to feed the packed attention
kernels in the traced steps (``packed_attention_bytes`` of the
configuration's reference: forward reads ``qkv`` and writes the context,
backward reads ``qkv`` and the cotangent and writes ``dqkv``; over the peak
bandwidth) as a share of the device time of the operations the
configuration names as those kernels. Bound by bytes, not by operations: at
sequence 128 the products need a fifth of that time. Nothing to read where
the kernels did not run (a mesh, another mask)."""
from benchmark.harness import reference_for

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if run["kind"] != "train" or not trace:
        return None
    config, mix = run["config"], run["mix"]
    want = config.get("trace_names", {}).get("packed_attention")
    device_s = sum(s for name, s in trace["ops"].items() if want in name) \
        if want else 0.0
    if not device_s:
        return None
    need = mix["trace_steps"] * reference_for(config).packed_attention_bytes(
        config, mix["global_batch"] // run["chips"], mix["seq_length"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / device_s
