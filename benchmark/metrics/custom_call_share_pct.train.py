"""Device time in ``tpu_custom_call`` operations (Pallas kernels) over the
traced window's busy time."""
LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if run["kind"] != "train" or not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["custom_call_s"] / trace["busy_s"]
