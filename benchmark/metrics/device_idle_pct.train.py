"""1 minus the union of the device's operation intervals over the traced
window, averaged over the chips."""
LAYER, UNIT, MOVES = "device", "%", "train_tokens_per_s"


def read(run):
    trace = run.get("trace")
    if run["kind"] != "train" or not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
