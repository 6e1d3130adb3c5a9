"""Window seconds over steps."""
LAYER, UNIT, MOVES = "train step", "ms", "train_tokens_per_s"


def read(run):
    if run["kind"] != "train":
        return None
    return 1e3 * run["window_s"] / run["steps"]
