"""Model FLOP/s utilisation: the operations the forward and backward passes
require (``train_flops`` of the configuration's reference; recomputation
not counted) times steps, over the window and the chips' bf16 peak."""
LAYER, UNIT, MOVES = "train step", "%", "train_tokens_per_s"


def read(run):
    if run["kind"] != "train":
        return None
    return 100.0 * run["flops_per_step"] * run["steps"] / run["window_s"] / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
