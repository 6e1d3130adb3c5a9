"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip, read before
the reference check runs."""
LAYER, UNIT, MOVES = "device", "GB", "serve_tokens_per_s"


def read(run):
    if run["kind"] != "serve" or run["memory_peak_bytes"] is None:
        return None
    return run["memory_peak_bytes"] / 1e9
