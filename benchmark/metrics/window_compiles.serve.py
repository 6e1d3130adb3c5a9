"""Programs compiled (or fetched from the compile cache) inside the window:
the engine's own count or jax's, whichever is larger. Not 0 makes the run
incorrect."""
LAYER, UNIT, MOVES = "engine", "count", "serve_tokens_per_s"


def read(run):
    return run.get("window_compiles")
