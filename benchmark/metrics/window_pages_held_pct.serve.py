"""The window pool group's pages in use over its pool, by the program's own
count in its decode step records (``window_pages_in_use``, written by an
engine whose model has a window group), mean over the window's decode steps.
A row's pages behind its window go back to the free list while the row
lives, so the share stays near rows x window / page whatever the rows'
lengths; it would climb with them if they did not."""
from benchmark.decoderecords import decode_counts

LAYER, UNIT, MOVES = "engine", "%", "serve_tokens_per_s"


def read(run):
    used = decode_counts(run, "window_pages_in_use")
    pool = run["config"].get("engine", {}).get("num_pages")
    if run["kind"] != "serve" or not used or not isinstance(pool, dict) \
            or not pool.get("window"):
        return None
    return 100.0 * sum(u[0] for u in used) / (len(used) * pool["window"])
