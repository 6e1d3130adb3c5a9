"""What the per-layer readers share: the window's slice of a run's records."""
from __future__ import annotations


def window_requests(run):
    """The requests due inside the window that the batcher took."""
    return [r for r in run["requests"] if r["in_window"] and "req" in r]


def window_steps(run, key="window"):
    """The steps that ended inside the window (or the traced slice)."""
    span = run.get(key)
    if not span:
        return []
    lo, hi = span
    return [s for s in run["steps"] if lo <= s["t1"] < hi]


def trace_share(run, key):
    """``trace[key]`` seconds as a share (%) of the traced window."""
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * trace[key] / trace["window_s"]
