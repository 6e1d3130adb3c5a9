#!/usr/bin/env python
"""Measured-profiling CI gate (``make profcheck``; docs/OBSERVABILITY.md
"Measured profiling", ISSUE 14).

Traces two of the shared golden program families (tools/families.py — the
SAME builders shardcheck/memcheck audit, so the profiled
programs can never drift from the gated ones): 2 real training steps of
the fsdp TrainStep and a window of real decode steps of the serving
engine, both on CPU with 8 virtual devices. The gate FAILS unless:

  - the **measured op timeline is non-empty** for both families — the
    XPlane parser produced real per-device op rows with timestamps;
  - **measured overlap** is computed and reported (zero measured
    overlap is allowed — CPU compiles collectives synchronously);
  - the **measured step time** sits within a sane band of the metrics
    registry's ``train_step_seconds`` histogram over the same steps
    (both watches timed the same wall clock);
  - ``prof_captures_total{trigger="api"}`` counted every capture.

``--inject-empty-trace`` is the failure-path test hook: it swaps each
family's timeline for an empty trace dir's, and the gate must exit 1
(tests/test_profcheck.py pins this).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

#: measured-vs-registry step-time agreement band (both are wall clocks of
#: the same steps; the trace adds parse/snapshot overhead outside the
#: step windows, so the band is generous but not vacuous)
STEP_TIME_BAND = (0.2, 5.0)


def _families():
    spec = importlib.util.spec_from_file_location(
        "profcheck_families_loader", os.path.join(REPO, "tools",
                                                  "families.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load()


def _inject_empty(cap):
    """Failure-path hook: replace the capture's parsed result with what
    an empty trace dir yields — every downstream assertion must fail."""
    from mxnet_tpu.observability import profiling

    empty = tempfile.mkdtemp(prefix="profcheck-empty-")
    cap.timeline = profiling.parse_trace(empty)
    cap.report = profiling.measured_report(cap.timeline)
    return cap


def check_family(name, cap, fails):
    """Run one family's assertions; returns the JSON row."""
    r = cap.report
    row = {
        "n_op_rows": len(r.op_rows),
        "devices": r.devices(),
        "measured_step_seconds": (sum(r.step_seconds())
                                  / len(r.step_seconds()))
        if r.step_seconds() else None,
        "hot_ops": [h["name"] for h in r.hot_ops(5)],
        "overlap_measured": round(r.overlap_fraction, 6),
    }
    if not r.op_rows:
        fails.append(f"{name}: measured op timeline is EMPTY — the trace "
                     "produced no device op rows (capture or parser "
                     "broken)")
    if not r.step_seconds():
        fails.append(f"{name}: no prof_step windows in the trace — step "
                     "correlation broken")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=2,
                    help="traced steps per family (default 2)")
    ap.add_argument("--inject-empty-trace", action="store_true",
                    help="test hook: parse an empty trace dir instead of "
                         "the real capture (the gate must fail)")
    args = ap.parse_args(argv)

    from mxnet_tpu import observability as obs

    run_dir = tempfile.mkdtemp(prefix="profcheck-obs-")
    obs.enable(run_dir)

    fams = _families()
    fails = []
    row = {"gate": "profcheck", "families": {}}

    # -- family 1: the fsdp training step (step_fsdp golden family) ----------
    ts, batch = fams._fsdp_step()
    # compile + warm OUTSIDE the cross-check window: the first
    # telemetry-on step pays XLA compile and would dominate the registry
    # mean the measured (post-warmup) step time is checked against
    ts(*batch)
    ts(*batch)
    obs.flush()  # telemetry lags the dispatch: level the registry first
    hist = obs.REGISTRY.get("train_step_seconds")
    c0 = hist.total_count() if hist is not None else 0
    s0 = hist.total_sum() if hist is not None else 0.0
    trace_dir = tempfile.mkdtemp(prefix="profcheck-step-")
    cap = ts.profile(*batch, steps=args.steps, warmup=1,
                     trace_dir=trace_dir)
    if args.inject_empty_trace:
        cap = _inject_empty(cap)
    row["families"]["step_fsdp"] = check_family("step_fsdp", cap, fails)

    # measured step time vs the metrics registry's step histogram over
    # the SAME (warm) steps: two watches on one wall clock must agree
    meas = row["families"]["step_fsdp"]["measured_step_seconds"]
    hist = obs.REGISTRY.get("train_step_seconds")
    reg_mean = None
    if hist is not None and hist.total_count() > c0:
        reg_mean = (hist.total_sum() - s0) / (hist.total_count() - c0)
    row["families"]["step_fsdp"]["registry_step_seconds_mean"] = reg_mean
    if meas is None or not reg_mean:
        fails.append("step_fsdp: no measured/registry step time to "
                     "cross-check")
    elif not (STEP_TIME_BAND[0] * reg_mean <= meas
              <= STEP_TIME_BAND[1] * reg_mean):
        fails.append(
            f"step_fsdp: measured step time {meas:.4f}s disagrees with "
            f"the registry step histogram mean {reg_mean:.4f}s beyond "
            f"{STEP_TIME_BAND} — the trace windows and the wall clock "
            "watched different steps")

    # -- family 2: the serving decode step (decode golden family) ------------
    eng = fams._engine()
    trace_dir = tempfile.mkdtemp(prefix="profcheck-decode-")
    cap = eng.profile(steps=max(2, args.steps), warmup=1,
                      trace_dir=trace_dir)
    if args.inject_empty_trace:
        cap = _inject_empty(cap)
    row["families"]["decode"] = check_family("decode", cap, fails)

    # -- capture accounting ---------------------------------------------------
    ctr = obs.REGISTRY.get("prof_captures_total")
    n_caps = int(ctr.total()) if ctr is not None else 0
    row["captures_total"] = n_caps
    if n_caps < 2:
        fails.append(f"prof_captures_total = {n_caps}, expected >= 2 "
                     "(one per family)")

    row["ok"] = not fails
    if fails:
        row["failures"] = fails
    print(json.dumps(row, indent=1, sort_keys=True, default=str))
    if fails:
        for msg in fails:
            print(f"FAIL: {msg}")
        return 1
    print("OK: measured op timelines non-empty for 2 shared golden "
          "families, measured overlap reported, measured step time agrees "
          "with the registry histogram")
    return 0


if __name__ == "__main__":
    sys.exit(main())
