#!/usr/bin/env python
"""Render a run summary from a telemetry directory (docs/OBSERVABILITY.md).

Reads the JSONL event log (``events*.jsonl`` + rotated predecessors) and
the registry dump (``metrics*.json``) written by ``obs.shutdown()``, and
prints one human-readable summary: training progress, recompiles, KVStore
collective cost, input-pipeline health, checkpoint IO, retry counters.

Usage::

    python tools/obs_report.py RUN_DIR            # table
    python tools/obs_report.py RUN_DIR --json     # machine-readable summary

Exits non-zero when the directory holds no telemetry (the ``make obs``
gate relies on this).

The parser is deliberately standalone-ish (only ``observability.events``
for the JSONL reader) so it runs without a working jax install.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def _load_events(run_dir):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from mxnet_tpu.observability.events import read_events

    return read_events(run_dir)


def _load_metrics(run_dir):
    """Merge every host's metrics*.json dump (counters/hist series add)."""
    merged = {}
    for path in sorted(glob.glob(os.path.join(run_dir, "metrics*.json"))):
        try:
            with open(path) as f:
                dump = json.load(f)
        except (OSError, ValueError):
            continue
        for name, m in dump.items():
            tgt = merged.setdefault(name, {"kind": m["kind"], "unit": m.get("unit", ""),
                                           "series": []})
            tgt["series"].extend(m.get("series", []))
    return merged


def _series_total(metrics, name, **labels):
    m = metrics.get(name)
    if m is None:
        return 0.0
    total = 0.0
    for s in m["series"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            v = s["value"]
            total += v if isinstance(v, (int, float)) else v.get("sum", 0.0)
    return total


def _hist_agg(metrics, name, **labels):
    """(count, sum, min, max) aggregated over matching series."""
    m = metrics.get(name)
    if m is None or m["kind"] != "histogram":
        return (0, 0.0, None, None)
    count, total, mn, mx = 0, 0.0, None, None
    for s in m["series"]:
        if not all(s["labels"].get(k) == v for k, v in labels.items()):
            continue
        v = s["value"]
        count += v.get("count", 0)
        total += v.get("sum", 0.0)
        if v.get("min") is not None:
            mn = v["min"] if mn is None else min(mn, v["min"])
        if v.get("max") is not None:
            mx = v["max"] if mx is None else max(mx, v["max"])
    return (count, total, mn, mx)


def _labels_of(metrics, name, key):
    m = metrics.get(name)
    if m is None:
        return []
    return sorted({s["labels"].get(key, "") for s in m["series"]})


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0


def _fmt_s(v):
    if v is None:
        return "-"
    return f"{v * 1e3:.2f} ms" if v < 1.0 else f"{v:.3f} s"


def summarize(run_dir):
    events = _load_events(run_dir)
    metrics = _load_metrics(run_dir)
    if not events and not metrics:
        return None

    steps = [e for e in events if e.get("event") == "train_step"]
    losses = [e["loss"] for e in steps if e.get("loss") is not None]
    recompiles = [e for e in events if e.get("event") == "recompile"]
    summary = {
        "run_dir": os.path.abspath(run_dir),
        "run_ids": sorted({e.get("run") for e in events if e.get("run")}),
        "hosts": sorted({e.get("host", 0) for e in events}),
        "events_total": len(events),
        "event_kinds": sorted({e.get("event", "?") for e in events}),
        "train": {},
        "kv": {},
        "data": {},
        "checkpoint": {},
        "retries": {},
    }

    # -- training ------------------------------------------------------------
    n_steps, t_steps, mn, mx = _hist_agg(metrics, "train_step_seconds")
    samples = _series_total(metrics, "train_samples_total")
    tokens = _series_total(metrics, "train_tokens_total")
    summary["train"] = {
        "steps": int(n_steps) or len(steps),
        "step_seconds_mean": (t_steps / n_steps) if n_steps else None,
        "step_seconds_min": mn, "step_seconds_max": mx,
        "samples_total": int(samples),
        "tokens_total": int(tokens),
        "samples_per_sec": (samples / t_steps) if t_steps else None,
        "tokens_per_sec": (tokens / t_steps) if t_steps else None,
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "grad_norm_last": next((e.get("grad_norm") for e in reversed(steps)
                                if e.get("grad_norm") is not None), None),
        "recompiles": int(_series_total(metrics, "train_recompiles_total"))
        or len(recompiles),
        "recompile_reasons": sorted({e.get("reason", "?") for e in recompiles}),
    }

    # -- kvstore collectives -------------------------------------------------
    for op in _labels_of(metrics, "kv_psum_seconds", "op"):
        cnt, tot, kmn, kmx = _hist_agg(metrics, "kv_psum_seconds", op=op)
        summary["kv"][op] = {
            "calls": int(cnt),
            "bytes": int(_series_total(metrics, "kv_psum_bytes_total", op=op)),
            "seconds_mean": (tot / cnt) if cnt else None,
            "seconds_min": kmn, "seconds_max": kmx,
        }
    buckets = metrics.get("kv_psum_dtype_buckets_total")
    if buckets:
        summary["kv"]["dtype_buckets"] = {
            s["labels"].get("dtype", "?"): int(s["value"])
            for s in buckets["series"]}

    # -- input pipeline ------------------------------------------------------
    wcnt, wtot, wmn, wmx = _hist_agg(metrics, "data_batch_wait_seconds")
    ccnt, ctot, _cmn, _cmx = _hist_agg(metrics, "data_compute_seconds")
    summary["data"] = {
        "batches": int(wcnt),
        "wait_seconds_mean": (wtot / wcnt) if wcnt else None,
        "wait_seconds_max": wmx,
        "compute_seconds_mean": (ctot / ccnt) if ccnt else None,
        "stalls": int(_series_total(metrics, "data_stalls_total")),
    }

    # -- checkpoints ---------------------------------------------------------
    scnt, stot, _smn, smx = _hist_agg(metrics, "ckpt_save_seconds")
    lcnt, ltot, _lmn, _lmx = _hist_agg(metrics, "ckpt_load_seconds")
    vcnt, vtot, _vmn, _vmx = _hist_agg(metrics, "ckpt_verify_seconds")
    summary["checkpoint"] = {
        "saves": int(scnt), "loads": int(lcnt),
        "save_seconds_mean": (stot / scnt) if scnt else None,
        "save_seconds_max": smx,
        "load_seconds_mean": (ltot / lcnt) if lcnt else None,
        "verify_seconds_mean": (vtot / vcnt) if vcnt else None,
        "bytes_saved": int(_series_total(metrics, "ckpt_bytes_total", op="save")),
        "bytes_loaded": int(_series_total(metrics, "ckpt_bytes_total", op="load")),
    }

    # -- retries -------------------------------------------------------------
    rm = metrics.get("retry_attempts_total")
    if rm:
        per_site = {}
        for s in rm["series"]:
            site = s["labels"].get("site", "?")
            ok = s["labels"].get("ok") == "true"
            d = per_site.setdefault(site, {"ok": 0, "failed": 0})
            d["ok" if ok else "failed"] += int(s["value"])
        summary["retries"] = per_site

    # -- measured profile (docs/OBSERVABILITY.md "Measured profiling") -------
    # the newest capture snapshot under the run dir (periodic captures
    # land in {run_dir}/prof/ when telemetry is on), rendered next to the
    # achieved-MFU gauge
    def _gauge(name):
        m = metrics.get(name)
        if not m or not m.get("series"):
            return None
        return m["series"][-1]["value"]

    prof = _latest_profile(run_dir)
    if prof is not None:
        r = prof.get("report", {})
        summary["profile"] = {
            "meta": prof.get("meta", {}),
            "steps": r.get("steps"),
            "step_seconds": r.get("step_seconds"),
            "hot_ops": r.get("hot_ops", [])[:10],
            "overlap_fraction": r.get("overlap_fraction"),
            "mfu": _gauge("train_mfu"),
        }
    return summary


def _latest_profile(run_dir):
    from mxnet_tpu.observability.profiling import latest_profile

    return latest_profile(run_dir)


def render(s):
    out = []
    w = out.append
    w(f"== telemetry report: {s['run_dir']}")
    w(f"   runs={','.join(s['run_ids']) or '-'} hosts={len(s['hosts'])} "
      f"events={s['events_total']} kinds={','.join(s['event_kinds'])}")
    t = s["train"]
    w("-- training")
    w(f"   steps={t['steps']}  step_time mean={_fmt_s(t['step_seconds_mean'])} "
      f"min={_fmt_s(t['step_seconds_min'])} max={_fmt_s(t['step_seconds_max'])}")
    if t["samples_per_sec"]:
        w(f"   throughput={t['samples_per_sec']:.1f} samples/sec "
          f"({t['tokens_per_sec']:.0f} tokens/sec, "
          f"{t['samples_total']} samples total)")
    if t["loss_first"] is not None:
        w(f"   loss {t['loss_first']:.5f} -> {t['loss_last']:.5f}"
          + (f"  grad_norm={t['grad_norm_last']:.4g}"
             if t["grad_norm_last"] is not None else ""))
    w(f"   recompiles={t['recompiles']} "
      f"({', '.join(t['recompile_reasons']) or 'none'})")
    if s["kv"]:
        w("-- kvstore collectives (DCN)")
        for op, k in s["kv"].items():
            if op == "dtype_buckets":
                w(f"   dtype buckets: " + ", ".join(
                    f"{d}×{n}" for d, n in sorted(k.items())))
                continue
            w(f"   {op}: calls={k['calls']} bytes={_fmt_bytes(k['bytes'])} "
              f"latency mean={_fmt_s(k['seconds_mean'])} "
              f"max={_fmt_s(k['seconds_max'])}")
    d = s["data"]
    if d["batches"]:
        w("-- input pipeline")
        w(f"   batches={d['batches']} wait mean={_fmt_s(d['wait_seconds_mean'])} "
          f"max={_fmt_s(d['wait_seconds_max'])} "
          f"compute mean={_fmt_s(d['compute_seconds_mean'])} "
          f"stalls={d['stalls']}")
    c = s["checkpoint"]
    if c["saves"] or c["loads"]:
        w("-- checkpoints")
        w(f"   saves={c['saves']} ({_fmt_bytes(c['bytes_saved'])}, "
          f"mean={_fmt_s(c['save_seconds_mean'])}, max={_fmt_s(c['save_seconds_max'])})  "
          f"loads={c['loads']} (mean={_fmt_s(c['load_seconds_mean'])}, "
          f"verify mean={_fmt_s(c['verify_seconds_mean'])})")
    if s["retries"]:
        w("-- retries")
        for site, r in sorted(s["retries"].items()):
            w(f"   {site}: ok={r['ok']} failed={r['failed']}")
    p = s.get("profile")
    if p:
        meta = p.get("meta", {})
        ctx = " ".join(f"{k}={meta[k]}" for k in ("step", "trigger")
                       if k in meta)
        w(f"-- hot ops (measured profile{', ' + ctx if ctx else ''})")
        if p.get("mfu") is not None:
            w(f"   achieved mfu={p['mfu']}"
              f"  measured overlap={p.get('overlap_fraction')}")
        for h in p.get("hot_ops", []):
            w(f"   {h['name'][:40]:<40} {h['op_class']:<12} "
              f"n={h['count']:<5} self={h['self_ns'] / 1e6:.3f} ms"
              + (f" bytes={h['bytes']}" if h.get("bytes") is not None
                 else ""))
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", help="telemetry directory (events*.jsonl + metrics*.json)")
    ap.add_argument("--json", action="store_true", help="print the summary as JSON")
    args = ap.parse_args(argv)
    s = summarize(args.run_dir)
    if s is None:
        print(f"obs_report: no telemetry found under {args.run_dir!r} "
              "(expected events*.jsonl and/or metrics*.json)", file=sys.stderr)
        return 1
    print(json.dumps(s, indent=1, sort_keys=True) if args.json else render(s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
