"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, the table of peaks, percentiles, and the one
result line. Driven by data: a configuration, a traffic mix and a per-layer
metric are files found by the names ``BENCHMARK.json`` gives, so a later
change adds files and entries and edits nothing here."""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNERS = {"train": "benchmark.train", "serve": "benchmark.serve"}


def say(msg):
    print(f"[benchmark] {msg}", flush=True)


# -- lookup -----------------------------------------------------------------
def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT, parked=False):
    """``BENCHMARK.json``; with ``parked``, joined by the entries of
    ``benchmark/parked.json``: cells that were built and run on the chip but
    are not admitted yet (PERF.md, Open questions). The command never reads
    them; ``benchmark.sweep``, ``benchmark.control`` and the CPU tests do."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    if parked:
        bench = with_parked(bench, load_json(
            os.path.join(root, "benchmark", "parked.json")))
    return bench


def with_parked(bench, parked):
    """``bench`` with the parked entries added: a new name is appended, a
    metric both have gains the parked cells in its ``workloads``."""
    out = dict(bench)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        merged = [dict(e) for e in bench[group]]
        by_name = {e["name"]: e for e in merged}
        for entry in parked.get(group, ()):
            mine = by_name.get(entry["name"])
            if mine is None:
                merged.append(dict(entry))
            elif "workloads" in mine:
                mine["workloads"] = mine["workloads"] + [
                    w for w in entry.get("workloads", ())
                    if w not in mine["workloads"]]
        out[group] = merged
    return out


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench, cell, root=ROOT):
    for entry in bench["configs"]:
        if entry["name"] == cell["config"]:
            return load_json(os.path.join(root, entry["file"]))
    raise KeyError(f"no configuration {cell['config']!r} in BENCHMARK.json")


def load_mix(cell, root=ROOT):
    return load_json(os.path.join(root, "benchmark", "traffic",
                                  cell["traffic"] + ".json"))


def load_reader(name, root=ROOT):
    """The module ``benchmark/metrics/<name>.py``: LAYER, UNIT, MOVES and
    ``read(run)``. Loaded by path, since a metric's name may hold dots."""
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(bench, cell, group):
    """The ``group`` (``end_to_end``/``per_layer``) metrics this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def system_for(config):
    return importlib.import_module("benchmark.systems." + config["model"])


def reference_for(config):
    return importlib.import_module("benchmark.reference." + config["model"])


def runner_for(config):
    return importlib.import_module(RUNNERS[config["kind"]])


# -- device -----------------------------------------------------------------
def place_compile_cache(root=ROOT):
    """Before jax is imported: the persistent compile cache at a fixed path
    in the checkout unless the environment already places it."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def require_devices(chips, platform="tpu"):
    """The cell's devices, or an exception: no fallback off the chip."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if any(d.platform != platform for d in devices) or len(devices) < chips:
        raise RuntimeError(
            f"this cell needs {chips} {platform} device(s); jax.devices() "
            f"found {[(d.platform, d.device_kind) for d in devices]}")
    return devices[:chips]


def peaks_for(kind, root=ROOT):
    table = load_json(os.path.join(root, "benchmark", "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks on record for device_kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices):
    """The peak on the fullest chip (None where the backend keeps none)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- arithmetic -------------------------------------------------------------
def percentile(values, q):
    """The ``q``-th percentile (0..100), interpolated between the two
    nearest ranks; raises on an empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# -- the result -------------------------------------------------------------
class Check:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, kind="max"):
        """``kind``: ``max`` passes while value <= limit, ``equal`` while
        value == limit, ``true`` while value is true."""
        ok = {"max": lambda: value is not None and math.isfinite(value)
              and value <= limit,
              "equal": lambda: value == limit,
              "true": lambda: bool(value)}[kind]()
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "kind": kind, "ok": ok})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def report(self):
        for r in self.rows:
            say(f"check {r['name']}: {r['value']} (limit {r['kind']} "
                f"{r['limit']}) {'ok' if r['ok'] else 'FAILED'}")


def compared(rows):
    """{short plain name: {value, limit, ok}} of a run's check rows: the
    name up to its bracketed remark, spaces as underscores."""
    def plain(v):  # json has no inf or nan
        return str(v) if isinstance(v, float) and not math.isfinite(v) else v

    return {r["name"].split(" (")[0].replace(" ", "_"):
            {"value": plain(r["value"]), "limit": r["limit"], "ok": r["ok"]}
            for r in rows}


def result_line(bench, cell, run, trace_on, devices, root=ROOT):
    """The last line of stdout, to the driver's contract."""
    metrics = {}
    if trace_on:
        for m in metrics_of(bench, cell, "per_layer"):
            value = load_reader(m["name"], root).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in metrics_of(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": float(run["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics, "device": device}
    trace = run.get("trace")
    if trace_on and trace is not None:
        from .trace.reduce import top

        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": top(trace["ops"]),
                             "idle_gaps": top(trace["idle_gaps"])}
    line["compared"] = compared(run["check"])  # last, as the contract has it
    return json.dumps(line)


def main(argv, platform="tpu", root=ROOT, parked=False):
    """One run of one cell. ``platform`` is ``tpu`` and ``parked`` false for
    the command; only the CPU tests pass others."""
    import argparse
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark(root, parked)
    cell = find_cell(bench, args.workload)
    config = load_config(bench, cell, root)
    mix = load_mix(cell, root)
    cache = place_compile_cache(root)
    devices = require_devices(cell["chips"], platform)
    peaks = peaks_for(devices[0].device_kind, root) if platform == "tpu" \
        else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {cache}")
    run = runner_for(config).run(
        cell=cell, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace_on=bool(args.trace), devices=devices,
        peaks=peaks, t_start=t_start, root=root)
    run.update(cell=cell, config=config, mix=mix)  # for the readers
    sys.stdout.flush()
    for name, row in compared(run["check"]).items():  # stderr's last lines
        print(f"compared {name}: {row['value']} limit {row['limit']} "
              f"{'ok' if row['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(result_line(bench, cell, run, bool(args.trace), devices, root),
          flush=True)
    return run


class HostLoad:
    """What the host gave this process between ``start()`` and ``stop()``:
    its CPU seconds, how often it was switched out against its will, its
    major page faults, and the time Python's garbage collector took. Every
    run prints it beside its pace, so that a run that reads low says whether
    the host was taken away from it or the device itself ran slowly."""

    def __init__(self):
        self.gc_s, self.gc_runs, self._gc_t0, self._before = 0.0, 0, None, None

    def _gc(self, phase, info):
        import time

        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_runs += 1
            self._gc_t0 = None

    @staticmethod
    def _usage():
        import resource

        u = resource.getrusage(resource.RUSAGE_SELF)
        return {"cpu_s": u.ru_utime + u.ru_stime, "switched_out": u.ru_nivcsw,
                "waits": u.ru_nvcsw, "major_faults": u.ru_majflt}

    def start(self):
        import gc

        gc.callbacks.append(self._gc)
        self._before = self._usage()

    def stop(self):
        import gc

        gc.callbacks.remove(self._gc)
        now = self._usage()
        return {**{k: round(now[k] - v, 3) for k, v in self._before.items()},
                "gc_s": round(self.gc_s, 4), "gc_runs": self.gc_runs}


class CompileCounter:
    """Programs that jax compiled, or fetched from the persistent cache,
    between ``start()`` and ``stop()``: a stall inside a measured window
    either way."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kwargs):
        if self.on and event in self.EVENTS:
            self.count += 1

    def start(self):
        self.count, self.on = 0, True

    def stop(self):
        self.on = False
        return self.count
