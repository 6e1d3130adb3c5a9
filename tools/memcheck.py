#!/usr/bin/env python
"""Golden-program memory gate (``make memcheck``; docs/ANALYSIS.md,
ISSUE 12).

Lowers the same representative program families as ``make shardcheck``
(8 virtual CPU devices for the mesh families), runs the buffer-liveness
pass (:mod:`mxnet_tpu.analysis.memory`) over each, and diffs the result
against the committed goldens in ``mxnet_tpu/analysis/goldens/mem_*.json``.
The gate FAILS when:

  - **peak residency regresses** beyond ``--tolerance`` (default 5%) —
    the per-device bytes that cap batch size, window length and page-pool
    size grew;
  - a **new materialization class** appears (``kv_gather_materialize`` /
    ``f32_upcast`` / ``long_lived_temp``) that the golden doesn't have —
    a fusion/layout change started materializing something it didn't;
  - **donation coverage drops** below the golden (a donated carry lost
    its in-place update, doubling its residency);
  - a ``kv_gather_materialize`` buffer appears in the paged decode/verify
    families at all (:data:`GATHER_FREE_FAMILIES`) — those programs read
    the page table inside the paged attention kernel (ISSUE 18) and must
    stay gather-free even across reblesses.

Category-attribution drift and peak *improvements* beyond tolerance pass
but are reported, so wins can be locked in by reblessing. The gate also
**cross-validates** the estimator itself: the mesh-less step and decode
programs' ``peak_bytes`` must agree with
``jax.stages.Compiled.memory_analysis()`` within the documented
:data:`~mxnet_tpu.analysis.VALIDATION_TOLERANCE` (skippable with
``--skip-validate`` when iterating on goldens only).

Intentional changes are reblessed with ``--update-golden`` (commit the
rewritten JSON with the change that caused it); ``--family`` restricts
the run; ``--inject-peak-regression`` is a test hook that inflates every
current peak by 20% so the failure path itself stays tested
(tests/test_memcheck.py).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

GOLDEN_DIR = os.path.join(REPO, "mxnet_tpu", "analysis", "goldens")


def _shardcheck():
    """The shared program-family builders (tools/families.py) — one
    definition of what 'the representative programs' are, every gate
    (shardcheck / memcheck) audits the same ten. Loaded
    under families.load()'s stable module name so the memoized model
    builds are shared per process. (Name kept: validate() reads
    ``_engine`` off it, as it always did off shardcheck.)"""
    spec = importlib.util.spec_from_file_location(
        "memcheck_families_loader", os.path.join(REPO, "tools",
                                                 "families.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load()


_FAMILIES = None


def families():
    global _FAMILIES
    if _FAMILIES is None:
        _FAMILIES = _shardcheck().FAMILIES
    return _FAMILIES


# gate-facing family order — ONE definition, owned by tools/families.py
FAMILY_NAMES = _shardcheck().FAMILY_NAMES


# -- snapshot / diff ---------------------------------------------------------
def snapshot(audit) -> dict:
    """JSON-safe golden record of one family's memory residency."""
    mem = audit.memory
    return {
        "n_inputs": len(audit.lowered.inputs),
        "peak_bytes": mem.peak_bytes,
        "temp_peak_bytes": mem.temp_peak_bytes,
        "input_bytes": mem.input_bytes,
        "donated_bytes": mem.donated_bytes,
        "by_category": dict(mem.by_category),
        "top_buffers": [[op, b] for op, b in
                        ((x.op, x.bytes) for x in mem.largest_buffers(5))],
        "materializations": mem.materialization_kinds(),
        "carry_donation": audit.carry_donation(),
    }


def diff(name: str, golden: dict, cur: dict, tol: float):
    """(failures, notes) of the current snapshot vs its golden."""
    fails, notes = [], []
    g, c = golden["peak_bytes"], cur["peak_bytes"]
    if c > g * (1 + tol):
        fails.append(f"{name}: peak residency regressed {g} -> {c} bytes "
                     f"(> {tol:.0%} tolerance) — rebless only if the "
                     "growth is intentional")
    elif c < g * (1 - tol):
        notes.append(f"{name}: peak residency improved {g} -> {c} bytes; "
                     "rebless with --update-golden to lock it in")
    new_kinds = sorted(set(cur["materializations"])
                       - set(golden["materializations"]))
    if new_kinds:
        fails.append(f"{name}: new materialization class(es) {new_kinds} "
                     f"not in the golden "
                     f"({sorted(golden['materializations'])}) — the "
                     "program started materializing something it didn't")
    if cur["carry_donation"] < golden["carry_donation"]:
        fails.append(f"{name}: carry donation dropped "
                     f"{golden['carry_donation']:.0%} -> "
                     f"{cur['carry_donation']:.0%} — a donated buffer is "
                     "being copied instead of updated in place")
    cats = set(golden["by_category"]) | set(cur["by_category"])
    for cat in sorted(cats):
        gb = golden["by_category"].get(cat, 0)
        cb = cur["by_category"].get(cat, 0)
        if gb and cb > gb * (1 + tol):
            notes.append(f"{name}: at-peak {cat!r} bytes drifted up "
                         f"{gb} -> {cb}")
    return fails, notes


def validate(fails, notes):
    """Estimator self-check: the liveness peak must agree with XLA's own
    memory_analysis() on the mesh-less step and decode programs within
    the documented tolerance (docs/ANALYSIS.md "Memory")."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.analysis import (VALIDATION_TOLERANCE, audit_compiled,
                                    jax_expected_peak, memory_report)
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep

    sc = _shardcheck()
    out = {"tolerance": VALIDATION_TOLERANCE, "programs": {}}

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.ones((8, 16))
    _ = net(x)
    ts = TrainStep(net, lambda o, *l: ((o - l[0]) ** 2).mean(),
                   optimizer.Adam(learning_rate=1e-3))
    eng = sc._engine()
    # one compile per program, shared by both sides of the comparison
    # (an explicit lower().compile() is not memoized by the jit cache;
    # categories don't move peak_bytes, so memory_report runs bare)
    compiled = {
        "step": ts.lower_hlo(x, nd.zeros((8, 8))).compile(),
        "decode": eng._decode_jit.lower(
            eng._params(), eng.cache, jnp.asarray(eng.last_tokens),
            jnp.asarray(eng.positions), jnp.asarray(eng.done),
            jax.random.key(0)).compile(),
    }
    for name, co in compiled.items():
        mem = memory_report(audit_compiled(co))
        want = jax_expected_peak(co.memory_analysis())
        err = (mem.peak_bytes - want) / want if want else 0.0
        out["programs"][name] = {
            "estimated_peak_bytes": mem.peak_bytes,
            "memory_analysis_bytes": want,
            "rel_err": round(err, 4),
        }
        if abs(err) > VALIDATION_TOLERANCE:
            fails.append(
                f"validate/{name}: liveness peak {mem.peak_bytes} vs "
                f"memory_analysis {want} ({err:+.1%}) exceeds the "
                f"documented ±{VALIDATION_TOLERANCE:.0%} tolerance — the "
                "estimator itself drifted")
        else:
            notes.append(f"validate/{name}: liveness peak within "
                         f"{err:+.1%} of memory_analysis()")
    return out


# families whose compiled program must stay free of pool-wide KV gather
# materialization FOREVER (ISSUE 18: the paged decode-attention kernel
# reads the page table in-kernel; this asserts the gather can never
# silently come back, independent of what the goldens say — it applies
# even while reblessing)
GATHER_FREE_FAMILIES = ("decode_paged", "verify_spec", "decode_prefix")


def assert_gather_free(name: str, cur: dict, fails: list):
    if name not in GATHER_FREE_FAMILIES:
        return
    n = cur["materializations"].get("kv_gather_materialize", 0)
    if n:
        fails.append(
            f"{name}: {n} kv_gather_materialize buffer(s) in a family the "
            "paged attention kernel must keep gather-free — the in-kernel "
            "page read was bypassed (check the paged_attention_kernel knob "
            "and paged_attention_supported())")


def _golden_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"mem_{name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update-golden", action="store_true",
                    help="rebless: write current snapshots as the goldens")
    ap.add_argument("--family", action="append", choices=FAMILY_NAMES,
                    help="restrict to named families (repeatable)")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative peak-byte drift allowed (default 5%%)")
    ap.add_argument("--inject-peak-regression", action="store_true",
                    help="test hook: inflate every current peak by 20%% "
                         "(the gate must fail)")
    ap.add_argument("--skip-validate", action="store_true",
                    help="skip the memory_analysis() cross-validation")
    args = ap.parse_args(argv)
    if args.inject_peak_regression and args.update_golden:
        ap.error("--inject-peak-regression is a failure-path test hook "
                 "and cannot be combined with --update-golden (it would "
                 "bless the inflated peaks into the goldens)")

    names = args.family or list(FAMILY_NAMES)
    fails, notes = [], []
    row = {"gate": "memcheck", "tolerance": args.tolerance, "families": {}}
    fams = families()
    for name in names:
        cur = snapshot(fams[name]())
        if args.inject_peak_regression:
            cur["peak_bytes"] = int(cur["peak_bytes"] * 1.2)
            cur["temp_peak_bytes"] = int(cur["temp_peak_bytes"] * 1.2)
        row["families"][name] = cur
        assert_gather_free(name, cur, fails)
        if args.update_golden:
            os.makedirs(GOLDEN_DIR, exist_ok=True)
            with open(_golden_path(name), "w") as f:
                json.dump(cur, f, indent=1, sort_keys=True)
                f.write("\n")
            notes.append(f"{name}: golden written")
            continue
        try:
            with open(_golden_path(name)) as f:
                golden = json.load(f)
        except (OSError, ValueError):
            fails.append(f"{name}: no committed golden at "
                         f"{os.path.relpath(_golden_path(name), REPO)} — "
                         "run tools/memcheck.py --update-golden and "
                         "commit it")
            continue
        f2, n2 = diff(name, golden, cur, args.tolerance)
        fails.extend(f2)
        notes.extend(n2)

    if not args.skip_validate:
        row["validation"] = validate(fails, notes)

    row["ok"] = not fails
    if fails:
        row["failures"] = fails
    if notes:
        row["notes"] = notes
    print(json.dumps(row, indent=1, sort_keys=True))
    for msg in notes:
        print(f"NOTE: {msg}")
    if fails:
        for msg in fails:
            print(f"FAIL: {msg}")
        return 1
    verb = "reblessed" if args.update_golden else "match goldens"
    print(f"OK: {len(names)} program families {verb} (peak residency "
          f"within {args.tolerance:.0%}, no new materialization classes, "
          "donation intact)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
