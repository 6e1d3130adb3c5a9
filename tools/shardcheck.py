#!/usr/bin/env python
"""Golden-program sharding + communication gate (``make shardcheck``;
docs/ANALYSIS.md, ISSUE 8).

Lowers the framework's representative program families on CPU (8 virtual
devices), runs the sharding contract checker and the communication cost
model over each, and diffs the result against the committed goldens in
``mxnet_tpu/analysis/goldens/``. The gate FAILS when:

  - any **sharding-contract violation** appears (a declared layout the
    compiled program doesn't honor);
  - an **accidental reshard** appears (a GSPMD all-gather fully
    materializing a declared-sharded tensor outside the intended ZeRO
    compute gathers);
  - a **new collective kind** shows up that the golden doesn't have (the
    mis-spec signature of arXiv:2004.13336 — reduce-scatter patterns
    degrading into all-gathers);
  - **comm bytes regress** beyond ``--tolerance`` (total or on any mesh
    axis);
  - **donation coverage** drops below the golden;
  - the **program fingerprint** (flat input shapes/dtypes) changes — the
    family itself was restructured.

Intentional changes are reblessed with ``--update-golden`` (commit the
rewritten JSON with the change that caused it). Byte *improvements*
beyond tolerance pass but are reported so the win can be locked in by
reblessing. ``--family`` restricts the run; ``--inject-all-gather`` is a
test hook that adds a synthetic all-gather to every current census so the
failure path itself stays tested (tests/test_shardcheck.py).
"""
from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

GOLDEN_DIR = os.path.join(REPO, "mxnet_tpu", "analysis", "goldens")


def _families_mod():
    """The shared golden-family builders (tools/families.py) — ONE
    definition of the representative programs for every gate
    (shardcheck / memcheck), loaded under a stable module
    name so the memoized model builds are shared per process."""
    spec = importlib.util.spec_from_file_location(
        "shardcheck_families_loader", os.path.join(REPO, "tools",
                                                   "families.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load()


#: name -> () -> ProgramAudit, from tools/families.py (kept as a module
#: attribute: the tests read shardcheck.FAMILIES)
FAMILIES = _families_mod().FAMILIES


# -- snapshot / diff ---------------------------------------------------------
def snapshot(audit) -> dict:
    """JSON-safe golden record of one program family. The fingerprint
    digests flat input shapes/dtypes (never parameter names — the
    process-global block counters make names run-dependent)."""
    sig = json.dumps([[dt, list(sh)] for dt, sh in audit.lowered.inputs],
                     separators=(",", ":"))
    comm = audit.comm
    rep = audit.compiled if audit.compiled is not None else audit.lowered
    return {
        "fingerprint": hashlib.sha256(sig.encode()).hexdigest()[:16],
        "n_inputs": len(audit.lowered.inputs),
        "collectives": rep.collective_counts(),
        "comm_total_bytes": comm.total_bytes() if comm else 0,
        "comm_by_axis": comm.by_axis() if comm else {},
        "comm_by_kind": comm.by_kind() if comm else {},
        "contract_violations": [str(v) for v in audit.contract],
        "accidental_reshards": ([str(r) for r in comm.reshards]
                                if comm else []),
        "carry_donation": audit.carry_donation(),
    }


def diff(name: str, golden: dict, cur: dict, tol: float):
    """(failures, notes) of the current snapshot vs its golden."""
    fails, notes = [], []
    if cur["contract_violations"]:
        for v in cur["contract_violations"]:
            fails.append(f"{name}: sharding contract violated — {v}")
    if cur["accidental_reshards"]:
        for r in cur["accidental_reshards"]:
            fails.append(f"{name}: accidental reshard — {r}")
    new_kinds = sorted(set(cur["collectives"]) - set(golden["collectives"]))
    if new_kinds:
        fails.append(f"{name}: new collective kind(s) {new_kinds} not in "
                     f"the golden ({sorted(golden['collectives'])}) — a "
                     "sharding change added communication")
    axes = set(golden["comm_by_axis"]) | set(cur["comm_by_axis"])
    for ax in sorted(axes):
        g = golden["comm_by_axis"].get(ax, 0)
        c = cur["comm_by_axis"].get(ax, 0)
        if c > g * (1 + tol) and c - g > 0:
            fails.append(f"{name}: comm bytes on axis {ax!r} regressed "
                         f"{g} -> {c} (> {tol:.0%} tolerance)")
        elif c < g * (1 - tol):
            notes.append(f"{name}: comm bytes on axis {ax!r} improved "
                         f"{g} -> {c}; rebless with --update-golden to "
                         "lock it in")
    g, c = golden["comm_total_bytes"], cur["comm_total_bytes"]
    if c > g * (1 + tol) and c - g > 0:
        fails.append(f"{name}: total comm bytes regressed {g} -> {c} "
                     f"(> {tol:.0%} tolerance)")
    if cur["carry_donation"] < golden["carry_donation"]:
        fails.append(f"{name}: carry donation dropped "
                     f"{golden['carry_donation']:.0%} -> "
                     f"{cur['carry_donation']:.0%}")
    if cur["fingerprint"] != golden["fingerprint"]:
        fails.append(f"{name}: program fingerprint changed "
                     f"({golden['fingerprint']} -> {cur['fingerprint']}) — "
                     "the family's input signature was restructured; "
                     "rebless intentional changes with --update-golden")
    return fails, notes


def _golden_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update-golden", action="store_true",
                    help="rebless: write current snapshots as the goldens")
    ap.add_argument("--family", action="append", choices=sorted(FAMILIES),
                    help="restrict to named families (repeatable)")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative comm-byte drift allowed (default 5%%)")
    ap.add_argument("--inject-all-gather", action="store_true",
                    help="test hook: add a synthetic all-gather to every "
                         "current census (the gate must fail)")
    args = ap.parse_args(argv)
    if args.inject_all_gather and args.update_golden:
        ap.error("--inject-all-gather is a failure-path test hook and "
                 "cannot be combined with --update-golden (it would "
                 "bless the injected census into the goldens)")

    names = args.family or sorted(FAMILIES)
    fails, notes = [], []
    row = {"gate": "shardcheck", "tolerance": args.tolerance, "families": {}}
    for name in names:
        audit = FAMILIES[name]()
        cur = snapshot(audit)
        if args.inject_all_gather:
            cur["collectives"]["all_gather"] = \
                cur["collectives"].get("all_gather", 0) + 1
            cur["comm_by_axis"]["?"] = cur["comm_by_axis"].get("?", 0) \
                + (1 << 20)
            cur["comm_total_bytes"] += 1 << 20
        row["families"][name] = cur
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
                         "run tools/shardcheck.py --update-golden and "
                         "commit it")
            continue
        f2, n2 = diff(name, golden, cur, args.tolerance)
        fails.extend(f2)
        notes.extend(n2)

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
    print(f"OK: {len(names)} program families {verb} (zero contract "
          "violations, no new collective kinds, comm bytes within "
          f"{args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
