"""Golden-program memory gate (ISSUE 12, docs/ANALYSIS.md "Memory"):
`make memcheck` as a test — the committed mem_* goldens match the current
programs, an injected >5% peak regression fails the build, the paged families
stay gather-free under the hard assert_gather_free() invariant
(ISSUE 18), and the --update-golden rebless workflow round-trips.

Runs tools/memcheck.py in-process (importlib) so each case can pick one
cheap program family and capture the JSON verdict without a subprocess
per family.
"""
import json

import pytest
from conftest import load_tool

# the families' names are plain data of tools/families.py: importing it
# builds and traces nothing
FAMILIES = load_tool("families").FAMILY_NAMES


@pytest.fixture(scope="module")
def memcheck():
    return load_tool("memcheck")


def _verdict(capsys):
    out = capsys.readouterr().out
    row, _ = json.JSONDecoder().raw_decode(out, out.index("{"))
    return row, out


@pytest.mark.parametrize("family", FAMILIES)
def test_gate_matches_committed_goldens(memcheck, capsys, family):
    """ISSUE 12 acceptance, one case a family so that a red one names
    itself and hides no other: the committed golden describes the current
    program — peak residency within tolerance, donation intact, no new
    materialization classes."""
    rc = memcheck.main(["--family", family, "--skip-validate"])
    row, out = _verdict(capsys)
    assert rc == 0 and row["ok"], out
    fam = row["families"][family]
    assert fam["carry_donation"] == 1.0
    assert fam["peak_bytes"] > 0
    assert fam["materializations"] == {}
    if family == "step_fsdp":
        # the fsdp step's carry categories are per-device shards
        assert set(fam["by_category"]) >= {"params", "opt_state",
                                           "activations", "batch"}


def test_injected_peak_regression_fails_gate(memcheck, capsys):
    """ISSUE 12 acceptance: a synthetic +20% peak (the --inject test
    hook) must fail the build as a >5% residency regression."""
    rc = memcheck.main(["--family", "step_dp8", "--inject-peak-regression",
                        "--skip-validate"])
    _, out = _verdict(capsys)
    assert rc == 1
    assert "peak residency regressed" in out


def test_paged_gather_free_is_asserted_not_just_blessed(memcheck, capsys):
    """ISSUE 18: the paged decode reads the page table inside the Pallas
    kernel, so the family is gather-FREE — and not merely because the
    golden says so: assert_gather_free() hard-fails on any
    kv_gather_materialize in the paged families, even during a rebless."""
    rc = memcheck.main(["--family", "decode_paged", "--skip-validate"])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"]
    fam = row["families"]["decode_paged"]
    assert fam["materializations"].get("kv_gather_materialize", 0) == 0
    assert fam["by_category"]["kv_pages"] > 0
    assert fam["carry_donation"] == 1.0
    # failure path: a reappearing gather fails regardless of the goldens
    fails = []
    memcheck.assert_gather_free(
        "verify_spec", {"materializations": {"kv_gather_materialize": 2}},
        fails)
    assert fails and "kv_gather_materialize" in fails[0]
    # ...and only the paged families carry the invariant
    fails = []
    memcheck.assert_gather_free(
        "decode", {"materializations": {"kv_gather_materialize": 2}}, fails)
    assert not fails


def test_validation_cross_checks_memory_analysis(memcheck, capsys):
    """The estimator self-check: liveness peak vs memory_analysis() on
    the mesh-less step and decode programs, within the documented
    tolerance, reported in the gate output."""
    rc = memcheck.main(["--family", "decode"])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"]
    progs = row["validation"]["programs"]
    tol = row["validation"]["tolerance"]
    assert set(progs) == {"step", "decode"}
    for name, p in progs.items():
        assert abs(p["rel_err"]) <= tol, (name, p)


def test_inject_cannot_combine_with_update_golden(memcheck, capsys):
    """The failure-path hook must never bless inflated peaks into the
    committed goldens."""
    with pytest.raises(SystemExit) as exc:
        memcheck.main(["--update-golden", "--inject-peak-regression"])
    assert exc.value.code == 2
    assert "cannot be combined" in capsys.readouterr().err


def test_update_golden_rebless_roundtrip(memcheck, capsys, monkeypatch,
                                         tmp_path):
    """--update-golden writes a fresh golden the plain gate then passes
    against; with no golden at all the gate fails with the rebless
    instruction instead of crashing."""
    monkeypatch.setattr(memcheck, "GOLDEN_DIR", str(tmp_path))
    rc = memcheck.main(["--family", "decode", "--skip-validate"])
    _, out = _verdict(capsys)
    assert rc == 1 and "no committed golden" in out
    assert "--update-golden" in out
    rc = memcheck.main(["--family", "decode", "--update-golden"])
    assert rc == 0
    golden = json.loads((tmp_path / "mem_decode.json").read_text())
    assert golden["carry_donation"] == 1.0
    assert golden["by_category"]["kv_cache"] > 0
    rc = memcheck.main(["--family", "decode", "--skip-validate"])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"]
