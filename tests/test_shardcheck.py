"""Golden-program sharding + communication gate (ISSUE 8,
docs/ANALYSIS.md): `make shardcheck` as a test — the committed goldens
match the current programs, a synthetic extra all-gather fails the build,
and the --update-golden rebless workflow round-trips.

Runs tools/shardcheck.py in-process (importlib) so each case can pick one
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
def shardcheck():
    return load_tool("shardcheck")


def _verdict(capsys):
    out = capsys.readouterr().out
    row, _ = json.JSONDecoder().raw_decode(out, out.index("{"))
    return row, out


@pytest.mark.parametrize("family", FAMILIES)
def test_gate_matches_committed_goldens(shardcheck, capsys, family):
    """ISSUE 8 acceptance, one case a family so that a red one names
    itself and hides no other: the committed golden describes the current
    program — zero contract violations, no new collective kinds, comm
    bytes within tolerance."""
    rc = shardcheck.main(["--family", family])
    row, out = _verdict(capsys)
    assert rc == 0 and row["ok"], out
    fam = row["families"][family]
    assert fam["contract_violations"] == []
    assert fam["accidental_reshards"] == []
    assert fam["carry_donation"] == 1.0
    if family == "step_fsdp":
        assert fam["comm_total_bytes"] > 0      # a non-empty CommReport
        assert set(fam["comm_by_axis"]) == {"fsdp", "dp×fsdp"}


def test_injected_all_gather_fails_gate(shardcheck, capsys):
    """ISSUE 8 acceptance: a synthetic extra all-gather (the --inject
    test hook) must fail the build — as a NEW collective kind on the
    all-reduce-only dp family, and as a comm-byte regression."""
    rc = shardcheck.main(["--family", "step_dp8", "--inject-all-gather"])
    _, out = _verdict(capsys)
    assert rc == 1
    assert "new collective kind(s) ['all_gather']" in out
    assert "comm bytes" in out and "regressed" in out


def test_paged_families_match_goldens(shardcheck, capsys):
    """ISSUE 11 satellite: the paged decode + speculative verify program
    families are pinned to committed goldens — zero collectives (the
    serving contract) and a fully donated page-table + pool carry."""
    rc = shardcheck.main(["--family", "decode_paged",
                          "--family", "verify_spec"])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"]
    for fam in ("decode_paged", "verify_spec"):
        assert row["families"][fam]["collectives"] == {}
        assert row["families"][fam]["carry_donation"] == 1.0


def test_inject_cannot_combine_with_update_golden(shardcheck, capsys):
    """The failure-path hook must never bless the injected census into
    the committed goldens."""
    with pytest.raises(SystemExit) as exc:
        shardcheck.main(["--update-golden", "--inject-all-gather"])
    assert exc.value.code == 2
    assert "cannot be combined" in capsys.readouterr().err


def test_update_golden_rebless_roundtrip(shardcheck, capsys, monkeypatch,
                                         tmp_path):
    """--update-golden writes a fresh golden that the plain gate then
    passes against; with no golden at all the gate fails with the
    rebless instruction instead of crashing."""
    monkeypatch.setattr(shardcheck, "GOLDEN_DIR", str(tmp_path))
    rc = shardcheck.main(["--family", "decode"])
    _, out = _verdict(capsys)
    assert rc == 1 and "no committed golden" in out
    assert "--update-golden" in out
    rc = shardcheck.main(["--family", "decode", "--update-golden"])
    assert rc == 0
    golden = json.loads((tmp_path / "decode.json").read_text())
    assert golden["collectives"] == {}          # serving: zero collectives
    assert golden["carry_donation"] == 1.0
    rc = shardcheck.main(["--family", "decode"])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"]
