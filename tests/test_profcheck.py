"""Measured-profiling gate (ISSUE 14, docs/OBSERVABILITY.md "Measured
profiling"): `make profcheck` as a test — real traces of the shared
golden families produce non-empty op timelines, measured overlap is
reported, and the --inject-empty-trace failure hook fails the build.

Runs tools/profcheck.py in-process (importlib) so the memoized family
builders (tools/families.py) are shared with the other gate tests in
this process.
"""
import json

import pytest
from conftest import load_tool


@pytest.fixture(scope="module")
def profcheck():
    return load_tool("profcheck")


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    # the gate enables telemetry process-wide; later tests in this
    # session must not inherit it
    from mxnet_tpu import observability as obs

    yield
    obs.disable()


def _verdict(capsys):
    out = capsys.readouterr().out
    row, _ = json.JSONDecoder().raw_decode(out, out.index("{"))
    return row, out


def test_gate_passes_and_reports_what_it_measured(profcheck, capsys):
    """ISSUE 14 acceptance: non-empty measured op timeline for >= 2
    shared golden families and measured overlap reported (zero allowed
    on CPU)."""
    rc = profcheck.main([])
    row, _ = _verdict(capsys)
    assert rc == 0 and row["ok"], row.get("failures")
    assert set(row["families"]) == {"step_fsdp", "decode"}
    for name, fam in row["families"].items():
        assert fam["n_op_rows"] > 0, name
        assert fam["measured_step_seconds"] > 0, name
        assert 0.0 <= fam["overlap_measured"] <= 1.0
    assert row["captures_total"] >= 2


def test_injected_empty_trace_fails_gate(profcheck, capsys):
    """The failure path stays tested: an empty trace (capture or parser
    broken) must fail the build with the op-timeline check."""
    rc = profcheck.main(["--inject-empty-trace"])
    row, out = _verdict(capsys)
    assert rc == 1 and not row["ok"]
    assert any("EMPTY" in f for f in row["failures"]), row["failures"]
