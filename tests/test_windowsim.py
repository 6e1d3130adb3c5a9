"""``tools/windowsim.py``: a serving window on the host. The batcher's rule
on hand-made requests (every free slot filled at a step's boundary, one
token a row a decode step, a prefill gives the first), the two ways a
prefill is charged, and THE FINDING OF PR 46 it was written for: with the
costs the chip read for that PR's parent and change,
``minicpm_sala_serve_longdoc``'s window reads the same tokens/s on both,
falls where a prefill gets a tenth cheaper, and tells the trees apart once
it is three times as long (PERF.md, Findings, PR 46, section 7). A
``benchmark`` PR that repairs the window turns the last test round."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import windowsim  # noqa: E402

# busy ms of one prefill (my chip run, PR 46: docs/pr46_chip/servescope_*.txt)
PARENT = {8192: 133.7, 16384: 286.8, 32768: 658.0, 65536: 1637.7}
CHANGE = {8192: 132.7, 12288: 209.0, 16384: 286.8, 20480: 379.9,
          32768: 658.0, 36864: 790.5, 65536: 1627.7}


def test_a_prefill_gives_a_token_and_a_step_one_a_row():
    # two slots, three requests due at once, answers of 3, 2 and 2 tokens; a
    # prefill costs 1 s, a decode step 0.1 s
    requests = [(0.0, 10, 3), (0.0, 10, 2), (0.0, 10, 2)]
    found = windowsim.simulate(requests, (0.0, 10.0), lambda n: 1.0, 0.1, 2)
    # 2 prefills, a step (the second row ends), the third prefill, a step
    # (the first ends), a last step: 3 + 2 + 2 tokens by 3.3 s
    assert found == {"tokens_per_s": 0.7, "finished": 3, "admitted": 3,
                     "queued": 0}
    late = windowsim.simulate(requests, (2.05, 10.0), lambda n: 1.0, 0.1, 2)
    assert (late["finished"], late["admitted"]) == (3, 1)
    assert late["tokens_per_s"] * 7.95 == pytest.approx(5)


def test_a_window_that_closes_on_a_queue_counts_it():
    requests = [(0.1 * i, 10, 500) for i in range(8)]
    found = windowsim.simulate(requests, (0.0, 2.0), lambda n: 0.5, 0.01, 2)
    assert (found["admitted"], found["finished"], found["queued"]) == (2, 0, 6)


@pytest.mark.parametrize("length,bucket_ms,stretch_ms", [
    (8192, 133.7, 132.7), (9000, 286.8, 209.0), (12288, 286.8, 209.0),
    (24000, 658.0, 379.9 + (658.0 - 379.9) / 3), (36864, 1637.7, 790.5),
    (65536, 1637.7, 1627.7)])
def test_a_prefill_is_charged_its_bucket_or_its_stretches(length, bucket_ms,
                                                          stretch_ms):
    by_bucket = windowsim.prefill_cost(PARENT, by_bucket=True, host_s=0.0)
    by_stretch = windowsim.prefill_cost(CHANGE, host_s=0.0)
    assert by_bucket(length) == pytest.approx(1e-3 * bucket_ms)
    assert by_stretch(length) == pytest.approx(1e-3 * stretch_ms)


def sim(table, by, *more):
    pairs = ",".join(f"{k}:{v}" for k, v in table.items())
    out = windowsim.main(["--prefill-ms", pairs, "--by", by, *more])
    return {k: v["tokens_per_s"] for k, v in out["runs"].items()}, out


def test_the_longdoc_window_does_not_rank_two_prefill_costs():
    parent, out = sim(PARENT, "bucket", "--scale", "1.15,1.0,0.9,0.6")
    change, _ = sim(CHANGE, "stretch")
    assert out["window"] == [30.0, 80.0]
    # the chip read 1,965.3 and 1,970.1 (medians of three pairs)
    assert parent["1.0"] == pytest.approx(1965.3, rel=0.015)
    assert change["1.0"] == pytest.approx(1970.1, rel=0.015)
    assert out["runs"]["1.0"]["finished"] == 65   # the chip: 64-65; 84
    # not monotonic: a tenth off every prefill reads LOWER than none off
    assert parent["1.15"] < parent["0.9"] < parent["1.0"] < parent["0.6"]
    # three times the window tells the trees apart, by more than a fifth
    longer = [sim(t, by, "--seconds", "150")[0]["1.0"]
              for t, by in ((PARENT, "bucket"), (CHANGE, "stretch"))]
    assert longer[1] > 1.2 * longer[0]
