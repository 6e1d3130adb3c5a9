"""The trace reduction on the small trace recorded on the chip and committed
beside it (``benchmark/trace/record_sample.py`` says how): three steps of a
small program with a loop in it, under the benchmark's host spans."""
import os

import pytest

from benchmark.trace import reduce as tr

from benchmark_tiny import REPO

SAMPLE = os.path.join(REPO, "benchmark", "trace", "sample_1chip.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(SAMPLE, "tpu")


def test_the_loader_finds_the_chip_its_operations_and_the_host_spans(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    chip = trace["devices"]["/device:TPU:0"]
    assert len(chip["modules"]) == 3
    assert all(n.startswith("jit_step(") for n, _, _ in chip["modules"])
    # each step: a copy pair, the loop and the four matmul-tanh of its body...
    kinds = {tr.op_base(n) for n, _, _ in chip["ops"]}
    assert {"while", "copy", "copy-done", "convolution_tanh_fusion",
            "multiply_reduce_fusion"} <= kinds
    spans = [n for n, _, _ in trace["host"]]
    assert spans.count("bench.window") == 1 and spans.count("bench.step") == 3
    assert spans.count("bench.wait") == 3
    with pytest.raises(KeyError):
        tr.load(SAMPLE, "gpu")


def test_busy_idle_and_per_operation_time_of_the_recorded_window(trace):
    got = tr.reduce(trace)
    (a, d), = [(a, d) for n, a, d in trace["host"] if n == "bench.window"]
    assert got["window_s"] == pytest.approx(d) == pytest.approx(2.31069e-3)
    assert got["chips"] == 1
    # the host's clock runs about a millisecond ahead of the device's in this
    # trace, so only the last of the three steps falls inside the window
    assert got["modules"] == {"jit_step": (1, pytest.approx(5.497e-6))}
    assert got["busy_s"] == pytest.approx(5.485e-6, rel=1e-3)
    # operations nest (the loop covers its body), so their own times add up
    # to the busy time, and the gaps to the rest of the window
    assert sum(got["ops"].values()) == pytest.approx(got["busy_s"], rel=1e-6)
    assert got["ops"]["convolution_tanh_fusion"] == pytest.approx(3.202e-6, rel=1e-3)
    assert got["ops"]["while"] < 1e-7  # the loop itself does next to nothing
    assert sum(got["idle_gaps"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-9)
    assert set(got["idle_gaps"]) == {"bench.step", "bench.wait",
                                     "device.between_ops"}
    assert got["collective_exposed_s"] == 0.0 and got["custom_call_s"] == 0.0
    idle_pct = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    assert idle_pct == pytest.approx(99.76, abs=0.01)


def test_the_whole_trace_holds_three_equal_steps(trace):
    chip = trace["devices"]["/device:TPU:0"]
    lo = min(a for _, a, _ in chip["ops"])
    hi = max(a + d for _, a, d in chip["ops"])
    per_op = tr.per_op_seconds(chip["ops"], lo, hi)
    busy = tr.measure([(a, b) for _, a, b in tr.clip(chip["ops"], lo, hi)])
    assert sum(per_op.values()) == pytest.approx(busy, rel=1e-6)
    assert busy == pytest.approx(sum(d for _, _, d in chip["modules"]), rel=0.01)
    assert tr.exposed_collective_seconds(chip["ops"], lo, hi) == 0.0


def test_a_pallas_kernel_is_found_by_its_operations_text_whatever_its_name():
    """``sample_kernel.xplane.pb``: the same program with a Pallas kernel
    named ``probe_scale`` after the loop. Its record in the trace is the
    instruction's text, ``custom-call(...), custom_call_target=
    "tpu_custom_call"``; by the name alone (PR 25) it read as no kernel."""
    trace = tr.load(os.path.join(os.path.dirname(SAMPLE),
                                 "sample_kernel.xplane.pb"), "tpu")
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    kernels = [n for n, _, _ in ops if tr.is_custom_call(n)]
    assert len(kernels) == 3  # one a step
    assert {tr.op_base(n) for n in kernels} == {"probe_scale"}
    assert {tr.op_code(n) for n in kernels} == {"custom-call"}
    assert all('custom_call_target="tpu_custom_call"' in n for n in kernels)
    assert not any(tr.is_custom_call(tr.op_base(n)) for n in kernels)
    codes = {tr.op_code(n) for n, _, _ in ops}
    assert {"while", "fusion", "copy", "copy-done", "custom-call"} <= codes
    got = tr.reduce(trace)
    assert got["custom_call_s"] == pytest.approx(got["ops"]["probe_scale"])
    assert got["custom_call_s"] == pytest.approx(6.0e-8, rel=0.01)
    assert 100 * got["custom_call_s"] / got["busy_s"] == pytest.approx(1.12, abs=0.01)
