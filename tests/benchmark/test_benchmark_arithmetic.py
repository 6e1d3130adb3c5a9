"""Percentiles, token gaps, timing from due time, the schedule generator and
the trace reduction's arithmetic, on hand-made numbers."""
import math
import types

import pytest

from benchmark import harness, records, serve, traffic
from benchmark.trace import reduce as tr


# -- percentiles and gaps ----------------------------------------------------
@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 11)), 90, 9.1), ([7], 99, 7.0), ([5, 1, 3], 100, 5.0),
    ([5, 1, 3], 0, 1.0)])
def test_percentile_interpolates_between_ranks(values, q, want):
    assert harness.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        harness.percentile([], 90)


def test_token_gaps_count_only_gaps_that_end_inside_the_window():
    times = [0.9, 1.1, 1.4, 2.0, 2.1]
    assert serve.token_gaps(times, 1.0, 2.0) == pytest.approx([0.2, 0.3])
    assert serve.token_gaps(times, 1.0, 2.05) == pytest.approx([0.2, 0.3, 0.6])
    assert serve.token_gaps([1.5], 1.0, 2.0) == []


def _req(admit, first):
    return types.SimpleNamespace(admit_t=admit, first_token_t=first)


def _run(requests):
    return {"kind": "serve", "origin": 100.0, "requests": requests,
            "window": (101.0, 103.0), "window_s": 2.0, "steps": []}


def test_requests_are_timed_from_their_due_time_not_from_submission():
    # due at 1.5 s, handed over 0.2 s late, admitted 0.3 s after that
    rec = {"due": 1.5, "in_window": True, "handoff_t": 101.7,
           "req": _req(102.0, 102.25)}
    run = _run([rec, {"due": 0.5, "in_window": False, "handoff_t": 100.5,
                      "req": _req(100.6, 100.7)}])
    late = harness.load_reader("gen_lateness_p99_ms").read(run)
    assert late == pytest.approx(200.0)
    # the end-to-end time to first token of the same record
    assert rec["req"].first_token_t - (run["origin"] + rec["due"]) == \
        pytest.approx(0.75)


def test_readers_of_steps_take_only_the_windows_steps():
    step = dict(prefill_s=0.0, decode_s=0.2, decoded_rows=16, admitted=0,
                pages_in_use=100, pending=0, held_positions=0)
    run = _run([])
    run.update(batch_size=64, num_pages=1000, steps=[
        dict(step, t0=100.5, t1=100.7, decode_s=9.9, decoded_rows=64),
        dict(step, t0=101.0, t1=101.2),
        dict(step, t0=101.2, t1=101.5, decode_s=0.3, decoded_rows=48,
             prefill_s=0.1, pages_in_use=300),
        dict(step, t0=102.9, t1=103.1, decode_s=9.9)])
    assert len(records.window_steps(run)) == 2
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("decode_step_mean_ms") == pytest.approx(250.0)
    assert read("slot_occupancy_pct.serve") == pytest.approx(50.0)
    assert read("kv_pages_held_pct.serve") == pytest.approx(20.0)
    assert read("prefill_share_pct.serve") == pytest.approx(5.0)
    run["steps"][1]["pending"], run["steps"][2]["pending"] = 3, 11
    assert read("backlog_growth_per_s") == pytest.approx(4.0)
    run["latency"] = serve.latency_stats("ttft", [0.1, 0.2, 0.3, 0.4, 0.5])
    assert read("ttft_p90_ms.obs") == pytest.approx(460.0)
    assert run["latency"]["ttft_mean_ms"] == pytest.approx(300.0)
    assert read("itl_p90_ms.obs") is None and serve.latency_stats("itl", []) == {}
    assert read("step_ms.train") is None  # a reader with nothing to read


def test_train_readers():
    run = {"kind": "train", "window_s": 10.0, "steps": 50, "chips": 4,
           "flops_per_step": 2e12, "peaks": {"bf16_flops_per_s": 1e13},
           "memory_peak_bytes": 6e9,
           "trace": {"window_s": 2.0, "busy_s": 1.9, "custom_call_s": 0.19,
                     "collective_exposed_s": 0.1}}
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("step_ms.train") == pytest.approx(200.0)
    assert read("mfu_pct.train") == pytest.approx(25.0)
    assert read("collective_exposed_pct.train") == pytest.approx(5.0)
    assert read("device_idle_pct.train") == pytest.approx(5.0)
    assert read("custom_call_share_pct.train") == pytest.approx(10.0)
    assert read("hbm_peak_gb.train") == pytest.approx(6.0)
    assert read("device_idle_pct.serve") is None


# -- the schedule generator --------------------------------------------------
MIX = {"rate_per_s": 8.0, "pattern_seed": 7, "lead_in_s": 8.0, "tail_s": 2.0,
       "prompt_len": {"median": 64, "sigma": 0.9, "min": 8, "max": 512},
       "answer_len": {"median": 20, "sigma": 0.7, "min": 4, "max": 96}}


def test_same_seed_same_schedule_and_a_large_seed_is_taken():
    a = traffic.serve_schedule(MIX, 50257, 2**31 + 12345, 20.0)
    b = traffic.serve_schedule(MIX, 50257, 2**31 + 12345, 20.0)
    assert a == b
    c = traffic.serve_schedule(MIX, 50257, 7, 20.0)
    assert [r["prompt"] for r in a["requests"]] != [r["prompt"] for r in c["requests"]]


def test_every_seed_gets_the_same_requests_at_the_same_times():
    """Above the knee even the cycle's starting point changes which requests
    a window serves (PERF.md, PR 26): one rule for every mix, the cycle is
    played from its beginning and the seed draws only the tokens."""
    a = traffic.serve_schedule(MIX, 50257, 1, 20.0)["requests"]
    b = traffic.serve_schedule(MIX, 50257, 2**31 + 7, 20.0)["requests"]
    shape = lambda rs: [(r["due"], len(r["prompt"]), r["max_new_tokens"],  # noqa: E731
                         r["in_window"]) for r in rs]
    assert shape(a) == shape(b) and len(a) > 160
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_the_window_is_one_period_and_its_neighbours_replay_the_cycle():
    a = traffic.serve_schedule(MIX, 50257, 1, 20.0)["requests"]
    due, prompts, answers = traffic.base_pattern(MIX, 20.0)
    window = [r for r in a if r["in_window"]]
    assert len(window) == 160
    assert [(len(r["prompt"]), r["max_new_tokens"]) for r in window] == \
        list(zip(prompts.tolist(), answers.tolist()))
    assert [r["due"] for r in window] == pytest.approx((due + 8.0).tolist())
    # the lead-in is the end of the cycle, the tail its beginning again
    sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in a]
    lead = sum(r["due"] < 8.0 for r in a)
    tail = len(a) - lead - 160
    assert lead and tail and sizes[:lead] == sizes[lead + 160 - lead:lead + 160]
    assert sizes[lead + 160:] == sizes[lead:lead + tail]


def test_lengths_stay_inside_their_clips_and_the_rate_is_the_mixs():
    s = traffic.serve_schedule(MIX, 50257, 3, 50.0)
    reqs = s["requests"]
    assert all(8 <= len(r["prompt"]) <= 512 for r in reqs)
    assert all(4 <= r["max_new_tokens"] <= 96 for r in reqs)
    assert all(1 <= t < 50257 for r in reqs for t in r["prompt"])
    in_window = [r for r in reqs if r["in_window"]]
    assert len(in_window) == 400  # rate x seconds, exactly, for every seed
    assert abs(len(in_window) - 8.0 * 50.0) <= 3 * math.sqrt(8.0 * 50.0)
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and s["window"] == (8.0, 58.0)
    assert all(8.0 <= r["due"] < 58.0 for r in in_window)
    answers = sorted(r["max_new_tokens"] for r in in_window)
    assert answers[len(answers) // 2] == 20  # the median is the mix's
    assert 22 < sum(answers) / len(answers) < 29
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    cv = math.sqrt(sum((g - mean) ** 2 for g in gaps) / len(gaps)) / mean
    assert 0.8 < cv < 1.2  # exponential gaps: as bursty as Poisson arrivals


def test_train_batches_differ_by_row_and_repeat_by_seed():
    mix = {"global_batch": 8, "seq_length": 16, "masked_per_seq": 3,
           "valid_length_min": 8, "pool_batches": 3}
    a = traffic.train_batches(mix, 500, 2, 2**31 + 5)
    b = traffic.train_batches(mix, 500, 2, 2**31 + 5)
    assert len(a) == 3 and all((x == y).all() for p, q in zip(a, b)
                               for x, y in zip(p, q))
    ids, _, valid, pos, *_ = a[0]
    assert len({tuple(r) for r in ids}) == 8
    assert ((pos < valid[:, None]).all() and (valid >= 8).all()
            and (valid <= 16).all())


# -- the trace reduction -----------------------------------------------------
OPS = [("%while.1 = while(...)", 0.0, 10.0), ("fusion.2", 1.0, 2.0),
       ("fusion.7", 3.0, 1.0), ("all-reduce.3", 4.0, 2.0), ("copy.1", 5.0, 3.0),
       ("all-gather-done.4", 12.0, 1.0), ("convert.9", 14.0, 1.0)]


def test_op_names_group_by_kind():
    assert tr.op_base("%fusion.123 = bf16[8]{0} fusion(...)") == "fusion"
    assert tr.op_base("copy-done.4") == "copy-done"
    assert tr.op_base("multiply_reduce_fusion") == "multiply_reduce_fusion"
    assert tr.is_collective("all-gather-start.12") and not tr.is_collective("copy.1")
    assert tr.is_custom_call("%custom-call.3") and tr.is_custom_call("tpu_custom_call.1")


KERNEL = ('%packed_attention_fwd.24 = bf16[64,128,1024]{2,1,0:T(8,128)(2,1)} '
          'custom-call(bf16[64,128,3072]{2,1,0:T(8,128)(2,1)} %fusion.1, '
          's32[64]{0:T(128)} %valid), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[64,128,3072]{2,1,0}}')


@pytest.mark.parametrize("text,code,kernel", [
    (KERNEL, "custom-call", True),
    # a Pallas kernel that returns several results
    ('%probe.2 = (f32[8,128]{1,0}, f32[8]{0:T(128)}) custom-call(f32[8,128]{1,0} '
     '%x), custom_call_target="tpu_custom_call"', "custom-call", True),
    # XLA's own custom calls, and a fusion that is only NAMED like a kernel
    ('%custom-call.5 = f32[4096,1024]{1,0:T(8,128)S(1)} custom-call(), '
     'custom_call_target="AllocateBuffer"', "custom-call", False),
    ('%custom_call_fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop, '
     'calls=%fused', "fusion", False),
    ('%copy-done = bf16[512,512]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16[512,512]'
     '{1,0:T(8,128)(2,1)S(1)}, bf16[512,512]{1,0}, u32[]{:S(2)}) %copy-start)',
     "copy-done", False),
    ('%while = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)S(1)}) while((s32[]'
     '{:T(128)}, bf16[256,512]{1,0}) %tuple.12), condition=%c, body=%b',
     "while", False),
    # a bare name carries no text: the name decides, as it did
    ("custom_call_packed_attention_bwd.7", None, True),
    ("packed_attention_bwd.7", None, False), ("fusion.3", None, False)])
def test_a_kernel_is_found_by_the_operations_text_and_only_then_by_its_name(
        text, code, kernel):
    assert tr.op_code(text) == code
    assert tr.is_custom_call(text) is kernel


def test_busy_is_the_union_of_intervals_clipped_to_the_window():
    busy = tr.measure([(a, b) for _, a, b in tr.clip(OPS, 0.0, 16.0)])
    assert busy == pytest.approx(12.0)  # 0-10, 12-13, 14-15
    assert tr.measure([(a, b) for _, a, b in tr.clip(OPS, 9.0, 12.5)]) == \
        pytest.approx(1.5)
    assert tr.merge([(3, 4), (1, 2), (2, 3.5)]) == [(1, 4)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]


def test_per_op_seconds_are_self_times():
    # one line of a device nests and never half-overlaps: the copy moved
    nested = [e if e[0] != "copy.1" else ("copy.1", 6.0, 2.0) for e in OPS]
    got = tr.per_op_seconds(nested, 0.0, 16.0)
    # the loop's own time is what its body does not cover: 10 - (2+1+2+2)
    assert got == pytest.approx({"while": 3.0, "fusion": 3.0, "all-reduce": 2.0,
                                 "copy": 2.0, "all-gather-done": 1.0,
                                 "convert": 1.0})
    assert sum(got.values()) == pytest.approx(12.0)  # = the busy time
    assert tr.per_op_seconds(nested, 2.0, 3.5) == pytest.approx(
        {"while": 0.0, "fusion": 1.5})


def test_exposed_collective_time_is_what_no_other_operation_covers():
    # all-reduce 4-6 is covered by copy 5-8 for one second; the done at 12-13
    # runs alone
    assert tr.exposed_collective_seconds(OPS, 0.0, 16.0) == pytest.approx(2.0)
    assert tr.exposed_collective_seconds(OPS, 0.0, 11.0) == pytest.approx(1.0)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    host = [("bench.step", 9.5, 2.0), ("bench.wait", 13.0, 0.4),
            ("bench.submit", 13.4, 0.6), ("other", 0.0, 16.0)]
    ops = OPS + [("tiny.1", 15.000001, 0.5)]
    gaps = tr.idle_gaps(ops, host, 0.0, 16.0)
    assert gaps["bench.step"] == pytest.approx(2.0)        # 10-12
    assert gaps["bench.submit"] == pytest.approx(1.0)      # 13-14, most of it
    assert gaps["device.between_ops"] == pytest.approx(1e-6)
    assert gaps["host.unattributed"] == pytest.approx(0.499999)
    assert sum(gaps.values()) == pytest.approx(16.0 - 12.5)
    assert tr.top(gaps, 2) == [["bench.step", pytest.approx(2.0)],
                               ["bench.submit", pytest.approx(1.0)]]


def test_reduce_averages_over_chips_and_takes_the_window_from_its_span():
    trace = {"host": [("bench.window", 1.0, 9.0), ("bench.step", 1.0, 9.0)],
             "devices": {
                 "/device:TPU:0": {"ops": [("fusion.1", 0.0, 6.0)],
                                   "modules": [("jit_step(123)", 1.0, 2.0),
                                               ("jit_step(123)", 4.0, 2.0)]},
                 "/device:TPU:1": {"ops": [("all-reduce.1", 2.0, 2.0),
                                           ("tpu_custom_call.2", 5.0, 1.0)],
                                   "modules": []}}}
    got = tr.reduce(trace)
    assert got["window_s"] == pytest.approx(9.0) and got["chips"] == 2
    assert got["busy_s"] == pytest.approx((5.0 + 3.0) / 2)
    assert got["collective_exposed_s"] == pytest.approx(1.0)
    assert got["custom_call_s"] == pytest.approx(0.5)
    assert got["ops"] == pytest.approx({"fusion": 2.5, "all-reduce": 1.0,
                                        "tpu_custom_call": 0.5})
    assert got["modules"] == {"jit_step": (2, pytest.approx(4.0))}
    assert got["idle_gaps"] == pytest.approx({"bench.step": 4.0})
    with pytest.raises(ValueError):
        tr.reduce({"host": [], "devices": trace["devices"]})


def test_pace_tells_a_stall_the_queue_covered_from_time_the_device_lost():
    from benchmark.train import pace

    # 0.1 s a step; the host is away from 2.0 s to 2.45 s and sees four
    # steps done at once when it is back: nothing lost
    done, t = [], 0.0
    for k in range(60):
        t += 0.1
        done.append(max(t, 2.45) if 20 <= k < 24 else t)
    summary, longest = pace(done, done[-1] - done[0] + 0.1, 60, after=4)
    assert summary["median_step_ms"] == pytest.approx(100.0, abs=0.01)
    assert summary["lost_s"] == pytest.approx(0.0, abs=1e-6)
    assert summary["gaps_over_1.5_medians"] == 1
    gap, step, after = longest[0]
    assert (round(gap, 3), step) == (0.45, 20) and after < 0.05
    # the same stall with nothing queued behind it: the window is longer
    late = [x + (0.45 if k >= 20 else 0.0) for k, x in enumerate(
        0.1 * (k + 1) for k in range(60))]
    summary, longest = pace(late, late[-1] - late[0] + 0.1, 60, after=4)
    assert summary["lost_s"] == pytest.approx(0.45, abs=1e-6)
    assert longest[0][2] == pytest.approx(0.1, abs=1e-6)
    assert pace([0.1], 0.1, 1) == ({}, [])


def test_host_load_counts_cpu_time_and_collections_between_start_and_stop():
    import gc

    from benchmark.harness import HostLoad

    host = HostLoad()
    host.start()
    sum(i * i for i in range(200000))
    gc.collect()
    load = host.stop()
    assert set(load) == {"cpu_s", "switched_out", "waits", "major_faults",
                         "gc_s", "gc_runs"}
    assert load["cpu_s"] > 0 and load["gc_runs"] >= 1 and load["gc_s"] > 0
    assert host._gc not in gc.callbacks
