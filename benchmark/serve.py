"""The serving runner: an open loop. A generator thread hands each request
over when it is due; the thread that owns the engine submits them at step
boundaries (the batcher has no lock), drives ``batcher.step()`` and keeps
every request's times. Requests are timed from their due time."""
from __future__ import annotations

import gc
import queue
import statistics
import threading
import time

from . import tracing, traffic
from .harness import (Check, CompileCounter, HostLoad, memory_peak_bytes,
                      percentile, reference_for, say, system_for)
from .weights import make_weights

clock = time.perf_counter  # the batcher's clock too


class Generator(threading.Thread):
    """Sleeps until each request is due and puts it on the hand-off queue.
    It does nothing else, so how late it runs is the load generator's own
    delay."""

    def __init__(self, requests, origin):
        super().__init__(name="bench-generator", daemon=True)
        self.requests, self.origin = requests, origin
        self.handoff = queue.SimpleQueue()
        self.stop = threading.Event()

    def run(self):
        for rec in self.requests:
            wait = self.origin + rec["due"] - clock()
            if wait > 0 and self.stop.wait(wait):
                return
            if self.stop.is_set():
                return
            rec["handoff_t"] = clock()
            self.handoff.put(rec)


class Server:
    """The engine's thread of the open loop, and its records."""

    def __init__(self, engine, batcher, generator):
        self.engine, self.batcher, self.gen = engine, batcher, generator
        self.waiting, self.live, self.steps = [], [], []

    def _submit_due(self):
        while True:
            try:
                rec = self.gen.handoff.get_nowait()
            except queue.Empty:
                return
            with tracing.span("bench.submit"):
                try:
                    rec["req"] = self.batcher.submit(
                        rec["prompt"], max_new_tokens=rec["max_new_tokens"])
                except ValueError as e:  # refused: counted as failed
                    rec["refused"], rec["end_t"] = str(e), clock()
                    continue
            rec["token_times"] = []
            if not rec["req"].done:  # shed at submission otherwise
                self.waiting.append(rec)

    def _step(self):
        eng = self.engine
        t0 = clock()
        with tracing.span("bench.step"):
            self.batcher.step()
        t1 = clock()
        admitted = [r for r in self.waiting if r["req"].admit_t is not None]
        self.waiting = [r for r in self.waiting
                        if r["req"].admit_t is None and not r["req"].done]
        self.live += admitted
        prefill_s = max((r["req"].first_token_t - t0 for r in admitted),
                        default=0.0)
        decoded = 0
        for r in self.live:
            have, times = len(r["req"].output), r["token_times"]
            if have > len(times):
                if not times:
                    times.append(r["req"].first_token_t)
                if have > len(times):  # one token a decode step
                    times += [t1] * (have - len(times))
                    decoded += 1
        self.live = [r for r in self.live if not r["req"].done]
        self.steps.append({
            "t0": t0, "t1": t1, "prefill_s": prefill_s,
            "decode_s": (t1 - t0 - prefill_s) if decoded else 0.0,
            "decoded_rows": decoded, "admitted": len(admitted),
            "pages_in_use": eng.pages_in_use, "pending": self.batcher.pending,
            "held_positions": int(eng.positions[~eng.done].sum())})

    def until(self, t_end, drained=None):
        """Serve until the clock passes ``t_end``; with ``drained`` (a
        predicate), until it holds and the generator has ended."""
        while clock() < t_end:
            self._submit_due()
            if self.batcher.pending or self.batcher.active:
                self._step()
            elif (drained is not None and not self.gen.is_alive()
                  and self.gen.handoff.empty()):
                return
            else:
                time.sleep(0.0005)
            if drained is not None and drained():
                return


def token_gaps(token_times, lo, hi):
    """Gaps between successive tokens of one request that end in [lo, hi)."""
    return [b - a for a, b in zip(token_times, token_times[1:]) if lo <= b < hi]


def latency_stats(name, seconds):
    """Mean, median and tails (ms) of a window's times to first token or
    gaps between tokens: ``{name}_mean_ms``, ``_p50_ms``, ``_p90_ms``,
    ``_p99_ms``. BENCHMARK.json says which are end-to-end metrics."""
    if not seconds:
        return {}
    out = {f"{name}_mean_ms": 1e3 * sum(seconds) / len(seconds)}
    for q in (50, 90, 99):
        out[f"{name}_p{q}_ms"] = 1e3 * percentile(seconds, q)
    return out


def logit_gaps(ref, weights, config, sample, shape, precision=None):
    """How far below the reference's best logit the judged tokens lie, over
    every generated position of the sampled requests: ``widest_gap`` (the
    worst position) and ``mean_gap`` (over all of them: most positions read
    0, so it counts how often and how far the judged token is not the
    reference's first), and the number of positions. The judged tokens are
    the served ones; with ``precision`` (the control) they are the tokens
    that precision puts first on the same positions."""
    import numpy as np

    worst, total, count = 0.0, 0.0, 0
    for prompt, output in sample:
        tokens, first, n = prompt + output[:-1], len(prompt) - 1, len(output)
        logits = ref.next_token_logits(weights, config, tokens, first, n,
                                       pad_to=shape[0], out_pad=shape[1])
        judged = np.asarray(output)
        if precision is not None:
            judged = ref.next_token_logits(
                weights, config, tokens, first, n, precision=precision,
                pad_to=shape[0], out_pad=shape[1]).argmax(axis=-1)
        gaps = logits.max(axis=-1) - logits[np.arange(n), judged]
        worst, total = max(worst, float(gaps.max())), total + float(gaps.sum())
        count += n
    return {"widest_gap": worst, "mean_gap": total / max(count, 1)}, count


def check_shape(mix):
    """One shape for every reference forward: the longest prompt and answer
    of the mix, the sequence padded to a multiple of 128."""
    longest = mix["prompt_len"]["max"] + mix["answer_len"]["max"]
    return (-(-longest // 128) * 128, mix["answer_len"]["max"])


def pick_sample(finished, seed, n):
    """The longest finished request and ``n - 1`` others drawn from the
    seed, as (prompt, output)."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"])
                                   + len(finished[i]["req"].output)))
    rest = traffic.rng_for(seed, 3).permutation(order[1:])[:max(n - 1, 0)]
    return [(finished[i]["prompt"], list(finished[i]["req"].output))
            for i in [order[0], *rest.tolist()]]


def run(cell, config, mix, seed, seconds, trace_on, devices, peaks, t_start,
        root):
    phases, t_phase = {}, clock()

    def phase(name):
        nonlocal t_phase
        now = clock()
        phases[name] = round(now - t_phase, 3)
        t_phase = now

    phases["import"] = round(t_phase - t_start, 3)
    ref = reference_for(config)
    weights = make_weights(ref.param_specs(config), seed)
    engine, batcher = system_for(config).build_serve(config, weights)
    say(f"engine: {engine.batch_size} slots, {engine.num_pages} pages of "
        f"{engine.page_size}, buckets {engine.prefill_buckets}, read path "
        f"{engine.read_path}")
    phase("build")
    extra_s = mix["trace_s"] + 6.0 if trace_on else 0.0
    schedule = traffic.serve_schedule(mix, config["n_vocab"], seed, seconds,
                                      extra_s)
    # every prefill bucket the mix can reach, and the decode program
    longest = mix["prompt_len"]["max"]
    buckets = [b for b in engine.prefill_buckets
               if b <= engine.bucket_for(longest)]
    rng = traffic.rng_for(seed, 4)
    for b in buckets:
        batcher.submit(rng.integers(1, config["n_vocab"], min(b, longest))
                       .tolist(), max_new_tokens=3)
    batcher.run_until_idle()
    phase("compile_and_warm_up")

    compiles, host = CompileCounter(), HostLoad()
    programs_before = engine.compiled_programs
    origin = clock()
    gen = Generator(schedule["requests"], origin)
    server = Server(engine, batcher, gen)
    due_recs = [r for r in schedule["requests"] if r["in_window"]]

    def window_done():
        return all("refused" in r or ("req" in r and r["req"].done)
                   for r in due_recs)

    trace, trace_span = None, None
    gen.start()
    try:
        # the window opens where the lead-in's last step ended and closes
        # where the step under way at the end of --seconds ended: whole steps
        # with all their tokens, none cut by an edge
        server.until(origin + schedule["window"][0])
        t_w0 = clock()
        setup_s = t_w0 - t_start
        phase("lead_in")
        compiles.start()
        host.start()
        server.until(origin + schedule["window"][1])
        t_w1 = clock()
        host_load = host.stop()
        window_compiles = max(compiles.stop(),
                              engine.compiled_programs - programs_before)
        if trace_on:
            trace = {}
            with tracing.traced(root, trace):
                server.until(clock() + 1.0)  # the profiler's start has passed
                t_a = clock()
                with tracing.span(tracing.WINDOW):
                    server.until(t_a + mix["trace_s"])
                trace_span = (t_a, clock())
        if mix["drain"]:  # until every request due in the window has ended
            server.until(clock() + 120.0, drained=window_done)
    finally:
        gen.stop.set()
        gen.join(timeout=10.0)
    peak = memory_peak_bytes(devices)

    # -- the window's numbers -----------------------------------------------
    # below the knee (the mix drains) the window's requests are those due in
    # it, and one that never finishes has failed; above it the queue grows
    # all through the run, so they are those that ENDED in it
    for r in schedule["requests"]:
        if "req" in r and r["req"].done:
            r["end_t"] = r["req"].finish_t
    window_recs = due_recs if mix["drain"] else [
        r for r in schedule["requests"] if t_w0 <= r.get("end_t", -1.0) < t_w1]
    finished = [r for r in window_recs if "req" in r
                and r["req"].finish_reason == "length"
                and len(r["req"].output) == r["max_new_tokens"]]
    failed = len(window_recs) - len(finished)
    ttft = [r["req"].first_token_t - (origin + r["due"])
            for r in schedule["requests"] if "req" in r
            and r["req"].first_token_t is not None
            and (r["in_window"] if mix["drain"]
                 else t_w0 <= r["req"].first_token_t < t_w1)]
    served = [t for r in schedule["requests"] for t in r.get("token_times", ())
              if t_w0 <= t < t_w1]
    gaps = [g for r in schedule["requests"] if r.get("token_times")
            for g in token_gaps(r["token_times"], t_w0, t_w1)]
    latency = {**latency_stats("ttft", ttft), **latency_stats("itl", gaps)}
    say("latency in the window (ms): " + ", ".join(
        f"{k[:-3]} {v:.1f}" for k, v in latency.items()))
    slow = sorted(((s["t1"] - s["t0"], s) for s in server.steps
                   if t_w0 <= s["t1"] < t_w1), key=lambda x: -x[0])[:5]
    say("slowest steps of the window (s total = prefill + decode, admitted, "
        "at s into the window): " + ", ".join(
            f"{d:.3f}={s['prefill_s']:.3f}+{s['decode_s']:.3f} "
            f"a{s['admitted']}@{s['t1'] - t_w0:.1f}" for d, s in slow))
    decode = [s["decode_s"] for s in server.steps
              if t_w0 <= s["t1"] < t_w1 and s["decoded_rows"]]
    if len(decode) > 1:  # a run that reads low: the device's step, or the host?
        median = statistics.median(decode)
        say(f"pace of the window: decode step median {1e3 * median:.2f} ms, "
            f"{sum(d > 1.5 * median for d in decode)} of {len(decode)} over "
            f"1.5 medians, {sum(decode) - len(decode) * median:.3f} s above "
            f"the median in all; host: {host_load}")
    if trace:  # the device's own time for each program of the traced slice
        say("programs of the traced slice (calls x device ms each): " + ", ".join(
            f"{name} {n} x {1e3 * total / n:.3f}" for name, (n, total)
            in sorted(trace["modules"].items(), key=lambda kv: -kv[1][1])))
    if trace_span:  # how much later the generator ran with tracing on
        late = [r["handoff_t"] - (origin + r["due"])
                for r in schedule["requests"] if "handoff_t" in r
                and trace_span[0] <= origin + r["due"] < trace_span[1]]
        say(f"generator lateness p99 in the traced slice: "
            f"{1e3 * percentile(late, 99):.3f} ms over {len(late)} requests")
    say(f"setup by phase (s): {phases}; window {t_w1 - t_w0:.3f} s: "
        f"{len(window_recs)} requests, {len(finished)} finished, "
        f"{len(served)} tokens served, {window_compiles} compiles")

    # -- correct: the reference over a sample of what the window served, once
    # the engine is freed; none of this is counted in setup_s ---------------
    sample = pick_sample(finished, seed, mix["check_requests"])
    num_pages, batch_size = engine.num_pages, engine.batch_size
    steps = server.steps
    del server, batcher, engine
    gc.collect()
    t_ref = clock()
    check = Check()
    gaps, compared = logit_gaps(ref, weights, config, sample, check_shape(mix))
    for name, limit in config["check"].items():  # widest_gap, mean_gap
        check.add(f"{name} (of a served token below the reference's best "
                  f"logit; {compared} tokens of {len(sample)} requests)",
                  gaps[name], limit)
    check.add("served_tokens_compared (at least one)", compared >= 1, True,
              "true")
    check.add("window_compiles (programs compiled inside the window)",
              window_compiles, 0, "equal")
    check.add("requests_failed (of the window: refused, shed or unfinished)",
              failed, 0, "equal")
    check.report()
    say(f"reference check took {clock() - t_ref:.1f} s")
    return {
        "kind": "serve", "window": (t_w0, t_w1), "window_s": t_w1 - t_w0,
        "origin": origin, "requests": schedule["requests"], "steps": steps,
        "trace_span": trace_span, "num_pages": num_pages,
        "batch_size": batch_size, "window_compiles": window_compiles,
        "chips": len(devices), "peaks": peaks,
        "latency": latency,
        "end_to_end": {**latency, "setup_s": setup_s,
                       "serve_tokens_per_s": len(served) / (t_w1 - t_w0)},
        "memory_peak_bytes": peak, "correct": check.correct,
        "attempted": len(window_recs), "failed": failed, "trace": trace,
        "setup_phases": phases, "check": check.rows, "sample": sample}


def control(run, config, mix, seed, devices, precision):
    """The control's number on the sample the run compared: the reference in
    ``precision`` (the one below the configuration's) in the program's
    place."""
    ref = reference_for(config)
    weights = make_weights(ref.param_specs(config), seed)
    return logit_gaps(ref, weights, config, run["sample"], check_shape(mix),
                      precision)[0]
