"""The training runner: free-running steps of the program's compiled step
on a pool of seeded host batches; ``correct`` from the first steps of that
same step object against the plain reference."""
from __future__ import annotations

import gc
import math
import statistics
import time

from . import tracing, traffic
from .harness import (Check, CompileCounter, HostLoad, memory_peak_bytes,
                      reference_for, say, system_for)
from .reference.norms import leaf_diff_norms, leaf_norms, worst_leaf_gap
from .weights import make_weights, weights_by_leaf


def first_steps(ts, names, batches, start_weights):
    """Drive the step object through ``batches`` by its own call and read
    what the comparison needs: each loss, the first gradient's norm by leaf
    as the optimizer got it (Adam's first moment after one step is
    ``(1 - beta1) * g``), and each leaf's change over the steps.
    ``start_weights()`` yields the starting weights again, leaf by leaf, for
    the change."""
    import jax

    losses, grad = [], None
    for batch in batches:
        losses.append(ts(*batch))
        if grad is None:
            scale = 1.0 / (1.0 - ts.optimizer.beta1)
            grad = {names[k]: scale * float(v) for k, v in jax.device_get(
                leaf_norms({k: s[0] for k, s in ts.opt_state.items()})).items()}
    # the starting weights are made again a leaf at a time: a second whole
    # copy (and the temporaries of making it) would raise the device's peak
    # above the program's own
    program_name = {v: k for k, v in names.items()}
    change = {}
    for key, start in start_weights():
        now = ts.params[program_name[key]]
        change[key] = leaf_diff_norms(now, jax.device_put(start, now.sharding))
    return {"loss": [float(x) for x in jax.device_get(losses)],
            "grad_norm": grad,
            "change_norm": {k: float(v) for k, v in
                            jax.device_get(change).items()}}


def compare(check, got, want, limits, matrices):
    """Each number compared, beside its limit (the limits and the readings
    they were set from are in PERF.md). The first gradient's norm is taken
    by the worst leaf twice: over the matrices (``matrices``, the leaves of
    two or more dimensions), where rounding shows as a steady excess of
    norm, and over the vectors, whose few elements make the same rounding a
    coin's toss from seed to seed. The first loss is a forward pass at the
    seeded weights and is held tightly; the later ones follow Adam's first
    steps, which move every weight by the whole rate whatever its gradient,
    so a seed now and then sends the program and the reference apart (their
    limit and the change's are three times the widest such reading)."""
    first, *later = (abs(g - w) / abs(w)
                     for g, w in zip(got["loss"], want["loss"]))
    check.add("loss_rel.first (the first step's loss)", first,
              limits["loss_rel.first"])
    check.add("loss_rel.later (worst of the steps after it)", max(later),
              limits["loss_rel.later"])
    for name, keep in (("matrices", lambda k: k in matrices),
                       ("vectors", lambda k: k not in matrices)):
        gap, leaf = worst_leaf_gap(
            {k: v for k, v in got["grad_norm"].items() if keep(k)},
            {k: v for k, v in want["grad_norm"].items() if keep(k)})
        check.add(f"grad_norm_rel.{name} (worst leaf: {leaf})", gap,
                  limits[f"grad_norm_rel.{name}"])
    gap, leaf = worst_leaf_gap(got["change_norm"], want["change_norm"])
    check.add(f"change_norm_rel (worst leaf: {leaf})", gap,
              limits["change_norm_rel"])


def pace(done_t, window_s, steps, after=8):
    """How the window's steps came in, from the times the host saw each one
    done: the median gap between completions is the device's own step while
    work is queued; ``lost_s`` is the window less ``steps`` such gaps, the
    time the device had nothing queued (a host away for longer than the
    steps in flight cover) or ran slower than its median. The longest gaps
    are listed with the mean of the ``after`` gaps that follow each: a stall
    the queue covered is followed by completions that come at once."""
    gaps = [b - a for a, b in zip(done_t, done_t[1:])]
    if len(gaps) < 2:
        return {}, []
    median = statistics.median(gaps)
    longest = sorted(range(len(gaps)), key=lambda k: -gaps[k])[:5]
    return ({"median_step_ms": round(1e3 * median, 3),
             "p99_gap_ms": round(1e3 * sorted(gaps)[int(0.99 * (len(gaps) - 1))], 3),
             "gaps_over_1.5_medians": sum(g > 1.5 * median for g in gaps),
             "lost_s": round(window_s - steps * median, 3)},
            [(gaps[k], k + 1, statistics.fmean(gaps[k + 1:k + 1 + after] or [0.0]))
             for k in longest])


def matrices_of(specs):
    return {name for name, shape, _ in specs if len(shape) >= 2}


def reference_readings(config, mix, seed, batches, devices, precision="float32"):
    """The reference's first steps from the same weights and batches, rows
    split over the chips where there are several."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ref = reference_for(config)
    weights = make_weights(ref.param_specs(config), seed)
    place, rows = (lambda block: block), mix["reference_block_rows"]
    if len(devices) > 1:
        mesh = Mesh(devices, ("rows",))
        weights = jax.device_put(weights, NamedSharding(mesh, P()))
        place = lambda block: jax.device_put(  # noqa: E731
            block, NamedSharding(mesh, P("rows")))
        rows *= len(devices)
    return ref.train_steps(weights, config, config["optimizer"], batches, rows,
                           precision, place)


def run(cell, config, mix, seed, seconds, trace_on, devices, peaks, t_start,
        root):
    import jax

    phases, t_phase = {}, time.perf_counter()

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases[name] = round(now - t_phase, 3)
        t_phase = now

    phases["import"] = round(t_phase - t_start, 3)
    ref = reference_for(config)
    specs = ref.param_specs(config)
    pool = traffic.train_batches(mix, config["vocab_size"],
                                 config["type_vocab_size"], seed)
    ts, names = system_for(config).build_train(
        config, mix, make_weights(specs, seed))
    phase("build")
    n_check = mix["check_steps"]
    got = first_steps(ts, names, pool[:n_check],
                      lambda: weights_by_leaf(specs, seed))
    phase("compile_and_first_steps")
    for batch in pool[n_check:n_check + mix["run_ahead"]]:
        loss = ts(*batch)
    jax.block_until_ready(loss)
    phase("warm_up")

    # -- the window: free-running steps, at most run_ahead in flight ---------
    losses, done_t, i = [], [], n_check + mix["run_ahead"]
    compiles, host = CompileCounter(), HostLoad()
    compiles.start()
    host.start()
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    while True:
        losses.append(ts(*pool[i % len(pool)]))
        i += 1
        if len(losses) > mix["run_ahead"]:
            jax.block_until_ready(losses[-1 - mix["run_ahead"]])
            done_t.append(time.perf_counter() - t0)
        if time.perf_counter() - t0 >= seconds:
            break
    t_close = time.perf_counter()
    jax.block_until_ready(losses[-1])
    window_s = time.perf_counter() - t0
    drain_s = time.perf_counter() - t_close
    steps, window_compiles, host_load = len(losses), compiles.stop(), host.stop()

    trace = None
    if trace_on:
        trace = {}
        with tracing.traced(root, trace):
            with tracing.span(tracing.WINDOW):
                for _ in range(mix["trace_steps"]):
                    with tracing.span("bench.input"):
                        batch = pool[i % len(pool)]
                    with tracing.span("bench.step"):
                        losses.append(ts(*batch))
                    i += 1
                    if len(losses) > mix["run_ahead"]:
                        with tracing.span("bench.wait"):
                            jax.block_until_ready(
                                losses[-1 - mix["run_ahead"]])
                with tracing.span("bench.wait"):
                    jax.block_until_ready(losses[-1])

    peak = memory_peak_bytes(devices)
    values = [float(x) for x in jax.device_get(losses)]
    failed = sum(not math.isfinite(x) for x in values)
    tokens = mix["global_batch"] * mix["seq_length"]
    say(f"setup by phase (s): {phases}; window {window_s:.3f} s, {steps} steps")
    summary, longest = pace(done_t, window_s, steps)
    if summary:  # the steps still queued when the clock ran out, by their time
        summary["queued_at_close"] = round(
            1e3 * drain_s / summary["median_step_ms"], 1)
    say(f"pace of the window: {summary}; host: {host_load}")
    say("longest waits for a step (s after the one before @step, then the "
        "mean of the next 8): "
        + ", ".join(f"{g:.3f}@{k} then {after:.3f}" for g, k, after in longest))

    # -- correct: the reference follows the same first steps, once the
    # program's state is freed; none of this is counted in setup_s ----------
    del ts, losses
    gc.collect()
    t_ref = time.perf_counter()
    want = reference_readings(config, mix, seed, pool[:n_check], devices)
    check = Check()
    compare(check, got, want, config["check"], matrices_of(specs))
    check.add("finite loss at every step of the window", failed, 0, "equal")
    check.add("programs compiled inside the window", window_compiles, 0,
              "equal")
    check.report()
    say(f"reference check took {time.perf_counter() - t_ref:.1f} s; "
        f"losses {got['loss']} against {want['loss']}")
    return {
        "kind": "train", "window_s": window_s, "steps": steps,
        "tokens_per_step": tokens, "chips": len(devices), "peaks": peaks,
        "flops_per_step": ref.train_flops(config, mix["global_batch"],
                                          mix["seq_length"],
                                          mix["masked_per_seq"]),
        "end_to_end": {"train_tokens_per_s": steps * tokens / window_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "correct": check.correct,
        "attempted": steps, "failed": failed, "trace": trace,
        "setup_phases": phases, "check": check.rows, "pace": summary,
        "host_load": host_load,
        "readings": {"program": got, "reference": want}}


def control(run, config, mix, seed, devices, precision):
    """The control's numbers: the reference in ``precision`` (the one below
    the configuration's), in the program's place, against the reference."""
    batches = traffic.train_batches(mix, config["vocab_size"],
                                    config["type_vocab_size"], seed,
                                    count=mix["check_steps"])
    low = reference_readings(config, mix, seed, batches, devices,
                             precision)
    check = Check()
    compare(check, low, run["readings"]["reference"],
            {k: float("inf") for k in config["check"]},
            matrices_of(reference_for(config).param_specs(config)))
    return {**{r["name"].split(" ")[0]: r["value"] for r in check.rows},
            "readings": low}
