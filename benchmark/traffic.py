"""The one general traffic generator: a mix file's parameters and a seed in,
batches or a request schedule out.

Every seed gets the SAME work. A serving mix is one period of traffic, as
long as the window: lengths are the distribution's quantiles (as many as
there are requests), arrival gaps the exponential's quantiles scaled to the
period, both shuffled once by the mix's own ``pattern_seed``. A run plays
that period round and round from its beginning, so every seed's window holds
the same requests at the same times; the seed draws the tokens (and the
weights). A tail percentile over a few hundred requests is steady only so:
with the order itself drawn from the seed, ``ttft_p90_ms`` spread by 13% over
three seeds, and with only the cycle's starting point drawn from it, tokens
per second above the knee still moved by 4.1% between seeds against 0.1-0.8%
between two runs of one seed (the engine serves two thirds of what is
offered, in order, so another starting point serves another stretch of the
cycle with other lengths; PERF.md, PR 26).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])


# -- training ---------------------------------------------------------------
def train_batches(mix, vocab_size, type_vocab_size, seed, count=None):
    """``count`` (default the mix's pool) distinct host batches of the
    masked-LM pretraining task: ids, types, valid length, masked positions,
    labels, weights, next-sentence labels. Rows all differ."""
    rng = rng_for(seed, 1)
    b, t, m = mix["global_batch"], mix["seq_length"], mix["masked_per_seq"]
    out = []
    for _ in range(count or mix["pool_batches"]):
        valid = rng.integers(mix["valid_length_min"], t + 1, b).astype(np.int32)
        pos = np.stack([np.sort(rng.choice(v, m, replace=False)) for v in valid])
        out.append((rng.integers(0, vocab_size, (b, t)).astype(np.int32),
                    rng.integers(0, type_vocab_size, (b, t)).astype(np.int32),
                    valid, pos.astype(np.int32),
                    rng.integers(0, vocab_size, (b, m)).astype(np.int32),
                    np.ones((b, m), np.float32),
                    rng.integers(0, 2, b).astype(np.int32)))
    return out


# -- serving ----------------------------------------------------------------
def quantile_lengths(spec, n):
    """``n`` lengths at the mid-quantiles of a clipped log-normal."""
    mu = math.log(spec["median"])
    qs = [(i + 0.5) / n for i in range(n)]
    xs = [math.exp(mu + spec["sigma"] * _NORMAL.inv_cdf(q)) for q in qs]
    return [int(min(max(round(x), spec["min"]), spec["max"])) for x in xs]


def quantile_gaps(n, duration):
    """``n`` exponential gaps (mid-quantiles) that add up to ``duration``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = duration / sum(raw)
    return [g * scale for g in raw]


def base_pattern(mix, seconds):
    """One period of the mix's traffic: ``rate x seconds`` requests as (due,
    prompt_len, answer_len) with due times in [0, seconds), the lengths and
    gaps shuffled once by the mix's own ``pattern_seed``. Successive arrivals
    are exactly the exponential quantile gaps apart."""
    n = int(round(mix["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError("the mix's rate gives no request in the window")
    rng = rng_for(mix["pattern_seed"], 0)
    gaps = rng.permutation(quantile_gaps(n, seconds))
    due = np.cumsum(gaps) - gaps.min() / 2.0
    return (due, rng.permutation(quantile_lengths(mix["prompt_len"], n)),
            rng.permutation(quantile_lengths(mix["answer_len"], n)))


def serve_schedule(mix, vocab_size, seed, seconds, extra_s=0.0):
    """The open-loop schedule: the mix's one period of traffic
    (:func:`base_pattern`) played round and round from its beginning through
    a lead-in, the window and a tail (and, in a traced run, ``extra_s``
    more). The window is one period long and begins where the cycle does, so
    it is one fixed stretch of requests for every seed; the seed draws the
    prompts' tokens. Each request is a dict with its due time (seconds from
    the schedule's start), its prompt and the number of tokens to generate;
    ``window`` is (start, end) on the same clock."""
    due, prompts, answers = base_pattern(mix, seconds)
    rng = rng_for(seed, 2)
    lead = mix["lead_in_s"]
    end = lead + seconds + mix["tail_s"] + extra_s
    rows = []
    for k in range(-int(lead // seconds) - 2, int(end // seconds) + 2):
        t = due + k * seconds + lead
        rows += [(float(t[i]), int(prompts[i]), int(answers[i]))
                 for i in np.flatnonzero((t >= 0.0) & (t < end))]
    requests = []
    for t, p, a in sorted(rows):
        requests.append({
            "due": t, "max_new_tokens": a,
            "prompt": rng.integers(1, vocab_size, p).astype(np.int32).tolist(),
            "in_window": lead <= t < lead + seconds})
    return {"requests": requests, "window": (lead, lead + seconds), "end": end}
