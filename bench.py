"""BERT-large pretraining step time on one TPU chip.

One process, the one that holds the chip: it requires a TPU, measures, and
prints ONE JSON line naming the device it ran on. No TPU, a ``device_kind``
with no entry in the peak table, or any error while building or stepping
is an uncaught exception and a non-zero exit — there is no CPU fallback
and no zero-valued line. Send it through the chip tool.

The step is the path docs/PERFORMANCE.md tells users to take: float32
master weights, ``TrainStep(amp="bfloat16")``, Adam; forward + backward +
update as one donated jit program. Batch 64 x seq 128 is the phase-1
pretraining shape ``chip_smoke.py`` also runs.

Baseline (BASELINE.md): reference-era GluonNLP BERT-large pretraining was
~60-80 seq/s per V100 (fp16, seq 128); vs_baseline uses the 70 seq/s
midpoint.

This is one cell, kept so the command keeps working. The benchmark proper
(a workloads list, traced windows, the ledger) is the next PR's, and may
replace this file.
"""
from __future__ import annotations

import json
import time

METRIC = "bert_large_samples_per_sec_chip"
MODEL, BATCH, SEQ, MASKED = "bert_large", 64, 128, 20  # chip_smoke's sizes
WARMUP, STEPS, WINDOWS = 3, 10, 3

# bf16 dense peak FLOP/s of one chip, keyed by jax's device_kind. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A device that
# is not here is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_for(kind: str) -> float:
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(f"no peak FLOP/s on record for device_kind {kind!r}; "
                       f"known: {sorted(PEAK_BF16_FLOPS)}")
    return PEAK_BF16_FLOPS[kind]


def bert_flops(batch, seq, masked, num_layers, units, hidden, vocab):
    """Training FLOPs (fwd + bwd ~= 3x fwd matmul FLOPs) per step."""
    per_token_layer = (
        4 * units * units * 2          # qkv + out proj
        + 2 * units * hidden * 2       # ffn in/out
        + 2 * seq * units * 2          # attention scores + context
    )
    fwd = batch * seq * per_token_layer * num_layers
    head = batch * masked * units * vocab * 2
    return 3 * (fwd + head)


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"bench.py measures on a TPU; jax.devices() "
                           f"found platform {dev.platform!r} "
                           f"({dev.device_kind!r})")
    peak = peak_for(dev.device_kind)

    from chip_smoke import build_bert_step
    from mxnet_tpu.models.bert import bert_configs

    ts, args = build_bert_step(MODEL, BATCH, SEQ, MASKED)
    t0 = time.perf_counter()
    for _ in range(WARMUP):  # the compile, then steady state
        loss = ts(*args)
    jax.block_until_ready(loss)
    warmup_s = time.perf_counter() - t0

    times = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            loss = ts(*args)
        jax.block_until_ready(loss)  # each step consumes the one before
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[len(times) // 2]

    cfg = bert_configs[MODEL]
    flops = bert_flops(BATCH, SEQ, MASKED, cfg["num_layers"], cfg["units"],
                       cfg["hidden_size"], cfg["vocab_size"])
    sps = STEPS * BATCH / dt
    print(json.dumps({
        "metric": METRIC,
        "value": round(sps, 2),
        "unit": "seq/s",
        "vs_baseline": round(sps / 70.0, 3),
        "batch": BATCH, "seq": SEQ, "steps": STEPS,
        "window_times_s": [round(t, 3) for t in times],
        "warmup_with_compile_s": round(warmup_s, 2),
        "loss": float(loss),
        "mfu_est": round(flops * STEPS / dt / peak, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "peak_flops": peak,
    }), flush=True)


if __name__ == "__main__":
    main()
