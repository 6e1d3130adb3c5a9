"""Secondary model benchmarks on one TPU chip: ResNet-50 (BASELINE config
#2: images/sec + MFU) and GPT-2 345M (config #5 shape, single-chip LM step).

bench.py owns the headline BERT-large line; this tool records the other
configs' hardware numbers. One process, the one that holds the chip; a
device_kind with no entry in bench.py's peak table (the CPU included) is an
error. Prints one JSON line per config. Send it through the chip tool.

Usage: python tools/modelbench.py [--models resnet50,gpt2_345m] [--steps 10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import peak_for as _peak  # one shared peak-FLOPs table


def _sync(x):
    import jax
    import numpy as np

    return float(np.asarray(jax.device_get(x)))


def _measure(step, args, steps, flops_per_step, kind, warmup=3):
    loss = None
    for _ in range(warmup):
        loss = step(*args)
        _sync(loss)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(*args)
        _sync(loss)
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    return {
        "steps": steps,
        "step_time_s": round(dt / steps, 4),
        "window_times_s": [round(t, 3) for t in times],
        "mfu_est": round(flops_per_step * steps / dt / _peak(kind), 4),
        "loss": _sync(loss),
    }




def _is_oom(e):
    s = repr(e)
    return ("RESOURCE_EXHAUSTED" in s or "ResourceExhausted" in s
            or "Out of memory" in s or "out of memory" in s)

def bench_resnet50(steps, kind, batch=128):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    from mxnet_tpu.parallel import TrainStep

    while batch >= 2:
        try:
            mx.random.seed(0)
            net = get_model("resnet50_v1", classes=1000)
            net.initialize()
            rs = np.random.RandomState(0)
            x = nd.array(rs.randn(batch, 3, 224, 224).astype("float32"))
            y = nd.array(rs.randint(0, 1000, (batch,)), dtype="int32")
            _ = net(x)
            net.cast("bfloat16")
            x = x.astype("bfloat16")

            def loss_fn(out, y):
                import jax
                import jax.numpy as jnp

                logits = (out._data if hasattr(out, "_data")
                          else out).astype(jnp.float32)
                yv = (y._data if hasattr(y, "_data")
                      else y).astype(jnp.int32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                return -jnp.take_along_axis(logp, yv[:, None], axis=-1).mean()

            ts = TrainStep(net, loss_fn,
                           optimizer.SGD(learning_rate=0.1, momentum=0.9),
                           mesh=None, n_model_inputs=1)
            # ResNet-50 fwd ~4.09 GFLOP/img @224; train ~= 3x fwd
            res = _measure(ts, (x, y), steps, 3 * 4.09e9 * batch, kind)
            res.update(metric="resnet50_images_per_sec", batch=batch,
                       value=round(batch / res["step_time_s"], 1),
                       unit="img/s")
            return res
        except Exception as e:
            if not _is_oom(e):
                raise  # deterministic bug: surface the traceback, don't retry
            err = repr(e)[:160]
            batch //= 2
    return {"metric": "resnet50_images_per_sec", "value": 0.0, "error": err}


def bench_gpt2(steps, kind, name="gpt2_345m", batch=4, seq=1024):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.models import gpt2
    from mxnet_tpu.parallel import TrainStep

    if name not in gpt2.gpt2_configs:
        return {"metric": f"{name}_tokens_per_sec", "value": 0.0,
                "error": f"unknown gpt2 config {name}; "
                         f"options {sorted(gpt2.gpt2_configs)}"}
    cfg0 = gpt2.gpt2_configs[name]
    seq = min(seq, cfg0["max_length"])  # OOB positions would embed garbage
    cfg = cfg0
    while batch >= 1:
        try:
            mx.random.seed(0)
            net = gpt2.GPT2Model(**cfg, dropout=0.0)
            net.initialize()
            rs = np.random.RandomState(0)
            ids = nd.array(rs.randint(0, cfg["vocab_size"], (batch, seq)),
                           dtype="int32")
            labels = nd.array(np.roll(np.asarray(ids.asnumpy()), -1, 1),
                              dtype="int32")
            _ = net(ids)
            net.cast("bfloat16")

            ts = TrainStep(net, gpt2.lm_loss,
                           optimizer.Adam(learning_rate=1e-4),
                           mesh=None, n_model_inputs=1)
            L, U, H, V = (cfg["num_layers"], cfg["units"],
                          cfg.get("hidden_size", 4 * cfg["units"]),
                          cfg["vocab_size"])
            per_tok = (4 * U * U + 2 * U * H + 2 * seq * U) * 2 * L
            flops = 3 * batch * seq * (per_tok + U * V * 2)
            res = _measure(ts, (ids, labels), steps, flops, kind)
            res.update(metric=f"{name}_tokens_per_sec", batch=batch, seq=seq,
                       value=round(batch * seq / res["step_time_s"], 1),
                       unit="tok/s")
            return res
        except Exception as e:
            if not _is_oom(e):
                raise
            err = repr(e)[:160]
            batch //= 2
    return {"metric": f"{name}_tokens_per_sec", "value": 0.0, "error": err}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="resnet50,gpt2_345m")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--resnet-batch", type=int, default=128,
                    help="starting batch for resnet50 (dryruns shrink it)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    _peak(kind)  # an unknown device fails here, before any model is built
    results = []
    for m in args.models.split(","):
        m = m.strip()
        if m == "resnet50":
            r = bench_resnet50(args.steps, kind, batch=args.resnet_batch)
        elif m.startswith("gpt2"):
            r = bench_gpt2(args.steps, kind, name=m)
        else:
            r = {"metric": m, "error": "unknown model"}
        r["platform"] = dev.platform
        r["device_kind"] = kind
        print(json.dumps(r), flush=True)
        results.append(r)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
