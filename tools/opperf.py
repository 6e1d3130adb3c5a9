#!/usr/bin/env python
"""Per-operator micro-benchmark runner.

Reference analog: ``benchmark/opperf/opperf.py`` — the suite that produced
the reference's per-op latency tables (BASELINE.md). Runs each registry op
on representative shapes, reporting median wall time over timed reps with a
jit-warmup first (compile excluded, like the reference's warmup).

Usage:
  python tools/opperf.py                      # default op set
  python tools/opperf.py --ops dot,softmax    # subset
  python tools/opperf.py --json results.json  # machine-readable dump
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# representative shapes per op family (reference: opperf's DEFAULT_* shapes,
# scaled to finish quickly on any backend)
_CASES = {
    "dot": lambda nd: (nd.array(np.random.rand(256, 256).astype(np.float32)),
                       nd.array(np.random.rand(256, 256).astype(np.float32))),
    "batch_dot": lambda nd: (nd.array(np.random.rand(8, 128, 128).astype(np.float32)),
                             nd.array(np.random.rand(8, 128, 128).astype(np.float32))),
    "add": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),
                       nd.array(np.random.rand(512, 512).astype(np.float32))),
    "multiply": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),
                            nd.array(np.random.rand(512, 512).astype(np.float32))),
    "exp": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "tanh": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "relu": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "sigmoid": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "softmax": lambda nd: (nd.array(np.random.rand(128, 1024).astype(np.float32)),),
    "log_softmax": lambda nd: (nd.array(np.random.rand(128, 1024).astype(np.float32)),),
    "sum": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "mean": lambda nd: (nd.array(np.random.rand(512, 512).astype(np.float32)),),
    "transpose": lambda nd: (nd.array(np.random.rand(256, 512).astype(np.float32)),),
    "concat": lambda nd: (nd.array(np.random.rand(256, 256).astype(np.float32)),
                          nd.array(np.random.rand(256, 256).astype(np.float32))),
    "take": lambda nd: (nd.array(np.random.rand(1024, 64).astype(np.float32)),
                        nd.array(np.random.randint(0, 1024, 256), dtype="int32")),
    "LayerNorm": lambda nd: (nd.array(np.random.rand(128, 768).astype(np.float32)),
                             nd.ones((768,)), nd.zeros((768,))),
    "FullyConnected": lambda nd: (
        nd.array(np.random.rand(128, 512).astype(np.float32)),
        nd.array(np.random.rand(256, 512).astype(np.float32)),
        nd.array(np.random.rand(256).astype(np.float32))),
    "Convolution": lambda nd: (
        nd.array(np.random.rand(8, 16, 32, 32).astype(np.float32)),
        nd.array(np.random.rand(32, 16, 3, 3).astype(np.float32)),
        nd.array(np.random.rand(32).astype(np.float32))),
    "linalg_potrf": lambda nd: (nd.array(
        (lambda a: a @ a.T + 64 * np.eye(64, dtype=np.float32))(
            np.random.rand(64, 64).astype(np.float32))),),
    "linalg_gemm2": lambda nd: (nd.array(np.random.rand(8, 128, 128).astype(np.float32)),
                                nd.array(np.random.rand(8, 128, 128).astype(np.float32))),
    "adam_update": lambda nd: (
        nd.array(np.random.rand(512, 512).astype(np.float32)),
        nd.array(np.random.rand(512, 512).astype(np.float32)),
        nd.array((np.random.rand(512, 512) * 0.1).astype(np.float32)),
        nd.array((np.abs(np.random.rand(512, 512)) * 0.01).astype(np.float32))),
    "softmax_cross_entropy_fused": lambda nd: (
        nd.array(np.random.rand(128, 1024).astype(np.float32)),
        nd.array(np.random.randint(0, 1024, 128), dtype="int32")),
    "paged_attention": lambda nd: _paged_attention_case(),
}


def _paged_attention_case():
    """Engine-internal surface (no nd registry entry): the paged decode
    read path at a small decode shape — f32 activations, bf16 pool."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    b, h, ch, ps, n_pages = 8, 2, 32, 16, 8
    pool_pages = b * n_pages
    return (jnp.asarray(rng.randn(b, h, 1, ch), jnp.float32),
            jnp.asarray(rng.randn(b, h, 1, ch), jnp.float32),
            jnp.asarray(rng.randn(b, h, 1, ch), jnp.float32),
            jnp.asarray(rng.randn(pool_pages + 1, ps, h * ch), jnp.bfloat16),
            jnp.asarray(rng.randn(pool_pages + 1, ps, h * ch), jnp.bfloat16),
            jnp.asarray(rng.randint(1, pool_pages + 1, (b, n_pages)),
                        jnp.int32),
            jnp.asarray(rng.randint(0, n_pages * ps - 1, (b,)), jnp.int32))


# kernel surfaces that live below the nd registry (the engine calls them
# directly); benched on raw jax arrays
def _extra_fn(name):
    if name == "paged_attention":
        import jax

        from mxnet_tpu.ops import pallas_paged_attention as ppa

        return jax.jit(ppa.paged_attention)
    raise KeyError(name)

_KWARGS = {
    "FullyConnected": {"num_hidden": 256},
    "Convolution": {"num_filter": 32, "kernel": (3, 3)},
    "concat": {"dim": 1},
    "adam_update": {"lr": 0.001},
}


def _sync(out):
    o = out[0] if isinstance(out, (tuple, list)) else out
    if hasattr(o, "wait_to_read"):
        o.wait_to_read()
    else:
        o.block_until_ready()


def bench_op(name, reps=20, warmup=3):
    from mxnet_tpu import nd

    mk = _CASES[name]
    args = mk(nd)
    kwargs = _KWARGS.get(name, {})
    fn = getattr(nd, name, None) or _extra_fn(name)
    for _ in range(warmup):
        out = fn(*args, **kwargs)
    _sync(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        _sync(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return {"op": name, "p50_us": round(times[len(times) // 2] * 1e6, 1),
            "min_us": round(times[0] * 1e6, 1),
            "max_us": round(times[-1] * 1e6, 1), "reps": reps}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="", help="comma-separated subset")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--json", default="", help="write results to this file")
    ap.add_argument("--platform", default="",
                    help="force a jax platform (e.g. cpu) before backend init")
    args = ap.parse_args()

    if args.platform:
        # must happen before the first backend touch
        import jax

        jax.config.update("jax_platforms", args.platform)

    names = [o for o in args.ops.split(",") if o] or sorted(_CASES)
    unknown = [n for n in names if n not in _CASES]
    if unknown:
        ap.error(f"no benchmark case for: {unknown}; known: {sorted(_CASES)}")

    import mxnet_tpu as mx

    mx.random.seed(0)
    results = [bench_op(n, reps=args.reps) for n in names]
    header = f"{'Operator':<20} {'p50(us)':>10} {'min(us)':>10} {'max(us)':>10}"
    print(header)
    print("-" * len(header))
    for r in results:
        print(f"{r['op']:<20} {r['p50_us']:>10} {r['min_us']:>10} {r['max_us']:>10}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
