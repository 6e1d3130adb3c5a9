#!/usr/bin/env python
"""Long-context causal LM step: sequence parallelism + O(L)-memory attention
(SURVEY §5.7 — a capability the reference does not have).

Two composable mechanisms, demonstrated end-to-end on a small decoder:

1. **Single chip, long sequence**: `multi_head_attention` routes to the
   Pallas flash kernel (O(L) memory, FlashAttention-2 backward) once
   seq >= 2048 (the kernel's gate; its crossover against einsum under the
   installed jax is not measured) — so one chip trains sequence
   lengths whose [B, H, T, T] score tensor could never materialize.
2. **Across chips**: the sequence axis itself is sharded over an `sp` mesh
   and K/V blocks rotate via `lax.ppermute` ring attention, with
   fully-future shards skipped under causality.

Run on CPU (no args) it builds an 8-virtual-device sp mesh; on a real
slice the same mesh spec spans chips over ICI.
"""
import argparse
import os

import numpy as np

# on a CPU host, expose 8 virtual devices so the sp mesh actually rotates;
# harmless on a real TPU slice (the flag only shapes the host platform) —
# must be set (appended, not clobbered) before jax's first import
_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()


def build_sp_mesh(n_devices=None):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < (n_devices or 2):
        # a 1-device "ring" never rotates — the demo would silently prove
        # nothing (e.g. jax was imported before our XLA_FLAGS edit)
        raise RuntimeError(
            f"only {len(devs)} device(s) visible; the sp mesh needs >= 2 "
            "(is jax pre-imported with a different XLA_FLAGS?)")
    return Mesh(np.array(devs[:n]), ("sp",))


def ring_lm_step(mesh, batch=1, heads=4, seq_global=8192, d=64, causal=True):
    """One sharded attention fwd+bwd over a sequence-parallel mesh."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring_attention import ring_attention

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(batch, heads, seq_global, d), jnp.float32) * 0.1
    k = jnp.asarray(rs.randn(batch, heads, seq_global, d), jnp.float32) * 0.1
    v = jnp.asarray(rs.randn(batch, heads, seq_global, d), jnp.float32)

    def loss(q, k, v):
        out = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    return float(val), [g.shape for g in grads]


def single_chip_flash_lm(seq=4096, steps=3, vocab=512, units=256, heads=4):
    """Train a tiny decoder at a flash-kernel sequence length on one chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.models import gpt2

    mx.random.seed(0)
    net = gpt2.GPT2Model(num_layers=2, units=units, num_heads=heads,
                         max_length=seq, vocab_size=vocab, dropout=0.0)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-4})
    rs = np.random.RandomState(0)
    ids = nd.array(rs.randint(0, vocab, (1, seq)), dtype="int32")
    labels = nd.array(np.roll(np.asarray(ids.asnumpy()), -1, 1), dtype="int32")
    losses = []
    for _ in range(steps):
        with autograd.record():
            loss = gpt2.lm_loss(net(ids), labels)
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-global", type=int, default=8192)
    ap.add_argument("--single-chip-seq", type=int, default=4096)
    ap.add_argument("--sp", type=int, default=None,
                    help="sp mesh size (default: all visible devices)")
    args = ap.parse_args()

    mesh = build_sp_mesh(args.sp)
    n = mesh.shape["sp"]
    print(f"sp mesh: {n} devices, {args.seq_global} global tokens "
          f"({args.seq_global // n} per device)")
    val, shapes = ring_lm_step(mesh, seq_global=args.seq_global)
    print(f"ring attention fwd+bwd ok: loss {val:.4f}, grad shapes {shapes}")

    losses = single_chip_flash_lm(seq=args.single_chip_seq)
    print(f"single-chip seq-{args.single_chip_seq} LM losses: "
          f"{[round(l, 4) for l in losses]}")


if __name__ == "__main__":
    main()
