"""The quickest proof that the system still starts on the chip.

One process, one TPU v5e chip, no network, weights from a seed. Drives the
two entry points a user takes, once each, at the full width of models the
repo lists (depth and all), and compiles every Pallas kernel the default
configuration can select:

  device   jax.devices() must be TPUs — checked before anything is built
  train    BERT-large pretraining through TrainStep(amp="bfloat16"), Adam
           with float32 masters, batch 64 x seq 128, a few steps
  serve    GPT-2 345M through GenerationEngine(paged=True, bf16 cache) and
           ContinuousBatcher; greedy tokens checked against a plain
           re-forward of the same net
  latent   DeepSeek-V2 at its toy widths (the published sizes do not fit
           beside the other phases) through GenerationEngine(paged=True,
           bf16 cache): the decode program's latent kernel compiled by
           Mosaic, its logits checked against a plain re-forward
  kernels  flash attention fwd+bwd, paged attention and packed attention
           fwd+bwd (BERT-large's: batch 64 x seq 128 x 3 x 1024, key mask),
           the experts' grouped products and the gated delta rule's decode
           step, compiled by Mosaic and compared with their XLA references;
           MiniCPM-SALA's 16,384 prefill program on a prompt of 12,288: the
           stretch the prompt does not reach is not run

Any failure in any phase raises and the process exits non-zero; nothing is
caught. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with exactly those keys; what each phase found is on the ``summary:`` line
before it. This is a smoke run: its seconds say the program ran, they are
not a benchmark.

    python chip_smoke.py            # one chip, all five phases
    python chip_smoke.py --chips 4  # device + train under the ZeRO layout
                                    # over four chips, against one chip

Send it through the chip tool; on a host without a TPU it fails at once.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import time

SEED = 0


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------
def require_tpu(min_devices=1):
    """The devices jax runs on, after checking that they are TPUs. No probe
    in a child, no waiting, no retry: one process owns the chip."""
    import jax

    devices = jax.devices()
    found = sorted({(d.platform, d.device_kind) for d in devices})
    if any(d.platform != "tpu" for d in devices):
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax.devices() found {len(devices)} "
            f"device(s) of (platform, kind) {found}")
    if len(devices) < min_devices:
        raise RuntimeError(
            f"chip_smoke --chips {min_devices} needs {min_devices} TPU "
            f"devices; jax.devices() found {len(devices)}: {devices}")
    return devices


def phase_device(min_devices):
    import jax
    import jaxlib

    devices = require_tpu(min_devices)
    import mxnet_tpu  # noqa: F401  (places the compile cache at import)

    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu")}
    say(f"device: platform={info['platform']} device_kind={info['kind']} "
        f"n_devices={info['count']} versions={versions} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}")
    return devices, info, versions


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------
def build_bert_step(model="bert_large", batch=64, seq=128, masked=20,
                    layout=None):
    """BERT pretraining as the docs tell users to run it: float32 masters,
    ``TrainStep(amp="bfloat16")``, Adam. Returns the step and one batch.
    Batch 64 x seq 128 without recomputation fits one v5e chip: the device
    reported a peak of 6.2 GB in use of its 16.9 GB (PR 21's run)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, optimizer
    from mxnet_tpu.models import bert
    from mxnet_tpu.parallel import TrainStep

    mx.random.seed(SEED)
    net = bert.get_bert(model, pretrain_head=True)
    net.initialize()
    vocab = bert.bert_configs[model]["vocab_size"]
    rs = np.random.RandomState(SEED)
    args = (
        nd.array(rs.randint(0, vocab, (batch, seq)), dtype="int32"),   # ids
        nd.zeros((batch, seq), dtype="int32"),                         # types
        nd.full((batch,), seq, dtype="int32"),                         # valid
        nd.array(rs.randint(0, seq, (batch, masked)), dtype="int32"),  # pos
        nd.array(rs.randint(0, vocab, (batch, masked)), dtype="int32"),
        nd.ones((batch, masked)),                                      # weights
        nd.array(rs.randint(0, 2, (batch,)), dtype="int32"),           # nsp
    )
    net(*(a[:2] for a in args[:4]))  # deferred init: shapes from two rows

    def loss_fn(out, labels, weights, nsp_labels):
        return bert.pretrain_loss(*out, labels, weights, nsp_labels)

    ts = TrainStep(net, loss_fn, optimizer.Adam(learning_rate=1e-4),
                   n_model_inputs=4, amp="bfloat16", layout=layout)
    return ts, args


def phase_train(devices, layout=None, steps=10, **size):
    """BERT-large pretraining steps on a repeated batch: one compile, then
    ``steps`` more."""
    import jax
    import numpy as np

    ts, args = build_bert_step(layout=layout, **size)

    losses, seconds = [], []
    for _ in range(1 + steps):  # the first call traces and compiles
        t0 = time.perf_counter()
        losses.append(ts(*args))
        jax.block_until_ready(losses[-1])
        seconds.append(time.perf_counter() - t0)
    losses = [float(x) for x in jax.device_get(losses)]
    compile_s, step_s = seconds[0], float(np.median(seconds[1:]))

    if not np.isfinite(losses).all():
        raise AssertionError(f"train: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    if max(seconds[1:]) > 10 * step_s:  # one compile, then steps
        raise AssertionError(f"train: a later step compiled again or "
                             f"stalled: {seconds}")

    # where the state lives: every parameter and optimizer moment on the
    # checked devices, float32 masters, and (under a layout) sharded
    # leaves spread over every device at 1/n of their bytes each
    state = jax.tree_util.tree_leaves((ts.params, ts.opt_state))
    want = set(devices) if layout is not None else {devices[0]}
    n_sharded = 0
    per_device = dict.fromkeys(devices, 0)
    for name, leaf in ts.params.items():
        if leaf.dtype != np.float32:
            raise AssertionError(f"train: master {name} is {leaf.dtype}")
    for leaf in state:
        if not leaf.sharding.device_set <= want:
            raise AssertionError(
                f"train: state on {leaf.sharding.device_set}, want {want}")
        shards = leaf.addressable_shards
        for sh in shards:
            per_device[sh.device] += sh.data.nbytes
        if shards[0].data.nbytes < leaf.nbytes:
            n_sharded += 1
            if (leaf.sharding.device_set != set(devices)
                    or shards[0].data.nbytes * len(devices) != leaf.nbytes):
                raise AssertionError(
                    f"train: sharded leaf {leaf.shape} not split evenly "
                    f"over {len(devices)} devices: {leaf.sharding}")
    total = sum(leaf.nbytes for leaf in state)
    mem = devices[0].memory_stats()
    out = {
        "model": size.get("model", "bert_large"),
        "batch_x_seq": list(args[0].shape),
        "amp": "bfloat16", "optimizer": "adam, float32 masters",
        "n_params": int(sum(p.size for p in ts.params.values())),
        "compile_plus_first_step_s": round(compile_s, 2),
        "step_s": round(step_s, 4), "steps": steps,
        "slowest_later_step_s": round(max(seconds[1:]), 4),
        "loss": [round(x, 5) for x in losses],
        "state_bytes": total,
        "state_bytes_per_device": [per_device[d] for d in devices],
        "sharded_leaves": n_sharded,
        "peak_bytes_in_use": mem["peak_bytes_in_use"],
        "bytes_limit": mem.get("bytes_limit"),
    }
    say(f"train: {out}")
    return out


def phase_train_four_chips(devices):
    """The same steps on one chip and under the ZeRO layout of
    docs/PARALLELISM.md over all four: state spread at a quarter per chip
    and the losses in agreement."""
    import numpy as np

    from mxnet_tpu.parallel import Layout

    one = phase_train(devices)
    gc.collect()  # the one-chip state must leave chip 0 first
    # pure ZeRO: no tensor-parallel rules (on a tp=1 mesh a matching tp
    # rule shards nothing and shadows the fsdp fallback — see PERF.md)
    layout = Layout(fsdp=4, fsdp_axis="fsdp")
    mesh_devices = set(layout.mesh().devices.flat)
    if mesh_devices != set(devices):
        raise AssertionError(f"layout mesh holds {mesh_devices}, "
                             f"not the {len(devices)} visible devices")
    four = phase_train(devices, layout=layout)
    if not four["sharded_leaves"]:
        raise AssertionError("four chips: no state leaf was sharded")
    share = max(four["state_bytes_per_device"]) / four["state_bytes"]
    if not 0.25 <= share < 0.27:
        raise AssertionError(f"four chips: a device holds {share:.3f} of "
                             "the state, want about a quarter")
    # bf16 matmuls reduced in another order, then Adam: a percent
    got, ref = np.array(four["loss"]), np.array(one["loss"])
    np.testing.assert_allclose(got, ref, rtol=2e-2)
    return {"one_chip": one, "four_chips": four,
            "max_loss_rel_diff": round(float(np.max(np.abs(got - ref)
                                                    / np.abs(ref))), 5),
            "state_share_per_device": round(share, 4)}


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------
def _reforward_greedy(net, prompt, n_new, length):
    """The oracle of the serving phase: greedy
    tokens from a plain full forward of the hybridized net over the
    growing sequence. The sequence sits in a fixed ``length`` buffer
    (causal attention keeps position i blind to the padding behind it), so
    the forward compiles once. Also returns the least top-2 logit margin
    met — how close the argmax came to a tie."""
    import numpy as np

    from mxnet_tpu import nd

    buf = np.zeros((1, length), np.int32)
    buf[0, :len(prompt)] = prompt
    cur, margin, out = len(prompt), float("inf"), []
    for _ in range(n_new):
        row = net(nd.array(buf, dtype="int32")).asnumpy()[0, cur - 1]
        top2 = np.partition(row, -2)[-2:]
        margin = min(margin, float(top2[1] - top2[0]))
        out.append(int(np.argmax(row)))
        buf[0, cur] = out[-1]
        cur += 1
    return out, margin


def phase_serve(devices, model="gpt2_345m", slots=4,
                prompt_lens=(12, 40, 9, 50, 14, 36), n_new=12):
    """Requests of several prompt lengths through the paged engine and the
    batcher: more requests than slots, two prefill buckets (16 and 64)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
    from mxnet_tpu.models import gpt2

    mx.random.seed(SEED)
    net = gpt2.get_gpt2(model, dropout=0.0)
    net.initialize()
    net(nd.array(np.zeros((1, 4)), dtype="int32"))  # deferred init
    vocab = gpt2.gpt2_configs[model]["vocab_size"]

    engine = GenerationEngine(net, batch_size=slots, paged=True,
                              cache_dtype="bfloat16")
    say(f"serve: paged decode read path: {engine.read_path}")
    for k_pool, _ in engine.pools:
        if k_pool.sharding.device_set != {devices[0]}:
            raise AssertionError(f"serve: pool on {k_pool.sharding}")
    rs = np.random.RandomState(SEED + 1)
    prompts = [rs.randint(1, vocab, n).tolist() for n in prompt_lens]
    buckets = sorted({engine.bucket_for(n) for n in prompt_lens})
    if len(buckets) < 2:
        raise AssertionError(f"serve: prompts fill one bucket {buckets}")

    def wave():
        batcher = ContinuousBatcher(engine)
        reqs = [batcher.submit(p, max_new_tokens=n_new) for p in prompts]
        t0 = time.perf_counter()
        batcher.run_until_idle()
        dt = time.perf_counter() - t0
        for r in reqs:
            if r.finish_reason != "length" or len(r.output) != n_new:
                raise AssertionError(
                    f"serve: request {r.id} ended {r.finish_reason!r} "
                    f"with {len(r.output)} tokens")
        return reqs, dt

    cold, cold_s = wave()   # compiles every program
    warm, warm_s = wave()   # the same traffic again: nothing compiles
    programs = engine.compiled_programs
    if programs != len(buckets) + 1:
        raise AssertionError(f"serve: {programs} programs compiled, want "
                             f"{len(buckets)} prefill buckets + 1 decode")
    if [r.output for r in warm] != [r.output for r in cold]:
        raise AssertionError("serve: second wave's greedy tokens differ")

    # outside any timing: the first request against the re-forward oracle
    net.hybridize()
    want, margin = _reforward_greedy(net, prompts[0], n_new, length=64)
    if cold[0].output != want:
        raise AssertionError(f"serve: engine tokens {cold[0].output} != "
                             f"re-forward tokens {want}")

    # which read path the decode program was built with
    decode_text = engine.lower_decode().compile().as_text()
    out = {
        "model": model, "slots": slots, "cache_dtype": "bfloat16",
        "requests": len(prompts), "prompt_lens": list(prompt_lens),
        "new_tokens_each": n_new, "prefill_buckets_used": buckets,
        "compiled_programs": programs,
        "read_path": engine.read_path,
        "decode_tpu_custom_calls": decode_text.count("tpu_custom_call"),
        "tokens_equal_reforward": True,
        "reforward_min_top2_margin": round(margin, 5),
        "cold_wave_s": round(cold_s, 2), "warm_wave_s": round(warm_s, 3),
        "ttft_warm_s": [round(r.ttft, 4) for r in warm],
        "ttft_cold_s": [round(r.ttft, 2) for r in cold],
        "bytes_in_use": devices[0].memory_stats()["bytes_in_use"],
    }
    say(f"serve: {out}")
    return out


def phase_serve_latent(devices, prompt_lens=(5, 40, 100, 124), n_new=8):
    """A toy-width DeepSeek-V2 decoded through the paged latent kernel, rows
    short and long enough to take both of its stretches of keys (128 and
    256), against one plain forward of each row's whole sequence (teacher
    forcing on the engine's own tokens: a near tie cannot split the two).
    The cache is bfloat16 and the re-forward is not, so logits are held to a
    quarter of their own spread: rounding reads about a hundredth, a row that
    attends to another's pages reads one."""
    import re

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.inference import GenerationEngine
    from mxnet_tpu.models import deepseek_v2

    mx.random.seed(SEED)
    net = deepseek_v2.get_deepseek_v2("deepseek_v2_tiny")
    net.initialize()
    net(nd.array(np.zeros((1, 4)), dtype="int32"))  # deferred init
    engine = GenerationEngine(net, batch_size=len(prompt_lens), paged=True,
                              page_size=16, max_length=256,
                              cache_dtype="bfloat16")
    say(f"latent: paged decode read path: {engine.read_path}")
    if not engine.read_path.startswith("pallas_paged_latent_kernel"):
        raise AssertionError(f"latent: the kernel's gate refuses: "
                             f"{engine.read_path}")
    rs = np.random.RandomState(SEED + 2)
    seqs = [rs.randint(1, net.logits_width(), n).tolist() for n in prompt_lens]
    for slot, prompt in enumerate(seqs):
        seqs[slot] = prompt + [engine.prefill(prompt, slot)]
    got = [[] for _ in seqs]
    for _ in range(n_new):
        tok, _, logits = engine.decode_step()
        for slot, seq in enumerate(seqs):
            got[slot].append(np.asarray(logits[slot]))
            seq.append(int(tok[slot]))
    worst, margin = 0.0, float("inf")
    for n, seq, rows in zip(prompt_lens, seqs, got):
        buf = np.zeros((1, 256), np.int32)
        buf[0, :len(seq)] = seq
        want = net(nd.array(buf, dtype="int32")).asnumpy()[0, n:n + n_new]
        rows = np.stack(rows)
        if not np.isfinite(rows).all():
            raise AssertionError("latent: the decode program's logits are "
                                 "not finite")
        worst = max(worst, float(np.max(np.abs(rows - want)) / np.std(want)))
        top2 = np.partition(want, -2, axis=-1)[:, -2:]
        margin = min(margin, float(np.min(top2[:, 1] - top2[:, 0])
                                   / np.std(want)))
    if worst > 0.25:
        raise AssertionError(f"latent: decode logits are {worst:.3f} of their "
                             f"spread off the re-forward's")
    text = engine.lower_decode().compile().as_text()
    calls = len(re.findall(r"%paged_latent_attention_decode[.\d]* = ", text))
    if calls != len(engine.pools):
        raise AssertionError(f"latent: {calls} latent kernels in the decode "
                             f"program, want one a layer ({len(engine.pools)})")
    out = {"model": "deepseek_v2_tiny", "cache_dtype": "bfloat16",
           "pool_shape": list(engine.pools[0][0].shape),
           "prompt_lens": list(prompt_lens), "new_tokens_each": n_new,
           "read_path": engine.read_path,
           "decode_latent_kernels": calls,
           "max_logit_diff_over_spread": round(worst, 5),
           "reforward_min_top2_margin_over_spread": round(margin, 5)}
    say(f"latent: {out}")
    return out


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------
def _custom_calls(fn, *args):
    """``tpu_custom_call`` ops in the program ``fn`` lowers to."""
    import jax

    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def _rel_err(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check_flash(causal, b=4, h=16, t=2048, d=64, interpret=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import flash_attention as fa

    rs = np.random.RandomState(SEED)
    q, k, v, w = (jnp.asarray(rs.randn(b, h, t, d), jnp.bfloat16)
                  for _ in range(4))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal,
                                  interpret=interpret)

    def ref(q, k, v):
        return fa._ref_attention(q, k, v, causal)

    def grads(f):
        return jax.grad(lambda q, k, v, w: jnp.sum(
            f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)),
            argnums=(0, 1, 2))

    calls = {"fwd": _custom_calls(flash, q, k, v),
             "bwd": _custom_calls(grads(flash), q, k, v, w)}
    if calls["fwd"] < 1 or calls["bwd"] < 3:  # fwd; fwd + dkv + dq
        raise AssertionError(f"flash causal={causal}: lowered without its "
                             f"Mosaic kernels: {calls}")
    errs = {"out": _rel_err(jax.jit(flash)(q, k, v), jax.jit(ref)(q, k, v))}
    got = jax.jit(grads(flash))(q, k, v, w)
    want = jax.jit(grads(ref))(q, k, v, w)
    errs.update({n: _rel_err(g, r) for n, g, r in zip(("dq", "dk", "dv"),
                                                      got, want)})
    if not all(e < 2e-2 for e in errs.values()):  # bf16 in, f32 softmax
        raise AssertionError(f"flash causal={causal}: off its einsum "
                             f"reference: {errs}")
    return {"shape": [b, h, t, d], "causal": causal,
            "tpu_custom_calls": calls,
            "rel_err": {n: round(e, 5) for n, e in errs.items()}}


def check_paged(rows=8, h=16, ch=64, ps=16, n_pages=64, interpret=None):
    """The serving cell's geometry, which the kernel's own gate admits:
    GPT-2 345M's 16 heads of 64 over a token-major bfloat16 pool, float32
    activations, one query token a row, rows of every length."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rs = np.random.RandomState(SEED)
    pool_pages = rows * n_pages
    k_pool, v_pool = (jnp.asarray(rs.randn(pool_pages + 1, ps, h * ch),
                                  jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(rs.permutation(pool_pages).reshape(rows, n_pages)
                        + 1, jnp.int32)
    position = jnp.asarray(rs.randint(0, n_pages * ps - 1, rows), jnp.int32)
    q, k_new, v_new = (jnp.asarray(rs.randn(rows, h, 1, ch), jnp.float32)
                       for _ in range(3))
    why = ppa.paged_attention_refusal(q, k_pool, table)
    if why is not None:
        raise AssertionError(f"paged: the kernel's gate refuses: {why}")

    def kernel(*a):
        return ppa.paged_attention(*a, interpret=interpret)

    a = (q, k_new, v_new, k_pool, v_pool, table, position)
    calls = _custom_calls(kernel, *a)
    if calls < 1:
        raise AssertionError("paged: lowered without its Mosaic kernel")
    got = jax.jit(kernel)(*a)
    want = jax.jit(att._paged_gather_mha)(*a)
    for g, r in zip(got[1:], want[1:]):  # the scatter is XLA on both sides
        if not bool(jnp.array_equal(g, r)):
            raise AssertionError("paged: pools differ after the scatter")
    err = _rel_err(got[0], want[0])
    if not err < 2e-2:
        raise AssertionError(f"paged: relative error {err} against the "
                             "XLA gather path")
    return {"rows": rows, "heads": h, "head": ch, "page": ps,
            "pages_per_row": n_pages, "pool": "bfloat16",
            "tpu_custom_calls": calls, "rel_err": round(err, 6)}


def check_index_scores(rows=8, j=64, d=128, ps=16, n_pages=128,
                       interpret=None):
    """The long-context cell's geometry, which the kernel's own gate admits:
    64 indexer heads of 128 over a bfloat16 key pool, one query a row, rows
    of every length; the pages no row holds are NaN and count for nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rs = np.random.RandomState(SEED)
    pool_pages = rows * n_pages
    position = rs.randint(0, n_pages * ps - 1, rows)
    table = rs.permutation(pool_pages).reshape(rows, n_pages) + 1
    pool = rs.randn(pool_pages + 1, ps, d).astype("f4")
    for r, p in enumerate(position):          # what a row does not hold
        pool[table[r, p // ps + 1:]] = np.nan
    pool, table = jnp.asarray(pool, jnp.bfloat16), jnp.asarray(table, jnp.int32)
    position = jnp.asarray(position, jnp.int32)
    q = jnp.asarray(rs.randn(rows, j, d), jnp.bfloat16)
    w = jnp.asarray(rs.randn(rows, j), jnp.float32)
    why = ppa.paged_index_scores_refusal(q[:, None], pool, table)
    if why is not None:
        raise AssertionError(f"index scores: the kernel's gate refuses: {why}")

    def kernel(*a):
        return ppa.paged_index_scores(*a, interpret=interpret)

    a = (q, w, pool, table, position)
    calls = _custom_calls(kernel, *a)
    if calls < 1:
        raise AssertionError("index scores: lowered without its Mosaic kernel")
    got = jax.jit(kernel)(*a)
    held = jnp.arange(n_pages * ps)[None, :] <= position[:, None]
    keys = jnp.nan_to_num(pool)[table].reshape(rows, n_pages * ps, d)
    want = att.index_scores(q[:, None], keys, w[:, None])[:, 0]
    if not bool(jnp.array_equal(jnp.isneginf(got), ~held)):
        raise AssertionError("index scores: -inf is not exactly what the "
                             "rows do not hold")
    err = _rel_err(jnp.where(held, got, 0.0), jnp.where(held, want, 0.0))
    if not err < 2e-2:
        raise AssertionError(f"index scores: relative error {err} against "
                             "the XLA gather path")
    return {"rows": rows, "heads": j, "head": d, "page": ps,
            "pages_per_row": n_pages, "pool": "bfloat16",
            "tpu_custom_calls": calls, "rel_err": round(err, 6)}


def check_gqa(rows=8, h=28, hkv=4, ch=128, ps=16, window=4096,
              interpret=None):
    """SmallThinker's geometry, which the kernel's own gate admits: 28 query
    heads over 4 key-value heads of 128, bfloat16 pools, one query a row,
    rows short and past the window, through a full layer's table in order
    and a window layer's ring; the pages no row holds are NaN and count for
    nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rs = np.random.RandomState(SEED)
    position = np.concatenate([rs.randint(0, 600, rows // 2),
                               rs.randint(window, 2 * window, rows - rows // 2)])
    out = {}
    for kind, win in (("full_layer", None), ("window_layer", window)):
        cols = (2 * window) // ps if win is None else win // ps + 3
        table, pages = np.zeros((rows, cols), np.int32), 0
        for r, p in enumerate(position):
            first = 0 if win is None else max(0, p - win + 1) // ps
            for s in range(first, p // ps + 1):
                pages += 1
                table[r, s % cols if win else s] = pages
        k_pool = rs.randn(2 * pages + 1, ps, hkv * ch).astype("f4")
        v_pool = rs.randn(2 * pages + 1, ps, hkv * ch).astype("f4")
        k_pool[pages + 1:] = v_pool[pages + 1:] = np.nan   # held by no row
        a = (jnp.asarray(rs.randn(rows, h, 1, ch), jnp.bfloat16),
             jnp.asarray(k_pool, jnp.bfloat16), jnp.asarray(v_pool, jnp.bfloat16),
             jnp.asarray(table), jnp.asarray(position, jnp.int32))
        why = ppa.paged_gqa_refusal(a[0], a[1], a[3], win)
        if why is not None:
            raise AssertionError(f"gqa {kind}: the kernel's gate refuses: {why}")

        def kernel(*a):
            return ppa.paged_gqa_read(*a, win, interpret=interpret)

        calls = _custom_calls(kernel, *a)
        if calls < 1:
            raise AssertionError(f"gqa {kind}: lowered without its Mosaic kernel")
        got = jax.jit(kernel)(*a)
        want = att._paged_gqa_gather_read(*a, win)
        if not bool(jnp.isfinite(got).all()):
            raise AssertionError(f"gqa {kind}: what a row does not hold "
                                 "reached its output")
        err = _rel_err(got, want)
        if not err < 2e-2:
            raise AssertionError(f"gqa {kind}: relative error {err} against "
                                 "the XLA gather path")
        out[kind] = {"columns": cols, "tpu_custom_calls": calls,
                     "rel_err": round(err, 6)}
    return {"rows": rows, "heads": [h, hkv], "head": ch, "page": ps,
            "window": window, "pool": "bfloat16", **out}


def check_grouped(tokens=48, top_k=6, d=512, w=256, experts=64, interpret=None):
    """The expert layer as the serving cells run it, at widths the grouped
    kernel's gate admits: ``held_expert_ffn`` with every expert held (a
    decode step's handful of rows a group) and with an eighth held (most
    sorted pairs behind the held ones, their row tiles never visited and
    their rows unwritten), against the same layer by ``lax.ragged_dot``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import observability as obs
    from mxnet_tpu.ops import pallas_grouped_matmul as gmm
    from mxnet_tpu.parallel import moe

    rs = np.random.RandomState(SEED)
    h = jnp.asarray(rs.randn(tokens, d), jnp.bfloat16)
    router = jnp.asarray(rs.randn(experts, d) * 0.3, jnp.float32)
    count, out = obs.counter("moe_path_total"), {}
    for kind, held in (("all_held", experts), ("an_eighth_held", experts // 8)):
        mats = [jnp.asarray(rs.randn(*shape) * shape[1] ** -0.5, jnp.bfloat16)
                for shape in ((held, d, w), (held, d, w), (held, w, d))]
        how = dict(held_experts=range(held), top_k=top_k, norm_topk_prob=True,
                   activation="relu")
        if interpret is None:
            why = gmm.grouped_matmul_refusal(tokens * top_k, d, w, h.dtype,
                                             mats[0].dtype)
            if why is not None:
                raise AssertionError(f"grouped {kind}: the gate refuses: {why}")

        def layer(h, *mats):
            return moe.held_expert_ffn(h, router, *mats, **how)[0]

        # an eighth held: the round about the products walks a prefix of
        # the sorted pairs (and carries the kernels at two row counts)
        built = dict(path="pallas_grouped", reason="", route="prefix_or_whole"
                     if moe.held_prefix_rows(tokens * top_k, held, experts)
                     else "whole")
        before = count.value(**built)
        calls = _custom_calls(layer, h, *mats)
        if (not interpret and calls < 2) or \
                count.value(**built) != before + 1:
            raise AssertionError(f"grouped {kind}: lowered without its Mosaic "
                                 f"kernels ({calls} custom calls)")
        got = jax.jit(layer)(h, *mats)
        # the same layer traced anew where the gate refuses: three
        # lax.ragged_dot
        was, gmm._on_tpu = gmm._on_tpu, lambda: False
        try:
            want = jax.jit(lambda *a: layer(*a))(h, *mats)
        finally:
            gmm._on_tpu = was
        if not bool(jnp.isfinite(got).all()):
            raise AssertionError(f"grouped {kind}: a row of no group reached "
                                 "the output")
        err = _rel_err(got, want)
        if not err < 2e-2:
            raise AssertionError(f"grouped {kind}: relative error {err} "
                                 "against lax.ragged_dot")
        out[kind] = {"held": held, "tpu_custom_calls": calls,
                     "rel_err": round(err, 6)}
    return {"pairs": tokens * top_k, "widths": [d, w], "experts": experts, **out}


def check_gdn(rows=8, heads=30, dk=96, dv=192, steps=3, interpret=None):
    """Olmo-Hybrid's linear layer in decode at its published widths: the
    kernel ``gdn_decode_step`` over a donated state, a few steps on end with
    some rows dead, against the XLA form: the live rows' reads and state
    agree, a dead row's state is the one it started with bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_gdn as gdn

    rs = np.random.RandomState(SEED)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    state = f32(rs.randn(rows, dk, heads * dv))
    live = jnp.asarray(np.arange(rows) % 3 != 1)
    if interpret is None:
        why = gdn.gdn_decode_refusal(
            state, jax.ShapeDtypeStruct((rows, heads, dk), jnp.float32),
            jax.ShapeDtypeStruct((rows, heads, dv), jnp.float32))
        if why is not None:
            raise AssertionError(f"gdn: the gate refuses: {why}")
    kernel = jax.jit(lambda s, *a: gdn.gdn_decode_step(s, *a, live,
                                                       interpret=interpret),
                     donate_argnums=(0,))
    calls = kernel.lower(state, *(f32(np.zeros(z)) for z in (
        (rows, heads, dk), (rows, heads, dk), (rows, heads, dv),
        (rows, heads), (rows, heads)))).as_text().count("tpu_custom_call")
    if not interpret and calls != 1:
        raise AssertionError(f"gdn: lowered with {calls} Mosaic kernels, not 1")
    got, want, errs = state + 0.0, state, []
    for _ in range(steps):
        args = (f32(unit(rs.randn(rows, heads, dk)) * dk ** -0.5),
                f32(unit(rs.randn(rows, heads, dk))),
                f32(rs.randn(rows, heads, dv)),
                f32(rs.uniform(0.5, 1.0, (rows, heads))),
                f32(rs.uniform(0.0, 2.0, (rows, heads))))
        o_got, got = kernel(got, *args)
        o_want, want = gdn.gdn_decode_xla(want, *args, live)
        errs.append(max(_rel_err(o_got, o_want), _rel_err(got, want)))
    if not max(errs) < 1e-5:
        raise AssertionError(f"gdn: relative error {max(errs)} against XLA")
    if not bool(jnp.array_equal(got[~live], state[~live])):
        raise AssertionError("gdn: a dead row's state was moved")
    # the chunked prefill against the kernel a position at a time: two
    # blocks of 64 from a zero state (its products must be float32's, not
    # one bfloat16 pass)
    t = 128
    q, k, v = (f32(unit(rs.randn(t, heads, dk)) * dk ** -0.5),
               f32(unit(rs.randn(t, heads, dk))), f32(rs.randn(t, heads, dv)))
    alpha, beta = (f32(rs.uniform(0.8, 1.0, (t, heads))),
                   f32(rs.uniform(0.0, 2.0, (t, heads))))
    o_chunk, s_chunk = jax.jit(gdn.gdn_chunk_prefill)(q, k, v, jnp.log(alpha),
                                                      beta)
    one = jax.jit(lambda s, *a: gdn.gdn_decode_step(
        s, *a, jnp.ones((1,), bool), interpret=interpret), donate_argnums=(0,))
    s_step, o_step = jnp.zeros((1, dk, heads * dv), jnp.float32), []
    for i in range(t):
        o, s_step = one(s_step, q[i:i + 1], k[i:i + 1], v[i:i + 1],
                        alpha[i:i + 1], beta[i:i + 1])
        o_step.append(o[0])
    chunk_err = max(_rel_err(o_chunk, jnp.stack(o_step)),
                    _rel_err(gdn.state_rows(s_chunk), s_step[0]))
    if not chunk_err < 1e-4:
        raise AssertionError(f"gdn: the chunked prefill lies {chunk_err} "
                             "from the recurrence")
    return {"rows": rows, "live": int(live.sum()), "heads": heads,
            "state": [dk, dv], "steps": steps, "tpu_custom_calls": calls,
            "rel_err": round(max(errs), 8),
            "chunk_prefill_rel_err": round(chunk_err, 8)}


def check_lightning(rows=8, heads=32, d=128, steps=3, interpret=None):
    """MiniCPM-SALA's lightning layer in decode at its published widths: the
    gated-delta kernel without its delta term (``lightning_decode_step``)
    over a donated state, some rows dead, against the XLA form; then the
    chunked prefill, two stretches that hand the state on with padding
    behind the prompt, against the kernel a position at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_gdn as gdn

    rs = np.random.RandomState(SEED)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    state = f32(rs.randn(rows, d, heads * d))
    live = jnp.asarray(np.arange(rows) % 3 != 1)
    shape = jax.ShapeDtypeStruct((rows, heads, d), jnp.float32)
    if interpret is None:
        why = gdn.gdn_decode_refusal(state, shape, shape)
        if why is not None:
            raise AssertionError(f"lightning: the gate refuses: {why}")
    decay = f32(np.exp(-2.0 ** (-8.0 * np.arange(1, heads + 1) / heads)))
    ones = jnp.ones((rows, heads), jnp.float32)
    kernel = jax.jit(lambda s, q, k, v: gdn.gdn_decode_step(
        s, q, k, v, ones * decay, ones, live, interpret=interpret,
        delta=False), donate_argnums=(0,))
    got, want, errs = state + 0.0, state, []
    for _ in range(steps):
        args = tuple(f32(rs.randn(rows, heads, d)) for _ in range(3))
        o_got, got = kernel(got, *args)
        o_want, want = gdn.gdn_decode_xla(want, *args, ones * decay, ones,
                                          live, delta=False)
        errs.append(max(_rel_err(o_got, o_want), _rel_err(got, want)))
    if not max(errs) < 1e-5:
        raise AssertionError(f"lightning: relative error {max(errs)} against XLA")
    if not bool(jnp.array_equal(got[~live], state[~live])):
        raise AssertionError("lightning: a dead row's state was moved")
    t, length = 256, 200
    q, k, v = (f32(rs.randn(t, heads, d)) * d ** -0.5 for _ in range(3))
    chunk = jax.jit(gdn.lightning_chunk_prefill, static_argnums=(5,))
    o1, s1 = chunk(q[:128], k[:128], v[:128], jnp.log(decay), length, 128)
    o2, s2 = chunk(q[128:], k[128:], v[128:], jnp.log(decay), length - 128,
                   128, None, s1)
    one = jax.jit(lambda s, q, k, v: gdn.gdn_decode_step(
        s, q, k, v, decay[None], jnp.ones((1, heads)), jnp.ones((1,), bool),
        interpret=interpret, delta=False), donate_argnums=(0,))
    s_step, o_step = jnp.zeros((1, d, heads * d), jnp.float32), []
    for i in range(length):
        o, s_step = one(s_step, q[i:i + 1], k[i:i + 1], v[i:i + 1])
        o_step.append(o[0])
    chunk_err = max(
        _rel_err(jnp.concatenate([o1, o2])[:length], jnp.stack(o_step)),
        _rel_err(gdn.state_rows(s2), s_step[0]))
    if not chunk_err < 1e-4:
        raise AssertionError(f"lightning: the chunked prefill lies {chunk_err} "
                             "from the recurrence")
    return {"rows": rows, "live": int(live.sum()), "heads": heads,
            "state": [d, d], "rel_err": round(max(errs), 8),
            "chunk_prefill_rel_err": round(chunk_err, 8)}


def check_block_list(rows=8, heads=32, kv=2, ch=128, ps=64, pages=600,
                     length=128, dtype="bfloat16", interpret=None):
    """MiniCPM-SALA's sparse layer in decode at its published widths: the
    block-list kernel ``paged_gqa_decode_selected`` (a list of up to 128 pages
    a row and key-value head, of a page that head's 128 lanes) against the
    XLA gather of the listed pages: rows that list every block they hold,
    rows that list 64 chosen ones, a list of one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rs = np.random.RandomState(SEED)
    q = jnp.asarray(rs.randn(rows, heads, 1, ch), dtype)
    k_pool, v_pool = (jnp.asarray(rs.randn(pages + 1, ps, kv * ch), dtype)
                      for _ in range(2))
    counts = np.minimum(np.array(
        [[1, 1]] + [[64, 64], [length, length], [37, 37]] * rows,
        np.int32)[:rows], length)
    blocks = np.stack([np.sort(np.stack([
        rs.permutation(4 * length)[:length] for _ in range(kv)]), axis=1)
        for _ in range(rows)]).astype(np.int32)
    own = np.array([blocks[b, :, counts[b, 0] - 1].max() for b in range(rows)])
    for b in range(rows):   # both heads' lists end with the query's own block
        blocks[b, :, counts[b, 0] - 1] = own[b]
    position = (own * ps + rs.randint(0, ps, rows)).astype(np.int32)
    page_ids = rs.randint(1, pages + 1, (rows, kv, length)).astype(np.int32)
    args = (q, k_pool, v_pool, jnp.asarray(page_ids), jnp.asarray(blocks * ps),
            jnp.asarray(counts), jnp.asarray(position))
    if interpret is None:
        why = ppa.paged_gqa_selected_refusal(q, k_pool, page_ids)
        if why is not None:
            raise AssertionError(f"block list: the gate refuses: {why}")
    kernel = jax.jit(lambda *a: ppa.paged_gqa_read(
        *a[:3], None, a[6], selected=a[3:6], interpret=interpret))
    calls = kernel.lower(*args).as_text().count("tpu_custom_call")
    if not interpret and calls != 1:
        raise AssertionError(f"block list: lowered with {calls} Mosaic kernels")
    got, want = kernel(*args), att._paged_block_gather_read(*args)
    if not bool(jnp.isfinite(got).all()):
        raise AssertionError("block list: the kernel's output is not finite")
    err = _rel_err(got, want)
    if not err < (2e-2 if dtype == "bfloat16" else 1e-5):
        raise AssertionError(f"block list: relative error {err} against XLA")
    return {"rows": rows, "heads": [heads, kv, ch], "page": ps,
            "lists": counts[:, 0].tolist(), "tpu_custom_calls": calls,
            "rel_err": round(err, 6)}


def check_chunk_scores(queries=512, heads=32, kv=2, ch=128, tokens=16384,
                       first=12288, dtype="bfloat16", interpret=None):
    """MiniCPM-SALA's sparse layer in a prefill at its published widths: the
    scoring kernel ``sparse_chunk_scores`` (a stretch's queries against the
    bucket's compressed keys, 32 positions every 16, pooled to blocks of 64
    inside the kernel) against the model's XLA form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import minicpm_sala as sala
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    cfg = dict(kernel_size=32, kernel_stride=16, block_size=64)
    sizes = cfg["block_size"], cfg["kernel_size"], cfg["kernel_stride"]
    rs = np.random.RandomState(SEED)
    q = jnp.asarray(rs.randn(queries, kv, heads // kv, ch), dtype)
    ck = jnp.asarray(rs.randn(tokens // 16 - 1, kv, ch), dtype)
    if interpret is None:
        why = ppa.sparse_chunk_scores_refusal(q, ck, *sizes)
        if why is not None:
            raise AssertionError(f"chunk scores: the gate refuses: {why}")
    kernel = jax.jit(lambda q, ck, at: ppa.sparse_chunk_scores(
        q, ppa.sparse_chunk_keys(ck, sizes[0], sizes[2]), at, *sizes,
        interpret=interpret)[:, :, :tokens // 64])
    at = jnp.asarray(first, jnp.int32)
    calls = kernel.lower(q, ck, at).as_text().count("tpu_custom_call")
    if not interpret and calls != 1:
        raise AssertionError(f"chunk scores: lowered with {calls} Mosaic "
                             "kernels")
    got = np.asarray(kernel(q, ck, at))
    pos = first + jnp.arange(queries, dtype=jnp.int32)
    want = np.moveaxis(np.asarray(sala.pooled_weights(
        sala.key_weights(q, ck, pos, cfg), pos, cfg, tokens // 64)), 1, 0)
    seen = np.isfinite(want)
    if not (np.isneginf(got) == ~seen).all():
        raise AssertionError("chunk scores: other blocks are scored than "
                             "XLA's")
    err = float(np.abs(got[seen] - want[seen]).max())
    if not err < 1e-5:
        raise AssertionError(f"chunk scores: {err} from XLA's weights")
    return {"queries": queries, "heads": [heads, kv, ch],
            "keys": int(ck.shape[0]), "first": first,
            "tpu_custom_calls": calls, "max_err": round(err, 8)}


def check_stretches_reached(bucket=16384, lengths=(12288, 16384)):
    """MiniCPM-SALA's prefill program at the cell's widths (the benchmark's
    configuration, its first sparse and first lightning layer, a vocabulary
    of 2,048): the 16,384 program lowers with its Mosaic kernels
    (``sparse_prefill``, ``sparse_chunk_scores``); a 12,288-token prompt runs
    three of its four stretches of 4,096 in every layer (``positions_run``
    behind the first token) and a 16,384-token prompt all four; and the first
    token lies within the cell's own limit of the float32 reference's best
    logit (``benchmark.serve.logit_gaps``' comparison, one position)."""
    import os

    import numpy as np

    from benchmark.reference import minicpm_sala as ref
    from benchmark.systems import minicpm_sala as adaptor
    from benchmark.weights import make_weights
    from mxnet_tpu import observability as obs
    from mxnet_tpu.models.minicpm_sala import _STRETCH

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "minicpm_sala.json")) as f:
        cfg = json.load(f)
    cfg.update(n_layer=2, n_vocab=2048)
    cfg["engine"] = dict(cfg["engine"], batch_size=len(lengths),
                         num_pages={"all": 640}, max_length=bucket + 64,
                         prefill_buckets=[bucket])
    weights = make_weights(ref.param_specs(cfg), SEED)
    engine, _ = adaptor.build_serve(cfg, weights)
    text = engine.lower_prefill(bucket).as_text()
    kernels = {name: text.count(name)
               for name in ("sparse_prefill", "sparse_chunk_scores")}
    if not all(kernels.values()):
        raise AssertionError(f"stretches: the {bucket} program lowered "
                             f"without a Mosaic kernel: {kernels}")
    rs = np.random.RandomState(SEED + 3)
    out = {"bucket": bucket, "kernels_named": kernels, "prompts": []}
    for slot, n in enumerate(lengths):
        prompt = rs.randint(1, cfg["n_vocab"], n).tolist()
        tok = engine.prefill(prompt, slot)
        ran = obs.step_records("prefill")[-1].counts["positions_run"]
        if ran != [-(-n // _STRETCH) * _STRETCH] * cfg["n_layer"]:
            raise AssertionError(f"stretches: a prompt of {n} ran {ran}")
        want = ref.next_token_logits(weights, cfg, prompt, n - 1, 1,
                                     pad_to=bucket, out_pad=32)[0]
        gap = float(want.max() - want[tok])
        if not gap <= cfg["check"]["widest_gap"]:
            raise AssertionError(
                f"stretches: the first token behind {n} tokens lies {gap} "
                f"under the float32 reference's best logit")
        out["prompts"].append({"length": n, "positions_run": ran,
                               "first_token_is_the_references":
                               bool(tok == int(want.argmax())),
                               "gap": round(gap, 6)})
    return out


def check_packed(b=64, t=128, heads=16, d=64, interpret=None):
    """The training cell's attention: the packed projection of BERT-large
    with the cell's key-padding mask (valid lengths T/2..T), forward and
    backward, against the unpack + einsum + transpose path it replaces."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_packed_attention as ppa

    c = heads * d
    rs = np.random.RandomState(SEED)
    qkv = jnp.asarray(rs.randn(b, t, 3 * c), jnp.bfloat16)
    w = jnp.asarray(rs.randn(b, t, c), jnp.bfloat16)
    valid = jnp.asarray(rs.randint(t // 2, t + 1, b), jnp.int32)
    mask = (jnp.arange(t, dtype=jnp.int32).reshape(1, 1, 1, t)
            < valid.reshape(b, 1, 1, 1))
    if interpret is None:
        why = ppa.packed_attention_refusal(qkv, mask, heads)
        if why is not None:
            raise AssertionError(f"packed: the kernel's gate refuses: {why}")

    def packed(qkv):
        return ppa.packed_attention(qkv, mask, heads, interpret=interpret)

    def ref(qkv):
        q, k, v = att._unpack_qkv(qkv, heads)
        return att._merge_heads(att._reference_mha(q, k, v, mask=mask))

    def grad(f):
        return jax.grad(lambda qkv, w: jnp.sum(
            f(qkv).astype(jnp.float32) * w.astype(jnp.float32)))

    calls = {"fwd": _custom_calls(packed, qkv),
             "bwd": _custom_calls(grad(packed), qkv, w)}
    if not interpret and min(calls.values()) < 1:
        raise AssertionError(f"packed: lowered without its Mosaic kernels: "
                             f"{calls}")
    errs = {"out": _rel_err(jax.jit(packed)(qkv), jax.jit(ref)(qkv))}
    got, want = jax.jit(grad(packed))(qkv, w), jax.jit(grad(ref))(qkv, w)
    for i, n in enumerate(("dq", "dk", "dv")):
        errs[n] = _rel_err(got[..., i * c:(i + 1) * c],
                           want[..., i * c:(i + 1) * c])
    if not all(e < 2e-2 for e in errs.values()):  # bf16 in, f32 softmax
        raise AssertionError(f"packed: off its einsum reference: {errs}")
    # a masked key takes no gradient at all
    dead = ~jnp.broadcast_to(mask.reshape(b, t, 1), (b, t, 2 * c))
    leak = float(jnp.max(jnp.abs(jnp.where(
        dead, got[..., c:].astype(jnp.float32), 0.0))))
    if leak != 0.0:
        raise AssertionError(f"packed: a masked key got gradient {leak}")
    return {"shape": [b, t, 3 * c], "heads": heads, "mask": "keys, T/2..T",
            "tpu_custom_calls": calls,
            "rel_err": {n: round(e, 5) for n, e in errs.items()}}


def phase_kernels():
    """Every Pallas kernel the default configuration can select on a TPU,
    called directly, compiled by Mosaic (a compile error fails the
    phase) and compared with its XLA reference."""
    out = {"flash_attention": [check_flash(causal=False),
                               check_flash(causal=True)],
           "paged_attention": check_paged(),
           "paged_index_scores": check_index_scores(),
           "paged_gqa_decode": check_gqa(),
           "grouped_matmul": check_grouped(),
           "gdn_decode_step": check_gdn(),
           "lightning_decode_step": check_lightning(),
           "paged_gqa_decode_selected": check_block_list(),
           "sparse_chunk_scores": check_chunk_scores(),
           "prefill_stretches_reached": check_stretches_reached(),
           "packed_attention": check_packed()}
    say(f"kernels: {out}")
    return out


# --------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the train phase under the ZeRO layout over "
                         "four chips, checked against one chip")
    args = ap.parse_args()

    t_start = time.perf_counter()
    devices, device, versions = phase_device(args.chips)
    phases = {}

    def run(name, fn, *a):
        t0 = time.perf_counter()
        result = fn(*a)
        gc.collect()  # the phase's device state goes before the next one
        phases[name] = {"status": "ok",
                        "seconds": round(time.perf_counter() - t0, 1),
                        **result}

    if args.chips == 4:
        run("train_four_chips", phase_train_four_chips, devices)
    else:
        run("train", phase_train, devices)
        run("serve", phase_serve, devices)
        run("latent", phase_serve_latent, devices)
        run("kernels", phase_kernels)
    say("summary: " + json.dumps({
        "versions": versions, "chips": args.chips,
        "seconds": round(time.perf_counter() - t_start, 1),
        "phases": phases}))
    # the last line, to the driver's contract: these keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
