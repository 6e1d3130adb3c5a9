"""On-chip check + timing of the Pallas kernels (flash attention, fused
LayerNorm, paged decode-attention over key/value pools and over a latent
pool, a long prefill's masked latent attention, the held experts' grouped
products, fused Adam, fused softmax-xent) against their XLA compositions.

Send it through the chip tool. The parent never imports jax, so it never
holds the chip: each case runs in a child process of its own, one at a
time, which owns the chip while it runs — and an OOM (the einsum path's
O(L^2) scores buffer at long seq, exactly the failure mode flash exists to
remove) cannot poison the HBM of later cases. Each child compiles the
kernel with Mosaic (``interpret=False``; a refusal is recorded with the
compiler's message), compares it with its reference, then times ``reps``
dependent iterations per dispatch chain with one sync at the end. Prints
one JSON line per case and exits non-zero if any case failed.

Usage:  python tools/kernelbench.py [--kinds ln,fused_adam] [--reps 15]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ATTN_CASES = [
    # (b, h, seq, d) — b*h shrinks as seq grows to keep qkv+grads resident
    (4, 8, 1024, 64), (4, 8, 2048, 64), (4, 8, 4096, 64), (1, 8, 8192, 64),
    (4, 8, 1024, 128), (4, 8, 2048, 128), (2, 8, 4096, 128), (1, 8, 8192, 128),
]
LN_CASES = [(8192, 1024), (32768, 1024), (8192, 4096)]

# paged attention: (b, h, ch, page_size, n_pages, tq, held): b rows of tq
# queries whose histories hold held/2 .. held positions (0: anything up to the
# table's width); the A/B is the kernel, which fetches the pages a row holds,
# against the XLA pool[table] gather of the table's whole width. GPT-2 345M's
# decode batch short, mixed and full, its verification and a prefill chunk;
# head 128; the widest table the kernel's VMEM budget admits
PAGED_CASES = [(64, 16, 64, 16, 64, 1, 128), (64, 16, 64, 16, 64, 1, 0),
               (64, 16, 64, 16, 64, 1, 1024), (64, 16, 64, 16, 64, 5, 256),
               (1, 16, 64, 16, 64, 64, 64), (8, 8, 128, 16, 64, 1, 512),
               (8, 8, 128, 16, 128, 1, 0)]
# paged latent attention (DeepSeek-V2's absorbed decode): (b, h, kl, rope,
# page_size, n_pages, tq, held), held as above; one layer of the serving
# cell's decode batch with short rows, the cell's rows, and rows of any length
LATENT_CASES = [(128, 128, 512, 64, 16, 256, 1, 128),
                (128, 128, 512, 64, 16, 256, 1, 1024),
                (128, 128, 512, 64, 16, 256, 1, 0)]
# grouped key-value heads over K/V pools (SmallThinker's decode, one layer):
# (b, query heads, key-value heads, ch, page_size, columns, window, lo, hi):
# b rows whose frontiers lie in lo .. hi; window 0 is a full layer (the table
# in order from position 0), else a window layer's ring; the A/B is the kernel
# ``paged_gqa_decode``, which walks the pages a row holds and reads in blocks,
# against the XLA gather of the table's whole width. ``--runs`` lays a share
# of every row's pages side by side in the pool (whole groups of the kernel's
# ``RUN_PAGES`` logical pages; the other groups' ids are shuffled): 1.0 is a
# fresh pool's table, 0.0 a shuffled one; the kernel alone is timed then
GQA_CASES = [(48, 28, 4, 128, 16, 640, 0, 512, 1024),
             (48, 28, 4, 128, 16, 640, 0, 4096, 10240),
             (48, 28, 4, 128, 16, 640, 0, 0, 10240),
             (48, 28, 4, 128, 16, 259, 4096, 512, 1024),
             (48, 28, 4, 128, 16, 259, 4096, 4096, 10240),
             (48, 28, 4, 128, 16, 259, 4096, 0, 10240)]
# masked prefill (dots3-note-prev's full layer, one chunk from position 0
# under the indexer's selection): (tokens, heads, nope, rope, vd, kl, ql,
# index heads, index dim, top_k); the A/B is the flash forward kernel under
# the mask against ``_masked_chunk_attention`` (XLA: keys in stretches)
MASKED_PREFILL_CASES = [(4096, 128, 128, 64, 128, 512, 1024, 64, 128, 2048),
                        (16384, 128, 128, 64, 128, 512, 1024, 64, 128, 2048)]
# the held experts' three grouped products (one expert layer's, bfloat16):
# (tokens, top_k, d, w, held, experts): every token picks top_k distinct
# experts of ``experts`` and the first ``held`` are here, so tokens * top_k
# sorted pairs of which held / experts belong to a group. SmallThinker's
# decode step and 4,096-token prefill block (all 64 held), DeepSeek-V2's
# decode step (8 of 160), dots3-note-prev's decode step and prefill block
# (8 of 256). The A/B is ``grouped_glu_ffn`` against three ``lax.ragged_dot``
GROUPED_MM_CASES = [(48, 6, 2560, 768, 64, 64), (4096, 6, 2560, 768, 64, 64),
                    (128, 6, 5120, 1536, 8, 160), (48, 8, 5120, 1536, 8, 256),
                    (4096, 8, 5120, 1536, 8, 256)]
# the gated delta rule's decode step (Olmo-Hybrid's linear layer, one layer):
# (rows, live rows, heads, dk, dv): the state of ``rows`` slots, of which
# the first ``live`` decode; the A/B is the kernel ``gdn_decode_step`` (a
# live row's state read once and written once in place, a dead row's not
# moved) against the XLA form; the state rides the timing chain's carry, so
# no copy of it is timed
GDN_CASES = [(16, 16, 30, 96, 192), (32, 32, 30, 96, 192),
             (48, 48, 30, 96, 192), (64, 64, 30, 96, 192),
             (48, 24, 30, 96, 192)]
# the same family without the delta term (MiniCPM-SALA's lightning layer, one
# layer: 32 heads of 128 x 128): (rows, live rows, heads, dk, dv)
LIGHTNING_CASES = [(32, 32, 32, 128, 128), (64, 64, 32, 128, 128),
                   (64, 40, 32, 128, 128)]
# the block-list decode read (MiniCPM-SALA's sparse layer): (rows, query
# heads, key-value heads, head size, page = block, pool pages, blocks a
# list): every row lists that many blocks a key-value head, scattered over
# the pool; the A/B is the kernel ``paged_gqa_decode_selected`` against the XLA
# gather of the listed pages
BLOCK_LIST_CASES = [(64, 32, 2, 128, 64, 24576, 64),
                    (64, 32, 2, 128, 64, 24576, 128),
                    (16, 32, 2, 128, 64, 24576, 64)]
# the prefill's scoring of compressed keys (MiniCPM-SALA's sparse layer): (a
# stretch's queries, query heads, key-value heads, head size, the bucket's
# tokens, the stretch's first position): the first selecting and the last
# stretch of the buckets of 16,384, 32,768 and 65,536 tokens (1,023, 2,047,
# 4,095 compressed keys); the A/B is the kernel ``sparse_chunk_scores``
# against the model's XLA form (``key_weights`` and ``block_scores``, 512
# queries at a time)
CHUNK_SCORES_CASES = [(4096, 32, 2, 128, 16384, 8192),
                      (4096, 32, 2, 128, 16384, 12288),
                      (4096, 32, 2, 128, 32768, 8192),
                      (4096, 32, 2, 128, 32768, 28672),
                      (4096, 32, 2, 128, 65536, 8192),
                      (4096, 32, 2, 128, 65536, 61440)]
# fused Adam: parameter element counts (one tensor per case; the mp variant
# also emits the bf16 model copy in the same pass)
ADAM_CASES = [(1 << 20,), (1 << 24,)]
# fused softmax-xent: (rows, classes) — LM-head shapes
XENT_CASES = [(8192, 32768), (16384, 50304)]

# conv layout A/B (round-3 verdict ask #7): NCHW dimension_numbers as the op
# is written vs explicit NHWC — settles whether XLA layout assignment makes
# the Python-level layout immaterial on TPU. (B, C, H, W, O, k)
CONV_CASES = [(32, 512, 28, 28, 512, 3), (64, 3, 224, 224, 64, 7)]

if os.environ.get("KERNELBENCH_TINY") == "1":
    # CPU dry-run: same code paths, CPU-survivable shapes (the kernels run
    # in interpret mode off-TPU, where seq 8192 would take hours)
    ATTN_CASES = [(1, 2, 256, 64)]
    LN_CASES = [(512, 256)]
    CONV_CASES = [(2, 8, 14, 14, 8, 3)]
    PAGED_CASES = [(2, 2, 32, 8, 4, 1, 0), (2, 2, 64, 16, 8, 3, 40)]
    LATENT_CASES = [(2, 4, 32, 8, 16, 8, 1, 0), (2, 4, 32, 8, 16, 16, 2, 100)]
    MASKED_PREFILL_CASES = [(256, 2, 128, 64, 128, 64, 64, 2, 32, 64)]
    GQA_CASES = [(3, 6, 2, 16, 4, 16, 0, 0, 60), (3, 6, 2, 16, 4, 4, 5, 0, 60)]
    GROUPED_MM_CASES = [(8, 2, 128, 128, 4, 4), (64, 2, 128, 256, 2, 8)]
    GDN_CASES = [(3, 3, 4, 8, 64), (4, 2, 4, 8, 64)]
    ADAM_CASES = [(1 << 12,)]
    XENT_CASES = [(64, 256)]


def _chain(fn, args, reps):
    import jax
    import jax.numpy as jnp

    # feed a scalar of the previous output back into the first arg so the
    # chain is sequentially dependent (no CSE collapsing reps into one call)
    def body(carry, _):
        first = args[0] + carry
        out = fn(first, *args[1:])
        leaf = jax.tree_util.tree_leaves(out)[0]
        return (leaf.reshape(-1)[0] * 1e-9).astype(args[0].dtype), ()

    carry, _ = jax.lax.scan(body, jnp.zeros((), args[0].dtype), None,
                            length=reps)
    return carry


def _timeit(fn, args, reps):
    """Median-of-3 per-iteration seconds with one host sync per window."""
    import jax
    import numpy as np

    chained = jax.jit(lambda *a: _chain(fn, a, reps))
    np.asarray(jax.device_get(chained(*args)))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        # timing harness: one blocking fetch per reps-step window —
        # lint: disable=JH008 -- the per-iteration sync IS the measurement
        np.asarray(jax.device_get(chained(*args)))
        times.append((time.perf_counter() - t0) / reps)
    return sorted(times)[1]


def run_attn_case(b, h, seq, d, causal, reps, fwd_only):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    case = {"kind": "attn", "b": b, "h": h, "d": d, "seq": seq,
            "causal": causal}
    # correctness on-chip. Oracle: einsum reference where its O(L^2) scores
    # buffer fits; the chunked path (numerically exact online softmax, pure
    # XLA, independently tested against einsum at short seq) beyond that.
    oracle = (fa._ref_attention if b * h * seq * seq * 4 < 2e9
              else fa._chunked_attention)
    case["oracle"] = oracle.__name__
    ref = oracle(q, k, v, causal)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=_INTERP)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32))))
    case["max_err"] = round(err, 5)
    case["correct"] = err < 0.05
    del ref, out

    def flash_f(q):
        return fa.flash_attention(q, k, v, causal=causal, interpret=_INTERP)

    def einsum_f(q):
        return fa._ref_attention(q, k, v, causal)

    def chunked_f(q):
        return fa._chunked_attention(q, k, v, causal)

    def with_grad(f):
        def g(q):
            return jax.grad(lambda q: jnp.sum(f(q).astype(jnp.float32)))(q)
        return g

    for label, f in (("flash", flash_f), ("einsum", einsum_f),
                     ("chunked", chunked_f)):
        try:
            t = _timeit(f if fwd_only else with_grad(f), (q,), reps)
            case[f"{label}_ms"] = round(t * 1e3, 3)
        except Exception as e:  # OOM etc. — that result IS informative
            case[f"{label}_error"] = repr(e)[:120]
    if "flash_ms" in case and "einsum_ms" in case:
        case["flash_vs_einsum"] = round(case["einsum_ms"] / case["flash_ms"], 2)
    return case


def run_ln_case(n, d, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import config as _config
    from mxnet_tpu.ops import pallas_layernorm as pln

    _config.set("fused_layernorm", True)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
    g = jnp.ones((d,), jnp.bfloat16)
    b = jnp.zeros((d,), jnp.bfloat16)
    case = {"kind": "ln", "n": n, "d": d}

    def composed(x):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
        return (y * g.astype(jnp.float32) + b.astype(jnp.float32)
                ).astype(x.dtype)

    out = pln.layer_norm_fused(x, g, b, interpret=_INTERP)
    ref = composed(x)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32))))
    case["max_err"] = round(err, 5)
    case["correct"] = err < 0.05
    del out, ref

    def fused(x):
        return pln.layer_norm_fused(x, g, b, interpret=_INTERP)

    for label, f in (("fused", fused), ("xla", composed)):
        try:
            case[f"{label}_ms"] = round(_timeit(f, (x,), reps) * 1e3, 3)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "fused_ms" in case and "xla_ms" in case:
        case["fused_vs_xla"] = round(case["xla_ms"] / case["fused_ms"], 2)
    return case


def run_conv_case(b, c, h, w, o, k, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    pad = k // 2
    x_nchw = jnp.asarray(rng.randn(b, c, h, w), jnp.bfloat16)
    w_oihw = jnp.asarray(rng.randn(o, c, k, k) * 0.05, jnp.bfloat16)
    x_nhwc = jnp.transpose(x_nchw, (0, 2, 3, 1))
    w_hwio = jnp.transpose(w_oihw, (2, 3, 1, 0))
    case = {"kind": "conv_layout", "b": b, "c": c, "hw": h, "o": o, "k": k}

    def conv_nchw(x):
        return jax.lax.conv_general_dilated(
            x, w_oihw, (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    def conv_nhwc(x):
        return jax.lax.conv_general_dilated(
            x, w_hwio, (1, 1), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    a = jnp.transpose(conv_nchw(x_nchw), (0, 2, 3, 1)).astype(jnp.float32)
    bb = conv_nhwc(x_nhwc).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(a - bb)))
    case["max_err"] = round(err, 4)
    case["correct"] = err < 1.0  # bf16 conv tolerance at these sizes
    del a, bb
    case["nchw_ms"] = round(_timeit(conv_nchw, (x_nchw,), reps) * 1e3, 3)
    case["nhwc_ms"] = round(_timeit(conv_nhwc, (x_nhwc,), reps) * 1e3, 3)
    case["nchw_vs_nhwc"] = round(case["nchw_ms"] / case["nhwc_ms"], 3)
    return case


def run_paged_case(b, h, ch, ps, n_pages, tq, held, reps):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.RandomState(0)
    pool_pages = b * n_pages
    cap = n_pages * ps
    k_pool = jnp.asarray(rng.randn(pool_pages + 1, ps, h * ch), jnp.bfloat16)
    v_pool = jnp.asarray(rng.randn(pool_pages + 1, ps, h * ch), jnp.bfloat16)
    table = jnp.asarray(rng.randint(1, pool_pages + 1, (b, n_pages)), jnp.int32)
    hi = min(held or cap, cap) - tq
    position = jnp.asarray(rng.randint(hi // 2 if held else 0, hi + 1, (b,)),
                           jnp.int32)
    # f32 activations over a bf16 pool: the engine's decode layout
    q = jnp.asarray(rng.randn(b, h, tq, ch), jnp.float32)
    kn = jnp.asarray(rng.randn(b, h, tq, ch), jnp.float32)
    vn = jnp.asarray(rng.randn(b, h, tq, ch), jnp.float32)
    case = {"kind": "paged_attn", "b": b, "h": h, "ch": ch, "ps": ps,
            "n_pages": n_pages, "tq": tq,
            "held_positions": int(position.sum()) + b * tq}
    if not _INTERP:
        case["gate"] = ppa.paged_attention_refusal(q, k_pool, table) or "kernel"
    # the write is the same XLA scatter on both paths: once, outside the A/B
    k_pool, v_pool = att._paged_write(kn, vn, k_pool, v_pool, table, position)

    def gather_ref(q):
        return att._paged_gather_read(q, k_pool, v_pool, table, position)

    def kernel(q):
        return ppa.paged_attention_read(q, k_pool, v_pool, table, position,
                                        interpret=_INTERP)

    ref, out = gather_ref(q), kernel(q)
    err = float(jnp.max(jnp.abs(out - ref)))
    case["max_err"] = round(err, 6)
    # the two paths sum in another order and a softmax weight may round to
    # the next bfloat16 (tests/test_pallas_paged_attention.py): outputs of
    # size about 1 agree to some 1e-3
    case["correct"] = bool(err < 0.01 and jnp.isfinite(out).all())
    del ref, out
    for label, f in (("kernel", kernel), ("gather", gather_ref)):
        try:
            case[f"{label}_ms"] = round(_timeit(f, (q,), reps) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "kernel_ms" in case and "gather_ms" in case:
        case["kernel_vs_gather"] = round(case["gather_ms"] / case["kernel_ms"], 2)
    if "kernel_ms" in case:   # bytes of the positions held, both pools
        gb = 2 * case["held_positions"] * h * ch * 2 / 1e9
        case["kernel_gb_per_s"] = round(gb / (case["kernel_ms"] / 1e3), 1)
    return case


def run_latent_case(b, h, kl, rope, ps, n_pages, tq, held, reps):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.RandomState(0)
    pool_pages = b * n_pages
    cap = n_pages * ps
    (pool,), = att.alloc_paged_latent_cache(pool_pages, ps, kl + rope, 1,
                                            "bfloat16")
    pool = pool.at[..., :kl + rope].set(jnp.asarray(
        rng.randn(pool_pages + 1, ps, kl + rope), jnp.bfloat16))
    table = jnp.asarray(rng.randint(1, pool_pages + 1, (b, n_pages)), jnp.int32)
    hi = min(held or cap, cap) - tq
    position = jnp.asarray(rng.randint(hi // 2 if held else 0, hi + 1, (b,)),
                           jnp.int32)
    # queries already in the latent space, bfloat16 as the cell's activations
    q_lat = jnp.asarray(rng.randn(b, tq, h, kl) * 0.3, jnp.bfloat16)
    q_rope = jnp.asarray(rng.randn(b, tq, h, rope) * 0.3, jnp.bfloat16)
    scale = 0.1147   # DeepSeek-V2's, YaRN's factor in it
    case = {"kind": "paged_latent", "b": b, "h": h, "kl": kl, "rope": rope,
            "ps": ps, "n_pages": n_pages, "tq": tq, "pool_width": pool.shape[2],
            "held_positions": int(position.sum()) + b * tq}
    if not _INTERP:
        case["gate"] = ppa.paged_latent_attention_refusal(
            q_lat, pool, table) or "kernel"

    def gather_ref(q_lat):
        hist = pool[table].reshape(b, cap, pool.shape[2])
        pos = position[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
        mask = jnp.arange(cap, dtype=jnp.int32)[None, None, :] <= pos[:, :, None]
        return att._weighted_latents(q_lat, q_rope, hist[..., :kl],
                                     hist[..., kl:kl + rope], mask, scale)

    def kernel(q_lat):
        return ppa.paged_latent_attention_read(q_lat, q_rope, pool, table,
                                               position, scale,
                                               interpret=_INTERP)

    if _INTERP:   # XLA:CPU lacks some bfloat16 products with float32 results
        q_lat, q_rope, pool = (x.astype(jnp.float32)
                               for x in (q_lat, q_rope, pool))
    ref, out = gather_ref(q_lat).astype(jnp.float32), kernel(q_lat)
    err = float(jnp.max(jnp.abs(out - ref)))
    case["max_err"] = round(err, 6)
    # the gather path rounds its output to bfloat16; the kernel returns the
    # float32 sums: weighted means of values of size about 1 agree to 1e-2
    case["correct"] = bool(err < 0.02 and jnp.isfinite(out).all())
    del ref, out
    for label, f in (("kernel", kernel), ("gather", gather_ref)):
        try:
            case[f"{label}_ms"] = round(_timeit(f, (q_lat,), reps) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "kernel_ms" in case and "gather_ms" in case:
        case["kernel_vs_gather"] = round(case["gather_ms"] / case["kernel_ms"], 2)
    if "kernel_ms" in case:   # bytes of the positions held, as the pool holds them
        gb = case["held_positions"] * pool.shape[2] * 2 / 1e9
        case["kernel_gb_per_s"] = round(gb / (case["kernel_ms"] / 1e3), 1)
    return case


def _shuffled_groups(table, where, runs, group, rng):
    """``table`` with a share ``1 - runs`` of the rows' whole groups of
    ``group`` logical pages taken apart: ``where`` is every held page's
    (row, column, logical page), the loose groups' ids are permuted among
    themselves, so those pages lie anywhere in the pool and the others side
    by side as before."""
    import numpy as np

    loose = {key for key in {(r, s // group) for r, _, s in where}
             if rng.rand() >= runs}
    at = [(r, c) for r, c, s in where if (r, s // group) in loose]
    if at:
        rows, cols = map(np.array, zip(*at))
        table[rows, cols] = rng.permutation(table[rows, cols])
    return table


def run_gqa_case(b, h, hkv, ch, ps, cols, window, lo, hi, reps, runs=None):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.RandomState(0)
    window = window or None
    dtype = jnp.float32 if _INTERP else jnp.bfloat16
    position = rng.randint(lo, hi, (b,)).astype(np.int32)
    # each row's pages: in order from 0, or the ring's columns of the pages
    # its window reaches; every other entry names the trash page
    table, pages, where = np.zeros((b, cols), np.int32), 0, []
    for r, p in enumerate(position):
        first = 0 if window is None else max(0, p - window + 1) // ps
        for s in range(first, p // ps + 1):
            pages += 1
            table[r, s % cols if window else s] = pages
            where.append((r, s % cols if window else s, s))
    k_pool, v_pool = (jnp.asarray(rng.randn(pages + 1, ps, hkv * ch), dtype)
                      for _ in range(2))
    if runs is not None:
        table = _shuffled_groups(table, where, runs, ppa.RUN_PAGES, rng)
    table, position = jnp.asarray(table), jnp.asarray(position)
    q = jnp.asarray(rng.randn(b, h, 1, ch) * 0.3, dtype)
    read = int(np.minimum(np.asarray(position) + 1,
                          window or 1 << 30).sum())
    case = {"kind": "paged_gqa", "b": b, "h": h, "hkv": hkv, "ch": ch,
            "ps": ps, "columns": cols, "window": window or 0, "lo": lo,
            "hi": hi, "positions_read": read}
    if runs is not None:
        case["runs"] = runs
    if not _INTERP:
        case["gate"] = ppa.paged_gqa_refusal(q, k_pool, table, window) \
            or "kernel"

    # the pools ride in as arguments: closed over, they are constants of the
    # timed program and its compile takes minutes at these sizes
    def gather_ref(q, k_pool, v_pool, table, position):
        return att._paged_gqa_gather_read(q, k_pool, v_pool, table, position,
                                          window)

    def kernel(q, k_pool, v_pool, table, position):
        return ppa.paged_gqa_read(q, k_pool, v_pool, table, position, window,
                                  interpret=_INTERP)

    args = (q, k_pool, v_pool, table, position)
    ref, out = gather_ref(*args), kernel(*args)
    err = float(jnp.max(jnp.abs(out - ref)))
    case["max_err"] = round(err, 6)
    # both return float32 sums of bfloat16 products; the weights are rounded
    # to bfloat16 before the second product on both paths
    case["correct"] = bool(err < 0.02 and jnp.isfinite(out).all())
    del ref, out
    # where the pages lie moves the kernel's time and not the gather's
    for label, f in (("kernel", kernel), ("gather", gather_ref))[
            :1 if runs is not None else 2]:
        try:
            case[f"{label}_ms"] = round(_timeit(f, args, reps) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "kernel_ms" in case and "gather_ms" in case:
        case["kernel_vs_gather"] = round(case["gather_ms"] / case["kernel_ms"], 2)
    gb = read * 2 * hkv * ch * jnp.dtype(dtype).itemsize / 1e9
    for label in ("kernel", "gather"):   # bytes of the positions READ
        if f"{label}_ms" in case:
            case[f"{label}_gb_per_s"] = round(gb / (case[f"{label}_ms"] / 1e3), 1)
    return case


def run_grouped_mm_case(tokens, top_k, d, w, held, experts, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mxnet_tpu.ops import pallas_grouped_matmul as gmm

    rng = np.random.RandomState(0)
    dtype = jnp.float32 if _INTERP else jnp.bfloat16
    pairs = tokens * top_k
    # a router without favourites: top_k distinct experts a token
    ids = np.argsort(rng.rand(tokens, experts), axis=1)[:, :top_k].reshape(-1)
    sizes = np.bincount(ids, minlength=experts)[:held].astype(np.int32)
    rows, hit = int(sizes.sum()), int((sizes > 0).sum())
    x = jnp.asarray(rng.randn(pairs, d) * 0.5, dtype)
    w_gate, w_up = (jnp.asarray(rng.randn(held, d, w) * d ** -0.5, dtype)
                    for _ in range(2))
    w_down = jnp.asarray(rng.randn(held, w, d) * w ** -0.5, dtype)
    # the sizes ride first and in float32: the timing chain adds its carry
    # to the first argument, and a pass over the rows (335 MB at 32,768 x
    # 5,120) would be timed with either path
    sizes = jnp.asarray(sizes, jnp.float32)
    case = {"kind": "grouped_mm", "pairs": pairs, "d": d, "w": w,
            "held": held, "experts": experts, "held_pairs": rows,
            "experts_hit": hit, "largest_group": int(sizes.max())}
    if not _INTERP:
        case["gate"] = gmm.grouped_matmul_refusal(pairs, d, w, dtype, dtype) \
            or "kernel"
    f32 = dict(preferred_element_type=jnp.float32)

    def ragged(sizes, x, w_gate, w_up, w_down):
        sizes = sizes.astype(jnp.int32)
        mid = jax.nn.relu(lax.ragged_dot(x, w_gate, sizes, **f32)) \
            * lax.ragged_dot(x, w_up, sizes, **f32)
        return lax.ragged_dot(mid.astype(x.dtype), w_down, sizes, **f32)

    def kernel(sizes, x, w_gate, w_up, w_down):
        return gmm.grouped_glu_ffn(x, w_gate, w_up, w_down,
                                   sizes.astype(jnp.int32), "relu",
                                   interpret=_INTERP)

    args = (sizes, x, w_gate, w_up, w_down)
    # the rows of no group are unwritten on both paths: compare the groups'
    ref, out = (np.asarray(f(*args))[:rows] for f in (ragged, kernel))
    err = float(np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)) \
        if rows else 0.0
    case["rel_err"] = round(err, 6)
    case["correct"] = bool(err < 0.02 and np.isfinite(out).all())
    del ref, out
    for label, f in (("kernel", kernel), ("ragged", ragged)):
        try:
            case[f"{label}_ms"] = round(_timeit(f, args, reps) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:300]
    if "kernel_ms" in case and "ragged_ms" in case:
        case["kernel_vs_ragged"] = round(case["ragged_ms"] / case["kernel_ms"], 2)
    # what the products need: the matrices of the experts that drew a pair
    # once, and two operations a weight and held pair
    gb = hit * 3 * d * w * jnp.dtype(dtype).itemsize / 1e9
    tflop = rows * 3 * 2 * d * w / 1e12
    case["weights_gb"], case["tflop"] = round(gb, 4), round(tflop, 4)
    for label in ("kernel", "ragged"):
        if f"{label}_ms" in case:
            sec = case[f"{label}_ms"] / 1e3
            case[f"{label}_gb_per_s"] = round(gb / sec, 1)
            case[f"{label}_tflop_per_s"] = round(tflop / sec, 2)
    return case


def run_block_list_case(rows, heads, kv, ch, ps, pages, listed, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.RandomState(0)
    length = -(-listed // 128) * 128
    q = jnp.asarray(rng.randn(rows, heads, 1, ch), jnp.bfloat16)
    k_pool, v_pool = (jnp.asarray(rng.randn(pages + 1, ps, kv * ch),
                                  jnp.bfloat16) for _ in range(2))
    blocks = np.sort(np.stack([np.stack([
        rng.permutation(4 * length)[:length] for _ in range(kv)])
        for _ in range(rows)]), axis=2).astype(np.int32)
    counts = np.full((rows, kv), listed, np.int32)
    position = (blocks[:, :, listed - 1].max(axis=1) * ps + ps // 2).astype(np.int32)
    lists = tuple(jnp.asarray(x) for x in (
        rng.randint(1, pages + 1, (rows, kv, length)).astype(np.int32),
        blocks * ps, counts, position))
    case = {"kind": "block_list", "rows": rows, "heads": [heads, kv, ch],
            "page": ps, "pages": pages, "listed": listed}
    if not _INTERP:
        case["gate"] = ppa.paged_gqa_selected_refusal(q, k_pool, lists[0]) \
            or "kernel"
    # the pools are ARGUMENTS: closed over, they would be constants of the
    # timed program
    forms = {"kernel": lambda q, k, v, *ls: ppa.paged_gqa_read(
                 q, k, v, None, ls[3], selected=ls[:3], interpret=_INTERP),
             "xla": att._paged_block_gather_read}
    got, want = (jax.jit(f)(q, k_pool, v_pool, *lists) for f in forms.values())
    err = float(jnp.abs(got - want).max())
    case["max_err"] = round(err, 6)
    case["correct"] = bool(err < 3e-2 and np.isfinite(np.asarray(got)).all())

    def timed(read):
        chain = jax.jit(lambda q, k, v, *ls: jax.lax.scan(
            lambda c, _: (read(c, k, v, *ls).astype(c.dtype), ()), q, None,
            length=reps)[0])
        chain(q, k_pool, v_pool, *lists)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = chain(q, k_pool, v_pool, *lists)
            np.asarray(jax.device_get(out[0, 0, 0, :1]))
            times.append((time.perf_counter() - t0) / reps)
        return sorted(times)[1]

    for label, read in forms.items():
        try:
            case[f"{label}_ms"] = round(timed(read) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:300]
    # what a read has to move: the listed blocks' key and value, a head's
    gb = rows * kv * listed * ps * 2 * ch * 2 / 1e9
    case["read_gb"] = round(gb, 4)
    for label in forms:
        if f"{label}_ms" in case:
            case[f"{label}_gb_per_s"] = round(gb / case[f"{label}_ms"] * 1e3, 1)
    return case


def run_chunk_scores_case(queries, heads, kv, ch, tokens, first, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import minicpm_sala as sala
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    cfg = dict(kernel_size=32, kernel_stride=16, block_size=64)
    size, stride, block = cfg["kernel_size"], cfg["kernel_stride"], \
        cfg["block_size"]
    rng = np.random.RandomState(0)
    n_keys, n_blocks = tokens // stride - size // stride + 1, tokens // block
    q = jnp.asarray(rng.randn(queries, kv, heads // kv, ch), jnp.bfloat16)
    ck = jnp.asarray(rng.randn(n_keys, kv, ch), jnp.bfloat16)
    case = {"kind": "chunk_scores", "queries": queries,
            "heads": [heads, kv, ch], "keys": n_keys, "first": first}
    if not _INTERP:
        case["gate"] = ppa.sparse_chunk_scores_refusal(
            q, ck, block, size, stride) or "kernel"

    def kernel(q, ck, first):
        return ppa.sparse_chunk_scores(
            q, ppa.sparse_chunk_keys(ck, block, stride), first, block, size,
            stride, interpret=_INTERP)[:, :, :n_blocks]

    def xla(q, ck, first):
        few = min(queries, sala._SELECT_QUERIES)

        def of(start):
            at = first + start + jnp.arange(few, dtype=jnp.int32)
            return sala.pooled_weights(sala.key_weights(
                jax.lax.dynamic_slice_in_dim(q, start, few, 0), ck, at, cfg),
                at, cfg, n_blocks)

        out = jax.lax.map(of, jnp.arange(0, queries, few, dtype=jnp.int32))
        return jnp.moveaxis(out.reshape(queries, kv, n_blocks), 1, 0)

    forms = {"kernel": kernel, "xla": xla}
    at = jnp.asarray(first, jnp.int32)
    got, want = (np.asarray(jax.jit(f)(q, ck, at)) for f in forms.values())
    seen = np.isfinite(want)
    err = float(np.abs(got[seen] - want[seen]).max())
    case["max_err"] = round(err, 8)
    case["correct"] = bool(err < 1e-5 and (np.isneginf(got) == ~seen).all())
    del got, want

    def timed(score):
        # every call's queries follow from the last one's scores, so the
        # chain's calls run one after another
        chain = jax.jit(lambda q, ck, at: jax.lax.scan(
            lambda c, _: (c + (score(c, ck, at)[0, :, :1, None, None] > 1e9
                               ).astype(c.dtype), ()), q, None,
            length=reps)[0])
        chain(q, ck, at)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = chain(q, ck, at)
            np.asarray(jax.device_get(out[0, 0, 0, :1]))
            times.append((time.perf_counter() - t0) / reps)
        return sorted(times)[1]

    for label, score in forms.items():
        try:
            case[f"{label}_ms"] = round(timed(score) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:300]
    # the products of the keys a query sees whole (two operations a key,
    # head and channel), and what has to cross HBM: queries and keys in,
    # the block scores out
    seen_keys = sum(min(max((first + i - size + 1) // stride + 1, 0), n_keys)
                    for i in range(queries))
    case["tflop"] = round(2 * seen_keys * heads * ch / 1e12, 4)
    case["hbm_gb"] = round((q.size * 2 + ck.size * 2
                            + kv * queries * n_blocks * 4) / 1e9, 4)
    for label in forms:
        if f"{label}_ms" in case:
            case[f"{label}_tflop_per_s"] = round(
                case["tflop"] / case[f"{label}_ms"] * 1e3, 2)
    if "kernel_ms" in case and "xla_ms" in case:
        case["kernel_vs_xla"] = round(case["xla_ms"] / case["kernel_ms"], 2)
    return case


def run_gdn_case(rows, n_live, heads, dk, dv, reps, delta=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_gdn as gdn

    rng = np.random.RandomState(0)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    state = f32(rng.randn(rows, dk, heads * dv))
    q = f32(unit(rng.randn(rows, heads, dk)) * dk ** -0.5)
    k = f32(unit(rng.randn(rows, heads, dk)))
    v = f32(rng.randn(rows, heads, dv))
    alpha = f32(rng.uniform(0.9, 1.0, (rows, heads)))
    beta = f32(rng.uniform(0.0, 2.0, (rows, heads)))
    live = jnp.arange(rows) < n_live
    case = {"kind": "gdn_decode" if delta else "lightning_decode",
            "rows": rows, "live": n_live, "heads": heads, "dk": dk, "dv": dv}
    if not _INTERP:
        case["gate"] = gdn.gdn_decode_refusal(state, q, v) or "kernel"
    forms = {"kernel": lambda s: gdn.gdn_decode_step(
                 s, q, k, v, alpha, beta, live, interpret=_INTERP,
                 delta=delta),
             "xla": lambda s: gdn.gdn_decode_xla(s, q, k, v, alpha, beta, live,
                                                 delta=delta)}
    (o_k, s_k), (o_x, s_x) = (jax.jit(f)(state) for f in forms.values())
    err = max(float(jnp.abs(o_k - o_x).max()), float(jnp.abs(s_k - s_x).max()))
    dead = bool((np.asarray(s_k)[n_live:] == np.asarray(state)[n_live:]).all())
    case["max_err"] = round(err, 8)
    case["correct"] = bool(err < 1e-4 and dead
                           and np.isfinite(np.asarray(o_k)).all())
    del o_k, s_k, o_x, s_x

    def timed(step):
        # the state is the chain's carry, donated: every call advances it
        # in place, as an engine's decode program does
        chain = jax.jit(lambda s: jax.lax.scan(
            lambda c, _: (step(c)[1], ()), s, None, length=reps)[0],
            donate_argnums=(0,))
        s = chain(state + 0.0)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            s = chain(s)
            np.asarray(jax.device_get(s[0, 0, :1]))
            times.append((time.perf_counter() - t0) / reps)
        return sorted(times)[1]

    for label, step in forms.items():
        try:
            case[f"{label}_ms"] = round(timed(step) * 1e3, 4)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:300]
    # what a step has to move: the live rows' state, read and written
    gb = n_live * 2 * heads * dk * dv * 4 / 1e9
    case["state_gb"] = round(gb, 4)
    for label in forms:
        if f"{label}_ms" in case:
            case[f"{label}_gb_per_s"] = round(gb / case[f"{label}_ms"] * 1e3, 1)
    if "kernel_ms" in case and "xla_ms" in case:
        case["kernel_vs_xla"] = round(case["xla_ms"] / case["kernel_ms"], 2)
    return case


def run_masked_prefill_case(t, heads, nope, rope, vd, kl, ql, idx_heads,
                            idx_dim, top_k, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention as att

    # XLA:CPU lacks some bfloat16 products with float32 results
    dtype = jnp.float32 if _INTERP else jnp.bfloat16
    rng = np.random.RandomState(0)
    rand = lambda *shape, std=1.0: jnp.asarray(  # noqa: E731
        rng.randn(*shape) * std, dtype)
    c_q, c_kv, k_rope = rand(1, t, ql), rand(1, t, kl), rand(1, t, rope)
    w_qb = rand(heads * (nope + rope), ql, std=0.02)
    w_kvb = rand(heads * (nope + vd), kl, std=0.02)
    gate = jax.nn.sigmoid(rand(1, t, heads))
    inv_freq = 8e7 ** (-np.arange(0, rope, 2) / rope)
    scale = (nope + rope) ** -0.5
    case = {"kind": "masked_prefill", "t": t, "heads": heads, "nope": nope,
            "rope": rope, "vd": vd, "kl": kl, "ql": ql, "top_k": top_k}
    # the selection as the program makes it: the indexer's top_k of every
    # query's row among the keys up to it (all of them while t < top_k)
    idx = (rand(1, t, idx_heads, idx_dim), rand(1, t, idx_dim),
           jnp.asarray(rng.rand(1, t, idx_heads), jnp.float32))
    select = jax.jit(lambda q: att.dsa_selection_mask(q, idx[1], idx[2], top_k))
    seen = select(idx[0])
    case["ones_a_row"] = round(float(seen.sum()) / t, 1)

    def xla(c_q):
        return att._masked_chunk_attention(c_q, w_qb, c_kv, k_rope, w_kvb,
                                           heads, seen, None, inv_freq, scale,
                                           gate)

    def kernel(c_q):
        return att._masked_chunk_kernel(c_q, w_qb, c_kv, k_rope, w_kvb, heads,
                                        seen, None, inv_freq, scale, gate,
                                        interpret=_INTERP)

    try:
        ref, out = jax.jit(xla)(c_q), jax.jit(kernel)(c_q)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        case["max_err"] = round(err, 6)
        # weighted means of values of size about 0.5, rounded to bfloat16
        # and summed in another order
        case["correct"] = bool(err < 0.01 and jnp.isfinite(
            out.astype(jnp.float32)).all())
        del ref, out
    except Exception as e:
        case["kernel_error"] = repr(e)[:600]
    for label, f, arg in (("kernel", kernel, c_q), ("xla", xla, c_q),
                          ("indexer", select, idx[0])):
        try:
            case[f"{label}_ms"] = round(_timeit(f, (arg,), reps) * 1e3, 3)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:300]
    if "kernel_ms" in case and "xla_ms" in case:
        case["kernel_vs_xla"] = round(case["xla_ms"] / case["kernel_ms"], 2)
    if "kernel_ms" in case:   # the products of the pairs at or under the diagonal
        flops = heads * t * (t + 1) * (nope + rope + vd)
        case["kernel_tflops"] = round(flops / case["kernel_ms"] / 1e9, 1)
    return case


def run_adam_case(n, reps):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import optimizer_ops as oo
    from mxnet_tpu.ops import pallas_optimizer as po

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(n), jnp.float32)
    g = jnp.asarray(rng.randn(n), jnp.bfloat16)
    m = jnp.asarray(rng.randn(n) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.randn(n)) * 0.01, jnp.float32)
    lr_t, wd = jnp.float32(1e-3), jnp.float32(1e-2)
    case = {"kind": "fused_adam", "n": n}

    def unfused(w):
        nw, nm, nv = oo.adam_update(w, g, m, v, lr_t, 0.9, 0.999, 1e-8,
                                    wd, 1.0, -1.0)
        return nw, nm, nv, nw.astype(jnp.bfloat16)  # the mp two-pass cast

    def fused(w):
        return po.adam_update_fused(w, g, m, v, lr_t, beta1=0.9, beta2=0.999,
                                    epsilon=1e-8, wd=wd,
                                    out_dtype=jnp.bfloat16, interpret=_INTERP)

    ref, out = unfused(w), fused(w)
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(ref, out)]
    case["max_err"] = round(max(errs[:3]), 8)   # f32 weight and moments
    case["max_err_bf16_copy"] = round(errs[3], 6)
    # an f32 ulp of difference may round the bf16 copy the other way: its
    # bound is one bf16 step at the weights' size (|w| < 8 here)
    case["correct"] = max(errs[:3]) < 1e-5 and errs[3] <= 2.0 ** -5
    del ref, out
    for label, f in (("fused", fused), ("xla", unfused)):
        try:
            case[f"{label}_ms"] = round(_timeit(f, (w,), reps) * 1e3, 3)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "fused_ms" in case and "xla_ms" in case:
        case["fused_vs_xla"] = round(case["xla_ms"] / case["fused_ms"], 2)
    return case


def run_xent_case(n, c, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_softmax_xent as px

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, c), jnp.bfloat16)
    lbl = jnp.asarray(rng.randint(0, c, (n,)), jnp.int32)
    co = jnp.ones((n,), jnp.float32)
    case = {"kind": "softmax_xent", "n": n, "c": c}

    def composed(x):
        lp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(lp, lbl[:, None], axis=-1)[:, 0]

    def fused(x):
        return px.softmax_cross_entropy_fused(x, lbl, interpret=_INTERP)

    ref, out = composed(x), fused(x)
    err = float(jnp.max(jnp.abs(ref - out)))
    case["max_err"] = round(err, 5)
    case["correct"] = err < 0.05
    del ref, out

    def with_grad(f):
        return jax.grad(lambda x: jnp.sum(f(x).astype(jnp.float32) * co))

    for label, f in (("fused", fused), ("xla", composed)):
        try:
            case[f"{label}_ms"] = round(
                _timeit(with_grad(f), (x,), reps) * 1e3, 3)
        except Exception as e:
            case[f"{label}_error"] = repr(e)[:120]
    if "fused_ms" in case and "xla_ms" in case:
        case["fused_vs_xla"] = round(case["xla_ms"] / case["fused_ms"], 2)
    return case


_INTERP = os.environ.get("KERNELBENCH_TINY") == "1"  # CPU dry-run: Pallas
# kernels only run in interpret mode off-TPU


def run_one(argv):
    spec = json.loads(argv[argv.index("--one") + 1])
    try:
        if spec["kind"] == "attn":
            case = run_attn_case(spec["b"], spec["h"], spec["seq"], spec["d"],
                                 spec["causal"], spec["reps"], spec["fwd_only"])
        elif spec["kind"] == "conv_layout":
            case = run_conv_case(spec["b"], spec["c"], spec["hw"], spec["hw"],
                                 spec["o"], spec["k"], spec["reps"])
        elif spec["kind"] == "paged_attn":
            case = run_paged_case(spec["b"], spec["h"], spec["ch"],
                                  spec["ps"], spec["n_pages"], spec["tq"],
                                  spec["held"], spec["reps"])
        elif spec["kind"] == "paged_latent":
            case = run_latent_case(spec["b"], spec["h"], spec["kl"],
                                   spec["rope"], spec["ps"], spec["n_pages"],
                                   spec["tq"], spec["held"], spec["reps"])
        elif spec["kind"] == "masked_prefill":
            case = run_masked_prefill_case(*spec["shape"], spec["reps"])
        elif spec["kind"] == "paged_gqa":
            case = run_gqa_case(*spec["shape"], spec["reps"],
                                spec.get("runs"))
        elif spec["kind"] == "grouped_mm":
            case = run_grouped_mm_case(*spec["shape"], spec["reps"])
        elif spec["kind"] == "gdn_decode":
            case = run_gdn_case(*spec["shape"], spec["reps"])
        elif spec["kind"] == "lightning_decode":
            case = run_gdn_case(*spec["shape"], spec["reps"], delta=False)
        elif spec["kind"] == "block_list":
            case = run_block_list_case(*spec["shape"], spec["reps"])
        elif spec["kind"] == "chunk_scores":
            case = run_chunk_scores_case(*spec["shape"], spec["reps"])
        elif spec["kind"] == "fused_adam":
            case = run_adam_case(spec["n"], spec["reps"])
        elif spec["kind"] == "softmax_xent":
            case = run_xent_case(spec["n"], spec["c"], spec["reps"])
        else:
            case = run_ln_case(spec["n"], spec["d"], spec["reps"])
    except Exception as e:  # a refusal is the case's result: keep its words
        case = dict(spec, error=repr(e)[:600])
    print("CASE " + json.dumps(case), flush=True)


def main():
    if "--one" in sys.argv:
        run_one(sys.argv)
        return
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--kinds", default="",
                    help="comma-separated case kinds to run (attn, ln, "
                         "conv_layout, paged_attn, paged_latent, "
                         "masked_prefill, paged_gqa, grouped_mm, "
                         "gdn_decode, lightning_decode, block_list, "
                         "chunk_scores, "
                         "fused_adam, softmax_xent); "
                         "default all")
    ap.add_argument("--runs", default="",
                    help="paged_gqa: comma-separated shares of a row's pages "
                         "laid side by side in the pool (1.0 a fresh pool's "
                         "table, 0.0 a shuffled one); every case at every "
                         "share, the kernel alone timed")
    ap.add_argument("--timeout", type=int, default=600)
    args = ap.parse_args()

    specs = []
    for b, h, seq, d in ATTN_CASES:
        for causal in (False, True):
            specs.append({"kind": "attn", "b": b, "h": h, "seq": seq,
                          "d": d, "causal": causal, "reps": args.reps,
                          "fwd_only": args.fwd_only})
    specs += [{"kind": "ln", "n": n, "d": d, "reps": args.reps}
              for n, d in LN_CASES]
    specs += [{"kind": "conv_layout", "b": b, "c": c, "hw": h, "o": o,
               "k": k, "reps": args.reps}
              for b, c, h, w, o, k in CONV_CASES]
    specs += [{"kind": "paged_attn", "b": b, "h": h, "ch": ch, "ps": ps,
               "n_pages": np_, "tq": tq, "held": held, "reps": args.reps}
              for b, h, ch, ps, np_, tq, held in PAGED_CASES]
    specs += [{"kind": "paged_latent", "b": b, "h": h, "kl": kl, "rope": rope,
               "ps": ps, "n_pages": np_, "tq": tq, "held": held,
               "reps": args.reps}
              for b, h, kl, rope, ps, np_, tq, held in LATENT_CASES]
    # a call is 0.1-0.6 s: a chain of three is long enough
    specs += [{"kind": "masked_prefill", "shape": list(shape),
               "reps": min(args.reps, 3)} for shape in MASKED_PREFILL_CASES]
    specs += [{"kind": "paged_gqa", "shape": list(shape), "reps": args.reps,
               **({} if runs is None else {"runs": runs})}
              for shape in GQA_CASES
              for runs in ([float(r) for r in args.runs.split(",") if r]
                           or [None])]
    specs += [{"kind": "grouped_mm", "shape": list(shape), "reps": args.reps}
              for shape in GROUPED_MM_CASES]
    specs += [{"kind": "gdn_decode", "shape": list(shape), "reps": args.reps}
              for shape in GDN_CASES]
    specs += [{"kind": "lightning_decode", "shape": list(shape),
               "reps": args.reps} for shape in LIGHTNING_CASES]
    specs += [{"kind": "block_list", "shape": list(shape), "reps": args.reps}
              for shape in BLOCK_LIST_CASES]
    # a call is 10-80 ms: a chain of five is long enough
    specs += [{"kind": "chunk_scores", "shape": list(shape),
               "reps": min(args.reps, 5)} for shape in CHUNK_SCORES_CASES]
    specs += [{"kind": "fused_adam", "n": n, "reps": args.reps}
              for (n,) in ADAM_CASES]
    specs += [{"kind": "softmax_xent", "n": n, "c": c, "reps": args.reps}
              for n, c in XENT_CASES]
    kinds = [k for k in args.kinds.split(",") if k]
    unknown = set(kinds) - {spec["kind"] for spec in specs}
    if unknown:
        ap.error(f"unknown kinds {sorted(unknown)}")
    if kinds:
        specs = [spec for spec in specs if spec["kind"] in kinds]

    def _run_spec(spec):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one",
             json.dumps(spec)],
            capture_output=True, text=True, timeout=args.timeout)
        lines = [ln for ln in (r.stdout or "").splitlines()
                 if ln.startswith("CASE ")]
        return (json.loads(lines[-1][5:]) if lines
                else dict(spec, error=f"child rc={r.returncode}: "
                          + (r.stderr or "")[-200:]))

    n_bad = 0
    for spec in specs:
        try:
            case = _run_spec(spec)
        except subprocess.TimeoutExpired:
            case = dict(spec, error=f"timeout {args.timeout}s")
        case.pop("reps", None)
        case.pop("fwd_only", None)
        if not case.get("correct", False):
            n_bad += 1
        print(json.dumps(case), flush=True)
    print(f"# {len(specs)} cases, {n_bad} failed-or-errored", file=sys.stderr)
    sys.exit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
