"""dots3-note-prev at a small size on the CPU, float32, seeded weights: the
Gluon model against the plain reference (``benchmark/reference``) on a whole
sequence; prefill then decode through BOTH pool groups of the paged engine
against the reference's full forward, at sizes where the selection binds
(``index_topk`` 8, 40 positions) and the window frees pages (window 5, page
2); what each flag of the configuration moves; the sigmoid router's shares
adding up; and what the engine's window group guarantees and refuses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import ContinuousBatcher, GenerationEngine
from mxnet_tpu.ops import attention
from mxnet_tpu.parallel import moe

from benchmark.reference import deepseek_v2 as ref_v2
from benchmark.reference import dots3_note as ref
from benchmark.systems import dots3_note as adaptor
from benchmark.weights import make_weights

SEED = 4294967311  # past 32 bits, as the driver's are
LAYERS = ["full_attention", "full_attention", "sliding_attention",
          "sliding_attention"]


def tiny_config(**over):
    """The published configuration's keys at toy sizes."""
    cfg = dict(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=24,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=80000000, attention_gate_type="headwise",
        swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=40,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        swa_rope_theta=50000, swa_attention_gate_type="headwise",
        sliding_window_size=5, index_n_heads=4, index_head_dim=16,
        index_topk=8, apply_mla_qkv_lora_rescale=True, rms_norm_eps=1e-5,
        layer_types=LAYERS, n_layer=4, first_k_dense_replace=1,
        n_shared_experts=1, n_routed_experts=16, num_experts_per_tok=3,
        norm_topk_prob=True, routed_scaling_factor=1, n_vocab=200,
        initializer_range=0.02, max_position_embeddings=256,
        held_experts=[0, 1, 2, 5, 9, 14], precision={"weights": "float32"},
        engine={"batch_size": 3, "paged": True, "page_size": 2,
                "num_pages": {"all": 90, "window": 20}, "max_length": 64,
                "cache_dtype": "float32", "prefill_buckets": [8, 16, 32]})
    cfg.update(over)
    return cfg


def reference_logits(cfg, weights, tokens, first, count, precision="float32"):
    return ref.next_token_logits(weights, cfg, list(tokens), first, count,
                                 precision=precision, pad_to=64, out_pad=32)


def served(cfg, weights, lengths=(13, 30, 7), steps=12, poison=False):
    """Prefill three rows and decode ``steps`` steps; per row (prompt,
    tokens, the logits every token was the argmax of), and the engine."""
    engine, _ = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg["n_vocab"], n).tolist() for n in lengths]
    rows = []
    for slot, prompt in enumerate(prompts):
        tok = engine.prefill(prompt, slot)
        rows.append((prompt, [tok], [np.asarray(engine._last_logits)]))
    w = engine._groups["window"]
    for _ in range(steps):
        if poison:  # what a free page of the window group holds is garbage
            free = jnp.asarray(list(w.free), jnp.int32)
            engine.pools = [
                tuple(b.at[free].set(jnp.nan) for b in layer)
                if g == "window" else layer
                for layer, g in zip(engine.pools, engine.layer_groups)]
        tok, _, logits = engine.decode_step()
        for slot, (_, out, lg) in enumerate(rows):
            out.append(int(tok[slot]))
            lg.append(np.asarray(logits[slot]))
        assert w is None or max(map(len, w.rows)) <= w.window // w.page_size + 3
    return rows, engine


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, make_weights(ref.param_specs(cfg), SEED)


def test_the_model_agrees_with_the_reference_on_a_whole_sequence(model):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    tokens = np.random.default_rng(0).integers(1, 200, 40)
    got = net(mx.nd.array(tokens[None], dtype="int32"))._data[0]
    want = reference_logits(cfg, weights, tokens, 0, 40)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # the selection binds here: attention over everything reads otherwise
    assert np.abs(reference_logits(cfg, weights, tokens, 0, 40, "no_selection")
                  - want).max() > 1e-2


def test_a_long_chunks_feed_forward_in_token_blocks_is_the_whole_chunks(
        model, monkeypatch):
    """The dense and the expert layers walked in blocks of tokens (what a
    prefill of more than 4,096 tokens does) give the whole chunk's logits."""
    from mxnet_tpu.models import dots3_note

    cfg, weights = model
    tokens = mx.nd.array(np.random.default_rng(3).integers(1, 200, (1, 32)),
                         dtype="int32")
    whole = adaptor.build_net(cfg, weights)(tokens)._data
    monkeypatch.setattr(dots3_note, "_FFN_TOKENS", 8)
    blocks = adaptor.build_net(cfg, weights)(tokens)._data
    assert float(jnp.abs(blocks - whole).max()) < 2e-5


@pytest.mark.parametrize("poison", [False, True])
def test_prefill_then_decode_through_both_pool_groups(model, poison):
    """Logits of the prefill and of every paged decode step against the
    reference's full forward over prompt + output, with the selection and the
    window both binding; with ``poison`` every free page of the window group
    holds NaN before each step, and nothing moves."""
    cfg, weights = model
    rows, engine = served(cfg, weights, poison=poison)
    for prompt, out, logits in rows:
        want = reference_logits(cfg, weights, prompt + out[:-1],
                                len(prompt) - 1, len(out))
        assert np.abs(np.stack(logits) - want).max() < 5e-5
        assert out == want.argmax(-1).tolist()
    w = engine._groups["window"]
    assert w.freed_total > 10 and engine.page_groups["window"]["in_use"] <= 12
    counts = obs.step_records("decode_step")[-1].counts
    held = int((engine.positions).sum())
    assert counts["dsa_held"] == [held, held]
    assert counts["dsa_read"] == [3 * 8, 3 * 8]          # index_topk a row
    assert counts["window_pages_in_use"] == [w.in_use]
    assert len(counts["moe_pairs_held"]) == 3


def test_selection_equals_full_attention_while_a_row_is_short(model):
    cfg, weights = model
    tokens = np.random.default_rng(2).integers(1, 200, 8)
    want = reference_logits(cfg, weights, tokens, 0, 8)
    assert np.array_equal(
        want, reference_logits(cfg, weights, tokens, 0, 8, "no_selection"))
    rows, _ = served(cfg, weights, lengths=(3, 2, 4), steps=4)
    for prompt, out, logits in rows:   # at most 8 positions: nothing left out
        full = reference_logits(cfg, weights, prompt + out[:-1],
                                len(prompt) - 1, len(out), "no_selection")
        assert np.abs(np.stack(logits) - full).max() < 5e-5


@pytest.mark.parametrize("flag", ["rescale", "gate", "swa_gate", "bias"])
def test_a_flag_moves_program_and_reference_alike(model, flag):
    cfg, weights = model
    flipped, w2 = dict(cfg), weights
    if flag == "rescale":
        flipped["apply_mla_qkv_lora_rescale"] = False
    elif flag == "gate":
        flipped["attention_gate_type"] = "none"
    elif flag == "swa_gate":
        flipped["swa_attention_gate_type"] = "none"
    else:
        w2 = {k: jnp.zeros_like(v) if k.endswith("router.bias") else v
              for k, v in weights.items()}
    if flag in ("gate", "swa_gate"):  # an ungated sublayer has no gate weight
        names = {n for n, _, _ in ref.param_specs(flipped)}
        w2 = {k: v for k, v in weights.items() if k in names}
    tokens = np.random.default_rng(3).integers(1, 200, 24)
    before = reference_logits(cfg, weights, tokens, 0, 24)
    after = reference_logits(flipped, w2, tokens, 0, 24)
    assert np.abs(after - before).max() > 1e-3
    net = adaptor.build_net(flipped, w2)
    got = net(mx.nd.array(tokens[None], dtype="int32"))._data[0]
    assert float(jnp.abs(got - after).max()) < 2e-5


def test_the_shares_of_the_sigmoid_router_add_up_to_the_uncut_layer(model):
    """All 4 shares' routed terms (4 of 16 experts each) plus the shared
    expert once are the uncut layer, in the program's grouped product and in
    the reference's loop alike."""
    cfg, weights = model
    p = "layer1."
    h = 0.5 * np.random.default_rng(4).standard_normal((11, 64)).astype("f4")
    uncut = make_weights(ref.param_specs(dict(cfg, held_experts=None)), SEED)
    whole = ref.routed_part(uncut, p, dict(cfg, held_experts=None), h, "float32")
    parts = []
    for share in range(4):
        ids = list(range(4 * share, 4 * share + 4))
        mine = {k: (v[jnp.asarray(ids)] if ".experts." in k else v)
                for k, v in uncut.items()}
        parts.append(ref.routed_part(mine, p, dict(cfg, held_experts=ids), h,
                                     "float32"))
        y, _ = moe.held_expert_ffn(
            jnp.asarray(h), uncut[p + "router.w"],
            jnp.swapaxes(mine[p + "experts.gate.w"], 1, 2),
            jnp.swapaxes(mine[p + "experts.up.w"], 1, 2),
            jnp.swapaxes(mine[p + "experts.down.w"], 1, 2), held_experts=ids,
            top_k=3, scoring="sigmoid", router_bias=uncut[p + "router.bias"],
            norm_topk_prob=True)
        assert float(jnp.abs(y - parts[-1]).max()) < 1e-5
    assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5
    # the bias chooses, the unbiased scores weigh: they add up to 1 a token
    weights_of, ids = ref.route(cfg, jnp.asarray(h), uncut[p + "router.w"],
                                uncut[p + "router.bias"])
    assert np.allclose(np.asarray(weights_of).sum(-1), 1.0, atol=1e-6)
    assert ids.shape == (11, 3)
    _ = ref_v2  # the shared expert is that module's, added once by hidden()


def test_the_window_group_has_its_own_allocator_and_table(model):
    cfg, weights = model
    rows, engine = served(cfg, weights, steps=3)
    groups = engine.page_groups
    assert list(groups) == ["all", "window"]
    assert groups["window"]["window"] == 5 and groups["all"]["window"] is None
    assert [t.shape for t in engine.page_table] == [(3, 32), (3, 5)]
    assert engine.layer_groups == ("all", "all", "window", "window")
    assert [len(layer) for layer in engine.pools] == [2, 2, 1, 1]
    # bytes a token: a full layer's latent and index key, a window layer's
    # latent, each in whole lane tiles, float32 here
    assert engine.cache_bytes_per_token == 4 * (2 * (128 + 128) + 2 * 128)
    assert "xla_gather_index (the backend is not a TPU)" in engine.read_path
    assert "xla_gather_ring" in engine.read_path
    # a row of 30 + 4 positions holds every page of the all group and the
    # window's few of the other
    w = engine._groups["window"]
    assert len(engine._pages.rows[1]) == 17 and len(w.rows[1]) <= 4
    engine.release_slot(1)
    assert w.rows[1] == {} and not engine._pages.rows[1]
    gauge = obs.gauge("gen_pages_in_use")
    assert gauge.value(group="window") == w.in_use
    assert gauge.value(group="all") == engine.pages_in_use


@pytest.mark.parametrize("what", ["prefix_cache", "draft_net", "fork_slot"])
def test_what_shares_pages_is_refused_for_a_model_with_a_window_group(model, what):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    kw = dict(cfg["engine"])
    if what == "fork_slot":
        engine = GenerationEngine(net, **kw)
        engine.prefill([3, 4, 5], 0)
        with pytest.raises(RuntimeError, match="window"):
            engine.fork_slot(0, 1)
        return
    kw.update({"prefix_cache": True} if what == "prefix_cache"
              else {"draft_net": net, "speculate_k": 2})
    with pytest.raises(ValueError, match="behind a window"):
        GenerationEngine(net, **kw)


def test_admission_waits_for_the_group_that_runs_short(model):
    """A window pool of 7 pages: two rows of 31-token prompts (3 pages each)
    leave the third request waiting on the WINDOW group though the all group
    has room; it is admitted when a row ends, and every request's tokens are
    the reference's."""
    cfg, weights = model
    cfg = dict(cfg, engine=dict(cfg["engine"],
                                num_pages={"all": 90, "window": 7}))
    engine, batcher = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, 31).tolist() for _ in range(3)]
    reqs = [batcher.submit(p, max_new_tokens=6) for p in prompts]
    batcher.step()
    assert [r.admit_t is not None for r in reqs] == [True, True, False]
    assert not engine.covers(prompts[2]) and engine.available_pages >= 16
    batcher.run_until_idle()
    for prompt, req in zip(prompts, reqs):
        assert req.finish_reason == "length"
        want = reference_logits(cfg, weights, prompt + list(req.output)[:-1],
                                len(prompt) - 1, 6)
        assert list(req.output) == want.argmax(-1).tolist()
    assert engine._groups["window"].in_use == 0 and engine.pages_in_use == 0


def test_a_row_that_finds_the_window_pool_dry_ends_page_exhausted(model):
    cfg, weights = model
    cfg = dict(cfg, engine=dict(cfg["engine"], batch_size=2,
                                num_pages={"all": 90, "window": 4}))
    engine, _ = adaptor.build_serve(cfg, weights)
    for slot in range(2):     # 2 pages each: positions 2..5
        engine.prefill(list(range(1, 7)), slot)
    assert len(engine._groups["window"].free) == 0
    _, done, _ = engine.decode_step()   # position 6 opens a page: none is left
    assert engine.page_exhausted.all() and done.all()
    assert obs.counter("gen_page_evictions_total").value(reason="exhausted") >= 2


def test_the_decode_program_has_no_operation_of_the_pools_whole_width(model):
    """rows x table width x the latent pool's columns: a gather of every
    row's whole history would have that shape; the sparse read's has rows x
    index_topk."""
    cfg, weights = model
    engine, _ = adaptor.build_serve(cfg, weights)
    text = engine.lower_decode().as_text()
    rows, cap, width = 3, 64, 128
    assert f"tensor<{rows}x{cap}x{width}xf32>" in text     # the index keys
    latent = engine.pools[0][0].shape[2]
    assert latent == width  # toy widths: both pools are one lane tile wide
    assert f"tensor<{rows}x8x{width}xf32>" in text          # index_topk rows
    assert obs.counter("sparse_read_path_total").value(
        path="xla_gather_rows",
        reason=attention.SPARSE_READ_BY_XLA) > 0
    assert obs.counter("sparse_read_path_total").value(
        path="xla_gather_index", reason="the backend is not a TPU") > 0
    assert isinstance(ContinuousBatcher(engine).engine, GenerationEngine)


# -- the selection's arithmetic, and the index-key kernel ---------------------
@pytest.mark.parametrize("ties,short", [(False, False), (True, False),
                                        (True, True)])
def test_the_kth_largest_by_bisection_is_lax_top_ks(ties, short):
    """``top_k_mask`` marks exactly the positions ``lax.top_k`` returns,
    equal scores in the order of their positions, rows shorter than ``k``
    (the rest ``-inf``) included."""
    import jax

    s = np.random.default_rng(7).standard_normal((5, 300)).astype("f4")
    if ties:
        s = np.round(s, 1) + 0.0
        s[1] = 0.0
    if short:
        s[:, 40:] = -np.inf
    for k in (1, 7, 150):
        values, idx = jax.lax.top_k(jnp.asarray(s), k)
        assert np.array_equal(attention.kth_largest(jnp.asarray(s), k)[:, 0],
                              values[:, -1])
        want = np.zeros(s.shape, bool)
        np.put_along_axis(want, np.asarray(idx), True, axis=1)
        assert np.array_equal(attention.top_k_mask(jnp.asarray(s), k), want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_index_kernel_scores_what_a_row_holds_and_nothing_else(dtype):
    """``paged_index_scores`` (interpreted) against the XLA gather of the
    table's whole width, rows of 1 to 256 positions; the pages a row does
    not hold are NaN in the pool and count for nothing."""
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    rng = np.random.default_rng(0)
    b, j, d, pages, ps, width = 5, 4, 128, 40, 16, 16
    pos = jnp.asarray([0, 15, 16, 100, 255], jnp.int32)
    table = np.zeros((b, width), np.int32)
    used = iter(rng.permutation(np.arange(1, pages + 1)))
    for row, p in enumerate(np.asarray(pos)):
        table[row, :p // ps + 1] = [next(used) for _ in range(p // ps + 1)]
    pool = rng.standard_normal((pages + 1, ps, d)).astype("f4")
    pool[np.setdiff1d(np.arange(pages + 1), table[table > 0])] = np.nan
    pool, table = jnp.asarray(pool, dtype), jnp.asarray(table)
    q = jnp.asarray(rng.standard_normal((b, j, d)), dtype)
    w = jnp.asarray(rng.standard_normal((b, j)), jnp.float32)
    assert ppa.paged_index_scores_refusal(q[:, None], pool, table) == \
        "the backend is not a TPU"
    got = ppa.paged_index_scores(q, w, pool, table, pos, interpret=True)
    keys = jnp.nan_to_num(pool)[table].reshape(b, width * ps, d)
    want = attention.index_scores(q[:, None], keys, w[:, None])[:, 0]
    held = jnp.arange(width * ps)[None] <= pos[:, None]
    assert np.array_equal(np.isneginf(got), ~np.asarray(held))
    assert float(jnp.abs(jnp.where(held, got - want, 0.0)).max()) < 2e-5


def test_a_decode_step_through_the_index_kernel_is_the_xla_paths(monkeypatch):
    """The engine's decode program with the kernel (interpreted: the gate is
    told the backend is a TPU) at a page of 16 and index keys of 128: the
    tokens and logits of the XLA path, and the counter says what was built."""
    from mxnet_tpu.ops import pallas_paged_attention as ppa

    cfg = tiny_config(index_head_dim=128, index_topk=24)
    cfg["engine"] = dict(cfg["engine"], page_size=16, max_length=256,
                         num_pages={"all": 40, "window": 12},
                         prefill_buckets=[32, 64])
    weights = make_weights(ref.param_specs(cfg), SEED)
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)
    before = obs.counter("sparse_read_path_total").value(
        path="paged_index_scores", reason="")
    rows, engine = served(cfg, weights, lengths=(40, 33, 9), steps=6)
    assert "paged_index_scores kernel" in engine.read_path
    assert obs.counter("sparse_read_path_total").value(
        path="paged_index_scores", reason="") == before + 2     # two layers
    for prompt, out, logits in rows:
        want = ref.next_token_logits(weights, cfg, prompt + out[:-1],
                                     len(prompt) - 1, len(out), pad_to=64,
                                     out_pad=8)
        assert np.abs(np.stack(logits) - want).max() < 5e-5


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_few_tokens_alone_read_as_they_do_among_many(scoring):
    """A decode step of a chip that holds a small share: six tokens, some
    held experts without a pair. Routing is a token's own, so the same
    tokens among many give the same rows, by the one grouped path
    (``moe_path_total{path=sorted_ragged_dot}``: toy widths on the CPU take
    the kernel's refusal) both times; the few walk their 24 pairs whole (no
    prefix of whole row tiles is shorter), the many a prefix of 96 of 256."""
    rng = np.random.default_rng(8)
    n, d, w, experts, held, k = 6, 16, 8, 32, [3, 11, 30], 4
    many = jnp.asarray(rng.standard_normal((64, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((experts, d)).astype("f4") * 0.3)
    bias = jnp.asarray(rng.standard_normal(experts).astype("f4") * 0.1)
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.2
            for s in ((3, d, w), (3, d, w), (3, w, d))]
    how = dict(held_experts=held, top_k=k, scoring=scoring, norm_topk_prob=True,
               router_bias=bias if scoring == "sigmoid" else None)
    count = obs.counter("moe_path_total")
    ragged = dict(path="sorted_ragged_dot", reason="the backend is not a TPU")
    built = [dict(ragged, route="whole"), dict(ragged, route="prefix_or_whole")]
    before = [count.value(**b) for b in built]
    few, (pairs, load) = moe.held_expert_ffn(many[:n], router, *mats, **how)
    all_, _ = moe.held_expert_ffn(many, router, *mats, **how)
    assert [count.value(**b) for b in built] == [b + 1 for b in before]
    assert float(jnp.abs(few - all_[:n]).max()) < 1e-5
    assert float(jnp.abs(few).max()) > 1e-3 and 0 < int(load) <= int(pairs)


@pytest.mark.parametrize("skewed", [False, True])
def test_a_long_prefills_held_pairs_are_the_references_plain_loop(skewed):
    """2,048 tokens x 8 experts a token: the held experts (2 of 32) draw an
    eighth of the sorted pairs or less, or (a bias that every token
    chooses, weighed unbiased) nearly all of them; the reference's plain
    loop either way."""
    rng = np.random.default_rng(6)
    n, d, w, experts, held = 2048, 16, 8, 32, [3, 11]
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = rng.standard_normal((experts, d)).astype("f4") * 0.3
    bias = np.zeros(experts, "f4")
    if skewed:
        bias[held] = 8.0          # chosen by every token, weighed unbiased
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.2
            for s in ((2, w, d), (2, w, d), (2, d, w))]
    cfg = dict(n_routed_experts=experts, num_experts_per_tok=8,
               norm_topk_prob=True, routed_scaling_factor=1,
               held_experts=held)
    params = {"l.router.w": jnp.asarray(router), "l.router.bias": jnp.asarray(bias),
              "l.experts.gate.w": mats[0], "l.experts.up.w": mats[1],
              "l.experts.down.w": mats[2]}
    want = ref.routed_part(params, "l.", cfg, h, "float32")
    got, (pairs, _) = moe.held_expert_ffn(
        h, jnp.asarray(router), jnp.swapaxes(mats[0], 1, 2),
        jnp.swapaxes(mats[1], 1, 2), jnp.swapaxes(mats[2], 1, 2),
        held_experts=held, top_k=8, scoring="sigmoid",
        router_bias=jnp.asarray(bias), norm_topk_prob=True)
    assert (int(pairs) > n * 8 // 8) is skewed
    assert float(jnp.abs(got - want).max()) < 1e-5


def _routed_by_hand(held_pairs, n=256, experts=32, held=(3, 11), k=4):
    """An expert layer whose tokens' choices are set by hand: the router is
    a scaled identity, so a token chooses the ``k`` experts whose
    coordinates of ``h`` stand out; ``held_pairs`` (token, expert) pairs go
    to the two held experts, two a token and one for an odd count, every
    other choice to absent ones. (h, router, bias, mats, reference's cfg and
    params.)"""
    rng = np.random.default_rng(12)
    absent = [e for e in range(experts) if e not in held]
    h = 0.3 * rng.uniform(-1, 1, (n, experts)).astype("f4")
    for i in range(n):
        mine = list(held[:max(0, min(2, held_pairs - 2 * i))])
        mine += list(rng.choice(absent, k - len(mine), replace=False))
        h[i, mine] = 2.0 + rng.uniform(0, 1, k)
    router = 4.0 * np.eye(experts, dtype="f4")
    bias = np.zeros(experts, "f4")
    d, w = experts, 8
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.2
            for s in ((2, w, d), (2, w, d), (2, d, w))]
    cfg = dict(n_routed_experts=experts, num_experts_per_tok=k,
               norm_topk_prob=True, routed_scaling_factor=1,
               held_experts=list(held))
    params = {"l.router.w": jnp.asarray(router), "l.router.bias": jnp.asarray(bias),
              "l.experts.gate.w": mats[0], "l.experts.up.w": mats[1],
              "l.experts.down.w": mats[2]}
    return jnp.asarray(h), jnp.asarray(router), jnp.asarray(bias), mats, cfg, params


# 256 tokens x 4 experts a token, 2 of 32 held: the prefix is 4 x 1,024 x
# 2 / 32 = 256 sorted pairs, whole row tiles of 32 as it stands
@pytest.mark.parametrize("held_pairs,whole", [(100, 0), (256, 0), (257, 1),
                                              (512, 1)],
                         ids=["under", "at_the_bound", "one_over",
                              "every_token_holds"])
def test_the_held_pairs_prefix_is_the_whole_length_bit_for_bit(
        held_pairs, whole, monkeypatch):
    """``held_expert_ffn`` walks a prefix of the sorted pairs while a call's
    held pairs fit it and every pair when they do not, inside one program:
    either way the whole-length program's sums bit for bit (no branch built:
    ``held_prefix_rows`` answering None), the reference's plain loop to
    1e-5, the same gradient, and ``moe_whole_path`` saying which ran."""
    h, router, bias, mats, cfg, params = _routed_by_hand(held_pairs)
    assert moe.held_prefix_rows(256 * 4, 2, 32) == 256

    def layer(h, *mats, route=True):
        return moe.held_expert_ffn(
            h, router, *(jnp.swapaxes(m, 1, 2) for m in mats),
            held_experts=cfg["held_experts"], top_k=4, scoring="sigmoid",
            router_bias=bias, norm_topk_prob=True, count_route=route)

    def loss(h, *mats):
        return (layer(h, *mats, route=False)[0] ** 2).sum()

    branch = jax.make_jaxpr(layer)(h, *mats)
    assert str(branch).count(" cond[") == 1
    got, (pairs, _, route) = jax.jit(layer)(h, *mats)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(h, *mats)
    assert int(pairs) == held_pairs and route.tolist() == [whole]
    want = ref.routed_part(params, "l.", cfg, h, "float32")
    assert float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(got - want).max()) < 1e-5

    # traced anew (other function objects: jax keeps a function's trace)
    monkeypatch.setattr(moe, "held_prefix_rows", lambda *a: None)
    assert " cond[" not in str(jax.make_jaxpr(lambda *a: layer(*a))(h, *mats))
    plain, (_, _, always) = jax.jit(lambda *a: layer(*a))(h, *mats)
    assert always.tolist() == [1]
    assert np.array_equal(np.asarray(got), np.asarray(plain))
    whole_grads = jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1, 2, 3)))
    for g, p in zip(grads, whole_grads(h, *mats)):
        assert float(jnp.abs(p).max()) > 0
        assert np.allclose(np.asarray(g), np.asarray(p), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("held,pairs_of", [
    (list(range(32)), 1024),        # every expert held (SmallThinker)
    ([3, 11, 20, 21, 22, 23, 30, 31], 1024),   # a quarter: 4 x the share is all
    ([3, 11], 32)],                 # few pairs: no prefix of whole tiles is shorter
    ids=["all_held", "a_quarter_held", "one_row_tile"])
def test_where_no_prefix_is_shorter_no_branch_is_built(held, pairs_of):
    """The program is then the whole-length one, operation for operation:
    no ``cond`` in what is traced, and the same text as with the rule
    answering None (SmallThinker's serving programs against the parent's
    tree: ``tests/test_lowered_text_guard.py``)."""
    rng = np.random.default_rng(2)
    n, d, w, experts, k = pairs_of // 4, 16, 8, 32, 4
    h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((experts, d)).astype("f4") * 0.3)
    mats = [jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.2
            for s in ((len(held), d, w), (len(held), d, w), (len(held), w, d))]
    assert moe.held_prefix_rows(pairs_of, len(held), experts) is None
    count = obs.counter("moe_path_total")
    built = dict(path="sorted_ragged_dot", reason="the backend is not a TPU",
                 route="whole")
    before = count.value(**built)
    traced = jax.make_jaxpr(lambda h: moe.held_expert_ffn(
        h, router, *mats, held_experts=held, top_k=k))(h)
    assert count.value(**built) == before + 1
    assert " cond[" not in str(traced)
    y, (pairs, load) = moe.held_expert_ffn(h, router, *mats,
                                           held_experts=held, top_k=k)
    assert 0 < int(load) <= int(pairs) <= pairs_of and bool(jnp.isfinite(y).all())


def test_a_long_chunks_queries_in_quarters_are_the_plain_masked_attention():
    """4,096 positions take the path that walks the queries a quarter at a
    time against the keys up to that quarter's end: the same context as one
    plain masked softmax over all keys, under a mask that is not only
    causal (every third key hidden)."""
    rng = np.random.default_rng(8)
    t, heads, nope, rope, vd, ql, kl = 4096, 2, 8, 4, 8, 6, 8
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    c_q, c_kv, k_rope = arr(1, t, ql), arr(1, t, kl), arr(1, t, rope)
    w_qb, w_kvb = arr(heads * (nope + rope), ql), arr(heads * (nope + vd), kl)
    gate = jnp.asarray(rng.uniform(0.2, 1.0, (1, t, heads)), jnp.float32)
    at = np.arange(t)
    seen = jnp.asarray(((at[None, :] <= at[:, None])
                        & ((at[None, :] % 3 > 0) | (at[None, :] == at[:, None])))[None])
    inv = (1.0, 0.1)
    got = attention._masked_chunk_attention(
        c_q, w_qb, c_kv, k_rope, w_kvb, heads, seen, None, inv, 0.3, gate,
        head_block=1)
    qn, qr = attention._queries_of(c_q, w_qb, heads, nope, None, inv)
    kv = jnp.einsum("bkl,hdl->bkhd", c_kv, w_kvb.reshape(heads, -1, kl))
    scores = (jnp.einsum("bthd,bkhd->bhtk", qn, kv[..., :nope])
              + jnp.einsum("bthr,bkr->bhtk", qr, k_rope)) * 0.3
    att = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhtk,bkhv->bthv", att, kv[..., nope:]) * gate[..., None]
    assert float(jnp.abs(got - want.reshape(1, t, -1)).max()) < 1e-4
