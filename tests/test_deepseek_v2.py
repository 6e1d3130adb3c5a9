"""DeepSeek-V2 at a small size on the CPU, float32, seeded weights: the
Gluon model against the plain reference (``benchmark/reference``), prefill
then decode through the paged latent cache against the reference's full
forward, latent attention's two forms against each other, group-limited
routing against the reference's, the shares of the expert layer adding up
to the uncut layer, and the engine's per-layer-state contract with two
kinds of model in one process."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import GenerationEngine
from mxnet_tpu.models import deepseek_v2, gpt2
from mxnet_tpu.ops import attention
from mxnet_tpu.parallel import moe

from benchmark.reference import deepseek_v2 as ref
from benchmark.systems import deepseek_v2 as adaptor
from benchmark.weights import make_weights

SEED = 4294967311  # past 32 bits, as the driver's are


def tiny_config(**over):
    """The published configuration's keys at toy sizes."""
    cfg = dict(
        hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24, kv_lora_rank=32,
        rms_norm_eps=1e-6, rope_theta=10000, n_layer=3,
        first_k_dense_replace=1, intermediate_size=96,
        moe_intermediate_size=24, n_shared_experts=2, n_routed_experts=16,
        n_group=4, topk_group=2, num_experts_per_tok=3, norm_topk_prob=False,
        routed_scaling_factor=16, n_vocab=200, initializer_range=0.02,
        max_position_embeddings=256,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 0.707, "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        held_experts=[0, 1, 5, 9], precision={"weights": "float32"},
        engine={"batch_size": 4, "paged": True, "page_size": 8,
                "num_pages": 64, "max_length": 128, "cache_dtype": "float32"})
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    weights = make_weights(ref.param_specs(cfg), SEED)
    return cfg, weights, adaptor.build_net(cfg, weights)


def reference_logits(cfg, weights, tokens, first, count):
    return ref.next_token_logits(weights, cfg, list(tokens), first, count,
                                 pad_to=8, out_pad=8)


def test_the_model_matches_the_reference_on_a_full_forward(model):
    cfg, weights, net = model
    tokens = np.random.default_rng(1).integers(1, cfg["n_vocab"], 45)
    got = net(mx.nd.array(tokens[None, :], dtype="int32"))._data[0]
    want = reference_logits(cfg, weights, tokens, 0, len(tokens))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_a_cached_forward_returns_the_expert_layers_counts_third(model):
    cfg, _, net = model
    pool = net.init_paged_cache(4, 8)
    _, new_cache, counts = net(
        mx.nd.array(np.arange(1, 13)[None, :], dtype="int32"),
        cache=[tuple(mx.nd.NDArray(b) for b in layer) for layer in pool],
        start_pos=mx.nd.array([0], dtype="int32"),
        page_table=mx.nd.array([[1, 2, 3, 4]], dtype="int32"))
    assert len(new_cache) == 3 and set(counts) == {
        "moe_pairs_held", "moe_max_load", "moe_whole_path"}
    assert counts["moe_pairs_held"].shape == (2,)  # an entry per expert layer
    # 4 of 16 held: four times their share is every pair, so no prefix of
    # the sorted pairs is built and each layer's one call walks them whole
    assert counts["moe_whole_path"].tolist() == [[1], [1]]
    # 12 tokens x 3 experts each, 4 of 16 held: some pairs, never all of them
    assert 0 < int(counts["moe_pairs_held"].max()) < 36
    assert int(counts["moe_max_load"].max()) <= int(counts["moe_pairs_held"].max())


def test_yarn_frequencies_are_the_references_and_blend_at_the_published_dims():
    got = attention.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    want = ref.yarn_inv_freq({"qk_rope_head_dim": 64, "rope_theta": 10000,
                              "rope_scaling": tiny_config()["rope_scaling"]})
    np.testing.assert_allclose(got, want, rtol=1e-12)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:10], plain[:10])          # fast: untouched
    np.testing.assert_allclose(got[23:], plain[23:] / 40.0)   # slow: by 40
    np.testing.assert_allclose(attention.yarn_inv_freq(64, 10000.0, 1.0), plain)


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(model):
    cfg, weights, net = model
    engine = GenerationEngine(net, **cfg["engine"])
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg["n_vocab"], n).tolist() for n in (5, 19, 33)]
    outs = [[engine.prefill(p, slot=i)] for i, p in enumerate(prompts)]
    first = [np.asarray(engine._prefill_logits[i]) for i in range(3)]
    logits = [[] for _ in prompts]
    for _ in range(9):
        tok, _, step_logits = engine.decode_step()
        for i in range(3):
            outs[i].append(int(tok[i]))
            logits[i].append(np.asarray(step_logits[i]))
    for i, (p, out) in enumerate(zip(prompts, outs)):
        want = reference_logits(cfg, weights, p + out[:-1], len(p) - 1, len(out))
        got = np.stack([first[i]] + logits[i])
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert out == want.argmax(axis=-1).tolist()
    # one pool a layer, the 40 values a token in one whole lane tile, nothing
    # decompressed in it; the gauge is what is allocated
    assert [tuple(b.shape for b in layer) for layer in engine.pools] == \
        [((65, 8, 128),)] * 3
    assert engine.cache_bytes_per_token == 128 * 4 * 3
    assert obs.gauge("gen_cache_bytes_per_token").value() == 1536.0
    assert "latent" in engine.read_path
    record = obs.step_records("decode_step")[-1]
    assert set(record.counts) == {"moe_pairs_held", "moe_max_load",
                                  "moe_whole_path"}
    assert len(record.counts["moe_pairs_held"]) == 2
    assert [m for m, _ in record.marks] == ["mx.gen.decode.pages",
                                            "mx.gen.decode.dispatch",
                                            "mx.gen.decode.read"]


def latent_inputs(b, t, heads=4, nope=16, rope=8, vd=16, kl=32, seed=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (b, t, heads, nope)),
            jax.random.normal(ks[1], (b, t, heads, rope)),
            jax.random.normal(ks[2], (b, t, kl)),
            jax.random.normal(ks[3], (b, t, rope)),
            0.2 * jax.random.normal(ks[4], (heads * (nope + vd), kl)))


def in_form(monkeypatch, form, *args, **kw):
    """The operator made to take ``form`` whatever the shapes."""
    monkeypatch.setattr(attention, "mla_form", lambda *dims: form)
    return attention.latent_attention(*args, **kw)


@pytest.mark.parametrize("paged", [False, True])
def test_absorbed_and_decompressed_are_the_same_mathematics(paged, monkeypatch):
    args = latent_inputs(2, 12)
    kw = {}
    if paged:  # rows at different positions of a pool that holds a history
        pool = jax.random.normal(jax.random.key(9), (9, 8, 40))
        kw = dict(cache=(pool,), position=jnp.array([5, 17], jnp.int32),
                  page_table=jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32))
    a = in_form(monkeypatch, "absorbed", *args, scale=0.2, **kw)
    d = in_form(monkeypatch, "decompressed", *args, scale=0.2, **kw)
    if paged:
        np.testing.assert_array_equal(a[1], d[1])  # the same pool written
        assert a[1].shape == (9, 8, 40)
        a, d = a[0], d[0]
    assert a.shape == (2, 12, 4 * 16)
    np.testing.assert_allclose(a, d, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("form", ["absorbed", "decompressed"])
def test_a_chunk_that_opens_its_rows_reads_itself_and_equals_the_unpaged_form(
        form, monkeypatch):
    """Rows at position 0 take the branch that reads the chunk's own latents
    (no gather over the page table's width); rows further on take the pool.
    Both are what the unpaged operator gives on the whole sequence."""
    args = latent_inputs(1, 24)
    monkeypatch.setattr(attention, "mla_form", lambda *dims: form)
    whole = attention.latent_attention(*args, scale=0.2)
    pool = jnp.zeros((5, 8, 40))
    table = jnp.array([[1, 2, 3, 4]], jnp.int32)
    head = tuple(a[:, :16] for a in args[:4]) + args[4:]
    tail = tuple(a[:, 16:] for a in args[:4]) + args[4:]
    first, pool = attention.latent_attention(
        *head, scale=0.2, cache=(pool,),
        position=jnp.zeros((1,), jnp.int32), page_table=table)
    second, pool = attention.latent_attention(
        *tail, scale=0.2, cache=(pool,),
        position=jnp.full((1,), 16, jnp.int32), page_table=table)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(pool[1:4].reshape(24, 40),
                               jnp.concatenate([args[2], args[3]], -1)[0])


def test_the_form_follows_the_operations_each_needs():
    dims = dict(nope=128, rope=64, vd=128, kl=512)
    assert attention.mla_form(1, **dims) == "absorbed"       # decode
    assert attention.mla_form(128, **dims) == "absorbed"
    assert attention.mla_form(256, **dims) == "decompressed"  # a long prefill
    before = obs.counter("mla_path_total").value(form="absorbed", read="none")
    attention.latent_attention(*latent_inputs(1, 2), scale=1.0)
    assert obs.counter("mla_path_total").value(
        form="absorbed", read="none") == before + 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_top_k_is_the_references_routing(seed):
    cfg = tiny_config()
    h = jax.random.normal(jax.random.key(seed), (37, cfg["hidden_size"]))
    router = jax.random.normal(jax.random.key(seed + 10), (16, cfg["hidden_size"]))
    want_w, want_ids = ref.route(cfg, h, router)
    probs = jax.nn.softmax(jnp.einsum("nd,ed->ne", h, router,
                                      precision="highest"), axis=-1)
    w, ids = moe.group_limited_topk(probs, 4, 2, 3)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(w * 16, want_w, rtol=1e-6)
    # every chosen expert lies in one of the token's two best groups
    best = np.argsort(-np.asarray(probs).reshape(37, 4, 4).max(-1), axis=1)[:, :2]
    assert all(set(np.asarray(ids[i]) // 4) <= set(best[i]) for i in range(37))


def test_the_shares_and_the_shared_experts_once_add_up_to_the_uncut_layer():
    """The share test (model-configs guide, section 4): 16 experts over 4
    chips, 4 each; every share's routed part, plus what every chip computes
    alike (the shared experts) counted once, is the whole layer."""
    cfg = tiny_config(held_experts=None, n_layer=2)
    weights = make_weights(ref.param_specs(cfg), SEED)
    p = "layer1."
    h = jax.random.normal(jax.random.key(5), (29, cfg["hidden_size"]))
    whole = ref.routed_part(weights, p, cfg, h, "float32") \
        + ref.shared_part(weights, p, h, "float32")
    total, pairs = ref.shared_part(weights, p, h, "float32"), 0
    for share in range(4):
        held = list(range(share, 16, 4))  # a share need not be contiguous
        part, (n, load) = moe.held_expert_ffn(
            h, weights[p + "router.w"],
            *(jnp.swapaxes(weights[p + f"experts.{k}.w"][jnp.array(held)], 1, 2)
              for k in ("gate", "up", "down")),
            held_experts=held, n_group=4, topk_group=2, top_k=3, scale=16.0)
        # each share is the reference's share, given the same held ids
        sliced = {k: (v[jnp.array(held)] if ".experts." in k else v)
                  for k, v in weights.items()}
        np.testing.assert_allclose(
            part, ref.routed_part(sliced, p, dict(cfg, held_experts=held), h,
                                  "float32"), atol=1e-5)
        total, pairs = total + part, pairs + int(n)
        assert 0 < int(load) <= int(n)
    assert pairs == 29 * 3  # no pair dropped, none computed twice
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_no_pair_is_dropped_whatever_the_load():
    """All tokens alike: every pair goes to the same experts, far over any
    capacity a Switch layer would set; each is computed."""
    cfg = tiny_config()
    weights = make_weights(ref.param_specs(cfg), SEED)
    p = "layer1."
    h = jnp.tile(jax.random.normal(jax.random.key(6), (1, 64)), (40, 1))
    _, ids = ref.route(cfg, h, weights[p + "router.w"])
    held = [int(e) for e in np.asarray(ids[0])]
    sliced = {k: (v[:3] if ".experts." in k else v) for k, v in weights.items()}
    part, (n, load) = moe.held_expert_ffn(
        h, weights[p + "router.w"],
        *(jnp.swapaxes(sliced[p + f"experts.{k}.w"], 1, 2)
          for k in ("gate", "up", "down")),
        held_experts=held, n_group=4, topk_group=2, top_k=3, scale=16.0)
    assert (int(n), int(load)) == (120, 40)
    np.testing.assert_allclose(
        part, ref.routed_part(sliced, p, dict(cfg, held_experts=held), h,
                              "float32"), atol=1e-5)


def test_two_kinds_of_per_layer_state_in_one_process(model):
    """The engine reads nothing from a pool's shape but that axis 0 is the
    page: GPT-2's (k_pool, v_pool) and DeepSeek-V2's one latent pool are
    served, forked copy-on-write and audited by the same code."""
    cfg, _, latent_net = model
    kv_net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, units=32, num_heads=2,
                           vocab_size=100, max_length=64)
    kv_net.initialize()
    kv_net(mx.nd.array(np.ones((1, 4)), dtype="int32"))
    engines = {
        "kv": GenerationEngine(kv_net, batch_size=2, paged=True, page_size=8,
                               num_pages=16, max_length=64),
        "latent": GenerationEngine(latent_net, batch_size=2, paged=True,
                                   page_size=8, num_pages=16, max_length=64)}
    assert [len(e.pools[0]) for e in engines.values()] == [2, 1]
    assert engines["kv"].pools[0][0].shape == (17, 8, 32)
    assert engines["latent"].pools[0][0].shape == (17, 8, 128)
    assert engines["kv"].cache_bytes_per_token == 2 * 2 * 32 * 4
    assert engines["kv"]._last_vocab() == 100
    assert engines["latent"]._last_vocab() == cfg["n_vocab"]
    for name, engine in engines.items():
        prompt = list(range(3, 14))
        engine.prefill(prompt, slot=0)
        engine.fork_slot(0, 1)           # shares both pages
        shared = engine.pages_in_use
        a, b = [], []
        for _ in range(8):               # crosses into the second page: copy
            tok, _, _ = engine.decode_step()
            a.append(int(tok[0]))
            b.append(int(tok[1]))
        assert a == b, name              # a fork decodes what its source does
        assert engine.pages_in_use > shared
        assert obs.counter("gen_cow_copies_total").value() >= 1
        assert "gather" in engine.read_path or "kernel" in engine.read_path
        audit = engine.audit(compile=False)
        assert len(audit.carry_indices) == 1 + sum(len(l) for l in engine.pools)
    assert obs.step_records("decode_step")[-1].counts is not None
    # a model with no counts leaves its decode program's outputs as they were
    lowered = engines["kv"].lower_decode()
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == \
        1 + 2 * len(engines["kv"].pools) + 3


def test_the_model_refuses_a_size_it_does_not_know():
    with pytest.raises(TypeError, match="unknown sizes"):
        deepseek_v2.get_deepseek_v2("deepseek_v2_tiny", heads=3)
    net = deepseek_v2.get_deepseek_v2("deepseek_v2_tiny", held_experts=[2, 3])
    assert net.cache_width == 40 and net.logits_width() == 200
    names = list(net.collect_params())
    assert sum("experts_gate" in n for n in names) == 2  # one stacked leaf a layer
    assert net.collect_params()[next(n for n in names if "experts_gate" in n)] \
        .shape == (2, 64, 24)


# -- decoding ahead -----------------------------------------------------------
def tiny_gpt2():
    net = gpt2.get_gpt2("gpt2_tiny", dropout=0.0, units=32, num_heads=2,
                        vocab_size=100, max_length=64)
    net.initialize()
    net(mx.nd.array(np.ones((1, 4)), dtype="int32"))
    return net, dict(batch_size=3, paged=True, page_size=8, num_pages=32,
                     max_length=64)


def served(net, settings, requests, ahead):
    """Every request's tokens through a batcher whose engine may, or may
    not, decode ahead; and how many steps were used and dropped."""
    from mxnet_tpu.inference import ContinuousBatcher

    engine = GenerationEngine(net, **settings)
    if not ahead:
        engine._may_decode_ahead = lambda: False
    batcher = ContinuousBatcher(engine)
    count = obs.counter("gen_decode_ahead_total")
    before = [count.value(outcome=o) for o in ("used", "dropped")]
    reqs = [batcher.submit(p, max_new_tokens=n) for p, n in requests]
    batcher.run_until_idle()
    assert engine._decode_jit._cache_size() == 1  # one program, either way
    return ([list(r.output) for r in reqs],
            [count.value(outcome=o) - b
             for o, b in zip(("used", "dropped"), before)])


@pytest.mark.parametrize("kind", ["kv", "latent"])
def test_decoding_ahead_serves_the_same_tokens(kind, model):
    """More requests than slots, answers of very different lengths: steps
    in which no row ends are dispatched ahead, the others are not, and every
    request gets the tokens it gets without."""
    if kind == "kv":
        net, settings = tiny_gpt2()
    else:
        net, settings = model[2], dict(model[0]["engine"], batch_size=3)
    rng = np.random.default_rng(8)
    requests = [(rng.integers(1, 90, int(n)).tolist(), int(m)) for n, m in
                zip(rng.integers(3, 20, 7), [12, 3, 17, 9, 2, 14, 6])]
    plain, (used, dropped) = served(net, settings, requests, ahead=False)
    assert (used, dropped) == (0, 0)
    ahead, (used, dropped) = served(net, settings, requests, ahead=True)
    assert ahead == plain
    assert used > 5 and dropped == 0  # the batcher asks only when it is safe


def test_a_step_dispatched_ahead_is_dropped_when_a_row_changes_hands(model):
    """The caller said the rows would stay and then released one, prefilled
    another prompt into its slot and forked a third: the step ahead is
    dropped, run again, and every row decodes what it decodes without."""
    cfg, _, net = model

    def run(ahead):
        engine = GenerationEngine(net, **dict(cfg["engine"], batch_size=3))
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, 90, n).tolist() for n in (9, 14, 9)]
        outs = {0: [engine.prefill(prompts[0], slot=0)],
                1: [engine.prefill(prompts[1], slot=1)]}
        for _ in range(4):
            tok, _, _ = engine.decode_step(ahead=ahead)
            for slot in outs:
                outs[slot].append(int(tok[slot]))
        engine.release_slot(1)               # a step is in flight here
        # the same length as the released row's position would have been
        outs[1] = [engine.prefill(prompts[2], slot=1)]
        engine.fork_slot(0, 2)
        outs[2] = []
        for _ in range(5):
            tok, _, _ = engine.decode_step(ahead=ahead)
            for slot in outs:
                outs[slot].append(int(tok[slot]))
        return outs

    count = obs.counter("gen_decode_ahead_total")
    before = count.value(outcome="dropped"), count.value(outcome="used")
    want, got = run(False), run(True)
    assert got == want and got[2] == got[0][-5:]
    assert count.value(outcome="dropped") == before[0] + 1
    assert count.value(outcome="used") == before[1] + 3 + 4
    marks = [m for m, _ in obs.step_records("decode_step")[-2].marks]
    assert marks == ["mx.gen.decode.pages", "mx.gen.decode.ahead",
                     "mx.gen.decode.read"]


def test_an_engine_that_may_stop_a_row_itself_never_decodes_ahead(model):
    """With an EOS id (or sampling) the rows' next state is not known before
    the tokens are: ``ahead=True`` is then a plain step."""
    cfg, _, net = model
    engine = GenerationEngine(net, eos_id=7,
                              **dict(cfg["engine"], batch_size=2))
    engine.prefill([3, 4, 5], slot=0)
    engine.decode_step(ahead=True)
    assert engine._ahead is None and not engine._may_decode_ahead()
