"""SmallThinker-21BA3B-Instruct at a small size on the CPU, float32, seeded
weights: the Gluon model against the plain reference (``benchmark/reference``)
on a whole sequence; prefill then decode through BOTH page groups of the
paged engine (ordinary key/value pools; a window of 5 over pages of 4, rows
ending and slots reused) against the reference's full forward; a window off
by one, positions on the wrong kind of layer and a router wired to the
experts' input each failing; the expert layer with every expert held against
the uncut reference layer, and four chips' shares adding up to it; and what
the window group holds at the published sizes."""
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import GenerationEngine
from mxnet_tpu.inference.pages import _WindowPages
from mxnet_tpu.ops import flash_attention
from mxnet_tpu.ops import pallas_paged_attention as ppa
from mxnet_tpu.parallel import moe

from benchmark.reference import smallthinker as ref
from benchmark.systems import smallthinker as adaptor
from benchmark.weights import make_weights

SEED = 4294967311  # past 32 bits, as the driver's are
PERIOD = [0, 1, 1, 1]
# float32 on the CPU: the engine and the reference differ by rounding of the
# last place only; the toy cell's limits (tests/benchmark) are these
TOY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-4}


def tiny_config(**over):
    """The published configuration's keys at toy sizes."""
    cfg = dict(
        model="smallthinker", hidden_size=64, num_attention_heads=6,
        num_key_value_heads=2, head_dim=16, rope_theta=1500000,
        sliding_window_size=5, rms_norm_eps=1e-6, n_layer=4,
        moe_ffn_hidden_size=24, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        rope_layout=PERIOD, sliding_window_layout=PERIOD, n_vocab=200,
        # widths 40 times under the published ones: at sqrt(40) times their
        # 0.02 the scores spread as the published widths' do, so a position
        # or a window moves what it would there
        initializer_range=0.1, max_position_embeddings=256,
        held_experts=list(range(8)), precision={"weights": "float32"},
        engine={"batch_size": 3, "paged": True, "page_size": 4,
                "num_pages": {"all": 64, "window": 12}, "max_length": 64,
                "cache_dtype": "float32", "prefill_buckets": [8, 16, 32]})
    cfg.update(over)
    return cfg


def reference_logits(cfg, weights, tokens, first, count, precision="float32"):
    return ref.next_token_logits(weights, cfg, list(tokens), first, count,
                                 precision=precision, pad_to=64, out_pad=32)


def gaps(cfg, weights, requests, precision="float32"):
    """``benchmark.serve.logit_gaps``' two numbers over finished requests:
    how far below the reference's best logit the served tokens lie."""
    worst, total, count = 0.0, 0.0, 0
    for prompt, out in requests:
        want = reference_logits(cfg, weights, prompt + out[:-1],
                                len(prompt) - 1, len(out), precision)
        gap = want.max(-1) - want[np.arange(len(out)), np.asarray(out)]
        worst, total, count = max(worst, gap.max()), total + gap.sum(), \
            count + len(out)
    return {"widest_gap": float(worst), "mean_gap": float(total / count)}


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, make_weights(ref.param_specs(cfg), SEED)


@pytest.fixture(scope="module")
def served(model):
    """Six requests through three slots (rows end, slots are reused, every
    row decodes far past the window): per request (prompt, output), every
    decode step's logits by request, and the engine."""
    cfg, weights = model
    engine, batcher = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(1)
    reqs = [batcher.submit(rng.integers(1, cfg["n_vocab"], n).tolist(),
                           max_new_tokens=m)
            for n, m in ((5, 12), (13, 20), (30, 9), (7, 25), (16, 16), (3, 30))]
    w = engine._groups["window"]
    while batcher.pending or batcher.active:
        batcher.step()
        assert max(map(len, w.rows)) <= w.columns - 1
    assert all(r.finish_reason == "length" for r in reqs)
    return [(list(r.prompt), list(r.output)) for r in reqs], engine


def test_the_model_agrees_with_the_reference_on_a_whole_sequence(model):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    tokens = np.random.default_rng(0).integers(1, 200, 40)
    got = net(mx.nd.array(tokens[None], dtype="int32"))._data[0]
    want = reference_logits(cfg, weights, tokens, 0, 40)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_a_long_chunks_expert_layer_in_token_blocks_is_the_whole_chunks(
        model, monkeypatch):
    """The expert layer walked in blocks of tokens (what a prefill of more
    than 4,096 tokens does) gives the whole chunk's logits: the router's
    input is cut with the experts'."""
    from mxnet_tpu.models import dots3_note

    cfg, weights = model
    tokens = mx.nd.array(np.random.default_rng(3).integers(1, 200, (1, 32)),
                         dtype="int32")
    whole = adaptor.build_net(cfg, weights)(tokens)._data
    monkeypatch.setattr(dots3_note, "_FFN_TOKENS", 8)
    blocks = adaptor.build_net(cfg, weights)(tokens)._data
    assert float(jnp.abs(blocks - whole).max()) < 2e-5


def test_prefill_then_decode_through_both_page_groups(model, served):
    """What the engine served through the ``all`` and the ``window`` group
    is the reference's full forward over prompt + output, to float32
    rounding: every token its argmax, the toy cell's limits kept."""
    cfg, weights = model
    requests, engine = served
    for prompt, out in requests:
        want = reference_logits(cfg, weights, prompt + out[:-1],
                                len(prompt) - 1, len(out))
        assert out == want.argmax(-1).tolist()
    got = gaps(cfg, weights, requests)
    assert all(got[k] <= TOY_LIMITS[k] for k in TOY_LIMITS), got
    assert engine.layer_groups == ("all", "window", "window", "window")
    assert engine._groups["window"].freed_total > 10
    assert "full layers: xla_gather (the backend is not a TPU)" in engine.read_path
    assert "window layers: xla_gather" in engine.read_path
    counts = obs.step_records("decode_step")[-1].counts
    assert len(counts["attn_read_full"]) == 1
    assert len(counts["attn_read_window"]) == 3
    assert len(counts["moe_experts_hit"]) == len(counts["moe_pairs_held"]) == 4
    assert set(counts["moe_pairs_held"]) == {3 * 3}   # every expert is held


@pytest.fixture(scope="module")
def decoded(model):
    """Three rows prefilled and decoded twelve steps: per row (prompt,
    tokens, the logits each token was the argmax of), and the engine."""
    cfg, weights = model
    engine, _ = adaptor.build_serve(cfg, weights)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 200, n).tolist() for n in (13, 30, 7)]
    rows = []
    for slot, prompt in enumerate(prompts):
        tok = engine.prefill(prompt, slot)
        rows.append((prompt, [tok], [np.asarray(engine._last_logits)]))
    for _ in range(12):
        tok, _, logits = engine.decode_step()
        for slot, (_, out, lg) in enumerate(rows):
            out.append(int(tok[slot]))
            lg.append(np.asarray(logits[slot]))
    return rows, engine


def _furthest(cfg, weights, rows, precision="float32"):
    """The largest distance of a served logit from the reference's."""
    return max(np.abs(np.stack(logits) - reference_logits(
        cfg, weights, prompt + out[:-1], len(prompt) - 1, len(out),
        precision)).max() for prompt, out, logits in rows)


def test_a_decode_steps_logits_are_the_references_to_float32_rounding(model,
                                                                      decoded):
    cfg, weights = model
    rows, engine = decoded
    assert _furthest(cfg, weights, rows) < 5e-5
    counts = obs.step_records("decode_step")[-1].counts
    held = int(engine.positions.sum())        # the step read one fewer a row
    assert counts["attn_read_full"] == [held]
    assert counts["attn_read_window"] == [3 * 5] * 3


CONTROLS = {
    "window_minus_1": "a window of 4 where the model's is 5",
    "no_window": "window layers that read everything",
    "rope_everywhere": "positions on the full layer too",
    "rope_nowhere": "none on the window layers",
    "router_reads_u": "the router wired to the experts' input",
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_model_wired_otherwise_is_not_what_was_served(model, decoded,
                                                        control):
    """A reference of other mathematics lies twenty times further from the
    served logits than float32 rounding: a window off by one, positions on
    the wrong kind of layer, a router that reads after attention."""
    cfg, weights = model
    assert _furthest(cfg, weights, decoded[0], control) > 20 * 5e-5


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_model_wired_otherwise_fails_the_toy_cells_limits(model, served,
                                                            control):
    """The served tokens against a reference of other mathematics, by the
    comparison that decides a cell's ``correct``: one of the limits the
    program passes fails, ten times over."""
    cfg, weights = model
    requests, _ = served
    got = gaps(cfg, weights, requests, control)
    assert any(got[k] > 10 * TOY_LIMITS[k] for k in TOY_LIMITS), got


def test_the_prefill_chunk_through_the_flash_kernel_is_the_xla_paths(model,
                                                                     monkeypatch):
    """On a chip a prefill chunk's attention is the flash forward kernel
    (``gqa_prefill``), under a band mask where the chunk is longer than the
    window: put into a CPU program (interpreted), the logits are the XLA
    path's to float32 rounding."""
    cfg, weights = model
    cfg = dict(cfg, head_dim=128, num_attention_heads=4, hidden_size=64,
               sliding_window_size=40,
               engine=dict(cfg["engine"], prefill_buckets=[128],
                           max_length=160, num_pages={"all": 64, "window": 48}))
    weights = make_weights(ref.param_specs(cfg), SEED)
    prompt = np.random.default_rng(3).integers(1, 200, 100).tolist()
    paths = obs.counter("paged_read_path_total")
    before = paths.value(path="gqa_chunk_kernel", reason="")
    plain, _ = adaptor.build_serve(cfg, weights)
    plain.prefill(prompt, 0)
    assert paths.value(path="gqa_chunk_kernel", reason="") == before
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "_resolve_interpret", lambda i: True)
    kernel, _ = adaptor.build_serve(cfg, weights)
    kernel.prefill(prompt, 0)
    assert paths.value(path="gqa_chunk_kernel", reason="") == before + 4
    assert np.abs(np.asarray(kernel._last_logits)
                  - np.asarray(plain._last_logits)).max() < 5e-5
    want = reference_logits(dict(cfg), weights, prompt, len(prompt) - 1, 1)
    assert np.abs(np.asarray(kernel._last_logits) - want[0]).max() < 5e-5


def test_a_decode_step_through_the_kernel_is_the_xla_paths(model, monkeypatch):
    """The decode program with ``paged_gqa_decode`` in it (interpreted; heads
    of a whole lane tile) serves the gather path's logits, through the ring
    and past the window."""
    cfg, weights = model
    cfg = dict(cfg, head_dim=128, num_attention_heads=4,
               engine=dict(cfg["engine"], page_size=8,
                           num_pages={"all": 32, "window": 12}))
    weights = make_weights(ref.param_specs(cfg), SEED)
    prompts = [np.random.default_rng(4).integers(1, 200, n).tolist()
               for n in (13, 30, 7)]

    def decoded(engine):
        for slot, prompt in enumerate(prompts):
            engine.prefill(prompt, slot)
        return [np.asarray(engine.decode_step()[2]) for _ in range(10)]

    plain = decoded(adaptor.build_serve(cfg, weights)[0])
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)
    monkeypatch.setattr(ppa, "_resolve_interpret", lambda i: True)
    engine, _ = adaptor.build_serve(cfg, weights)
    assert engine.read_path == "full layers: gqa_kernel; window layers: gqa_kernel"
    for got, want in zip(decoded(engine), plain):
        assert np.abs(got - want).max() < 5e-5


# -- the expert layer --------------------------------------------------------
def _layer_operands(cfg, weights, n=40, seed=5):
    p = "layer1."
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(n, cfg["hidden_size"])), jnp.float32)
    h = jnp.asarray(rng.normal(size=(n, cfg["hidden_size"])), jnp.float32)
    return p, u, h


def _held_part(cfg, weights, p, u, h, held):
    """What ``held`` experts add, by the program's layer."""
    mats = [jnp.swapaxes(weights[p + f"experts.{k}.w"][jnp.asarray(held)], 1, 2)
            for k in ("gate", "up", "down")]
    return moe.held_expert_ffn(
        u, weights[p + "router.w"], *mats, held_experts=held,
        top_k=cfg["moe_num_active_primary_experts"],
        norm_topk_prob=cfg["norm_topk_prob"], router_h=h, activation="relu",
        count_hit=True)


def test_every_expert_held_is_the_uncut_reference_layer(model):
    cfg, weights = model
    p, u, h = _layer_operands(cfg, weights)
    got, (pairs, load, hit) = _held_part(cfg, weights, p, u, h, range(8))
    want = ref.routed_part(weights, p, cfg, u, h, "float32")
    assert float(jnp.abs(got - want).max()) < 2e-6
    assert int(pairs) == 40 * 3 and int(hit) == 8 and int(load) >= 15
    # the router reads ``h``: routed by ``u`` the layer gives something else
    wrong = ref.routed_part(weights, p, cfg, u, u, "float32")
    assert float(jnp.abs(got - wrong).max()) > 1e-3
    # and the gate is a ReLU: SwiGLU's output differs
    mats = [jnp.swapaxes(weights[p + f"experts.{k}.w"], 1, 2)
            for k in ("gate", "up", "down")]
    silu, _ = moe.held_expert_ffn(
        u, weights[p + "router.w"], *mats, held_experts=range(8), top_k=3,
        norm_topk_prob=True, router_h=h)
    assert float(jnp.abs(silu - want).max()) > 1e-4
    with pytest.raises(ValueError, match="unknown activation"):
        moe.held_expert_ffn(u, weights[p + "router.w"], *mats,
                            held_experts=range(8), top_k=3, activation="gelu")


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer(model):
    """The guide's share test: 8 experts over four chips of 2; nothing is
    computed on every chip alike (no shared expert), so the shares' sum is
    the uncut reference layer, and their pairs are all of them."""
    cfg, weights = model
    p, u, h = _layer_operands(cfg, weights)
    want = ref.routed_part(weights, p, dict(cfg, held_experts=None), u, h,
                           "float32")
    total, pairs = 0.0, 0
    for chip in range(4):
        held = [2 * chip, 2 * chip + 1]
        part, (n, _, _) = _held_part(cfg, weights, p, u, h, held)
        share = ref.routed_part(
            {k: (v[jnp.asarray(held)] if ".experts." in k else v)
             for k, v in weights.items()}, p, dict(cfg, held_experts=held),
            u, h, "float32")
        assert float(jnp.abs(part - share).max()) < 2e-6
        total, pairs = total + part, pairs + int(n)
    assert float(jnp.abs(total - want).max()) < 2e-6 and pairs == 40 * 3


# -- the window group at the published sizes ----------------------------------
def test_a_window_of_4096_over_pages_of_16_never_holds_more_than_258_pages():
    w = _WindowPages(num_pages=600, batch_size=2, page_size=16, window=4096)
    assert w.columns == 259
    for slot, length in ((0, 8192), (1, 700)):
        assert w.needed(length) <= 258
        row = w.admit(slot, length)
        assert len(w.rows[slot]) == w.needed(length) <= 258
        assert np.count_nonzero(row) == len(w.rows[slot])
        for position in range(length, length + 600):
            done = [row != slot for row in range(2)]
            assert not w.grow(done, [position] * 2)[3]   # no row left dry
            held = w.rows[slot]
            assert len(held) <= 258
            # every position the row's next softmax reads lies in a held page
            assert min(held) == w.low_page(position)
            assert max(held) == position // 16
    assert w.freed_total > 30 and w.in_use == sum(map(len, w.rows))


def test_admission_counts_both_groups(model):
    cfg, weights = model
    cfg = dict(cfg, engine=dict(cfg["engine"],
                                num_pages={"all": 64, "window": 3}))
    engine, batcher = adaptor.build_serve(cfg, weights)
    long_prompt = list(range(1, 31))
    assert engine.covers(long_prompt)
    engine.prefill(long_prompt, 0)          # holds 2 of the window's 3 pages
    assert not engine.covers(long_prompt) and engine.free_pages > 8
    with pytest.raises(ValueError, match="prefix_cache= and draft_net= are "
                                         "refused"):
        GenerationEngine(adaptor.build_net(cfg, weights), prefix_cache=True,
                         **cfg["engine"])
