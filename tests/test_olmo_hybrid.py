"""Olmo-Hybrid-7B at a small size on the CPU, float32, seeded weights: the
Gluon model against the plain reference (``benchmark/reference``) on a
whole sequence; prefill then decode through the full layers' page pools AND
the linear layers' slot state against the reference's full forward; the same
prompt in two buckets leaving the same state (padding writes nothing); a
slot used again after a longer tenant; rows ending while others decode;
steps dispatched ahead, used and dropped; forks, the prefix cache and
speculation refused by name; every control failing the toy cell's limits;
and the decode kernel (interpreted) inside an engine."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import GenerationEngine
from mxnet_tpu.ops import pallas_gdn

from benchmark.reference import olmo_hybrid as ref
from benchmark.systems import olmo_hybrid as adaptor
from benchmark.weights import make_weights

SEED = 4294967311  # past 32 bits, as the driver's are
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# float32 on the CPU: the engine and the reference differ by rounding of the
# last place only; the toy cell's limits (tests/benchmark) are these
TOY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-4}
CONTROLS = ("fp8", "state_bf16", "no_decay", "beta_le_1", "no_conv",
            "pad_writes_state")


def tiny_config(**over):
    """The published configuration's keys at toy sizes."""
    cfg = dict(
        model="olmo_hybrid", hidden_size=32, intermediate_size=48,
        num_attention_heads=2, num_key_value_heads=2, head_dim=16,
        rms_norm_eps=1e-6, n_layer=4, layer_types=PERIOD,
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=64,
        linear_conv_kernel_dim=4, n_vocab=200, max_position_embeddings=128,
        # widths a hundred times under the published ones: at five times
        # their 0.02 the logits spread as the published widths' do; decays
        # of 0.8-0.98 a position, so a state lives through a toy answer
        initializer_range=0.1,
        decay_init={"A_log_mean": 0.0, "A_log_std": 0.5, "dt_bias_mean": -3.0,
                    "dt_bias_std": 0.7, "a_proj_std": 0.02, "conv_std": 0.3},
        precision={"weights": "float32"},
        engine={"batch_size": 3, "paged": True, "page_size": 4,
                "num_pages": {"all": 64}, "max_length": 64,
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


def within(limits, got):
    return all(got[k] <= limits[k] for k in limits)


def prompts_of(rng, cfg, lengths):
    return [rng.integers(1, cfg["n_vocab"], n).tolist() for n in lengths]


def linear_states(engine):
    """The slot state of every linear layer, on the host."""
    return [tuple(np.asarray(b) for b in layer)
            for layer, g in zip(engine.pools, engine.layer_groups)
            if g == "slot"]


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, make_weights(ref.param_specs(cfg), SEED)


def build(cfg, weights, **engine):
    cfg = dict(cfg, engine=dict(cfg["engine"], **engine))
    return adaptor.build_serve(cfg, weights)


def test_the_model_is_the_reference_on_a_whole_sequence(model):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 40)
    got = net(mx.nd.array(tokens[None], dtype="int32")).asnumpy()[0]
    want = reference_logits(cfg, weights, tokens, 0, 40)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the decays sit where a state matters: most of a unit a position
    alphas = ref.decay_quantiles(weights, cfg, tokens)["all"]
    assert 0.5 < alphas[10] and alphas[50] < 0.999


def test_the_adaptor_hands_every_leaf_over_and_moves_the_decays(model):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    params = net.collect_params()
    a_log = next(p for n, p in params.items() if n.endswith("layer0_gdn_A_log"))
    np.testing.assert_allclose(
        a_log.data().asnumpy(),
        np.asarray(weights["layer0.gdn.A_log"]) + cfg["decay_init"]["A_log_mean"])
    dt = next(p for n, p in params.items() if n.endswith("layer2_gdn_dt_bias"))
    np.testing.assert_allclose(
        dt.data().asnumpy(),
        np.asarray(weights["layer2.gdn.dt_bias"]) + cfg["decay_init"]["dt_bias_mean"])
    assert len(params) == len(weights)


def test_prefill_then_decode_is_the_references_full_forward(model):
    """Through pools and state, slots of different lengths side by side."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    assert engine.read_path == (
        "full layers: xla_gather (the backend is not a TPU); "
        "linear layers: gdn_xla (the backend is not a TPU)")
    assert engine.layer_groups == ("slot", "slot", "slot", "all")
    # 3 linear layers x 3 slots x (8 x 2 x 64 state + 4 x 160 tail + 1) x 4 B
    assert engine.slot_state_bytes == 3 * 3 * (8 * 128 + 4 * 160 + 1) * 4
    # keys and values alone: one full layer, 2 x 2 heads x 16, float32
    assert engine.cache_bytes_per_token == 2 * 2 * 16 * 4
    assert obs.gauge("gen_slot_state_bytes").value() == engine.slot_state_bytes
    prompts = prompts_of(np.random.default_rng(1), cfg, (5, 13, 8))
    outs = [[engine.prefill(p, slot=i)] for i, p in enumerate(prompts)]
    step_logits = []
    for _ in range(19):
        tok, _, logits = engine.decode_step()
        step_logits.append(np.asarray(logits))
        for i, out in enumerate(outs):
            out.append(int(tok[i]))
    got = gaps(cfg, weights, list(zip(prompts, outs)))
    assert within(TOY_LIMITS, got), got
    # logits, not tokens: every decode step's against the reference's
    for i, (p, o) in enumerate(zip(prompts, outs)):
        want = reference_logits(cfg, weights, p + o[:-1], len(p), 19)
        np.testing.assert_allclose([s[i] for s in step_logits], want,
                                   atol=3e-5)


def test_the_same_prompt_in_two_buckets_leaves_the_same_state(model):
    """Padding writes nothing: the state and the convolution's tail behind
    a prompt of 7 are those of a bucket of 8 in a bucket of 32."""
    cfg, weights = model
    prompt = prompts_of(np.random.default_rng(2), cfg, (7,))[0]
    left = []
    for buckets in ([8], [32]):
        engine, _ = build(cfg, weights, prefill_buckets=buckets)
        tok = engine.prefill(prompt, slot=1)
        left.append((tok, linear_states(engine)))
    assert left[0][0] == left[1][0]
    for (s0, tail0, n0), (s1, tail1, n1) in zip(left[0][1], left[1][1]):
        # (the projections' products round differently at 8 rows and at 32)
        np.testing.assert_allclose(s0[1], s1[1], atol=1e-5)
        np.testing.assert_allclose(tail0[1], tail1[1], atol=1e-5)
        assert n0[1] == n1[1] == 7
        assert np.abs(s0[1]).max() > 0.01     # and it is a state
        assert not s0[0].any() and not s0[2].any()   # other slots untouched


def test_a_prompt_shorter_than_the_convolution_keeps_zeros_before_it(model):
    cfg, weights = model
    engine, _ = build(cfg, weights)
    prompt = prompts_of(np.random.default_rng(3), cfg, (2,))[0]
    out = engine.generate([prompt], max_new_tokens=10)[0]
    assert within(TOY_LIMITS, gaps(cfg, weights, [(prompt, out)]))
    tail = linear_states(engine)[0][1][0]
    assert tail.shape == (4, 2 * (8 + 8 + 64))


def test_a_slot_used_again_after_a_longer_tenant(model):
    """The next tenant sees nothing of the last: its prefill writes the
    slot's state from zero."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    long_, short = prompts_of(np.random.default_rng(4), cfg, (30, 6))
    engine.generate([long_], max_new_tokens=25)
    assert np.abs(linear_states(engine)[0][0][0]).max() > 0.01
    engine.release_slot(0)
    out = engine.generate([short], max_new_tokens=15)[0]
    assert within(TOY_LIMITS, gaps(cfg, weights, [(short, out)]))
    fresh, _ = build(cfg, weights)
    assert fresh.generate([short], max_new_tokens=15)[0] == out


def test_rows_end_while_others_decode_and_slots_change_hands(model):
    """Seven requests of different lengths through three slots: rows end
    mid-batch, their slots go to the queue's next, the others' state is
    advanced and nobody else's."""
    cfg, weights = model
    engine, batcher = build(cfg, weights)
    # the ring is the process's: another file's engine of four slots may
    # have written to it in this worker
    before = obs.step_records("decode_step")[-1:]
    rng = np.random.default_rng(5)
    prompts = prompts_of(rng, cfg, (5, 21, 9, 14, 3, 30, 7))
    budgets = [4, 17, 9, 25, 12, 6, 20]
    reqs = [batcher.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    batcher.run_until_idle()
    assert [len(r.output) for r in reqs] == budgets
    got = gaps(cfg, weights, [(p, list(r.output))
                              for p, r in zip(prompts, reqs)])
    assert within(TOY_LIMITS, got), got
    ring = obs.step_records("decode_step")
    if before:
        ring = ring[next(i for i in range(len(ring) - 1, -1, -1)
                         if ring[i] is before[0]) + 1:]
    rows = [r.counts["state_rows"] for r in ring
            if r.counts and "state_rows" in r.counts]
    assert rows and all(len(r) == 3 and len(set(r)) == 1 for r in rows)
    assert {r[0] for r in rows} <= {1, 2, 3} and any(r[0] < 3 for r in rows)


def test_steps_dispatched_ahead_are_used_and_a_dropped_one_costs_nothing(model):
    """Every slot full and far from its end: the engine dispatches ahead. A
    request cancelled then changes a row's hands, the step ahead is dropped
    and run again, and the rows that had taken its token already read their
    state and leave it: their answers are the reference's."""
    cfg, weights = model
    engine, batcher = build(cfg, weights)
    used = obs.counter("gen_decode_ahead_total")
    before = {o: used.value(outcome=o) for o in ("used", "dropped")}
    prompts = prompts_of(np.random.default_rng(6), cfg, (6, 11, 4, 9))
    reqs = [batcher.submit(p, max_new_tokens=30) for p in prompts]
    for _ in range(8):
        batcher.step()
    assert used.value(outcome="used") > before["used"]
    assert engine._ahead is not None      # a step is in flight
    batcher.cancel(reqs[1])               # a row changes hands under it
    batcher.run_until_idle()
    assert used.value(outcome="dropped") == before["dropped"] + 1
    kept = [(p, list(r.output)) for p, r in zip(prompts, reqs) if r is not reqs[1]]
    assert [len(o) for _, o in kept] == [30, 30, 30]
    got = gaps(cfg, weights, kept)
    assert within(TOY_LIMITS, got), got


def test_what_would_need_a_copy_of_the_state_is_refused_by_name(model):
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    engine, _ = build(cfg, weights)
    engine.prefill([3, 4, 5], slot=0)
    with pytest.raises(RuntimeError, match="fork_slot.*state by slot"):
        engine.fork_slot(0, 1)
    for kw in (dict(prefix_cache=True), dict(draft_net=net, speculate_k=2)):
        with pytest.raises(ValueError, match="keeps state by slot.*prefix_cache= "
                                             "and draft_net= are refused"):
            GenerationEngine(net, **dict(cfg["engine"], **kw))


@pytest.fixture(scope="module")
def served(model):
    cfg, weights = model
    engine, batcher = build(cfg, weights)
    prompts = prompts_of(np.random.default_rng(7), cfg, (5, 12, 7, 19, 10, 3))
    reqs = [batcher.submit(p, max_new_tokens=40) for p in prompts]
    batcher.run_until_idle()
    return [(p, list(r.output)) for p, r in zip(prompts, reqs)]


def test_what_the_engine_served_is_within_the_toy_limits(model, served):
    cfg, weights = model
    assert within(TOY_LIMITS, gaps(cfg, weights, served))


@pytest.mark.parametrize("control", CONTROLS + ("bfloat16",))
def test_every_control_fails_the_toy_cells_limits(model, served, control):
    """The reference under each control in the program's place: the tokens
    it puts first lie past a limit that the program passes."""
    cfg, weights = model
    judged = []
    for prompt, out in served:
        low = reference_logits(cfg, weights, prompt + out[:-1],
                               len(prompt) - 1, len(out), control)
        judged.append((prompt, low.argmax(-1).tolist()))
    got = gaps(cfg, weights, judged)
    assert not within(TOY_LIMITS, got), (control, got)


def test_the_decode_kernel_inside_an_engine(model, monkeypatch):
    """The kernel (interpreted) where the chip's would be: the engine's
    decode program advances the state through ``gdn_decode_step``."""
    cfg, weights = model
    monkeypatch.setattr(pallas_gdn, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_gdn, "_resolve_interpret", lambda i: True)
    traced = []
    kernel = pallas_gdn.gdn_decode_step
    monkeypatch.setattr(pallas_gdn, "gdn_decode_step",
                        lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    engine, _ = build(cfg, weights)
    assert engine.read_path.endswith("linear layers: gdn_kernel")
    prompts = prompts_of(np.random.default_rng(8), cfg, (5, 13))
    outs = engine.generate(prompts, max_new_tokens=12)
    assert within(TOY_LIMITS, gaps(cfg, weights, list(zip(prompts, outs))))
    assert len(traced) == 3     # one a linear layer of the decode program


def test_the_decode_program_carries_the_state_in_place(model):
    """Every leaf of the carry, the state among them, is donated and
    aliased to an output: no copy of the state a step."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    audit = engine.audit(compile=False)
    assert audit.carry_donation() == 1.0
    leaves = sum(len(layer) for layer in engine.pools) + 1   # and the table
    assert len(audit.carry_indices) == leaves == 3 * 3 + 2 + 1
