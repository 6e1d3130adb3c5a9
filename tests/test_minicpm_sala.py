"""MiniCPM-SALA at a small size on the CPU, float32, seeded weights: two
sparse and two lightning layers, 4 query heads over 1 key-value head, a
page and a block of 4, compressed keys of 2 every 1, 5 blocks selected of
which 3 are forced (the first, the query's own and the one before it),
dense up to 8 keys. The Gluon model against the plain reference
(``benchmark/reference``) on a whole sequence; prefill then decode through
the sparse layers' pools, their compressed keys and the lightning layers'
slot state against the reference's ONE full forward, on rows that stay
dense, rows that start past the dense length and rows that cross it while
decoding; the tables of selected pages; a compressed key written by the
decode step that completes it against a prefill's; a prefill in stretches;
the chunked lightning prefill against the scan; the same prompt in two
buckets; a prompt that ends in each stretch of its bucket (the stretches
past it are not run); a slot's next tenant; rows ending while others decode; steps
dispatched ahead, used and dropped; forks, the prefix cache and speculation
refused by name; every control failing the toy limits ten times over; the
counts of a decode step and of a prefill; and the three decode kernels
(interpreted) inside an engine."""
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import GenerationEngine
from mxnet_tpu.models import minicpm_sala as model_module
from mxnet_tpu.ops import pallas_gdn, pallas_paged_attention

from benchmark.reference import minicpm_sala as ref
from benchmark.systems import minicpm_sala as adaptor
from benchmark.weights import make_weights

SEED = 4294967311  # past 32 bits, as the driver's are
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"]
# the published sparse_config a sixteenth the size: a block of 4, a
# compressed key of 2 every 1 (four start in a block, one reaches in from
# the block before, as at 32 / 16 / 64), 5 blocks selected of which the
# first, the query's own and the one before it are forced
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, topk=5,
              init_blocks=1, window_size=4, dense_len=8)
# a compressed key completes every second position and a page late
WIDE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=5,
            init_blocks=1, window_size=8, dense_len=16)
# float32 on the CPU: the engine and the reference differ by rounding of the
# last place only; the toy cell's limits (tests/benchmark) are these
TOY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-4}
CONTROLS = ("fp8",) + ref.MATH_CONTROLS


def tiny_config(**over):
    """The published configuration's keys at toy sizes."""
    cfg = dict(
        model="minicpm_sala", hidden_size=32, intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=1, head_dim=8,
        rms_norm_eps=1e-6, n_layer=4, num_hidden_layers=32,
        mixer_types=MIXERS, lightning_nh=2, lightning_nkv=2,
        lightning_head_dim=8, n_vocab=200, max_position_embeddings=128,
        # embeddings as they are, not times the published 12: at 32 wide
        # the token's own embedding would drown both mixers and every answer
        # fall into one cycle that no control of the mixers can leave
        rope_theta=10000, scale_emb=1, scale_depth=1.4,
        # the head reads the last norm whole (the published 256 of 4,096
        # would leave toy logits a sixteenth the size)
        dim_model_base=32, sparse_config=dict(SPARSE),
        # widths a hundred times under the published ones: at five times
        # their 0.02 the logits spread as the published widths' do
        initializer_range=0.1, precision={"weights": "float32"},
        engine={"batch_size": 3, "paged": True, "page_size": 4,
                "num_pages": {"all": 120}, "max_length": 128,
                "cache_dtype": "float32", "prefill_buckets": [16, 32, 64]})
    cfg.update(over)
    return cfg


def wide_config(**over):
    """The toy with blocks and pages of 8 and a stride of 2."""
    cfg = tiny_config(sparse_config=dict(WIDE), **over)
    cfg["engine"] = dict(cfg["engine"], page_size=8,
                         num_pages={"all": 60})
    return cfg


def reference_logits(cfg, weights, tokens, first, count, precision="float32"):
    return ref.next_token_logits(weights, cfg, list(tokens), first, count,
                                 precision=precision, pad_to=32, out_pad=32)


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


def slot_states(engine):
    """The slot state of every lightning layer, on the host."""
    return [tuple(np.asarray(b) for b in layer)
            for layer, g in zip(engine.pools, engine.layer_groups)
            if g == "slot"]


def blocks_read(cfg, sees):
    """Blocks a key-value head's reads visit for a query that sees ``sees``
    keys, and the blocks it holds."""
    sparse = cfg["sparse_config"]
    held = -(-sees // sparse["block_size"])
    return (held if sees <= sparse["dense_len"]
            else min(held, sparse["topk"])), held


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config()
    return cfg, make_weights(ref.param_specs(cfg), SEED)


def build(cfg, weights, **engine):
    cfg = dict(cfg, engine=dict(cfg["engine"], **engine))
    return adaptor.build_serve(cfg, weights)


def test_the_model_is_the_reference_on_a_whole_sequence(model):
    """96 positions: 8 read densely, 88 by the selection."""
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    assert len(net.collect_params()) == len(weights)
    tokens = np.random.default_rng(0).integers(1, cfg["n_vocab"], 96)
    got = net(mx.nd.array(tokens[None], dtype="int32")).asnumpy()[0]
    want = reference_logits(cfg, weights, tokens, 0, 96)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the selection is not idle there: dense attention reads otherwise
    # (5 blocks hold 20 keys at most: the first difference is at the 21st)
    dense = reference_logits(cfg, weights, tokens, 0, 96, "no_selection")
    np.testing.assert_allclose(dense[:20], want[:20], atol=1e-6)
    assert np.abs(dense[20:] - want[20:]).max() > 1e-3


def test_a_whole_sequence_in_stretches_is_the_same(model, monkeypatch):
    """A long prefill's mixers walk the prompt a stretch at a time (4,096
    tokens at the published size): the state and the keys carry over."""
    cfg, weights = model
    net = adaptor.build_net(cfg, weights)
    tokens = np.random.default_rng(1).integers(1, cfg["n_vocab"], (2, 64))
    whole = net(mx.nd.array(tokens, dtype="int32")).asnumpy()
    monkeypatch.setattr(model_module, "_STRETCH", 16)
    monkeypatch.setattr(model_module, "_CHUNK", 4)
    monkeypatch.setattr(model_module, "_SELECT_QUERIES", 8)
    net = adaptor.build_net(cfg, weights)
    np.testing.assert_allclose(
        net(mx.nd.array(tokens, dtype="int32")).asnumpy(), whole, atol=2e-5)


@pytest.mark.parametrize("chunk", [4, 16, 128])
def test_the_chunked_lightning_prefill_is_the_scan(chunk):
    """Intra-chunk ``(Q K^T . D) V`` plus ``Q S`` against the recurrence
    position by position (the reference's ``lax.scan``), padding behind the
    real length writing nothing."""
    rng = np.random.default_rng(20)
    t, h, ch, real = 48, 2, 8, 41
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, ch)), jnp.float32)
               for _ in range(3))
    lam = np.asarray([0.9, 0.6], np.float32)
    want = np.asarray(ref.decayed_state(q[:real], k[:real], v[:real],
                                        jnp.asarray(lam)))
    got, state = pallas_gdn.lightning_chunk_prefill(
        q, k, v, np.log(lam), real, chunk)
    np.testing.assert_allclose(np.asarray(got)[:real], want, atol=1e-5)
    # the state behind the last REAL position: one more step from it
    again, _ = pallas_gdn.lightning_chunk_prefill(
        q[:real], k[:real], v[:real], np.log(lam), real, chunk)
    np.testing.assert_allclose(np.asarray(again), want, atol=1e-5)
    step = np.einsum("hkv,hk->hv", np.asarray(state) * lam[:, None, None]
                     + np.einsum("hk,hv->hkv", k[real], v[real]), q[real])
    longer = np.asarray(ref.decayed_state(q[:real + 1], k[:real + 1],
                                          v[:real + 1], jnp.asarray(lam)))
    np.testing.assert_allclose(step, longer[real], atol=1e-5)


@pytest.mark.parametrize("sparse", [SPARSE, WIDE], ids=["block4", "block8"])
def test_the_programs_choice_is_the_references_and_holds_the_forced_blocks(
        sparse):
    cfg = dict(sparse)
    rng = np.random.default_rng(2)
    t, kv, group, ch = 96, 2, 3, 8
    q = rng.normal(size=(t, kv, group, ch)).astype(np.float32)
    k = rng.normal(size=(t, kv, ch)).astype(np.float32)
    ck = np.asarray(ref.compressed_keys(jnp.asarray(k), cfg))
    at = np.arange(t)
    n_blocks = t // cfg["block_size"]
    weights = model_module.key_weights(jnp.asarray(q), jnp.asarray(ck),
                                       jnp.asarray(at), cfg)
    rank, forced = model_module.block_ranks(weights, jnp.asarray(at), cfg,
                                            n_blocks)
    rank, forced = np.asarray(rank), np.asarray(forced)
    ids = np.argsort(-rank, axis=-1, kind="stable")[..., :cfg["topk"]]
    for g in range(kv):
        want = np.asarray(ref.chosen_blocks(
            jnp.asarray(q[:, g]), jnp.asarray(ck[:, g]), jnp.asarray(at), cfg,
            n_blocks, "float32"))
        for pos in range(cfg["dense_len"], t):
            own = pos // cfg["block_size"]
            taken = {m for m in ids[pos, g].tolist() if m <= own}
            assert taken == set(np.flatnonzero(want[pos]).tolist())
            assert len(taken) == min(own + 1, cfg["topk"])
            assert {0, own - 1, own} <= taken
            assert set(np.flatnonzero(forced[pos, g])) == {0, own - 1, own}


def test_the_tables_of_selected_pages_hold_the_forced_blocks_and_held_pages():
    """``selected_pages``: what ``paged_gqa_read(selected=)`` walks. Rows on
    both sides of the dense length and one that holds fewer blocks than are
    selected: every entry that counts is a page the row holds, in the
    blocks' order, the forced blocks among them."""
    cfg = dict(SPARSE)
    rng = np.random.default_rng(21)
    position = np.asarray([2, 7, 8, 13, 30, 63])
    cols, kv = 16, 2
    table = np.zeros((len(position), cols), np.int32)
    for b, at in enumerate(position):   # the pages a row holds, and no more
        table[b, :at // 4 + 1] = 100 * (b + 1) + np.arange(at // 4 + 1)
    s = rng.random((len(position), kv, cols * 4)).astype(np.float32)
    pages, blocks, counts = (np.asarray(x) for x in model_module.selected_pages(
        jnp.asarray(s), jnp.asarray(table), jnp.asarray(position), cfg))
    assert pages.shape == blocks.shape == (6, kv, 5) and counts.shape == (6, kv)
    for b, at in enumerate(position):
        own = at // 4
        read, held = blocks_read({"sparse_config": cfg}, at + 1)
        assert held == own + 1
        for g in range(kv):
            n = counts[b, g]
            assert n == read
            listed = blocks[b, g, :n]
            assert (np.diff(listed) > 0).all() and listed.max() <= own
            assert {0, max(own - 1, 0), own} <= set(listed.tolist())
            np.testing.assert_array_equal(pages[b, g, :n], table[b, listed])
            assert (pages[b, g, :n] > 0).all()
    # past the dense length the two heads choose by their own scores
    assert (blocks[5, 0] != blocks[5, 1]).any()


def test_the_choice_agrees_with_itself_in_float32_and_less_under_fp8(model):
    """``choice_agreement`` (what step 0 reads at the timed size): the share
    of the blocks chosen by score that the same selection with rounded
    operands chooses too."""
    cfg, weights = model
    tokens = np.random.default_rng(12).integers(1, cfg["n_vocab"], 96)
    assert ref.choice_agreement(weights, cfg, tokens, 0, "float32", 4) == 1.0
    assert 0.3 < ref.choice_agreement(weights, cfg, tokens, 0, "fp8", 4) < 1.0


def test_prefill_then_decode_is_the_references_full_forward(model):
    """Through the pools, the compressed keys and the state: a row that
    stays dense for a while, a row that starts past the dense length and a
    row that crosses it while decoding, side by side."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    assert engine.read_path == (
        "sparse layers: selected_pages_xla (the backend is not a TPU); "
        "selector: block_scores_xla (the backend is not a TPU); "
        "lightning layers: lightning_xla (the backend is not a TPU)")
    assert engine.layer_groups == ("all", "slot", "slot", "all")
    # 2 lightning layers x 3 slots x (8 x 2 x 8 state + 1) x 4 B
    assert engine.slot_state_bytes == 2 * 3 * (8 * 16 + 1) * 4
    # keys, values and a compressed key every position: two sparse layers,
    # 1 key-value head of 8, float32
    assert engine.cache_bytes_per_token == 2 * (2 + 1) * 1 * 8 * 4
    assert obs.gauge("gen_slot_state_bytes").value() == engine.slot_state_bytes
    assert obs.gauge("gen_compressed_key_bytes").value() == 2 * 121 * 4 * 8 * 4
    read_total, held_total = (obs.counter(f"gen_blocks_{n}_total")
                              for n in ("read", "held"))
    before = read_total.value(), held_total.value()
    lengths = (2, 50, 6)
    prompts = prompts_of(np.random.default_rng(3), cfg, lengths)
    outs = [[engine.prefill(p, slot=i)] for i, p in enumerate(prompts)]
    fills = [r.counts for r in obs.step_records("prefill")[-3:]]
    step_logits, counts = [], []
    for _ in range(24):
        tok, _, logits = engine.decode_step()
        step_logits.append(np.asarray(logits))
        counts.append(obs.step_records("decode_step")[-1].counts)
        for i, out in enumerate(outs):
            out.append(int(tok[i]))
    got = gaps(cfg, weights, list(zip(prompts, outs)))
    assert within(TOY_LIMITS, got), got
    # logits, not tokens: every decode step's against the reference's
    for i, (p, o) in enumerate(zip(prompts, outs)):
        want = reference_logits(cfg, weights, p + o[:-1], len(p), 24)
        np.testing.assert_allclose([s[i] for s in step_logits], want,
                                   atol=3e-5)
    # the program's own counts, an entry a layer of the kind: step n reads
    # rows that see 3 + n, 51 + n and 7 + n keys
    for n, step in enumerate(counts):
        read, held = zip(*(blocks_read(cfg, m + 1 + n) for m in lengths))
        assert step["blocks_read"] == [sum(read)] * 2, n
        assert step["blocks_held"] == [sum(held)] * 2, n
        assert step["compressed_written"] == [3, 3]
        assert step["state_rows"] == [3, 3]
    # the third row sees its 9th key at its 3rd step and selects from its
    # 21st on; the first from its 19th step on
    shares = [c["blocks_read"][0] / c["blocks_held"][0] for c in counts]
    assert shares[0] == (1 + 5 + 2) / (1 + 13 + 2) and shares[-1] < shares[0]
    # a prefill's counts come back behind its first token: every real
    # query's blocks, the compressed keys whose last key is real
    for m, fill in zip(lengths, fills):
        read, held = zip(*(blocks_read(cfg, q + 1) for q in range(m)))
        assert fill["blocks_read"] == [sum(read)] * 2
        assert fill["blocks_held"] == [sum(held)] * 2
        assert fill["compressed_written"] == [m - 1] * 2
        assert fill["state_rows"] == [1, 1] and fill["prompt"] == m
    total = lambda name: sum(c[name][0] + c[name][1] for c in fills + counts)  # noqa: E731
    assert read_total.value() - before[0] == total("blocks_read")
    assert held_total.value() - before[1] == total("blocks_held")


def test_a_compressed_key_written_by_a_decode_step_is_a_prefills():
    """The selector's cache behind prefill + decode equals the cache behind
    one prefill of the same tokens, wherever a key is complete (blocks of 8,
    a key of 4 every 2: one completes every second step, some a page late)."""
    cfg = wide_config()
    weights = make_weights(ref.param_specs(cfg), SEED)
    prompt = prompts_of(np.random.default_rng(4), cfg, (21,))[0]
    engine, _ = build(cfg, weights)
    out = engine.generate([prompt], max_new_tokens=40)[0]
    every = prompt + out[:-1]                       # 60 tokens hold keys
    other, _ = build(cfg, weights)
    other.prefill(every, slot=0)
    written = [r.counts["compressed_written"][0]
               for r in obs.step_records("decode_step")[-39:]]
    assert written == [(21 + n) % 2 for n in range(39)]   # odd positions end one

    def compressed(e):
        pool = np.asarray(e.pools[0][2])
        table = np.asarray(e.page_table)[0]
        return pool[table[:8]].reshape(32, 8)       # 4 a page of 8

    complete = (len(every) - 4) // 2 + 1            # j with 2j + 3 <= 59
    got, want = compressed(engine), compressed(other)
    np.testing.assert_allclose(got[:complete], want[:complete], atol=1e-6)
    assert np.abs(want[:complete]).min(axis=1).max() > 0
    # a key whose last position is not written yet is not in the cache
    assert not got[complete:].any() and not want[complete:].any()
    # and it is the mean of the keys the pool holds
    keys = np.asarray(engine.pools[0][0])[np.asarray(engine.page_table)[0][:8]]
    keys = keys.reshape(64, 8)
    np.testing.assert_allclose(got[5], keys[10:14].mean(axis=0), atol=1e-6)


def small_stretches(monkeypatch):
    """Stretches of 16 tokens (4,096 at the published size): a bucket of 64
    is four."""
    monkeypatch.setattr(model_module, "_STRETCH", 16)
    monkeypatch.setattr(model_module, "_CHUNK", 4)
    monkeypatch.setattr(model_module, "_SELECT_QUERIES", 8)


@pytest.mark.parametrize("stretches", [1, 4])
def test_the_same_prompt_in_two_buckets_leaves_the_same_state(
        model, monkeypatch, stretches):
    """Padding writes nothing: the state behind a prompt of 13 in a bucket
    of 16 is the state in a bucket of 64, one stretch or four of which the
    last three are not run, and no compressed key of the padding reaches
    the cache."""
    cfg, weights = model
    if stretches > 1:
        small_stretches(monkeypatch)
    prompt = prompts_of(np.random.default_rng(5), cfg, (13,))[0]
    left = []
    for buckets in ([16], [64]):
        engine, _ = build(cfg, weights, prefill_buckets=buckets)
        tok = engine.prefill(prompt, slot=1)
        ck = np.asarray(engine.pools[0][2])[np.asarray(engine.page_table)[1][:4]]
        left.append((tok, slot_states(engine), ck.reshape(16, 8)))
        # the stretches the 13 tokens reach: the first of four, or the one
        ran = obs.step_records("prefill")[-1].counts["positions_run"]
        assert ran == [buckets[0] if stretches == 1 else 16] * 4
    assert left[0][0] == left[1][0]
    for (s0, n0), (s1, n1) in zip(left[0][1], left[1][1]):
        # (the projections' products round differently at 16 rows and at 64)
        np.testing.assert_allclose(s0[1], s1[1], atol=1e-5)
        assert n0[1] == n1[1] == 13
        assert np.abs(s0[1]).max() > 0.01     # and it is a state
        assert not s0[0].any() and not s0[2].any()   # other slots untouched
    np.testing.assert_allclose(left[0][2], left[1][2], atol=1e-6)
    assert left[0][2][:12].any(axis=1).all() and not left[0][2][12:].any()


def left_by_a_prefill(engine, prompt, slot):
    """What a prefill leaves: the first token, its logits, the lightning
    layers' state and positions at the slot, a sparse layer's keys and
    values at the prompt's positions and the compressed keys of every page
    the row holds, and the program's counts."""
    tok = engine.prefill(prompt, slot=slot)
    held = -(-len(prompt) // 4)                             # pages of 4
    table = np.asarray(engine.page_table)[slot][:held]
    rows = lambda pool: np.asarray(pool)[table].reshape(4 * held, -1)  # noqa: E731
    sparse = [tuple(rows(pool) for pool in layer)
              for layer, g in zip(engine.pools, engine.layer_groups)
              if g == "all"]
    return dict(
        token=tok, logits=np.asarray(engine._last_logits),
        state=[(s[slot], n[slot]) for s, n in slot_states(engine)],
        keys=[(k[:len(prompt)], v[:len(prompt)]) for k, v, _ in sparse],
        compressed=[ck for _, _, ck in sparse],
        counts=obs.step_records("prefill")[-1].counts)


@pytest.mark.parametrize("length", [13, 29, 45, 61, 64])
def test_a_prefill_runs_only_the_stretches_its_prompt_reaches(
        model, monkeypatch, length):
    """A bucket of 64 in four stretches of 16, the prompt ending in the
    1st, 2nd, 3rd and last of them, and filling the bucket: what the prefill
    leaves is what the smallest bucket that holds the prompt leaves (every
    stretch of that one reaches the prompt) and the reference's full
    forward, and every layer ran the stretches the prompt reaches."""
    cfg, weights = model
    small_stretches(monkeypatch)
    prompt = prompts_of(np.random.default_rng(length), cfg, (length,))[0]
    total = obs.counter("gen_positions_run_total")
    before = total.value()
    wide, _ = build(cfg, weights, prefill_buckets=[64])
    close, _ = build(cfg, weights, prefill_buckets=[16, 32, 48, 64])
    got, want = (left_by_a_prefill(e, prompt, 2) for e in (wide, close))
    reached = -(-length // 16) * 16
    assert got["counts"]["bucket"] == 64
    assert want["counts"]["bucket"] == reached
    assert got["counts"]["positions_run"] == [reached] * 4
    assert want["counts"]["positions_run"] == [reached] * 4
    assert total.value() - before == 2 * 4 * reached
    assert got["token"] == want["token"]
    np.testing.assert_allclose(got["logits"], want["logits"], atol=1e-5)
    np.testing.assert_allclose(
        got["logits"],
        reference_logits(cfg, weights, prompt, length - 1, 1)[0], atol=3e-5)
    for (s0, n0), (s1, n1) in zip(got["state"], want["state"]):
        np.testing.assert_allclose(s0, s1, atol=1e-5)
        assert n0 == n1 == length and np.abs(s0).max() > 0.01
    for (k0, v0), (k1, v1) in zip(got["keys"], want["keys"]):
        np.testing.assert_allclose(k0, k1, atol=1e-5)
        np.testing.assert_allclose(v0, v1, atol=1e-5)
        assert k0.any(axis=1).all()
    # a compressed key of 2 every 1: those whose last key is real
    for ck0, ck1 in zip(got["compressed"], want["compressed"]):
        np.testing.assert_allclose(ck0, ck1, atol=1e-5)
        assert ck0[:length - 1].any(axis=1).all() \
            and not ck0[length - 1:].any()
    read, held = zip(*(blocks_read(cfg, q + 1) for q in range(length)))
    for counts in (got["counts"], want["counts"]):
        assert counts["blocks_read"] == [sum(read)] * 2
        assert counts["blocks_held"] == [sum(held)] * 2
        assert counts["compressed_written"] == [length - 1] * 2
        assert counts["state_rows"] == [1, 1]


def test_a_stretch_past_the_first_stands_under_a_predicate(model, monkeypatch):
    """The lowered prefill program of a bucket of four stretches: a sparse
    layer's three stretches past the first are a conditional each (the first
    always runs), a lightning layer's scanned stretch is one; a bucket of one
    stretch has none, and the decode program none and no stretch: its text
    does not move with the stretch's length (its SHA-256 is held to PR 43's
    tree in ``tests/test_lowered_text_guard.py``)."""
    cfg, weights = model
    engine, _ = build(cfg, weights, prefill_buckets=[16, 64])
    decode = engine.lower_decode().as_text()
    assert "stablehlo.case" not in engine.lower_prefill(64).as_text()
    small_stretches(monkeypatch)
    engine, _ = build(cfg, weights, prefill_buckets=[16, 64])
    assert engine.lower_prefill(64).as_text().count("stablehlo.case") \
        == 2 * 3 + 2 * 1
    assert "stablehlo.case" not in engine.lower_prefill(16).as_text()
    assert "stablehlo.case" not in decode
    assert engine.lower_decode().as_text() == decode


def test_a_slot_used_again_after_a_longer_tenant(model):
    """The next tenant sees nothing of the last: its prefill writes the
    slot's state from zero."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    long_, short = prompts_of(np.random.default_rng(6), cfg, (60, 6))
    engine.generate([long_], max_new_tokens=25)
    assert np.abs(slot_states(engine)[0][0][0]).max() > 0.01
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
    before = obs.step_records("decode_step")[-1:]
    rng = np.random.default_rng(7)
    prompts = prompts_of(rng, cfg, (5, 40, 9, 64, 3, 30, 7))
    budgets = [4, 17, 9, 25, 12, 30, 20]
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
    assert rows and all(len(r) == 2 and len(set(r)) == 1 for r in rows)
    assert {r[0] for r in rows} <= {1, 2, 3} and any(r[0] < 3 for r in rows)
    shares = [r.counts["blocks_read"][0] / r.counts["blocks_held"][0]
              for r in ring if r.counts and "blocks_read" in r.counts]
    assert min(shares) < 0.5 < max(shares) <= 1.0, shares


def test_steps_dispatched_ahead_are_used_and_a_dropped_one_costs_nothing(model):
    """Every slot full and far from its end: the engine dispatches ahead. A
    request cancelled then changes a row's hands, the step ahead is dropped
    and run again, and the rows that had taken its token already read their
    state and leave it: their answers are the reference's."""
    cfg, weights = model
    engine, batcher = build(cfg, weights)
    used = obs.counter("gen_decode_ahead_total")
    before = {o: used.value(outcome=o) for o in ("used", "dropped")}
    prompts = prompts_of(np.random.default_rng(8), cfg, (6, 11, 40, 9))
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
    with pytest.raises(ValueError, match="a page is a block"):
        GenerationEngine(net, **dict(cfg["engine"], page_size=8))


# a control that moves the dense length's edge needs one to move: with 5
# blocks selected of the 2 that 8 keys fill, by call and by position agree
EDGE = dict(SPARSE, dense_len=32)


def serve_six(cfg, weights):
    engine, batcher = build(cfg, weights)
    prompts = prompts_of(np.random.default_rng(9), cfg, (5, 50, 27, 64, 40, 30))
    reqs = [batcher.submit(p, max_new_tokens=40) for p in prompts]
    batcher.run_until_idle()
    return cfg, [(p, list(r.output)) for p, r in zip(prompts, reqs)]


@pytest.fixture(scope="module")
def served(model):
    return serve_six(*model)


@pytest.fixture(scope="module")
def served_edge(model):
    return serve_six(tiny_config(sparse_config=dict(EDGE)), model[1])


@pytest.mark.parametrize("which", ["served", "served_edge"])
def test_what_the_engine_served_is_within_the_toy_limits(model, request, which):
    cfg, requests = request.getfixturevalue(which)
    assert within(TOY_LIMITS, gaps(cfg, model[1], requests))


@pytest.mark.parametrize("control", CONTROLS + ("bfloat16",))
def test_every_control_fails_the_toy_cells_limits(model, request, control):
    """The reference under each control in the program's place: the tokens
    it puts first lie past a limit that the program passes, ten times over.
    ``dense_by_call`` is judged where the dense length has an edge to move."""
    cfg, requests = request.getfixturevalue(
        "served_edge" if control == "dense_by_call" else "served")
    judged, moved = [], 0.0
    for prompt, out in requests:
        at = (prompt + out[:-1], len(prompt) - 1, len(out))
        low = reference_logits(cfg, model[1], *at, control)
        judged.append((prompt, low.argmax(-1).tolist()))
        moved = max(moved, np.abs(
            low - reference_logits(cfg, model[1], *at)).max())
    if control == "early_key":
        # one more compressed key in the selection's softmax moves the
        # scores of blocks that lie close and flips no toy token: judged
        # on the logits, which the program holds to 3e-5 of the reference's
        assert moved > 10 * 3e-5, moved
        return
    got = gaps(cfg, model[1], judged)
    assert any(got[k] > 10 * TOY_LIMITS[k] for k in TOY_LIMITS), (control, got)


def test_the_kernels_inside_an_engine(monkeypatch):
    """The kernels (interpreted) where the chip's would be, at widths their
    gates admit (heads of 128 in the sparse layers, pages of 8, 128
    compressed keys a row, two lightning heads of 64 a lane tile): the
    decode program weighs the compressed keys through ``paged_block_scores``,
    reads the selected pages through ``paged_gqa_read(selected=)`` and
    advances the state through ``gdn_decode_step(delta=False)``; a prefill of
    a whole 128 tokens goes through the flash forward kernel under the
    selection's mask, which ``sparse_chunk_scores`` scored."""
    from mxnet_tpu.ops import flash_attention
    cfg = wide_config(head_dim=128, lightning_head_dim=64,
                      initializer_range=0.05)
    # tables of 8 pages: the kernel walks whole chunks of 8
    cfg["sparse_config"] = dict(WIDE, topk=8, dense_len=64)
    cfg["engine"] = dict(cfg["engine"], max_length=256,
                         prefill_buckets=[32, 64, 128])
    weights = make_weights(ref.param_specs(cfg), SEED)
    for module in (pallas_gdn, pallas_paged_attention, flash_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
        monkeypatch.setattr(module, "_resolve_interpret", lambda i: True)
    traced = {"state": 0, "tables": 0, "scores": 0, "chunk": 0, "select": 0}
    forward = flash_attention._flash_fwd
    step, read = pallas_gdn.gdn_decode_step, pallas_paged_attention.paged_gqa_read
    scores = pallas_paged_attention.paged_block_scores

    def counted(name, fn):
        def call(*a, **kw):
            traced[name] += 1
            return fn(*a, **kw)
        return call

    monkeypatch.setattr(pallas_gdn, "gdn_decode_step", counted("state", step))
    monkeypatch.setattr(pallas_paged_attention, "paged_gqa_read",
                        counted("tables", read))
    monkeypatch.setattr(pallas_paged_attention, "paged_block_scores",
                        counted("scores", scores))
    monkeypatch.setattr(flash_attention, "_flash_fwd",
                        counted("chunk", forward))
    monkeypatch.setattr(
        pallas_paged_attention, "sparse_chunk_scores",
        counted("select", pallas_paged_attention.sparse_chunk_scores))
    engine, _ = build(cfg, weights)
    assert engine.read_path == ("sparse layers: selected_pages_kernel; "
                                "selector: block_scores_kernel; "
                                "lightning layers: lightning_kernel")
    prompts = prompts_of(np.random.default_rng(10), cfg, (5, 90, 58))
    outs = engine.generate(prompts, max_new_tokens=12)
    got = gaps(cfg, weights, list(zip(prompts, outs)))
    assert within(TOY_LIMITS, got), got
    # one a layer of its kind; the bucket of 128 a sparse layer's one
    # key-value head (the buckets of 32 and 64 are not whole 128-key tiles:
    # XLA's softmax)
    # XLA's softmax); the bucket of 128 is one stretch, which passes the
    # dense length of 64: its selection weighs the compressed
    # keys through ``sparse_chunk_scores``
    assert traced == {"state": 2, "tables": 2, "scores": 2, "chunk": 2,
                      "select": 2}
    assert obs.counter("sparse_read_path_total").value(
        path="chunk_scores_kernel", reason="") >= 2
    paths = obs.counter("paged_read_path_total")
    assert paths.value(path="sparse_chunk_kernel", reason="") >= 1
    assert paths.value(path="selected_pages_kernel", reason="") >= 1


def test_the_decode_program_carries_pools_and_state_in_place(model):
    """Every leaf of the carry, the compressed keys and the state among
    them, is donated and aliased to an output: no copy a step."""
    cfg, weights = model
    engine, _ = build(cfg, weights)
    audit = engine.audit(compile=False)
    assert audit.carry_donation() == 1.0
    leaves = sum(len(layer) for layer in engine.pools) + 1   # and the table
    assert len(audit.carry_indices) == leaves == 2 * 3 + 2 * 2 + 1
