"""A long prefill's masked latent attention through the flash forward kernel
(``ops/flash_attention.py:_flash_fwd(mask=)``, interpreted on the CPU):
against ``attention._masked_chunk_attention`` (XLA: keys in stretches, the
kernel's oracle and fallback) and against a plain float32 softmax, at toy
widths; the gate ``masked_prefill_refusal`` reason by reason; and a toy
dots3-note-prev engine that says which path its prefill programs took, counts
it, and serves the same tokens through both."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import config
from mxnet_tpu import observability as obs
from mxnet_tpu.ops import attention
from mxnet_tpu.ops import flash_attention as fa

from benchmark.reference import dots3_note as ref
from benchmark.weights import make_weights

from test_dots3_note import SEED, served, tiny_config

HEADS, NOPE, ROPE, VD, KL, QL = 4, 16, 8, 32, 24, 20   # vd != nope + rope


def operands(t, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rand = lambda *shape, std=1.0: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * std, dtype)
    return dict(c_q=rand(1, t, QL), w_qb=rand(HEADS * (NOPE + ROPE), QL, std=0.3),
                c_kv=rand(1, t, KL), k_rope=rand(1, t, ROPE),
                w_kvb=rand(HEADS * (NOPE + VD), KL, std=0.3),
                gate=jax.nn.sigmoid(rand(1, t, HEADS)),
                inv_freq=8e7 ** (-np.arange(0, ROPE, 2) / ROPE))


def mask_of(kind, t, seed=0):
    """(1, t, t) bool. ``selection``: the ``top_k_mask`` of scores with many
    ties (few distinct values, as an indexer of few heads gives) under the
    diagonal, 40 a row: whole key blocks hold nothing a row sees.
    ``causal``: every key up to the query's own. ``padded``: the selection,
    and the queries past 200 (a prompt shorter than its bucket) see nothing
    at all."""
    cols = jnp.arange(t)
    causal = (cols[None, :] <= cols[:, None])[None]
    if kind == "causal":
        return causal
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.integers(0, 6, (1, t, t)), jnp.float32)
    seen = causal & attention.top_k_mask(jnp.where(causal, scores, -jnp.inf), 40)
    if kind == "padded":
        seen = seen & (cols < 200)[None, :, None]
    return seen


def plain_softmax(o, seen, scale, gate):
    """The same attention written out: every head's queries, keys and values
    from the latents, one float32 softmax over the keys a query sees."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    t = o["c_q"].shape[1]
    q = (f32(o["c_q"])[0] @ f32(o["w_qb"]).T).reshape(t, HEADS, NOPE + ROPE)
    qr = attention.rotary_embedding(q[None, ..., NOPE:], None, o["inv_freq"])[0]
    kv = jnp.einsum("kl,hdl->khd", f32(o["c_kv"])[0],
                    f32(o["w_kvb"]).reshape(HEADS, NOPE + VD, KL))
    scores = (jnp.einsum("thd,khd->htk", q[..., :NOPE], kv[..., :NOPE])
              + jnp.einsum("thr,kr->htk", qr, f32(o["k_rope"])[0])) * scale
    att = jax.nn.softmax(jnp.where(seen[0][None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("htk,khv->thv", att, kv[..., NOPE:])
    if gate is not None:
        out = out * f32(gate)[0][..., None]
    return out.reshape(1, t, HEADS * VD)


@pytest.mark.parametrize("dtype,t,kind,gated", [
    ("float32", 256, "selection", True), ("float32", 384, "selection", False),
    ("float32", 256, "causal", True), ("float32", 384, "padded", True),
    ("bfloat16", 256, "selection", True), ("bfloat16", 384, "causal", False),
    ("bfloat16", 256, "padded", False)])
def test_the_kernel_is_the_xla_paths_attention_and_a_plain_softmaxs(
        dtype, t, kind, gated):
    """Blocks of 128 keys: two or three a row, those above the diagonal
    skipped, heads two to a grid step. With float32 operands the three agree
    to float32 rounding (sums in another order); with bfloat16 operands
    kernel and XLA path round alike (operands, weights, output) and agree
    within bfloat16's steps, and both stand as far from float32."""
    o = operands(t, jnp.dtype(dtype))
    seen, scale = mask_of(kind, t), (NOPE + ROPE) ** -0.5
    gate = o["gate"] if gated else None
    args = (o["c_q"], o["w_qb"], o["c_kv"], o["k_rope"], o["w_kvb"], HEADS,
            seen, None, o["inv_freq"], scale, gate)
    got = attention._masked_chunk_kernel(*args, group=2, block=128,
                                         interpret=True)
    xla = attention._masked_chunk_attention(*args)
    assert got.shape == xla.shape == (1, t, HEADS * VD) and got.dtype == xla.dtype
    # a query that sees nothing has no softmax: the kernel writes zeros
    # there, XLA a mean of every value; nobody reads a padded query
    live = np.asarray(seen[0].any(axis=-1))
    assert live.sum() == (200 if kind == "padded" else t)
    got, xla = (np.asarray(x, np.float32)[0, live] for x in (got, xla))
    want = np.asarray(plain_softmax(o, seen, scale, gate))[0, live]
    tol = 2e-5 if dtype == "float32" else 0.05
    assert np.abs(got - xla).max() < tol and np.abs(got - want).max() < 2 * tol
    assert np.isfinite(got).all() and np.abs(want).max() > 0.5
    if dtype == "bfloat16":   # as close to float32 as the XLA path is
        assert np.abs(got - want).mean() < 1.5 * np.abs(xla - want).mean()


def test_a_mask_tile_is_read_once_for_the_heads_of_a_step():
    """The mask's block index does not name the head: the kernel's lowered
    text holds ONE mask operand of (T, T) bytes whatever the heads, and the
    result does not depend on how many heads share a step."""
    t = 256
    o = operands(t, jnp.float32, seed=3)
    seen, scale = mask_of("selection", t, seed=3), 0.2
    args = (o["c_q"], o["w_qb"], o["c_kv"], o["k_rope"], o["w_kvb"], HEADS,
            seen, None, o["inv_freq"], scale, o["gate"])
    outs = [attention._masked_chunk_kernel(*args, group=g, block=128,
                                           interpret=True) for g in (1, 2, 4)]
    assert all(np.array_equal(outs[0], x) for x in outs[1:])
    text = jax.jit(lambda m: fa._flash_fwd(
        jnp.zeros((1, 4, t, 128)), jnp.zeros((1, 4, t, 128)),
        jnp.zeros((1, 4, t, 128)), True, block_q=128, block_k=128, mask=m,
        group=2, interpret=True)).lower(seen[0]).as_text()
    assert f"tensor<{t}x{t}xi8>" in text and f"x{t}x{t}xi8>" not in text.replace(
        f"tensor<{t}x{t}xi8>", "")


class _Mesh:
    size = 4


REFUSALS = [
    ("knob", "paged_attention_kernel knob is off"),
    ("cpu", "the backend is not a TPU"),
    ("float16", "queries, keys and values are not all float32 or all bfloat16"),
    ("mixed", "queries, keys and values are not all float32 or all bfloat16"),
    ("rows", "2 rows: the kernel takes one mask for all its heads"),
    ("mask", "a mask of shape (1, 200, 128) is not one chunk's (1, 200, 200)"),
    ("tiles", "200 tokens are not whole 128-key tiles"),
    ("mesh", "a mesh of 4 devices is active"),
    ("none", None),
]


@pytest.mark.parametrize("case,reason", REFUSALS, ids=[c for c, _ in REFUSALS])
def test_the_gate_names_the_first_condition_that_fails(monkeypatch, case, reason):
    """Every reason of ``masked_prefill_refusal``, each with every LATER
    condition failing too: the first is the one named."""
    order = [c for c, _ in REFUSALS]
    failing = set(order[order.index(case):]) - {"none"}
    b = 2 if "rows" in failing else 1
    t = 200 if "tiles" in failing else 256
    dt = jnp.float16 if "float16" in failing else jnp.bfloat16
    block = lambda d, dtype=dt: jax.ShapeDtypeStruct((b, t, 16, d), dtype)  # noqa: E731
    v = block(128, jnp.float32 if "mixed" in failing else dt)
    seen = jax.ShapeDtypeStruct((b, t, 128 if "mask" in failing else t), bool)
    monkeypatch.setattr(fa, "_on_tpu", lambda: "cpu" not in failing)
    if "mesh" in failing:
        from mxnet_tpu import _mesh_state
        monkeypatch.setattr(_mesh_state, "current_mesh", lambda: _Mesh())
    was = config.get("paged_attention_kernel")
    config.set("paged_attention_kernel", "knob" not in failing)
    try:
        assert fa.masked_prefill_refusal(block(192), block(192), v, seen) == reason
    finally:
        config.set("paged_attention_kernel", was)


def test_an_engine_says_which_path_its_prefill_took_and_serves_the_same_tokens(
        monkeypatch):
    """A toy dots3-note-prev engine with a prefill bucket of whole lane
    tiles (128; ``index_topk`` 24, so the selection binds from the 25th
    token). On the CPU the gate refuses, ``engine.read_path`` and
    ``sparse_read_path_total{path="chunk_mask", reason}`` say why, and XLA
    serves. Told that the backend is a TPU (the kernel interpreted), the
    engine prints the kernel, the counter takes ``chunk_mask_kernel`` once a
    full layer, and the same prompts give the same tokens, the logits equal
    to float32 rounding and both the plain reference's."""
    cfg = tiny_config(index_topk=24)
    cfg["engine"] = dict(cfg["engine"], page_size=16, max_length=256,
                         num_pages={"all": 40, "window": 12},
                         prefill_buckets=[128])
    weights = make_weights(ref.param_specs(cfg), SEED)
    count = obs.counter("sparse_read_path_total")
    why = "the backend is not a TPU"
    before = (count.value(path="chunk_mask", reason=why),
              count.value(path="chunk_mask_kernel", reason=""))
    by_xla, engine = served(cfg, weights, lengths=(100, 61, 9), steps=4)
    assert f"full layers: prefill attention chunk_mask ({why})" in engine.read_path
    assert count.value(path="chunk_mask", reason=why) == before[0] + 2  # layers
    assert count.value(path="chunk_mask_kernel", reason="") == before[1]

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: True)
    by_kernel, engine = served(cfg, weights, lengths=(100, 61, 9), steps=4)
    assert "full layers: prefill attention masked_prefill kernel" in engine.read_path
    assert count.value(path="chunk_mask_kernel", reason="") == before[1] + 2
    assert count.value(path="chunk_mask", reason=why) == before[0] + 2
    for (prompt, out, logits), (_, out_x, logits_x) in zip(by_kernel, by_xla):
        assert out == out_x
        assert np.abs(np.stack(logits) - np.stack(logits_x)).max() < 5e-5
        want = ref.next_token_logits(weights, cfg, prompt + out[:-1],
                                     len(prompt) - 1, len(out), pad_to=64,
                                     out_pad=8)
        assert np.abs(np.stack(logits) - want).max() < 5e-5
