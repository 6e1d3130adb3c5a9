"""The gated delta rule's operators (``ops/pallas_gdn.py``) against the
position-by-position recurrence of the plain reference
(``benchmark/reference/olmo_hybrid.py:delta_rule``), float32 on the CPU: the
decode kernel (interpret mode) and its XLA form, one token a row from a
random state, with a write strength near 2, decays near 0 and near 1, dead
rows untouched bit for bit; the chunked prefill at lengths that are and are
not whole blocks, padding that writes nothing; the gate's reasons."""
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_gdn as gdn

from benchmark.reference.olmo_hybrid import delta_rule


def operands(rng, t, heads, dk, dv):
    """q, k (unit length a head), v, alpha, beta of ``t`` positions, with
    every edge the layer reaches: beta near 2 and 0, alpha near 0 and 1."""
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f32 = lambda x: x.astype(np.float32)  # noqa: E731
    q = f32(unit(rng.normal(size=(t, heads, dk)))) * dk ** -0.5
    k = f32(unit(rng.normal(size=(t, heads, dk))))
    v = f32(rng.normal(size=(t, heads, dv)))
    alpha = f32(rng.uniform(0.05, 1.0, size=(t, heads)))
    beta = f32(rng.uniform(0.0, 2.0, size=(t, heads)))
    alpha[::3], alpha[1::7] = 0.9995, 1e-3
    beta[::2], beta[1::5] = 1.999, 1e-4
    return q, k, v, alpha, beta


def one_step(state, q, k, v, alpha, beta):
    """The recurrence's one position in numpy float64, ``state`` (B, H, dk,
    dv): (o, state')."""
    s = state.astype(np.float64) * alpha[:, :, None, None]
    read = np.einsum("bhkv,bhk->bhv", s, k)
    s = s + np.einsum("bhk,bhv->bhkv", k, beta[..., None] * (v - read))
    return np.einsum("bhkv,bhk->bhv", s, q), s


# (heads, dk, dv): two heads a lane group (2 x 64 = 128 lanes), one head a
# group (128), and toy widths where all heads are one group
SHAPES = [(4, 8, 64), (3, 16, 128), (2, 8, 16)]


@pytest.mark.parametrize("heads,dk,dv", SHAPES)
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_decode_step_is_the_recurrences_one_position(heads, dk, dv, form):
    rng = np.random.default_rng(heads * dv)
    b = 6
    state = rng.normal(size=(b, heads, dk, dv)).astype(np.float32)
    q, k, v, alpha, beta = operands(rng, b, heads, dk, dv)
    live = np.array([1, 0, 1, 1, 0, 1], bool)
    rows = gdn.state_rows(jnp.asarray(state))
    assert rows.shape == (b, dk, heads * dv)
    np.testing.assert_array_equal(gdn.state_heads(rows, heads), state)
    if form == "kernel":
        o, new = gdn.gdn_decode_step(rows, q, k, v, alpha, beta, live,
                                     interpret=True)
    else:
        o, new = gdn.gdn_decode_xla(rows, q, k, v, alpha, beta, live)
    want_o, want_s = one_step(state, q, k, v, alpha, beta)
    got_s = np.asarray(gdn.state_heads(new, heads))
    np.testing.assert_allclose(np.asarray(o)[live], want_o[live], atol=3e-6)
    np.testing.assert_allclose(got_s[live], want_s[live], atol=3e-6)
    # a dead row: its state bit for bit, its read zero
    np.testing.assert_array_equal(got_s[~live], state[~live])
    assert not np.asarray(o)[~live].any()


@pytest.mark.parametrize("live", [
    [0, 0, 0, 0, 0], [0, 0, 0, 0, 1], [1, 0, 0, 0, 0], [0, 1, 0, 1, 0],
    [1, 1, 1, 1, 1]])
def test_the_kernel_and_the_xla_form_agree_whichever_rows_live(live):
    rng = np.random.default_rng(5)
    heads, dk, dv = 4, 8, 64
    live = np.asarray(live, bool)
    state = rng.normal(size=(5, heads, dk, dv)).astype(np.float32)
    q, k, v, alpha, beta = operands(rng, 5, heads, dk, dv)
    rows = gdn.state_rows(jnp.asarray(state))
    o1, s1 = gdn.gdn_decode_step(rows, q, k, v, alpha, beta, live,
                                 interpret=True)
    o2, s2 = gdn.gdn_decode_xla(rows, q, k, v, alpha, beta, live)
    np.testing.assert_allclose(o1, o2, atol=2e-6)
    np.testing.assert_allclose(s1, s2, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(s1)[~live],
                                  np.asarray(rows)[~live])
    assert not np.asarray(o1)[~live].any()


def test_a_row_held_at_alpha_one_beta_zero_reads_and_leaves_its_state():
    """What a decode step run a second time asks of the operator."""
    rng = np.random.default_rng(9)
    heads, dk, dv = 4, 8, 64
    state = rng.normal(size=(3, heads, dk, dv)).astype(np.float32)
    q, k, v, _, _ = operands(rng, 3, heads, dk, dv)
    ones, zeros = np.ones((3, heads), np.float32), np.zeros((3, heads), np.float32)
    rows = gdn.state_rows(jnp.asarray(state))
    for step in (gdn.gdn_decode_xla,
                 lambda *a: gdn.gdn_decode_step(*a, interpret=True)):
        o, new = step(rows, q, k, v, ones, zeros, np.ones(3, bool))
        np.testing.assert_array_equal(new, rows)
        np.testing.assert_allclose(
            o, np.einsum("bhkv,bhk->bhv", state, q), atol=2e-6)


@pytest.mark.parametrize("length,chunk", [(128, 64), (64, 64), (40, 64),
                                          (150, 64), (16, 4), (13, 4), (3, 4)])
def test_the_chunked_prefill_is_the_recurrence(length, chunk):
    """Lengths that are and are not whole blocks: the operator fills the
    last block with padding (log alpha 0, beta 0), which writes nothing."""
    rng = np.random.default_rng(length)
    heads, dk, dv = 3, 8, 16
    q, k, v, alpha, beta = operands(rng, length, heads, dk, dv)
    want = np.asarray(delta_rule(q, k, v, alpha, beta))
    o, state = gdn.gdn_chunk_prefill(q, k, v, np.log(alpha), beta, chunk)
    np.testing.assert_allclose(o, want, atol=3e-5)
    # the state behind the last REAL position: one more position read from
    # it is what the recurrence reads there
    q1, k1, v1, a1, b1 = operands(rng, 1, heads, dk, dv)
    more = delta_rule(*(np.concatenate([x, y]) for x, y in zip(
        (q, k, v, alpha, beta), (q1, k1, v1, a1, b1))))[-1]
    got, _ = gdn.gdn_decode_xla(gdn.state_rows(state)[None], q1, k1, v1,
                                a1, b1, np.ones(1, bool))
    np.testing.assert_allclose(got[0], more, atol=3e-5)


def test_the_gate_names_why_the_kernel_does_not_run(monkeypatch):
    import jax

    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    state = shape((48, 96, 30 * 192), f32)
    q, v = shape((48, 30, 96), f32), shape((48, 30, 192), f32)
    assert gdn.gdn_decode_refusal(state, q, v) == "the backend is not a TPU"
    monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
    assert gdn.gdn_decode_refusal(state, q, v) is None
    assert "float32" in gdn.gdn_decode_refusal(
        shape(state.shape, jnp.bfloat16), q, v)
    toy = gdn.gdn_decode_refusal(shape((4, 8, 2 * 16), f32),
                                 shape((4, 2, 8), f32), shape((4, 2, 16), f32))
    assert "whole (8, 128) tiles" in toy
    assert gdn._heads_a_group(30, 192) == 2 and gdn._heads_a_group(4, 64) == 2
    assert gdn._heads_a_group(3, 128) == 1 and gdn._heads_a_group(2, 16) == 2


def test_the_chip_smokes_check_rehearsed_at_a_toy_size():
    """``chip_smoke.py``'s kernels phase calls this at 30 x 96 x 192 on the
    chip; here the same steps, the kernel interpreted."""
    import chip_smoke

    found = chip_smoke.check_gdn(rows=4, heads=4, dk=8, dv=64, interpret=True)
    assert found["live"] == 3 and found["rel_err"] < 1e-5


# -- the same family without the delta term (Lightning Attention) -----------
def lightning_reference(q, k, v, lam):
    """``S_t = lam S_(t-1) + k_t v_t^T``, ``o_t = S_t^T q_t`` position by
    position in float64; at equal key and value widths also the plain
    reference's own scan."""
    s, out = np.zeros((q.shape[1], q.shape[2], v.shape[2])), []
    for q_t, k_t, v_t in zip(q, k, v):
        s = s * lam[:, None, None] + np.einsum("hk,hv->hkv", k_t, v_t)
        out.append(np.einsum("hkv,hk->hv", s, q_t))
    return np.stack(out)


def test_the_plain_references_scan_is_that_recurrence():
    from benchmark.reference.minicpm_sala import decayed_state

    rng = np.random.default_rng(0)
    q, k, v, _, _ = operands(rng, 50, 3, 8, 8)
    lam = np.array([0.3, 0.9, 0.999], np.float32)
    np.testing.assert_allclose(
        decayed_state(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(lam)),
        lightning_reference(q, k, v, lam), atol=2e-5)


@pytest.mark.parametrize("heads,dk,dv", SHAPES)
@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_a_step_without_the_delta_term_is_the_decayed_outer_product(
        heads, dk, dv, form):
    rng = np.random.default_rng(heads + dv)
    b = 6
    state = rng.normal(size=(b, heads, dk, dv)).astype(np.float32)
    q, k, v, alpha, _ = operands(rng, b, heads, dk, dv)
    live = np.array([1, 0, 1, 1, 0, 1], bool)
    ones = np.ones_like(alpha)
    rows = gdn.state_rows(jnp.asarray(state))
    step = gdn.gdn_decode_xla if form == "xla" else \
        lambda *a, **kw: gdn.gdn_decode_step(*a, interpret=True, **kw)
    o, new = step(rows, q, k, v, alpha, ones, live, delta=False)
    want_s = state * alpha[:, :, None, None] \
        + np.einsum("bhk,bhv->bhkv", k, v)
    want_o = np.einsum("bhkv,bhk->bhv", want_s, q)
    got_s = np.asarray(gdn.state_heads(new, heads))
    np.testing.assert_allclose(np.asarray(o)[live], want_o[live], atol=3e-6)
    np.testing.assert_allclose(got_s[live], want_s[live], atol=3e-6)
    np.testing.assert_array_equal(got_s[~live], state[~live])
    assert not np.asarray(o)[~live].any()
    # held at decay one and write zero (a step run twice): read and left
    o, new = step(rows, q, k, v, ones, 0 * ones, live, delta=False)
    np.testing.assert_array_equal(new, rows)


@pytest.mark.parametrize("length,chunk", [(128, 64), (40, 64), (150, 64),
                                          (13, 4), (3, 4)])
def test_the_lightning_prefill_is_the_recurrence_and_stops_at_the_length(
        length, chunk):
    """A prompt of ``length`` in a bucket half as long again: the padding
    neither decays nor writes the state; a block's reads leave the scan
    through ``emit``; two stretches hand the state on."""
    rng = np.random.default_rng(length)
    heads, dk, dv = 3, 8, 16
    t = length + length // 2 + 1
    q, k, v, _, _ = operands(rng, t, heads, dk, dv)
    lam = np.array([0.3, 0.9, 0.999], np.float32)
    want = lightning_reference(q[:length], k[:length], v[:length], lam)
    o, state = gdn.lightning_chunk_prefill(q, k, v, np.log(lam), length, chunk)
    np.testing.assert_allclose(o[:length], want, atol=3e-5)
    # the state behind the last REAL position: one more position read from it
    more = lightning_reference(
        np.concatenate([q[:length], q[-1:]]), np.concatenate([k[:length], k[-1:]]),
        np.concatenate([v[:length], v[-1:]]), lam)[-1]
    got, _ = gdn.gdn_decode_xla(
        gdn.state_rows(state)[None], q[-1:], k[-1:], v[-1:], lam[None],
        np.ones((1, heads), np.float32), np.ones(1, bool), delta=False)
    np.testing.assert_allclose(got[0], more, atol=3e-5)
    # in two stretches, the second from the first's state, reads flattened
    cut = (t // 2 // chunk + 1) * chunk
    if cut >= t:
        return
    flat = lambda x: x.reshape(x.shape[0], -1) * 2.0  # noqa: E731
    o1, s1 = gdn.lightning_chunk_prefill(q[:cut], k[:cut], v[:cut],
                                         np.log(lam), length, chunk, flat)
    o2, s2 = gdn.lightning_chunk_prefill(q[cut:], k[cut:], v[cut:], np.log(lam),
                                         length - cut, chunk, flat, s1)
    both = np.concatenate([o1, o2])[:length]
    np.testing.assert_allclose(both, 2.0 * want.reshape(length, -1), atol=6e-5)
    np.testing.assert_allclose(s2, state, atol=3e-5)


def test_the_chip_smokes_lightning_check_rehearsed_at_a_toy_size():
    import chip_smoke

    found = chip_smoke.check_lightning(rows=4, heads=4, d=64, interpret=True)
    assert found["live"] == 3 and found["rel_err"] < 1e-5
    assert found["chunk_prefill_rel_err"] < 1e-4
