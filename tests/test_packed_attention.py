"""The packed short-sequence attention kernel (interpret mode on the CPU)
against ``attention._reference_mha`` on the unpacked tensors, the gate of
``self_attention_packed``, and ``BERTAttention`` against the transposing
code it replaced (kept here as the oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _mesh_state
from mxnet_tpu import observability as obs
from mxnet_tpu.models import bert
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_packed_attention as ppa

HEADS = 2


def _oracle(qkv, mask, heads):
    """What ``BERTAttention.hybrid_forward`` did before the operator:
    reshape, transpose, attend, transpose back."""
    b, t, c3 = qkv.shape
    c = c3 // 3
    x = qkv.reshape((b, t, 3, heads, c // heads)).transpose((2, 0, 3, 1, 4))
    out = att.multi_head_attention(x[0], x[1], x[2], mask=mask)
    return out.transpose((0, 2, 1, 3)).reshape((b, t, c))


def _reference(qkv, mask, heads):
    q, k, v = att._unpack_qkv(qkv, heads)
    return att._merge_heads(att._reference_mha(q, k, v, mask=mask))


def _key_mask(lengths, t):
    lengths = jnp.asarray(lengths, jnp.int32)
    return (jnp.arange(t, dtype=jnp.int32).reshape(1, 1, 1, t)
            < lengths.reshape(-1, 1, 1, 1))


def _weighted(f, w):
    return lambda qkv: jnp.sum(f(qkv).astype(jnp.float32) * w)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "keymask"])
@pytest.mark.parametrize("t,d", [(128, 64), (256, 64), (128, 128), (512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_matches_reference(dtype, t, d, masked):
    rs = np.random.RandomState(t + d)
    b, c = 3, HEADS * d
    qkv = jnp.asarray(rs.randn(b, t, 3 * c), dtype)
    w = jnp.asarray(rs.randn(b, t, c), jnp.float32)
    lengths = [1, t // 2 + 3, t]
    mask = _key_mask(lengths, t) if masked else None

    def kernel(x):
        return ppa.packed_attention(x, mask, HEADS, interpret=True)

    def reference(x):
        return _reference(x, mask, HEADS)

    def readings(f, x):
        out, dqkv = f(x), jax.grad(_weighted(f, w))(x)
        assert out.shape == (b, t, c) and dqkv.shape == x.shape
        assert out.dtype == dqkv.dtype == x.dtype
        return [np.asarray(a, np.float32) for a in
                (out, dqkv[..., :c], dqkv[..., c:2 * c], dqkv[..., 2 * c:])]

    got = readings(kernel, qkv)
    # the reference in float32 on the same values. float32: 1e-5 of it.
    # bfloat16: one step of the format (2^-7 of the largest value) of it,
    # and, since the bfloat16 einsum path rounds its scores and is itself
    # up to two steps from there, three steps of that path
    exact = readings(reference, qkv.astype(jnp.float32))
    checks = [(exact, 1e-5 if dtype == jnp.float32 else 2.0 ** -7)]
    if dtype == jnp.bfloat16:
        checks.append((readings(reference, qkv), 3 * 2.0 ** -7))
    for want, step in checks:
        for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
            assert np.all(np.isfinite(a)), name
            assert np.max(np.abs(a - r)) <= step * max(1.0, np.max(np.abs(r))), \
                (name, step)
    if masked:  # a masked key takes no gradient at all: exactly 0
        for row, n in enumerate(lengths):
            assert not np.any(got[2][row, n:]) and not np.any(got[3][row, n:])


def _count(path):
    return obs.counter("attention_path_total").value(path=path)


GATE = {  # name: (qkv shape, dtype, mask, heads, mesh devices, what the reason names)
    "query_dependent_mask": ((2, 128, 384), jnp.float32, "full", 2, 1,
                             "not keys-only"),
    "t96": ((2, 96, 384), jnp.float32, "keys", 2, 1, "sequence length 96"),
    "d80": ((2, 128, 3 * 8 * 80), jnp.float32, "keys", 8, 1, "head size 80"),
    "float16": ((2, 128, 384), jnp.float16, "keys", 2, 1, "float16"),
    "mesh_of_two": ((2, 128, 384), jnp.float32, "keys", 2, 2,
                    "mesh of 2 devices"),
    "t2048": ((1, 2048, 384), jnp.float32, None, 2, 1, "bytes of VMEM"),
}


@pytest.mark.parametrize("case", sorted(GATE))
def test_gate_refuses_and_falls_back(case, monkeypatch):
    shape, dtype, mask_kind, heads, n_mesh, reason = GATE[case]
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)  # the gate's view only
    b, t, _ = shape
    rs = np.random.RandomState(1)
    qkv = jnp.asarray(rs.randn(*shape), dtype)
    mask = {None: None, "keys": _key_mask([t // 2, t][:b], t),
            "full": jnp.asarray(rs.rand(b, 1, t, t) < 0.7).at[..., 0].set(True)
            }[mask_kind]
    mesh = (jax.sharding.Mesh(np.array(jax.devices()[:n_mesh]), ("dp",))
            if n_mesh > 1 else None)
    with _mesh_state.active_mesh(mesh):
        why = ppa.packed_attention_refusal(qkv, mask, heads)
        assert why is not None and reason in why
        before = {p: _count(p) for p in ("packed_kernel", "flash", "einsum")}
        got = att.self_attention_packed(qkv, mask=mask, heads=heads)
    after = {p: _count(p) for p in before}
    assert after == {**before, "einsum": before["einsum"] + 1}
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(_oracle(qkv, mask, heads),
                                             np.float32))


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "as_if_tpu"])
def test_gate_admits_the_cells_shape_on_a_tpu_only(tpu, monkeypatch):
    monkeypatch.setattr(ppa, "_on_tpu", lambda: tpu)
    rs = np.random.RandomState(2)
    qkv = jnp.asarray(rs.randn(2, 128, 384), jnp.bfloat16)
    mask = _key_mask([70, 128], 128)
    why = ppa.packed_attention_refusal(qkv, mask, HEADS)
    path = "packed_kernel" if tpu else "einsum"
    assert (why is None) if tpu else ("not a TPU" in why)
    before = _count(path)
    got = att.self_attention_packed(qkv, mask=mask, heads=HEADS)
    assert _count(path) == before + 1
    want = np.asarray(_oracle(qkv, mask, HEADS), np.float32)
    if tpu:  # the kernel ran (interpreted: the backend is still the CPU)
        assert np.max(np.abs(np.asarray(got, np.float32) - want)) \
            <= 2.0 ** -7 * np.max(np.abs(want))
    else:
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)


class _TransposingAttention(bert.BERTAttention):
    """The code ``BERTAttention.hybrid_forward`` held before
    ``self_attention_packed``, verbatim."""

    def hybrid_forward(self, F, x, mask=None):
        b, t, c = x.shape
        h = self._heads
        qkv = self.qkv(x)  # (B, T, 3C)
        qkv = qkv.reshape((b, t, 3, h, c // h)).transpose((2, 0, 3, 1, 4))
        q, k, v = qkv[0], qkv[1], qkv[2]  # (B, H, T, Ch)
        out = F.multi_head_attention(q, k, v, mask=mask)
        out = out.transpose((0, 2, 1, 3)).reshape((b, t, c))
        return self.dropout(self.proj(out))


@pytest.mark.parametrize("hybridize", [False, True], ids=["eager", "hybrid"])
def test_bert_attention_bit_identical_to_the_transposing_code(hybridize):
    rs = np.random.RandomState(3)
    b, t, units = 2, 128, 128
    x_np = rs.randn(b, t, units).astype(np.float32)
    w = mx.nd.array(rs.randn(b, t, units).astype(np.float32))
    mask = mx.nd.array(np.asarray(_key_mask([50, 128], t)))
    results = []
    weights = None
    for cls in (bert.BERTAttention, _TransposingAttention):
        net = cls(units, HEADS, dropout=0.0, prefix="attn_")
        net.initialize(mx.init.Normal(0.5))
        x = mx.nd.array(x_np)
        x.attach_grad()
        net(x, mask)  # shapes, so that parameters exist
        params = [p for _, p in sorted(net.collect_params().items())]
        if weights is None:
            weights = [p.data().asnumpy() for p in params]
        for p, value in zip(params, weights):
            p.set_data(mx.nd.array(value))
        if hybridize:
            net.hybridize()
        with mx.autograd.record():
            y = net(x, mask)
            loss = (y * w).sum()
        loss.backward()
        results.append([y.asnumpy(), x.grad.asnumpy()]
                       + [p.grad().asnumpy() for p in params])
    for a, r in zip(*results):
        assert np.any(a != 0)
        np.testing.assert_array_equal(a, r)
