"""Both plain references against the repo's models at a small size, and the
precisions the controls compute in."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import bert as ref_bert, gpt2 as ref_gpt2, precision
from benchmark.reference.norms import worst_leaf_gap
from benchmark.systems import bert as sys_bert, gpt2 as sys_gpt2
from benchmark.weights import make_weights, seed_key, weights_by_leaf

from benchmark_tiny import tiny_bert, tiny_gpt2


def test_weights_repeat_by_seed_and_take_a_large_seed():
    specs = ref_gpt2.param_specs(tiny_gpt2())
    a, b = make_weights(specs, 2**31 + 9), make_weights(specs, 2**31 + 9)
    c = make_weights(specs, 9)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["embed.word"] == c["embed.word"]).all()
    assert float(jnp.std(a["embed.word"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(a["embed.position"])) == pytest.approx(0.01, rel=0.05)
    assert (a["layer0.ln1.gamma"] == 1).all() and (a["layer0.qkv.b"] == 0).all()
    assert jax.random.key_data(seed_key(2**31 + 1)).tolist() != \
        jax.random.key_data(seed_key(1)).tolist()
    # leaf by leaf (the pass that holds no second copy) gives the same weights
    one_by_one = dict(weights_by_leaf(specs, 2**31 + 9))
    assert set(one_by_one) == set(a)
    assert all((one_by_one[k] == a[k]).all() for k in a)


def test_bert_reference_agrees_with_the_repos_model_in_float32():
    from mxnet_tpu import nd
    from mxnet_tpu.models import bert

    cfg = tiny_bert()
    weights = make_weights(ref_bert.param_specs(cfg), 5)
    net = bert.get_bert("bert_large", pretrain_head=True, dropout=0.0,
                        num_layers=2, units=64, hidden_size=256, num_heads=2,
                        max_length=32, vocab_size=500)
    names = sys_bert.hand_over(net, weights)
    assert len(names) == len(weights)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 500, (4, 16)).astype(np.int32)
    types = rs.randint(0, 2, (4, 16)).astype(np.int32)
    valid = np.array([16, 9, 12, 16], np.int32)
    pos = rs.randint(0, 9, (4, 3)).astype(np.int32)
    labels = rs.randint(0, 500, (4, 3)).astype(np.int32)
    nsp = rs.randint(0, 2, 4).astype(np.int32)
    mlm, nsp_scores = net(*(nd.array(a, dtype="int32")
                            for a in (ids, types, valid, pos)))
    want_mlm, want_nsp = ref_bert.forward(weights, cfg, ids, types, valid, pos)
    np.testing.assert_allclose(mlm.asnumpy(), want_mlm, atol=2e-5)
    np.testing.assert_allclose(nsp_scores.asnumpy(), want_nsp, atol=2e-5)
    got = bert.pretrain_loss(mlm, nsp_scores, nd.array(labels, dtype="int32"),
                             nd.ones((4, 3)), nd.array(nsp, dtype="int32"))
    block = (ids, types, valid, pos, labels, np.ones((4, 3), np.float32), nsp)
    # two blocks of two rows add up to the whole batch's loss
    want = sum(float(ref_bert.loss_part(
        weights, cfg, tuple(a[r:r + 2] for a in block), 12.0, 4.0, "float32"))
        for r in (0, 2))
    assert float(got.asnumpy()) == pytest.approx(want, rel=1e-5)


def test_gpt2_reference_agrees_with_the_repos_model_in_float32():
    from mxnet_tpu import nd
    from mxnet_tpu.models import gpt2

    cfg = tiny_gpt2()
    weights = make_weights(ref_gpt2.param_specs(cfg), 6)
    net = gpt2.get_gpt2("gpt2_345m", dropout=0.0, num_layers=2, units=64,
                        num_heads=2, max_length=128, vocab_size=500)
    sys_bert.hand_over(net, weights, sys_gpt2._NAMES)
    tokens = np.random.RandomState(1).randint(1, 500, 21).astype(np.int32)
    got = net(nd.array(tokens[None], dtype="int32")).asnumpy()[0]
    want = ref_gpt2.next_token_logits(weights, cfg, tokens.tolist(), 4, 17,
                                      pad_to=32, out_pad=8)
    assert want.shape == (17, 500)
    np.testing.assert_allclose(got[4:], want, atol=2e-5)


def test_an_unknown_parameter_of_the_program_is_an_error():
    with pytest.raises(KeyError):
        sys_bert.reference_key("bertmodel0_new_thing_weight")


def test_lower_precisions_round_operands_and_pass_gradients_through():
    x = jnp.asarray([0.1234567, -3.14159, 100.7], jnp.float32)
    assert (precision.operand(x, "float32") == x).all()
    bf = precision.operand(x, "bfloat16")
    assert (bf == x.astype(jnp.bfloat16).astype(jnp.float32)).all()
    f8 = precision.operand(x, "fp8")
    err = lambda y: float(jnp.max(jnp.abs(y - x) / jnp.abs(x)))  # noqa: E731
    assert 0 < err(bf) < 2 ** -8 < err(f8) < 2 ** -3
    grad = jax.grad(lambda v: jnp.sum(precision.operand(v, "fp8") ** 2))(x)
    np.testing.assert_allclose(grad, 2 * f8, rtol=1e-6)
    with pytest.raises(ValueError):
        precision.operand(x, "int4")


def test_worst_leaf_gap_measures_against_the_median_leaf_at_least():
    want = {"a": 10.0, "b": 1.0, "c": 1e-9}
    gap, leaf = worst_leaf_gap({"a": 10.5, "b": 1.2, "c": 0.1}, want)
    # c's own norm is all but zero: it is measured against the median leaf
    assert (leaf, gap) == ("b", pytest.approx(0.2))
    gap, leaf = worst_leaf_gap({"a": 10.0, "b": 1.0, "c": 0.5}, want)
    assert (leaf, gap) == ("c", pytest.approx(0.5))


def test_decode_bytes_and_train_flops_follow_the_shapes():
    from benchmark.harness import load_json
    import os
    from benchmark_tiny import REPO

    g = load_json(os.path.join(REPO, "benchmark/configs/gpt2_345m.json"))
    n_params = sum(int(np.prod(s)) for _, s, _ in ref_gpt2.param_specs(g))
    empty = ref_gpt2.decode_step_bytes(g, 0)
    # every weight but the position table, float32
    assert empty == (n_params - 1024 * 1024) * 4
    assert ref_gpt2.decode_step_bytes(g, 1000) - empty == 1000 * 2 * 24 * 1024 * 2
    b = load_json(os.path.join(REPO, "benchmark/configs/bert_large.json"))
    assert ref_bert.train_flops(b, 64, 128, 20) == pytest.approx(1.5393e13, rel=1e-4)
