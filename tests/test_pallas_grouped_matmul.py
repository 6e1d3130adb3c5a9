"""The grouped-matmul kernel (``grouped_matmul_gate_up`` / ``_down``)
interpreted on the CPU against a plain float32 loop over the groups: groups
of no row, one group of every row, groups that straddle row tiles, rows of
no group poisoned with NaN, a row count that is no whole tile, a decode
step's and a prefill block's loads. The plan of the visits against a
count by hand. ``held_expert_ffn`` by the kernel's path against its
``ragged_dot`` path, value and gradient. And the gate: every reason, the
first failing condition named."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu._mesh_state import active_mesh
from mxnet_tpu.ops import pallas_grouped_matmul as gmm
from mxnet_tpu.parallel import moe

D, W = 256, 128
RELATIVE = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _decode_like():
    # 48 rows of six over 64 experts, all held: 4.5 rows a group
    return np.random.default_rng(3).multinomial(288, np.ones(64) / 64)


# name -> (rows, group sizes, row tile or None for the kernel's own choice)
LOADS = {
    "groups_of_no_row": (64, [0, 40, 0, 0, 24, 0], 16),
    "one_group_of_every_row": (96, [96], 32),
    "groups_astride_the_tiles": (160, [5, 30, 1, 60, 17, 47], 16),
    "rows_of_no_group": (128, [7, 0, 26, 17], 16),
    "rows_no_whole_tile": (77, [3, 40, 20], 16),
    "no_row_at_all": (64, [0, 0, 0], 16),
    "decode_like": (288, _decode_like(), None),
    "prefill_like_an_eighth_held": (2048, [31, 40, 0, 55, 22, 61, 9, 38], None),
}


def _operands(rng, rows, sizes, dtype, widths):
    """Rows with those of no group POISONED, and one matrix a group of each
    width pair."""
    held = int(np.sum(sizes))
    x = rng.standard_normal((rows, widths[0][0])).astype("f4")
    x[held:] = np.nan
    mats = [jnp.asarray(rng.standard_normal((len(sizes), k, n)) * k ** -0.5, dtype)
            for k, n in widths]
    return jnp.asarray(x, dtype), mats, held


def _loop(x, mats, sizes, then=lambda *ys: ys[0]):
    """float32, a group at a time."""
    x = np.asarray(x.astype(jnp.float32))
    out, at = [], 0
    for g, size in enumerate(sizes):
        rows = x[at:at + size]
        out.append(then(*(rows @ np.asarray(m[g].astype(jnp.float32))
                          for m in mats)))
        at += size
    return np.concatenate(out) if out else np.zeros((0, mats[0].shape[2]), "f4")


def _agrees(got, want, dtype):
    assert np.isfinite(got).all()
    if want.size:
        assert np.abs(got - want).max() <= RELATIVE[dtype] * np.abs(want).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("load", list(LOADS))
def test_a_grouped_product_is_the_plain_loop_over_the_groups(load, dtype):
    rows, sizes, tile = LOADS[load]
    x, (w,), held = _operands(np.random.default_rng(0), rows, sizes, dtype,
                              [(D, W)])
    got = gmm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                             row_tile=tile, interpret=True)
    assert got.shape == (rows, W) and got.dtype == jnp.float32
    # the rows of no group are the caller's to mask
    _agrees(np.asarray(got)[:held], _loop(x, [w], sizes), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("load,stretch", [("groups_astride_the_tiles", None),
                                          ("rows_of_no_group", None),
                                          ("decode_like", None),
                                          ("prefill_like_an_eighth_held", 128)])
def test_the_gated_feed_forward_is_the_plain_loop(load, stretch, activation,
                                                  dtype):
    rows, sizes, tile = LOADS[load]
    x, (w_gate, w_up, w_down), held = _operands(
        np.random.default_rng(1), rows, sizes, dtype, [(D, W), (D, W), (W, D)])
    got = gmm.grouped_glu_ffn(x, w_gate, w_up, w_down,
                              jnp.asarray(sizes, jnp.int32), activation,
                              row_tile=tile, sub_rows=stretch, interpret=True)
    act = {"silu": lambda g: g / (1 + np.exp(-g)),
           "relu": lambda g: np.maximum(g, 0)}[activation]
    mid = _loop(x, [w_gate, w_up], sizes, lambda g, u: act(g) * u)
    mid = jnp.asarray(mid).astype(dtype)        # the cast where the layer's is
    _agrees(np.asarray(got)[:held], _loop(mid, [w_down], sizes), dtype)


@pytest.mark.parametrize("tile", [16, 128])
@pytest.mark.parametrize("load", list(LOADS))
def test_the_plan_visits_a_group_once_a_tile_it_has_a_row_in(load, tile):
    rows, sizes, _ = LOADS[load]
    tiles = -(-rows // tile)
    group, of_tile, bounds, visits = gmm._visit_plan(
        jnp.asarray(sizes, jnp.int32), tiles, tile)
    want, at = [], 0
    for g, size in enumerate(sizes):
        if size:
            want += [(g, t) for t in range(at // tile, (at + size - 1) // tile + 1)]
        at += size
    visits = int(visits)
    assert visits == len(want) <= tiles + len(sizes) - 1 == group.shape[0]
    assert list(zip(np.asarray(group)[:visits].tolist(),
                    np.asarray(of_tile)[:visits].tolist())) == want
    assert np.asarray(bounds).tolist() == [0] + np.cumsum(sizes).tolist()
    # no tile past the last group's last row, no group of no row
    assert all(t * tile < at and sizes[g] for g, t in want)
    assert (np.asarray(of_tile) < tiles).all() and (np.asarray(group) < len(sizes)).all()


def test_a_row_tile_that_is_no_whole_stretches_is_refused():
    x = jnp.zeros((64, D), jnp.float32)
    w = jnp.zeros((2, D, W), jnp.float32)
    sizes = jnp.asarray([10, 20], jnp.int32)
    with pytest.raises(ValueError, match="whole stretches"):
        gmm.grouped_matmul(x, w, sizes, row_tile=48, sub_rows=32, interpret=True)
    with pytest.raises(ValueError, match="do not take rows"):
        gmm.grouped_matmul(x, w[:1], sizes, interpret=True)
    with pytest.raises(ValueError, match="against weights in"):
        gmm.grouped_matmul(x, w.astype(jnp.bfloat16), sizes, interpret=True)


# --------------------------------------------------------------------------
# held_expert_ffn through the kernel
# --------------------------------------------------------------------------
N, EXPERTS, TOP_K = 24, 8, 2


def _layer(rng, held):
    h = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((EXPERTS, D)).astype("f4") * 0.3)
    mats = [jnp.asarray(rng.standard_normal(s).astype("f4") * 0.1)
            for s in ((len(held), D, W), (len(held), D, W), (len(held), W, D))]
    return h, router, mats


@pytest.fixture
def kernel_path(monkeypatch):
    """The gate as one TPU chip answers it, the kernel interpreted."""
    def on():
        monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
        monkeypatch.setattr(gmm, "_resolve_interpret", lambda i: True)
    return on


@pytest.mark.parametrize("held", [list(range(EXPERTS)), [1, 5]],
                         ids=["all_held", "few_held"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_layer_by_the_kernel_is_the_layer_by_ragged_dot(
        activation, scoring, held, kernel_path):
    h, router, mats = _layer(np.random.default_rng(5), held)
    how = dict(held_experts=held, top_k=TOP_K, scoring=scoring,
               activation=activation, norm_topk_prob=True, count_hit=True)
    count = obs.counter("moe_path_total")
    ragged = dict(path="sorted_ragged_dot", reason="the backend is not a TPU",
                  route="whole")     # 48 pairs: no prefix is shorter
    kernel = dict(path="pallas_grouped", reason="", route="whole")
    before = count.value(**ragged), count.value(**kernel)
    want, want_stats = moe.held_expert_ffn(h, router, *mats, **how)
    kernel_path()
    layer = jax.jit(lambda h: moe.held_expert_ffn(h, router, *mats, **how))
    got, stats = layer(h)
    layer(h)                                      # counted once a trace
    assert (count.value(**ragged), count.value(**kernel)) == \
        (before[0] + 1, before[1] + 1)
    assert [int(s) for s in stats] == [int(s) for s in want_stats]
    assert float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max()) + 1e-6


@pytest.mark.parametrize("held,wrt", [(list(range(EXPERTS)), (0, 1, 2, 3)),
                                      ([1, 5], (1, 2, 3))],
                         ids=["all_held", "few_held"])
def test_the_gradient_through_the_kernels_path_is_ragged_dots(held, wrt,
                                                              kernel_path):
    """With few held the tokens' own gradient is left out: the router's
    weight of a pair of no group meets ``0 * (an unwritten row)`` on any
    backend that leaves such rows unwritten (the interpreter's are NaN, the
    CPU's ``ragged_dot`` zeroes them), by either path."""
    rng = np.random.default_rng(7)
    h, router, mats = _layer(rng, held)
    weigh = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)

    def loss(h, *mats):
        y, _ = moe.held_expert_ffn(h, router, *mats, held_experts=held,
                                   top_k=TOP_K, norm_topk_prob=True)
        return (y * weigh).sum()

    want = jax.grad(loss, argnums=wrt)(h, *mats)
    kernel_path()
    got = jax.grad(loss, argnums=wrt)(h, *mats)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        assert float(jnp.abs(g - w).max()) <= 1e-5 * float(jnp.abs(w).max())


# --------------------------------------------------------------------------
# the gate
# --------------------------------------------------------------------------
def test_the_gate_names_the_backend_first():
    assert gmm.grouped_matmul_refusal(288, 2560, 768, jnp.bfloat16,
                                      jnp.bfloat16) == "the backend is not a TPU"


@pytest.mark.parametrize("pairs,d,w,x_dtype,w_dtype,words", [
    (288, 2560, 768, jnp.bfloat16, jnp.bfloat16, None),
    (24576, 2560, 768, jnp.bfloat16, jnp.bfloat16, None),
    (768, 5120, 1536, jnp.bfloat16, jnp.bfloat16, None),
    (32768, 5120, 1536, jnp.float32, jnp.float32, None),
    (288, 2560, 768, jnp.float32, jnp.bfloat16,
     "rows in float32 against weights in bfloat16"),
    (288, 2560, 768, jnp.float16, jnp.float16,
     "dtype float16 is not bfloat16 or float32"),
    (288, 16, 8, jnp.float32, jnp.float32,
     "widths 16 and 8 are not whole 128-lane tiles"),
    (288, 2560, 100, jnp.float32, jnp.float32,
     "widths 2560 and 100 are not whole 128-lane tiles"),
    (288, 1 << 17, 128, jnp.bfloat16, jnp.bfloat16,
     "a block over the whole of 131072 x 128 does not fit 48 MiB of VMEM"),
])
def test_the_gate_names_the_first_rule_that_fails(pairs, d, w, x_dtype, w_dtype,
                                                  words, monkeypatch):
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    assert gmm.grouped_matmul_refusal(pairs, d, w, x_dtype, w_dtype) == words


def test_the_gate_refuses_under_a_mesh(monkeypatch):
    monkeypatch.setattr(gmm, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("ep",))
    with active_mesh(mesh):
        assert gmm.grouped_matmul_refusal(
            288, 2560, 768, jnp.bfloat16, jnp.bfloat16) == \
            "a mesh of 2 devices is active"
