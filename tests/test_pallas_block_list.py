"""The block-list decode read (``pallas_paged_attention.paged_gqa_read(selected=)``,
interpret mode) against the XLA gather of the listed pages
(``attention._paged_block_gather_read``), float32 on the CPU: lists of one
block and of every block, lists that end inside a chunk, other lists a
key-value head, positions inside the last listed block; what was not fetched
counts for nothing; the gate's reasons."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_paged_attention as ppa


def case(rng, counts, heads=4, kv=2, ch=128, ps=8, pages=40, length=16,
         fill=None):
    """Random pools and, a row and key-value head, a sorted list of
    ``length`` blocks of which ``counts`` count; the query stands in the
    last block either head lists."""
    counts = np.asarray(counts, np.int32)
    b = len(counts)
    q = jnp.asarray(rng.normal(size=(b, heads, 1, ch)), jnp.float32)
    pools = [rng.normal(size=(pages + 1, ps, kv * ch)).astype(np.float32)
             for _ in range(2)]
    blocks = np.sort(rng.permuted(np.tile(np.arange(2 * length), (b, kv, 1)),
                                  axis=2)[:, :, :length], axis=2).astype(np.int32)
    page_ids = rng.integers(1, pages + 1, (b, kv, length)).astype(np.int32)
    last = np.array([max(blocks[i, g, counts[i, g] - 1] for g in range(kv))
                     for i in range(b)])
    position = (last * ps + rng.integers(0, ps, b)).astype(np.int32)
    if fill is not None:   # pages no list names hold ``fill``
        named = {int(page_ids[i, g, j]) for i in range(b) for g in range(kv)
                 for j in range(counts[i, g])}
        for pool in pools:
            pool[[p for p in range(pages + 1) if p not in named]] = fill
    return (q, jnp.asarray(pools[0]), jnp.asarray(pools[1]),
            jnp.asarray(page_ids), jnp.asarray(blocks * ps),
            jnp.asarray(counts), jnp.asarray(position))


def _read_selected(q, k_pool, v_pool, pages, starts, counts, position, **kw):
    """``paged_gqa_read`` over a table of selected pages."""
    return ppa.paged_gqa_read(q, k_pool, v_pool, None, position,
                              selected=(pages, starts, counts), **kw)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_kernel_is_the_gather_of_the_listed_pages(chunk):
    rng = np.random.default_rng(chunk)
    args = case(rng, [[1, 3], [16, 9], [8, 8], [2, 16], [5, 1]])
    want = att._paged_block_gather_read(*args)
    got = _read_selected(*args, block_pages=chunk, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_group_of_sixteen_heads_and_bfloat16_pools():
    rng = np.random.default_rng(1)
    q, kp, vp, *rest = case(rng, [[16, 16], [4, 7], [1, 1]], heads=32, ps=16)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    want = att._paged_block_gather_read(q, kp, vp, *rest)
    got = _read_selected(q, kp, vp, *rest, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-2)


def test_what_no_list_names_counts_for_nothing():
    """NaN in every page that no list names, the trash page among them, and
    in the listed pages past a list's count: neither form reads them."""
    rng = np.random.default_rng(2)
    args = case(rng, [[3, 5], [16, 2], [1, 9]], fill=np.nan)
    want = att._paged_block_gather_read(*args)
    got = _read_selected(*args, interpret=True)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-6), ("bfloat16", 2e-3)])
def test_the_scoring_kernel_is_the_models_einsum(dtype, atol):
    """A group's summed softmax weights over the compressed keys wholly at
    or before the query: rows with every key valid, some, one and none."""
    from mxnet_tpu.models.minicpm_sala import key_weights

    rng = np.random.default_rng(3)
    b, kv, group, ch, n = 5, 2, 3, 128, 256
    cfg = dict(kernel_size=4, kernel_stride=2)
    q = jnp.asarray(rng.normal(size=(b, kv, group, ch)), dtype)
    keys = jnp.asarray(rng.normal(size=(b, n, kv * ch)), dtype)
    position = jnp.asarray([2 * n + 5, 301, 3, 1, 200], jnp.int32)
    want = key_weights(q, keys.reshape(b, n, kv, ch), position, cfg)
    got = ppa.paged_block_scores(q, keys, position, 4, 2, interpret=True)
    np.testing.assert_allclose(got, want, atol=atol)
    valid = np.arange(n)[None, :] * 2 + 3 <= np.asarray(position)[:, None]
    assert not np.asarray(got)[~np.broadcast_to(valid[:, None], got.shape)].any()
    np.testing.assert_allclose(np.asarray(got).sum(-1)[valid.any(1)], group,
                               atol=3 * atol * n)
    assert not np.asarray(got)[3].any()      # no key is whole yet: no weight


def test_the_gate_names_why_the_kernel_does_not_run(monkeypatch):
    shape = jax.ShapeDtypeStruct
    q = shape((64, 32, 1, 128), jnp.bfloat16)
    pool = shape((24577, 64, 256), jnp.bfloat16)
    lists = shape((64, 2, 128), jnp.int32)
    assert ppa.paged_gqa_selected_refusal(q, pool, lists) \
        == "the backend is not a TPU"
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)
    assert ppa.paged_gqa_selected_refusal(q, pool, lists) is None
    assert "2 queries a row" in ppa.paged_gqa_selected_refusal(
        shape((64, 32, 2, 128), jnp.bfloat16), pool, lists)
    assert "whole groups" in ppa.paged_gqa_selected_refusal(
        shape((64, 4, 1, 8), jnp.float32), shape((97, 8, 16), jnp.float32),
        shape((64, 2, 8), jnp.int32))
    assert "not whole chunks" in ppa.paged_gqa_selected_refusal(
        q, pool, shape((64, 2, 100), jnp.int32))
    assert "sublanes" in ppa.paged_gqa_selected_refusal(
        q, shape((97, 8, 256), jnp.bfloat16), lists)
    q4, keys = shape((64, 2, 16, 128), jnp.bfloat16), \
        shape((64, 2304, 256), jnp.bfloat16)
    assert ppa.paged_block_scores_refusal(q4, keys) is None
    assert "not both" in ppa.paged_block_scores_refusal(
        shape(q4.shape, jnp.float32), keys)
    assert "lane tiles" in ppa.paged_block_scores_refusal(
        q4, shape((64, 96, 256), jnp.bfloat16))


def _pooled_weights(q, ck, at, cfg, n_blocks):
    """The XLA form: ``key_weights`` pooled by ``block_scores``, (Hkv, S,
    M)."""
    from mxnet_tpu.models import minicpm_sala as sala

    return jnp.moveaxis(sala.pooled_weights(
        sala.key_weights(q, ck, at, cfg), at, cfg, n_blocks), 1, 0)


_SELECTOR = dict(kernel_size=32, kernel_stride=16, block_size=64)
# (dtype, tokens of the bucket, the stretch's first position, queries,
# selector): key counts that are (2,047 + 1 of 32 every 16 over 32,768 is
# not; 128 x 4 of 4 every 2 over 1,030 is) and are not multiples of 128, a
# stretch from position 0 (its first queries see no whole key) and ones that
# start past a dense length, the last stretch of a bucket (its padding's
# keys are weighed like any other), two keys that reach into the next block
CHUNK_CASES = [
    ("float32", 4096, 0, 64, _SELECTOR),
    ("float32", 4096, 4032, 64, _SELECTOR),
    ("float32", 32768, 8192, 32, _SELECTOR),
    ("bfloat16", 32768, 32704, 64, _SELECTOR),
    ("bfloat16", 12288, 8192, 64, _SELECTOR),
    ("float32", 1030, 512, 32, dict(kernel_size=4, kernel_stride=2,
                                    block_size=8)),
    ("float32", 2000, 1024, 32, dict(kernel_size=6, kernel_stride=2,
                                     block_size=8)),
    ("bfloat16", 2000, 1968, 32, dict(kernel_size=6, kernel_stride=2,
                                      block_size=8)),
]


@pytest.mark.parametrize("dtype,tokens,first,queries,cfg", CHUNK_CASES)
def test_the_prefills_scoring_kernel_is_the_models_pooled_weights(
        dtype, tokens, first, queries, cfg):
    """A stretch's block scores, pooled inside the kernel from phases of the
    compressed keys, against ``key_weights`` + ``block_scores`` to float32
    rounding: the same blocks are ``-inf`` (no whole key touches them)."""
    rng = np.random.default_rng(11)
    kv, group, ch = 2, 3, 128
    size, stride, block = (cfg[k] for k in (
        "kernel_size", "kernel_stride", "block_size"))
    j, n_blocks = tokens // stride - size // stride + 1, -(-tokens // block)
    q = jnp.asarray(rng.normal(size=(queries, kv, group, ch)), dtype)
    ck = jnp.asarray(rng.normal(size=(j, kv, ch)), dtype)
    at = first + jnp.arange(queries, dtype=jnp.int32)
    want = np.asarray(_pooled_weights(q, ck, at, cfg, n_blocks))
    got = np.asarray(ppa.sparse_chunk_scores(
        q, ppa.sparse_chunk_keys(ck, block, stride), first, block, size,
        stride, interpret=True))
    assert got.shape[:2] == (kv, queries) and got.shape[2] % 128 == 0
    assert np.isneginf(got[:, :, n_blocks:]).all()
    got = got[:, :, :n_blocks]
    np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
    seen = np.isfinite(want)
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-6, atol=1e-7)
    assert (got[seen] >= 0).all() and got[seen].max() <= group * (1 + 1e-6)


CHUNK_REFUSALS = [
    ("knob", "paged_attention_kernel knob is off"),
    ("cpu", "the backend is not a TPU"),
    ("float16", "queries float16 and keys float16 are not both float32 or "
                "both bfloat16"),
    ("mixed", "queries float32 and keys bfloat16 are not both float32 or "
              "both bfloat16"),
    ("tiles", "4100 queries against keys (4095, 2, 128) under 2 heads of 128 "
              "are not whole tiles of 16 queries and 128 lanes"),
    ("lanes", "4096 queries against keys (4095, 2, 64) under 2 heads of 64 "
              "are not whole tiles of 16 queries and 128 lanes"),
    ("phases", "compressed keys of 32 every 24 do not lie in phases of a "
               "block of 64"),
    # the published max_length's keys and their scores are not held in VMEM
    ("vmem", "a head's 32767 compressed keys and their scores need 54394880 "
             "bytes of VMEM (budget 25165824)"),
    ("mesh", "a mesh of 2 devices is active"),
    ("none", None),
]


@pytest.mark.parametrize("case,reason", CHUNK_REFUSALS,
                         ids=[c for c, _ in CHUNK_REFUSALS])
def test_the_prefills_scoring_gate_names_why_it_does_not_run(monkeypatch,
                                                             case, reason):
    """Every reason of ``sparse_chunk_scores_refusal`` by its words; the
    three selecting buckets of the cell pass."""
    from mxnet_tpu import config

    dtype = {"float16": jnp.float16, "mixed": jnp.float32}.get(
        case, jnp.bfloat16)
    ch = 64 if case == "lanes" else 128
    q = jax.ShapeDtypeStruct((4100 if case == "tiles" else 4096, 2, 16, ch),
                             dtype)
    keys = lambda n: jax.ShapeDtypeStruct(  # noqa: E731
        (n, 2, ch), jnp.bfloat16 if case == "mixed" else dtype)
    monkeypatch.setattr(ppa, "_on_tpu", lambda: case != "cpu")
    if case == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
        monkeypatch.setattr(ppa, "current_mesh", lambda: mesh)
    was = config.get("paged_attention_kernel")
    config.set("paged_attention_kernel", case != "knob")
    try:
        assert ppa.sparse_chunk_scores_refusal(
            q, keys(32767 if case == "vmem" else 4095), 64, 32,
            24 if case == "phases" else 16) == reason
        if case == "none":
            for tokens in (16384, 32768):
                assert ppa.sparse_chunk_scores_refusal(
                    q, keys(tokens // 16 - 1), 64, 32, 16) is None
    finally:
        config.set("paged_attention_kernel", was)


# the toy selector (tests/test_minicpm_sala.py) under heads of 128, the width
# the kernel's gate admits: 5 blocks of 4 selected, of which the first, the
# query's own and the one before it are forced; dense up to 8 keys
_TOY = dict(num_heads=4, num_kv_heads=2, head_dim=128, units=32,
            rms_norm_eps=1e-6, kernel_size=2, kernel_stride=1, block_size=4,
            topk=5, init_blocks=1, window_size=4, dense_len=8)


def _toy_selection(keys, dtype, tokens=192, first=64, queries=128):
    """(with the kernel, without it, positions): the blocks ``_taken`` marks
    for a stretch of the toy layer, ``keys``: how the compressed keys are
    drawn."""
    from mxnet_tpu.models.minicpm_sala import BlockSparseAttention

    layer = BlockSparseAttention(_TOY, prefix="toy_")
    rng = np.random.default_rng(5)
    j = tokens - 1
    q = jnp.asarray(rng.normal(size=(queries, 2, 2, 128)), dtype)
    ck = {"random": rng.normal(size=(j, 2, 128)),
          # every score equal: the choice is by position alone
          "equal": np.zeros((j, 2, 128)),
          # runs of equal keys: ties among scores that differ elsewhere
          "runs": np.repeat(rng.normal(size=(-(-j // 24), 2, 128)), 24, 0)[:j],
          }[keys]
    ck = jnp.asarray(ck, dtype)
    at = first + jnp.arange(queries, dtype=jnp.int32)
    laid = ppa.sparse_chunk_keys(ck, 4, 1)
    n_blocks = tokens // 4
    return (np.asarray(layer._taken(q, ck, at, n_blocks, laid)),
            np.asarray(layer._taken(q, ck, at, n_blocks)), np.asarray(at))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("keys", ["random", "equal", "runs"])
def test_the_selection_marks_the_same_blocks_with_the_kernel(monkeypatch, keys,
                                                             dtype):
    """``_taken`` through ``sparse_chunk_scores`` (interpreted) and through
    ``key_weights``: the same blocks, every forced one among them, exactly
    ``topk`` a query, equal scores in the order of their positions."""
    monkeypatch.setattr(ppa, "_resolve_interpret", lambda i: True)
    got, want, at = _toy_selection(keys, dtype)
    np.testing.assert_array_equal(got, want)
    assert got.shape == (2, 128, 48) and (got.sum(-1) == 5).all()
    own = at // 4
    for m in (np.zeros_like(own), own - 1, own):       # the forced blocks
        assert got[:, np.arange(128), m].all()
    assert not (got & (np.arange(48)[None, None] > own[None, :, None])).any()
    if keys == "equal":    # the first two by position beside the forced
        assert got[:, :, 1:3].all()


def test_a_stretch_that_crosses_the_dense_length_reads_all_it_holds_before_it(
        monkeypatch):
    monkeypatch.setattr(ppa, "_resolve_interpret", lambda i: True)
    got, want, at = _toy_selection("random", "float32", tokens=64, first=0,
                                   queries=64)
    np.testing.assert_array_equal(got, want)
    held = np.arange(16)[None] <= (at // 4)[:, None]
    np.testing.assert_array_equal(got[0][at < 8], held[at < 8])
    assert (got[:, at >= 20].sum(-1) == 5).all()


@pytest.mark.parametrize("tpu,path,reason", [
    (True, "chunk_scores_kernel", ""),
    (False, "chunk_scores_xla", "the backend is not a TPU")])
def test_a_prefill_counts_which_scoring_it_was_built_with(monkeypatch, tpu,
                                                          path, reason):
    from mxnet_tpu import observability as obs
    from mxnet_tpu.models.minicpm_sala import BlockSparseAttention

    monkeypatch.setattr(ppa, "_on_tpu", lambda: tpu)
    layer = BlockSparseAttention(_TOY, prefix="toy_")
    counter = obs.counter("sparse_read_path_total")
    before = counter.value(path=path, reason=reason)
    laid = layer._chunk_keys(jnp.zeros((63, 2, 128), jnp.float32), 32)
    assert counter.value(path=path, reason=reason) == before + 1
    assert (laid is not None) == tpu
    if tpu:    # (Hkv, phases, key tiles, blocks a tile, Ch)
        assert laid.shape == (2, 4, 1, 128, 128)


def test_the_chip_smokes_check_rehearsed_at_a_toy_size():
    import chip_smoke

    found = chip_smoke.check_block_list(rows=4, heads=4, pages=40, ps=8,
                                        length=16, dtype="float32",
                                        interpret=True)
    assert found["rel_err"] < 1e-5 and found["lists"][0] == 1


def test_the_chip_smokes_scoring_check_rehearsed_at_a_toy_size():
    import chip_smoke

    found = chip_smoke.check_chunk_scores(queries=32, heads=4, tokens=2048,
                                          first=1024, dtype="float32",
                                          interpret=True)
    assert found["max_err"] < 1e-6 and found["keys"] == 127


# -- the kernels that take no table of selected pages are the parent's -------
# SHA-256 of the kernels' programs as jax traces them (the jaxpr's text, the
# pallas_call's body in it, source locations taken out), at SmallThinker's
# and Olmo-Hybrid's shapes, recorded on the tree of PR 41 (PR 43's parent):
# ``paged_gqa_read`` without ``selected=`` and ``gdn_decode_step`` with the
# delta rule build, operation for operation, what they built there.
_PARENTS = {
    "smallthinker_full":
        "cdbccc4eadc90e1d3e830d452fc72728daf1591a32d8102280e4405455c06afc",
    "smallthinker_window":
        "10318698b3ae0700c3f095eb9b16f5a7de55cf2f809bee4c027c76392b25b8c8",
    "olmo_full":
        "8a1b3f1cf1c797de47037ab40ac39793ac35c9e819fc4de14e2917ee7f9aec87",
    "olmo_gdn":
        "7cadacbc86ba2654589be728bd43c4ac1d8482539498e70af0df22350537eda6",
}


def _traced(name):
    from mxnet_tpu.ops import pallas_gdn as gdn
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    gqa = lambda window: (  # noqa: E731
        lambda q, k, v, t, p: ppa.paged_gqa_read(q, k, v, t, p, window,
                                                 interpret=False))
    pools = lambda pages, w: [((pages + 1, 16, w), bf)] * 2  # noqa: E731
    fn, shapes = {
        "smallthinker_full": (gqa(None), [
            ((48, 28, 1, 128), bf), *pools(24576, 512), ((48, 640), i32),
            ((48,), i32)]),
        "smallthinker_window": (gqa(4096), [
            ((48, 28, 1, 128), bf), *pools(12416, 512), ((48, 259), i32),
            ((48,), i32)]),
        "olmo_full": (gqa(None), [
            ((48, 30, 1, 128), bf), *pools(3584, 3840), ((48, 256), i32),
            ((48,), i32)]),
        "olmo_gdn": (
            lambda s, q, k, v, a, b, live: gdn.gdn_decode_step(
                s, q, k, v, a, b, live, interpret=False),
            [((48, 96, 5760), f32), ((48, 30, 96), f32), ((48, 30, 96), f32),
             ((48, 30, 192), f32), ((48, 30), f32), ((48, 30), f32),
             ((48,), jnp.bool_)]),
    }[name]
    return str(jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, d)
                                    for s, d in shapes)))


@pytest.mark.parametrize("name", sorted(_PARENTS))
def test_a_kernel_that_takes_no_table_builds_the_parents_program(name):
    import hashlib
    import re

    text = re.sub(r" at [^\s\]\)]*:\d+", "", _traced(name))
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENTS[name]
