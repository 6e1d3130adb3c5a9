"""The paged attention kernel against the XLA gather path (the kernel
interpreted on the CPU), and the token-major pool both read.

The XLA path is the dense cache's own arithmetic on a gathered history,
bit for bit (`test_paged_inference.py` asserts that through the engine).
The kernel takes the heads of a lane tile in one product over 128 lanes
and only the first 128, 256, ... keys of a history, so on the CPU its sums
run in another order than the einsum's: it is held to the XLA path within
a few units of float32's last place, and to ITSELF exactly wherever what it
must not read changes (stale or NaN pages, the other rows of the batch)."""
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import config as _config
from mxnet_tpu import observability as obs
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_paged_attention as ppa

# float32 sums of up to 1,024 products of operands of size about 1, taken in
# another order: some 1e-6 of an output of size about 1
CLOSE = dict(rtol=2e-5, atol=2e-5)


def _assert_close(got, ref, pool_dtype=jnp.float32):
    """Within CLOSE; for a bfloat16 pool a softmax weight whose float32 value
    differs in its last place may round to the next bfloat16 (1/256 of the
    weight): rare, and small."""
    got, ref = np.asarray(got), np.asarray(ref)
    if pool_dtype != jnp.bfloat16:
        return np.testing.assert_allclose(got, ref, **CLOSE)
    np.testing.assert_allclose(got, ref, rtol=0, atol=4e-3)
    off = ~np.isclose(got, ref, **CLOSE)
    assert off.mean() < 0.01, f"{off.mean():.2%} of the outputs differ"


def _mk_case(rs, b, h, tq, ch, ps, n_pages, pool_pages, dtype=jnp.float32,
             qdtype=jnp.float32, position=None):
    k_pool = jnp.asarray(rs.randn(pool_pages + 1, ps, h * ch), dtype)
    v_pool = jnp.asarray(rs.randn(pool_pages + 1, ps, h * ch), dtype)
    table = jnp.asarray(rs.randint(1, pool_pages + 1, (b, n_pages)), jnp.int32)
    cap = n_pages * ps
    if position is None:
        position = rs.randint(0, cap - tq + 1, (b,))
    position = jnp.asarray(position, jnp.int32)
    q = jnp.asarray(rs.randn(b, h, tq, ch), qdtype)
    k_new = jnp.asarray(rs.randn(b, h, tq, ch), qdtype)
    v_new = jnp.asarray(rs.randn(b, h, tq, ch), qdtype)
    return q, k_new, v_new, k_pool, v_pool, table, position


def _both(case):
    """(XLA path, kernel) on one case: outputs and both pools."""
    ref = att._paged_gather_mha(*case)
    got = ppa.paged_attention(*case, interpret=True)
    for r, g in zip(ref[1:], got[1:]):      # the pools: one write, exact
        np.testing.assert_array_equal(np.asarray(r, np.float32),
                                      np.asarray(g, np.float32))
    assert got[0].shape == ref[0].shape and got[0].dtype == jnp.float32
    return np.asarray(ref[0]), np.asarray(got[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq", [1, 5])
def test_paged_kernel_matches_gather(dtype, tq):
    rs = np.random.RandomState(0)
    case = _mk_case(rs, b=3, h=2, tq=tq, ch=16, ps=8, n_pages=8,
                    pool_pages=12, dtype=dtype)
    ref, got = _both(case)
    _assert_close(got, ref, dtype)


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq", [1, 5])
def test_paged_kernel_cell_shape_class(qdtype, tq):
    """GPT-2 345M's own shape class: 16 heads of 64 (two to a lane tile),
    pages of 16, a bfloat16 pool; a small table. Rows of length 0, 1, one
    page exactly, one past a page, mid-table, and the table's full width,
    which between them take every stretch of keys the kernel has."""
    rs = np.random.RandomState(4)
    cap = 16 * 16
    position = [0, 1, 15, 16, 131, cap - tq]
    case = _mk_case(rs, b=6, h=16, tq=tq, ch=64, ps=16, n_pages=16,
                    pool_pages=40, dtype=jnp.bfloat16, qdtype=qdtype,
                    position=position)
    ref, got = _both(case)
    _assert_close(got, ref, jnp.bfloat16)


@pytest.mark.parametrize("ps,n_pages", [(6, 11), (8, 3)])
def test_paged_kernel_ragged_final_page(ps, n_pages):
    """Odd page sizes / capacities (cap = n_pages*ps not a power of two,
    final page partially filled) — positions at the very frontier of the
    last page must mask like the gather path."""
    rs = np.random.RandomState(1)
    cap = ps * n_pages
    # one row mid-page, one row writing the LAST slot of the last page
    case = _mk_case(rs, b=2, h=2, tq=1, ch=16, ps=ps, n_pages=n_pages,
                    pool_pages=14, position=[ps + 2, cap - 1])
    ref, got = _both(case)
    np.testing.assert_allclose(got, ref, **CLOSE)


def test_paged_kernel_trash_page_rows():
    """A released row (all table slots = 0) attends over the trash page
    like the XLA path, and a row past the table's capacity writes there."""
    rs = np.random.RandomState(2)
    q, kn, vn, kp, vp, table, _ = _mk_case(
        rs, b=3, h=2, tq=1, ch=16, ps=8, n_pages=4, pool_pages=10)
    table = table.at[0].set(0)
    position = jnp.asarray([0, 32, 17], jnp.int32)   # row 1: past capacity
    ref, got = _both((q, kn, vn, kp, vp, table, position))
    np.testing.assert_allclose(got, ref, **CLOSE)


def test_paged_kernel_under_jit():
    """The kernel must trace cleanly inside jit (the engine's compiled
    decode program)."""
    rs = np.random.RandomState(3)
    case = _mk_case(rs, b=2, h=2, tq=1, ch=16, ps=8, n_pages=4, pool_pages=6)
    ref = att._paged_gather_mha(*case)[0]
    got = jax.jit(lambda *a: ppa.paged_attention(*a, interpret=True))(*case)[0]
    _assert_close(got, ref)


@pytest.mark.parametrize("tq", [1, 5])
def test_what_was_not_fetched_counts_for_nothing(tq):
    """Pages a row does not hold are never fetched, and what an earlier row
    left in a history slot is never used: with every unheld page of the pool
    (the trash page too) full of NaN, and NaN past row 0's frontier on its
    last page (which row 2 then finds in its slot, past its own pages, with
    weight 0: a weight of 0 does not clear a NaN), the other rows give what
    they give on a clean pool, bit for bit, and that is finite."""
    rs = np.random.RandomState(5)
    ps, n_pages, b = 16, 16, 5
    position = np.asarray([200, 3, 129, 0, 40])
    q, kn, vn, kp, vp, _, pos = _mk_case(
        rs, b=b, h=16, tq=tq, ch=64, ps=ps, n_pages=n_pages, pool_pages=60,
        dtype=jnp.bfloat16, position=position)
    held = (position + tq - 1) // ps + 1
    table = np.zeros((b, n_pages), np.int32)
    ids = iter(range(1, 61))
    for row in range(b):
        table[row, :held[row]] = [next(ids) for _ in range(held[row])]
    clean = ppa.paged_attention(q, kn, vn, kp, vp, jnp.asarray(table), pos,
                                interpret=True)[0]
    unheld = np.ones((61, ps), bool)
    unheld[table[table > 0]] = False
    unheld[table[0, held[0] - 1], 13:] = True   # keys 205-207 of row 0's last page
    poison = lambda pool: jnp.where(unheld[:, :, None], jnp.nan, pool)  # noqa: E731
    dirty = ppa.paged_attention(q, kn, vn, poison(kp), poison(vp),
                                jnp.asarray(table), pos, interpret=True)[0]
    assert np.isnan(np.asarray(dirty[0])).any()   # the NaN did reach the slot
    assert np.isfinite(np.asarray(dirty[1:])).all()
    np.testing.assert_array_equal(np.asarray(clean[1:]), np.asarray(dirty[1:]))
    ref = att._paged_gather_mha(q, kn, vn, kp, vp, jnp.asarray(table), pos)[0]
    _assert_close(clean, ref, jnp.bfloat16)


def test_a_row_does_not_depend_on_its_neighbours():
    """Row b of a batch gives what it gives alone, bit for bit: nothing of
    the row before it (its pages in the other history slot, its values past
    this row's pages) reaches the products."""
    rs = np.random.RandomState(6)
    case = _mk_case(rs, b=4, h=16, tq=1, ch=64, ps=16, n_pages=16,
                    pool_pages=70, dtype=jnp.bfloat16,
                    position=[250, 5, 140, 17])
    q, kn, vn, kp, vp, table, pos = case
    whole = np.asarray(ppa.paged_attention(*case, interpret=True)[0])
    kp2, vp2 = att._paged_write(kn, vn, kp, vp, table, pos)
    for row in range(4):
        alone = ppa.paged_attention_read(
            q[row:row + 1], kp2, vp2, table[row:row + 1], pos[row:row + 1],
            interpret=True)
        np.testing.assert_array_equal(whole[row], np.asarray(alone)[0])


def test_pool_is_token_major_and_written_at_page_and_offset():
    """`alloc_paged_kv_cache` makes `(P+1, page, H*Ch)` pools, and token t of
    a row lands at `[table[t // page], t % page]` with head h in columns
    `h*Ch .. (h+1)*Ch`: where `cache_sequence` and the copy-on-write program
    (which index the page axis alone) expect a page's tokens."""
    (k_pool, v_pool), = att.alloc_paged_kv_cache(5, 4, 8, 16, 1)
    assert k_pool.shape == v_pool.shape == (6, 8, 64)
    rs = np.random.RandomState(7)
    k_new = jnp.asarray(rs.randn(2, 4, 3, 16), jnp.float32)
    v_new = jnp.asarray(rs.randn(2, 4, 3, 16), jnp.float32)
    table = jnp.asarray([[2, 5, 0], [4, 1, 3]], jnp.int32)
    position = jnp.asarray([7, 22], jnp.int32)     # row 1 runs past 3 pages
    kp, vp = att._paged_write(k_new, v_new, k_pool, v_pool, table, position)
    want = {(2, 7): (0, 0), (5, 0): (0, 1), (5, 1): (0, 2),
            (3, 6): (1, 0), (3, 7): (1, 1), (0, 0): (1, 2)}  # past capacity -> trash
    for (page, off), (row, t) in want.items():
        np.testing.assert_array_equal(
            np.asarray(kp[page, off]).reshape(4, 16), np.asarray(k_new[row, :, t]))
        np.testing.assert_array_equal(
            np.asarray(vp[page, off]).reshape(4, 16), np.asarray(v_new[row, :, t]))
    written = np.zeros((6, 8), bool)
    for page, off in want:
        written[page, off] = True
    assert not np.asarray(kp)[~written].any()      # and nothing else


def test_paged_supported_gating():
    """On the CPU the operator takes the XLA path (the dense cache's own
    arithmetic); the knob turns the kernel off wherever it would run."""
    q = jnp.zeros((2, 2, 1, 64), jnp.float32)
    k_pool = jnp.zeros((5, 16, 128), jnp.bfloat16)
    table = jnp.zeros((2, 4), jnp.int32)
    assert ppa.paged_attention_refusal(q, k_pool, table) == \
        "the backend is not a TPU"
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        assert ppa.paged_attention_supported(q, k_pool, table)
        _config.set("paged_attention_kernel", False)
        try:
            assert "knob" in ppa.paged_attention_refusal(q, k_pool, table)
        finally:
            _config.set("paged_attention_kernel", True)


_RULES = [
    # heads, head size, page, table width, Tq, query dtype, pool dtype, refusal
    ("gpt2_345m_decode", 16, 64, 16, 64, 1, jnp.float32, jnp.bfloat16, None),
    ("gpt2_345m_verify", 16, 64, 16, 64, 5, jnp.float32, jnp.bfloat16, None),
    ("bf16_query", 16, 64, 16, 64, 1, jnp.bfloat16, jnp.bfloat16, None),
    ("head_128", 2, 128, 8, 4, 1, jnp.float32, jnp.float32, None),
    ("float32_pool_page_8", 16, 64, 8, 64, 1, jnp.float32, jnp.float32, None),
    ("columns_96", 1, 96, 16, 4, 1, jnp.float32, jnp.bfloat16, "lane tiles"),
    ("head_48_of_384", 8, 48, 16, 4, 1, jnp.float32, jnp.bfloat16, "lane tiles"),
    ("page_6", 16, 64, 6, 4, 1, jnp.float32, jnp.float32, "page size 6"),
    ("page_8_bf16", 16, 64, 8, 4, 1, jnp.float32, jnp.bfloat16, "page size 8"),
    ("prefill_512", 16, 64, 16, 64, 512, jnp.float32, jnp.bfloat16, "VMEM"),
    ("table_too_wide", 16, 64, 16, 4096, 1, jnp.float32, jnp.bfloat16, "VMEM"),
    ("int8_pool", 16, 64, 32, 4, 1, jnp.float32, jnp.int8, "pool dtype"),
    ("float16_query", 16, 64, 16, 4, 1, jnp.float16, jnp.bfloat16, "query dtype"),
]


@pytest.mark.parametrize("h,ch,ps,n_pages,tq,qdtype,pdtype,refusal",
                         [r[1:] for r in _RULES], ids=[r[0] for r in _RULES])
def test_paged_supported_tpu_shape_rules(h, ch, ps, n_pages, tq, qdtype,
                                         pdtype, refusal):
    """The hardware gate reads its operands: whole lane tiles of heads
    (head 64 x 16 and head 128 alike), pages that are whole sublane tiles of
    the pool's dtype, and a VMEM-bounded history and score block (decode and
    verification pass, a prefill bucket of 512 does not)."""
    q = jax.ShapeDtypeStruct((2, h, tq, ch), qdtype)
    pool = jax.ShapeDtypeStruct((9, ps, h * ch), pdtype)
    table = jax.ShapeDtypeStruct((2, n_pages), jnp.int32)
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        why = ppa.paged_attention_refusal(q, pool, table)
    if refusal is None:
        assert why is None
    else:
        assert why is not None and refusal in why


def test_read_path_is_counted_at_trace_time():
    """`paged_read_path_total{path, reason}` says which read path a program
    was built with: the XLA gather and why on the CPU, the kernel where the
    gate passes (here with the backend check patched, interpreted)."""
    rs = np.random.RandomState(8)
    case = _mk_case(rs, b=2, h=2, tq=1, ch=64, ps=16, n_pages=4, pool_pages=6,
                    dtype=jnp.bfloat16)
    counter = obs.counter("paged_read_path_total")
    why = "the backend is not a TPU"
    before = counter.value(path="xla_gather", reason=why)
    ref = att._paged_cached_mha(*case)[0]
    assert counter.value(path="xla_gather", reason=why) == before + 1
    before = counter.value(path="kernel", reason="")
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        got = att._paged_cached_mha(*case)[0]
    assert counter.value(path="kernel", reason="") == before + 1
    _assert_close(got, ref, jnp.bfloat16)
