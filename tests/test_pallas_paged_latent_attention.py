"""The paged latent kernel (DeepSeek-V2's absorbed form) against the XLA
gather path, the kernel interpreted on the CPU, and the lane-whole latent
pool both read.

As for `test_pallas_paged_attention.py`: the kernel takes one product over
the pool's whole width (latent, rotated key and zero lanes) and only the
first 128, 256, ... keys of a history, so its float32 sums run in another
order than the einsums': it is held to the XLA path within a few units of
float32's last place, and to ITSELF exactly wherever what it must not read
changes (NaN in pages a row does not hold, the other rows of the batch)."""
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config as _config
from mxnet_tpu import observability as obs
from mxnet_tpu.inference import GenerationEngine
from mxnet_tpu.models import deepseek_v2
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_paged_attention as ppa

from test_pallas_paged_attention import CLOSE, _assert_close

TOY = dict(h=4, nope=16, rope=8, vd=16, kl=32)          # a 128-lane pool
CELL = dict(h=16, nope=128, rope=64, vd=128, kl=512)    # DeepSeek-V2's 640


def _mk(rs, b, tq, ps, n_pages, pool_pages, dims=TOY, dtype=jnp.float32,
        qdtype=jnp.float32, position=None):
    """`latent_attention`'s operands over a pool of random latents whose pad
    lanes are zero, as every write leaves them, and `q_lat`, queries already
    in the latent space, for the kernel alone. The operator's own queries
    and weights are float32 (XLA:CPU has no bfloat16 product with a float32
    result for some of its einsums); `qdtype` is `q_lat`'s and `q_rope`'s."""
    h, nope, rope, vd, kl = (dims[k] for k in ("h", "nope", "rope", "vd", "kl"))
    (pool,), = att.alloc_paged_latent_cache(pool_pages, ps, kl + rope, 1, dtype)
    pool = pool.at[..., :kl + rope].set(
        jnp.asarray(rs.randn(pool_pages + 1, ps, kl + rope), dtype))
    table = jnp.asarray(rs.randint(1, pool_pages + 1, (b, n_pages)), jnp.int32)
    cap = n_pages * ps
    if position is None:
        position = rs.randint(0, cap - tq + 1, (b,))
    rnd = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa: E731
    return dict(q_nope=rnd(b, tq, h, nope), q_rope=rnd(b, tq, h, rope),
                c_kv=rnd(b, tq, kl), k_rope=rnd(b, tq, rope),
                w_kvb=rnd(h * (nope + vd), kl) * 0.2, scale=0.17,
                cache=(pool,), position=jnp.asarray(position, jnp.int32),
                page_table=table,
                q_lat=jnp.asarray(rs.randn(b, tq, h, kl), qdtype), qdtype=qdtype)


def _op(case):
    """`latent_attention` on a case under jit: (context, pool)."""
    arrays = {k: case[k] for k in ("q_nope", "q_rope", "c_kv", "k_rope",
                                   "w_kvb", "cache", "position", "page_table")}
    return jax.jit(lambda a: att.latent_attention(scale=case["scale"], **a))(
        arrays)


def _kernel_op(case):
    """`latent_attention` as a TPU builds it, the kernel interpreted."""
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        assert ppa.paged_latent_attention_refusal(
            case["q_nope"], case["cache"][0], case["page_table"]) is None
        return _op(case)


def _both(case):
    """(XLA path, kernel) contexts on one case; the pools equal exactly."""
    ref, ref_pool = _op(case)
    got, got_pool = _kernel_op(case)
    np.testing.assert_array_equal(np.asarray(ref_pool, np.float32),
                                  np.asarray(got_pool, np.float32))
    assert got.shape == ref.shape and got.dtype == ref.dtype
    return np.asarray(ref, np.float32), np.asarray(got, np.float32)


def _kernel_latents(case, pool=None):
    """What the kernel alone returns for `q_lat` and `q_rope` over the
    case's pool as it stands (nothing written): (B, Tq, H, kl) float32."""
    return ppa.paged_latent_attention_read(
        case["q_lat"], case["q_rope"].astype(case["qdtype"]),
        case["cache"][0] if pool is None else pool, case["page_table"],
        case["position"], case["scale"], interpret=True)


def _gathered_latents(case):
    """The same by the XLA path's arithmetic: the table's whole width
    gathered, `_mla_absorbed`'s two products and softmax. Operands are
    rounded to the pool's dtype and multiplied in float32, which is a
    product of that dtype accumulated in float32."""
    (pool,) = case["cache"]
    b, tq, h, kl = case["q_lat"].shape
    rope = case["q_rope"].shape[-1]
    rounded = lambda x: x.astype(pool.dtype).astype(jnp.float32)  # noqa: E731
    hist = rounded(pool[case["page_table"]].reshape(b, -1, pool.shape[2]))
    c, r = hist[..., :kl], hist[..., kl:kl + rope]
    scores = (jnp.einsum("bthl,bkl->bhtk", rounded(case["q_lat"]), c)
              + jnp.einsum("bthr,bkr->bhtk",
                           rounded(case["q_rope"].astype(case["qdtype"])), r))
    pos = case["position"][:, None] + jnp.arange(tq)[None, :]
    mask = jnp.arange(hist.shape[1])[None, None, :] <= pos[:, :, None]
    p = att._mla_softmax(scores, mask, case["scale"], pool.dtype)
    return jnp.einsum("bhtk,bkl->bthl", p.astype(jnp.float32), c)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq", [1, 2])
def test_latent_kernel_matches_gather(dtype, tq):
    """The whole operator, write and read, both paths; and the kernel's own
    float32 output against the gather's arithmetic."""
    rs = np.random.RandomState(0)
    case = _mk(rs, b=3, tq=tq, ps=8 if dtype == jnp.float32 else 16,
               n_pages=8, pool_pages=12, dtype=dtype)
    ref, got = _both(case)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, ref, **CLOSE)
    else:   # the kernel rounds the softmax's weights to the pool's dtype, as
        # a TPU's one-pass product does; the CPU's XLA path keeps float32
        np.testing.assert_allclose(got, ref, rtol=0, atol=2e-2)
    _assert_close(_kernel_latents(case), _gathered_latents(case), dtype)


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tq", [1, 2])
def test_latent_kernel_cell_shape_class(qdtype, tq):
    """DeepSeek-V2's own widths (latent 512, rotary 64, a 640-lane bfloat16
    pool, pages of 16) at 16 heads and a small table. Rows of length 0, 1,
    one page exactly, one past a page, mid-table and the table's full width
    take both stretches of keys the kernel has here."""
    rs = np.random.RandomState(4)
    cap = 16 * 16
    case = _mk(rs, b=6, tq=tq, ps=16, n_pages=16, pool_pages=40, dims=CELL,
               dtype=jnp.bfloat16, qdtype=qdtype,
               position=[0, 1, 15, 16, 131, cap - tq])
    assert case["cache"][0].shape == (41, 16, 640)
    _assert_close(_kernel_latents(case), _gathered_latents(case),
                  jnp.bfloat16)


@pytest.mark.parametrize("ps,n_pages", [(6, 11), (8, 3)])
def test_latent_kernel_ragged_final_page(ps, n_pages):
    """Odd page sizes and capacities (the gate refuses them on a TPU; the
    kernel's arithmetic does not depend on them): positions at the very
    frontier of the last page mask like the gather path."""
    rs = np.random.RandomState(1)
    cap = ps * n_pages
    case = _mk(rs, b=2, tq=1, ps=ps, n_pages=n_pages, pool_pages=14,
               position=[ps + 2, cap - 1])
    np.testing.assert_allclose(np.asarray(_kernel_latents(case)),
                               np.asarray(_gathered_latents(case)), **CLOSE)


def test_latent_kernel_trash_page_and_released_rows():
    """A released row (every table slot 0) attends over the trash page like
    the XLA path, and a row past the table's capacity writes there."""
    rs = np.random.RandomState(2)
    case = _mk(rs, b=3, tq=1, ps=8, n_pages=4, pool_pages=10,
               position=[0, 32, 17])                 # row 1: past capacity
    case["page_table"] = case["page_table"].at[0].set(0)
    ref, got = _both(case)
    np.testing.assert_allclose(got, ref, **CLOSE)


def test_latent_kernel_under_jit():
    """The kernel must trace cleanly inside jit (the engine's compiled
    decode program)."""
    rs = np.random.RandomState(3)
    case = _mk(rs, b=2, tq=1, ps=8, n_pages=4, pool_pages=6)
    ref, got = _both(case)
    np.testing.assert_allclose(got, ref, **CLOSE)


@pytest.mark.parametrize("tq", [1, 2])
def test_what_was_not_fetched_counts_for_nothing(tq):
    """With every page no row holds (the trash page too) full of NaN, and
    NaN past row 0's frontier on its last page (which row 2 then finds in
    its history slot, past its own pages, with weight 0), the other rows
    give what they give on a clean pool, bit for bit, and that is finite."""
    rs = np.random.RandomState(5)
    ps, n_pages, b = 16, 16, 5
    position = np.asarray([200, 3, 129, 0, 40])
    case = _mk(rs, b=b, tq=tq, ps=ps, n_pages=n_pages, pool_pages=60,
               dims=CELL, dtype=jnp.bfloat16, position=position)
    held = (position + tq - 1) // ps + 1
    table = np.zeros((b, n_pages), np.int32)
    ids = iter(range(1, 61))
    for row in range(b):
        table[row, :held[row]] = [next(ids) for _ in range(held[row])]
    case["page_table"] = jnp.asarray(table)
    (pool,) = case["cache"]
    clean = _kernel_latents(case)
    unheld = np.ones((61, ps), bool)
    unheld[table[table > 0]] = False
    unheld[table[0, held[0] - 1], 13:] = True   # keys 205-207 of row 0's last page
    dirty = _kernel_latents(
        case, pool=jnp.where(unheld[:, :, None], jnp.nan, pool))
    assert np.isnan(np.asarray(dirty[0])).any()   # the NaN did reach the slot
    assert np.isfinite(np.asarray(dirty[1:])).all()
    np.testing.assert_array_equal(np.asarray(clean[1:]), np.asarray(dirty[1:]))
    _assert_close(clean, _gathered_latents(case), jnp.bfloat16)


def test_a_row_does_not_depend_on_its_neighbours():
    """Row b of a batch gives what it gives alone, bit for bit: nothing of
    the row before it (its pages in the other history slot, its values past
    this row's pages) reaches the products."""
    rs = np.random.RandomState(6)
    case = _mk(rs, b=4, tq=1, ps=16, n_pages=16, pool_pages=70, dims=CELL,
               dtype=jnp.bfloat16, position=[250, 5, 140, 17])
    whole = np.asarray(_kernel_latents(case))
    for row in range(4):
        one = dict(case, **{k: case[k][row:row + 1] for k in (
            "q_lat", "q_rope", "position", "page_table")})
        alone = _kernel_latents(one)
        np.testing.assert_array_equal(whole[row], np.asarray(alone)[0])


def test_every_length_bucket_is_taken():
    """A table 4,096 positions wide has six stretches of keys (128, 256, ...
    4,096); one row in each, in no order, against the gather."""
    assert [n * 16 for n in ppa._page_buckets(16, 256)] == \
        [128, 256, 512, 1024, 2048, 4096]
    rs = np.random.RandomState(7)
    case = _mk(rs, b=6, tq=1, ps=16, n_pages=256, pool_pages=300,
               position=[1000, 100, 4095, 200, 2047, 400])
    np.testing.assert_allclose(np.asarray(_kernel_latents(case)),
                               np.asarray(_gathered_latents(case)), **CLOSE)


def test_pool_is_whole_lane_tiles_and_the_pad_lanes_stay_zero():
    """`alloc_paged_latent_cache` rounds the width up to 128-lane tiles
    (576 -> 640, 40 -> 128, 128 stays), token t of a row lands at
    `[table[t // page], t % page]` as `[c_kv ; k_rope ; 0]`, and nothing
    but zeros is ever written past the two parts."""
    shape = lambda width: att.alloc_paged_latent_cache(  # noqa: E731
        5, 8, width, 2)[1][0].shape
    assert [shape(w) for w in (576, 40, 128)] == \
        [(6, 8, 640), (6, 8, 128), (6, 8, 128)]
    rs = np.random.RandomState(8)
    case = _mk(rs, b=2, tq=3, ps=8, n_pages=3, pool_pages=5,
               position=[7, 22])                    # row 1 runs past 3 pages
    case["cache"] = att.alloc_paged_latent_cache(5, 8, 40, 1)[0]
    case["page_table"] = jnp.asarray([[2, 5, 0], [4, 1, 3]], jnp.int32)
    for build in (_op, _kernel_op):
        _, pool = build(case)
        want = {(2, 7): (0, 0), (5, 0): (0, 1), (5, 1): (0, 2),
                (3, 6): (1, 0), (3, 7): (1, 1), (0, 0): (1, 2)}  # -> trash
        written = np.zeros((6, 8), bool)
        for (page, off), (row, t) in want.items():
            np.testing.assert_array_equal(
                np.asarray(pool[page, off, :40]),
                np.concatenate([np.asarray(case["c_kv"][row, t]),
                                np.asarray(case["k_rope"][row, t])]))
            written[page, off] = True
        assert not np.asarray(pool)[~written].any()      # and nothing else
        assert not np.asarray(pool)[..., 40:].any()      # the pad lanes


_RULES = [
    # heads, page, table width, Tq, pool width, query dtype, pool dtype,
    # form, refusal
    ("deepseek_v2_decode", 128, 16, 256, 1, 640, jnp.bfloat16, jnp.bfloat16,
     "absorbed", None),
    ("float32_query", 128, 16, 256, 1, 640, jnp.float32, jnp.bfloat16,
     "absorbed", None),
    ("toy_float32_page_8", 4, 8, 16, 1, 128, jnp.float32, jnp.float32,
     "absorbed", None),
    ("toy_two_queries", 4, 8, 16, 2, 128, jnp.float32, jnp.float32,
     "absorbed", None),
    ("width_576", 128, 16, 256, 1, 576, jnp.bfloat16, jnp.bfloat16,
     "absorbed", "576 columns are not whole"),
    ("width_40", 4, 8, 16, 1, 40, jnp.float32, jnp.float32, "absorbed",
     "40 columns are not whole"),
    ("int8_pool", 128, 32, 128, 1, 640, jnp.bfloat16, jnp.int8, "absorbed",
     "pool dtype"),
    ("float16_query", 128, 16, 256, 1, 640, jnp.float16, jnp.bfloat16,
     "absorbed", "query dtype"),
    ("page_8_bf16", 128, 8, 512, 1, 640, jnp.bfloat16, jnp.bfloat16,
     "absorbed", "page size 8"),
    ("page_6", 4, 6, 16, 1, 128, jnp.float32, jnp.float32, "absorbed",
     "page size 6"),
    ("prefill_chunk_16", 128, 16, 256, 16, 640, jnp.bfloat16, jnp.bfloat16,
     "absorbed", "VMEM"),
    ("two_queries_at_4096", 128, 16, 256, 2, 640, jnp.bfloat16, jnp.bfloat16,
     "absorbed", None),
    ("four_queries_at_4096", 128, 16, 256, 4, 640, jnp.bfloat16, jnp.bfloat16,
     "absorbed", "VMEM"),
    ("decompressed", 128, 16, 256, 256, 640, jnp.bfloat16, jnp.bfloat16,
     "decompressed", "decompressed form"),
]


@pytest.mark.parametrize("h,ps,n_pages,tq,w,qdtype,pdtype,form,refusal",
                         [r[1:] for r in _RULES], ids=[r[0] for r in _RULES])
def test_latent_gate_reads_its_operands(h, ps, n_pages, tq, w, qdtype, pdtype,
                                        form, refusal):
    q = jax.ShapeDtypeStruct((128, tq, h, 128), qdtype)
    pool = jax.ShapeDtypeStruct((9, ps, w), pdtype)
    table = jax.ShapeDtypeStruct((128, n_pages), jnp.int32)
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        why = ppa.paged_latent_attention_refusal(q, pool, table, form)
    if refusal is None:
        assert why is None
    else:
        assert why is not None and refusal in why


def test_latent_gate_backend_knob_and_mesh():
    q = jax.ShapeDtypeStruct((2, 1, 4, 16), jnp.float32)
    pool = jax.ShapeDtypeStruct((5, 8, 128), jnp.float32)
    table = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    assert ppa.paged_latent_attention_refusal(q, pool, table) == \
        "the backend is not a TPU"
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        assert ppa.paged_latent_attention_refusal(q, pool, table) is None
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("x",))
        with mock.patch.object(ppa, "current_mesh", return_value=mesh):
            assert "a mesh of 2 devices" in \
                ppa.paged_latent_attention_refusal(q, pool, table)
        _config.set("paged_attention_kernel", False)
        try:
            assert "knob" in ppa.paged_latent_attention_refusal(q, pool, table)
        finally:
            _config.set("paged_attention_kernel", True)


def test_read_path_is_counted_at_trace_time():
    """`mla_path_total{form, read}` and `paged_read_path_total{path, reason}`
    say which read path a program was built with, and why not the kernel."""
    rs = np.random.RandomState(9)
    case = _mk(rs, b=2, tq=1, ps=8, n_pages=4, pool_pages=6)
    mla, read = obs.counter("mla_path_total"), obs.counter("paged_read_path_total")
    why = "the backend is not a TPU"
    before = (mla.value(form="absorbed", read="xla_gather"),
              read.value(path="xla_gather", reason=why))
    _op(case)
    assert (mla.value(form="absorbed", read="xla_gather"),
            read.value(path="xla_gather", reason=why)) == \
        (before[0] + 1, before[1] + 1)
    before = (mla.value(form="absorbed", read="kernel"),
              read.value(path="kernel", reason=""))
    _kernel_op(case)
    assert (mla.value(form="absorbed", read="kernel"),
            read.value(path="kernel", reason="")) == \
        (before[0] + 1, before[1] + 1)


def test_the_engine_names_its_read_path_and_serves_the_same_tokens():
    """`engine.read_path` answers from the gate, and a toy DeepSeek-V2 served
    through the kernel (decode and the prefill chunks its VMEM rule admits)
    gives the tokens the XLA path gives."""
    net = deepseek_v2.get_deepseek_v2("deepseek_v2_tiny")
    net.initialize()
    net(mx.nd.array(np.ones((1, 4)), dtype="int32"))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 200, n).tolist() for n in (5, 19, 33)]

    def serve():
        engine = GenerationEngine(net, batch_size=4, paged=True, page_size=8,
                                  num_pages=64, max_length=128)
        out = [[engine.prefill(p, slot=i)] for i, p in enumerate(prompts)]
        for _ in range(9):
            tok, _, _ = engine.decode_step()
            for i in range(3):
                out[i].append(int(tok[i]))
        return engine.read_path, out

    path, want = serve()
    assert path == "xla_gather_latent (absorbed; the backend is not a TPU)"
    kernel = obs.counter("mla_path_total").value(form="absorbed", read="kernel")
    with mock.patch.object(ppa, "_on_tpu", return_value=True):
        path, got = serve()
    assert path == "pallas_paged_latent_kernel (absorbed)"
    assert obs.counter("mla_path_total").value(
        form="absorbed", read="kernel") > kernel
    assert got == want
