"""The grouped-heads paged decode kernel (``paged_gqa_decode``) interpreted on
the CPU against the XLA gather (``attention._paged_gqa_gather_read``) and a
plain float32 softmax over each row's own history: a window or none, the
ring wrapped or not, rows shorter than one block, seven query heads to a
key-value head and one to one, a released row; every page a row does not
hold is poisoned with NaN. Tables whose ids lie in runs, in none, and mixed
(PR 39: a run of neighbouring pages is ONE copy): bit for bit the
page-by-page path's result. And the gate: every reason, the first failing
condition named."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import config
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import pallas_paged_attention as ppa


def paged_case(rng, positions, window, ps, cols, hkv, group, ch, dtype):
    """Pools, the rows' tables (in order, or the ring an engine's window
    group hands out) and the rows' own histories; a row with position None
    is released: its table names the trash page only."""
    b = len(positions)
    hist_k = [rng.normal(size=((p or 0) + 1, hkv * ch)) for p in positions]
    hist_v = [rng.normal(size=((p or 0) + 1, hkv * ch)) for p in positions]
    pages = 1 + sum(0 if p is None else p // ps + 1 for p in positions)
    k_pool = np.full((pages, ps, hkv * ch), np.nan)
    v_pool = np.full((pages, ps, hkv * ch), np.nan)
    k_pool[0], v_pool[0] = rng.normal(size=(2, ps, hkv * ch))  # trash: finite
    table, nxt = np.zeros((b, cols), np.int32), 1
    for r, p in enumerate(positions):
        if p is None:
            continue
        first = 0 if window is None else max(0, p - window + 1) // ps
        for s in range(first, p // ps + 1):
            table[r, s % cols if window else s] = nxt
            n = min(ps, p + 1 - s * ps)
            k_pool[nxt, :n] = hist_k[r][s * ps:s * ps + n]
            v_pool[nxt, :n] = hist_v[r][s * ps:s * ps + n]
            nxt += 1
    q = rng.normal(size=(b, hkv * group, 1, ch))
    cast = lambda x: jnp.asarray(x, dtype)  # noqa: E731
    return (cast(q), cast(k_pool), cast(v_pool), jnp.asarray(table),
            jnp.asarray([p or 0 for p in positions], jnp.int32), hist_k, hist_v)


def plain_softmax(q, hist_k, hist_v, positions, window, hkv, group, ch):
    """float32, a row and a head at a time, over the row's own history."""
    out = np.zeros(q.shape, np.float32)
    for r, p in enumerate(positions):
        if p is None:
            continue
        lo = 0 if window is None else max(0, p - window + 1)
        k = hist_k[r][lo:p + 1].reshape(-1, hkv, ch)
        v = hist_v[r][lo:p + 1].reshape(-1, hkv, ch)
        for h in range(hkv * group):
            s = k[:, h // group] @ np.asarray(q[r, h, 0], np.float64) / np.sqrt(ch)
            w = np.exp(s - s.max())
            out[r, h, 0] = (w / w.sum()) @ v[:, h // group]
    return out


@pytest.mark.parametrize("window,ps,cols,positions,group,block_pages", [
    (None, 8, 8, [0, 5, 17, 63, 30], 7, 2),          # table in order
    (None, 8, 8, [3, 7, 2], 7, 4),                   # shorter than one block
    (None, 8, 8, [3, 40, None], 1, 2),               # 1 : 1, a released row
    (20, 8, 5, [0, 5, 17, 19], 7, 2),                # window not yet full
    (20, 8, 5, [63, 130, 41, 20, None], 7, 2),       # the ring wrapped
    (20, 8, 5, [100, 39], 1, 1),                     # a page a block
    (5, 4, 4, [60, 4, 11], 3, 2),                    # the toy model's sizes
])
def test_the_kernel_reads_what_a_row_holds_and_reads(window, ps, cols,
                                                     positions, group,
                                                     block_pages):
    rng = np.random.default_rng(0)
    hkv, ch = 2, 128
    q, k_pool, v_pool, table, pos, hk, hv = paged_case(
        rng, positions, window, ps, cols, hkv, group, ch, jnp.float32)
    got = ppa.paged_gqa_read(q, k_pool, v_pool, table, pos, window,
                             block_pages=block_pages, interpret=True)
    live = [r for r, p in enumerate(positions) if p is not None]
    assert bool(jnp.isfinite(got[jnp.asarray(live)]).all())
    gather = att._paged_gqa_gather_read(q, k_pool, v_pool, table, pos, window)
    assert float(jnp.abs(got - gather).max()) < 2e-5   # released rows too
    want = plain_softmax(np.asarray(q), hk, hv, positions, window, hkv, group, ch)
    assert np.abs(np.asarray(got)[live] - want[live]).max() < 2e-5


def table_case(rng, rows, window, ps, cols, hkv, group, ch, spare=3):
    """Pools and tables from the rows' OWN page ids: ``rows`` is a list of
    ``(position, {logical page: id})`` (every page the row reads, from its
    lower bound's to its frontier's) or ``(position, ids, like)``: the
    row's history is row ``like``'s as far as both reach (a fork: shared
    ids hold one history). Pages no row holds, ``spare`` past the largest
    id among them, are NaN; the trash page is finite."""
    hist_k, hist_v = [], []
    for row in rows:
        p = row[0]
        k, v = rng.normal(size=(2, p + 1, hkv * ch))
        if len(row) > 2:
            n = min(p, rows[row[2]][0]) + 1
            k[:n], v[:n] = hist_k[row[2]][:n], hist_v[row[2]][:n]
        hist_k.append(k)
        hist_v.append(v)
    pages = 1 + spare + max(max(row[1].values()) for row in rows)
    k_pool = np.full((pages, ps, hkv * ch), np.nan)
    v_pool = np.full((pages, ps, hkv * ch), np.nan)
    k_pool[0], v_pool[0] = rng.normal(size=(2, ps, hkv * ch))
    table = np.zeros((len(rows), cols), np.int32)
    for r, row in enumerate(rows):
        p, ids = row[:2]
        for s, pid in ids.items():
            table[r, s % cols if window else s] = pid
            n = min(ps, p + 1 - s * ps)
            if pid and n > 0:
                k_pool[pid, :n] = hist_k[r][s * ps:s * ps + n]
                v_pool[pid, :n] = hist_v[r][s * ps:s * ps + n]
    q = rng.normal(size=(len(rows), hkv * group, 1, ch))
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return (f32(q), f32(k_pool), f32(v_pool), jnp.asarray(table),
            jnp.asarray([row[0] for row in rows], jnp.int32), hist_k, hist_v)


def _ids(first, last, start):
    """Logical pages ``first .. last`` on consecutive ids from ``start``."""
    return {s: start + s - first for s in range(first, last + 1)}


# (window, page size, columns, block_pages, run_pages, rows): what lies where
RUN_TABLES = {
    # every group of every block is a run: pages 0 .. 11 on ids 1 .. 12
    "all_runs": (None, 8, 16, 4, 2, [(95, _ids(0, 11, 1)),
                                      (40, _ids(0, 5, 20))]),
    # ids fall as the pages rise: no two neighbours anywhere
    "no_runs": (None, 8, 16, 4, 2, [
        (95, {s: 30 - 2 * s for s in range(12)}),
        (40, {s: 29 - 2 * s for s in range(6)})]),
    # one block of eight pages holds runs of four and singles between them
    "mixed_in_one_block": (None, 8, 16, 8, 4, [
        (63, {0: 9, 1: 1, 2: 2, 3: 3, 4: 4, 5: 20, 6: 11, 7: 12}),
        (120, {**_ids(0, 3, 30), 4: 5, **_ids(5, 8, 40), **_ids(9, 15, 50)})]),
    # the frontier's page is the last of a run, one position into it
    "run_ends_at_the_frontier": (None, 8, 16, 4, 2, [(56, _ids(0, 7, 3)),
                                                      (24, _ids(0, 3, 11))]),
    # the window's lower bound lies in the middle of a run (pages 5 .. 12
    # on ids 1 .. 8, read from page 7): the block starts there, its groups
    # with it, and what lies behind the bound is not read
    "run_cut_by_the_lower_bound": (40, 8, 9, 4, 4, [
        (100, _ids(5, 12, 1)), (150, _ids(11, 18, 21))]),
    # consecutive ids in the ring's last columns and its first
    "run_across_the_wrap": (40, 8, 9, 4, 2, [(150, _ids(13, 18, 1)),
                                              (75, _ids(4, 9, 11))]),
    # a column that names the trash page before ids 1, 2, 3: page 0 starts
    # no run, ids 1 and 2 are one
    "run_beside_the_trash_page": (None, 8, 8, 4, 2, [
        (31, {0: 0, 1: 1, 2: 2, 3: 3}), (20, _ids(0, 2, 5))]),
    # a fork: the second row reads the first row's pages 0 .. 3 and its own
    "forked_rows_share_a_prefix": (None, 8, 16, 4, 2, [
        (50, _ids(0, 6, 1)), (61, {**_ids(0, 3, 1), **_ids(4, 7, 10)}, 0),
        (39, {**_ids(0, 3, 1), 4: 20}, 0)]),
}


@pytest.mark.parametrize("name", sorted(RUN_TABLES))
def test_a_run_of_neighbouring_pages_is_one_copy_and_the_same_result(name):
    window, ps, cols, block_pages, run_pages, rows = RUN_TABLES[name]
    rng = np.random.default_rng(3)
    hkv, group, ch = 2, 7, 128
    q, k_pool, v_pool, table, pos, hk, hv = table_case(
        rng, rows, window, ps, cols, hkv, group, ch)
    marked = np.asarray(ppa._mark_runs(table, run_pages, window is not None))
    assert (np.abs(marked) == np.asarray(table)).all()
    if name in ("no_runs",):
        assert (marked >= 0).all()
    else:
        assert (marked < 0).any()
    assert (marked[np.asarray(table) == 0] == 0).all()
    read = lambda run: ppa.paged_gqa_read(  # noqa: E731
        q, k_pool, v_pool, table, pos, window, block_pages=block_pages,
        run_pages=run, interpret=True)
    got, by_page = read(run_pages), read(1)
    assert bool(jnp.isfinite(got).all())
    # the same bytes land in the same buffer rows: bit for bit
    assert bool((got == by_page).all())
    positions = [row[0] for row in rows]
    want = plain_softmax(np.asarray(q), hk, hv, positions, window, hkv, group,
                         ch)
    if name == "run_beside_the_trash_page":   # row 0's first page is trash
        want, got = want[1:], got[1:]
    assert np.abs(np.asarray(got) - want).max() < 2e-5


def test_the_tables_marks_are_the_runs_the_kernel_may_copy_whole():
    table = jnp.asarray([[5, 6, 7, 8, 0, 1, 2, 9],
                         [3, 4, 0, 0, 0, 0, 1, 2]], jnp.int32)
    in_order = np.asarray(ppa._mark_runs(table, 2, False))
    assert in_order.tolist() == [[-5, -6, -7, 8, 0, -1, 2, 9],
                                 [-3, 4, 0, 0, 0, 0, -1, 2]]
    ring = np.asarray(ppa._mark_runs(table, 2, True))   # 2 -> 3 wraps
    assert ring.tolist() == [[-5, -6, -7, 8, 0, -1, 2, 9],
                             [-3, 4, 0, 0, 0, 0, -1, -2]]
    assert np.asarray(ppa._mark_runs(table, 4, True)).tolist() == [
        [-5, 6, 7, 8, 0, 1, 2, 9], [3, 4, 0, 0, 0, 0, -1, 2]]
    # a run of one is every page that is not the trash page
    assert (np.asarray(ppa._mark_runs(table, 1, False))
            == -np.asarray(table)).all()


def test_bfloat16_pools_keep_the_gather_paths_precision():
    rng = np.random.default_rng(1)
    q, k_pool, v_pool, table, pos, *_ = paged_case(
        rng, [100, 7, 55], 20, 16, 4, 2, 7, 128, jnp.bfloat16)
    got = ppa.paged_gqa_read(q, k_pool, v_pool, table, pos, 20,
                             block_pages=2, interpret=True)
    gather = att._paged_gqa_gather_read(q, k_pool, v_pool, table, pos, 20)
    # both round the softmax's weights to bfloat16 before the second product
    assert float(jnp.abs(got - gather).max()) < 2e-2


def _operands(b=4, h=28, tq=1, ch=128, hkv=4, ps=16, cols=259,
              q_dtype=jnp.bfloat16, pool_dtype=jnp.bfloat16):
    S = jax.ShapeDtypeStruct
    return (S((b, h, tq, ch), q_dtype), S((9, ps, hkv * ch), pool_dtype),
            S((b, cols), jnp.int32))


@pytest.mark.parametrize("change,window,reason", [
    ({}, 4096, None),
    ({"cols": 640}, None, None),
    ({"tq": 2}, None, "2 queries a row"),
    ({"pool_dtype": jnp.float16}, None, "pool dtype float16"),
    ({"q_dtype": jnp.float16}, None, "query dtype float16"),
    ({"ch": 64}, None, "not whole groups of whole 128-lane heads"),
    ({"h": 30}, None, "not whole groups of whole 128-lane heads"),
    ({"ps": 8}, None, "page size 8 is not a multiple of 16 sublanes"),
    ({"ps": 48}, None, "divides a block of 512 positions"),
    ({"cols": 200}, 4096, "a window of 4096 positions does not fit a ring of "
                          "200 pages"),
])
def test_the_gate_names_the_first_condition_that_fails(monkeypatch, change,
                                                       window, reason):
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)
    why = ppa.paged_gqa_refusal(*_operands(**change), window)
    assert (why is None) if reason is None else (reason in why), why


def test_the_gate_reads_the_knob_the_backend_and_the_mesh(monkeypatch):
    q, pool, table = _operands()
    assert ppa.paged_gqa_refusal(q, pool, table, 4096) == \
        "the backend is not a TPU"
    monkeypatch.setattr(ppa, "_on_tpu", lambda: True)
    # the knob comes first, then the backend, then the operands: a float16
    # pool is not named while the knob is off
    bad = _operands(pool_dtype=jnp.float16)
    was = config.get("paged_attention_kernel")
    config.set("paged_attention_kernel", False)
    try:
        assert ppa.paged_gqa_refusal(*bad, None) == \
            "paged_attention_kernel knob is off"
    finally:
        config.set("paged_attention_kernel", was)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("x",))
    monkeypatch.setattr(ppa, "current_mesh", lambda: mesh)
    assert ppa.paged_gqa_refusal(q, pool, table, 4096) == \
        "a mesh of 2 devices is active"
