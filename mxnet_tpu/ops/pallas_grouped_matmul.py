"""Grouped matrix products whose row blocks follow the groups, one Pallas
kernel (docs/INFERENCE.md "The expert layer", docs/PERFORMANCE.md "Custom
kernels").

``parallel/moe.py:held_expert_ffn`` sorts its (token, expert) pairs by
expert, the held experts' first, and multiplies group ``g``'s rows by expert
``g``'s matrices: ``lax.ragged_dot``'s contract. This kernel keeps that
contract and reads what the groups hold:

  - *visits*: the rows are cut into tiles of ``tm``. A group is visited once
    for every tile it has a row in, the groups in order, and the plan of the
    visits (each visit's group and tile, every group's first row) rides in
    as scalars. A tile that straddles two groups is visited once a group,
    each time storing its own rows under a mask; a group of size 0 is never
    visited; **row tiles past the last group's last row are never visited**
    and their output rows stay unwritten, as the TPU's ``ragged_dot`` leaves
    them (``held_expert_ffn`` masks them). The grid's length is the count of
    visits, a scalar of the step: one program serves every load.
  - *weights cross HBM once a group*: a visit's right operand is the block
    ``[K, tn]`` of its group's matrix over the WHOLE contraction, so
    successive visits of one group (and one column tile) name the same block
    and the pipeline does not fetch it again. With a handful of rows a group
    (decode) the product is the stream of the weights.
  - *tiles from the static shapes*: the row tile is small where there are
    few rows and large where the products are bound by operations
    (:func:`_row_tiles`), the column tile the widest that keeps the blocks
    inside ``_BLOCK_BYTES`` of VMEM. One algorithm, other tiles: no knob.
  - *gate and up in one call*: with two right operands the kernel reads the
    rows once and writes ``act(x @ w_gate) * (x @ w_up)`` in the operands'
    dtype; the float32 pair is never written. Operands as held, float32
    accumulation, the activation in float32.
  - *names*: ``grouped_matmul_gate_up`` and ``grouped_matmul_down`` in a
    device trace.

The gate (:func:`grouped_matmul_refusal`) reads what it can observe; where
it refuses, the caller keeps its ``lax.ragged_dot`` lines (the CPU tests'
toy widths, anything under a mesh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mesh_state import current_mesh
from .pallas_common import LANES as _LANES
from .pallas_common import on_tpu as _on_tpu
from .pallas_common import resolve_interpret as _resolve_interpret

__all__ = ["ACTIVATIONS", "grouped_matmul", "grouped_glu_ffn",
           "grouped_matmul_refusal", "whole_row_tiles"]

# What a call's double-buffered blocks (rows, weights, output) and float32
# products may take of a v5e core's 128 MiB of VMEM; the call asks for what
# _block_bytes counts and a margin
_BLOCK_BYTES = 48 * 1024 * 1024
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _row_tiles(m):
    """(rows a tile, rows a stretch of it) from the static count of sorted
    rows ``m``. With a handful of rows a group (a decode step) a visit is
    the copy of its expert's matrices and the tile hardly matters: 288 rows
    over 64 groups read 1.080 / 1.060 / 1.054 / 1.054 ms at 16 / 32 / 64 /
    128, 384 and 768 rows with 8 groups held 0.564 and 0.566 at 32 against
    0.578 at 16 and at 128. Where the products are bound by operations, a
    tile of 256 computed in stretches of 128 (the MXU's own height: a
    stretch two groups share is computed once a group, one a group has no
    row in is not) beat every plain tile: 3,072 rows 1.272 ms against 1.362
    (128) and 1.411 (32), 6,144 rows 1.397 against 1.604 (128), 24,576 rows
    3.307 against 3.429 (128) and 3.539 (256); 512 in stretches of 128 read
    3.258 there but 2.556 against 2.062 at 32,768 rows of which 1,045 have
    a group (PERF.md, PR 36: one chip call, bfloat16, v5e). No count was
    found at which the TPU's ``ragged_dot`` wins."""
    return (32, 32) if m <= 1024 else (256, 128)


def whole_row_tiles(m):
    """``m`` rows rounded up to whole row tiles of a call of that many."""
    tm, _ = _row_tiles(m)
    return -(-m // tm) * tm


def _block_bytes(tm, k, tn, n_rhs, itemsize, out_itemsize):
    """VMEM of one call: two buffers of each block and the float32 products
    of a visit."""
    blocks = tm * k * itemsize + n_rhs * k * tn * itemsize + tm * tn * out_itemsize
    return 2 * blocks + (n_rhs + 1) * tm * tn * 4


def _col_tile(tm, k, n, n_rhs, itemsize, out_itemsize):
    """The widest tile of whole lanes that divides ``n`` and keeps the
    blocks inside ``_BLOCK_BYTES``; None where none does."""
    for parts in range(1, n // _LANES + 1):
        tn = n // parts
        if n % parts or tn % _LANES:
            continue
        if _block_bytes(tm, k, tn, n_rhs, itemsize, out_itemsize) <= _BLOCK_BYTES:
            return tn
    return None


def grouped_matmul_refusal(pairs, d, w, x_dtype, w_dtype):
    """Why the kernel does NOT run an expert layer's three products, or None
    when it does: ``pairs`` sorted rows of width ``d`` in ``x_dtype``,
    experts of width ``w`` in ``w_dtype``. The first condition that fails is
    the one named; callers keep ``lax.ragged_dot`` then."""
    if not _on_tpu():
        return "the backend is not a TPU"
    if jnp.dtype(x_dtype) != jnp.dtype(w_dtype):
        return (f"rows in {jnp.dtype(x_dtype).name} against weights in "
                f"{jnp.dtype(w_dtype).name}")
    if jnp.dtype(w_dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"dtype {jnp.dtype(w_dtype).name} is not bfloat16 or float32"
    if d % _LANES or w % _LANES:
        return f"widths {d} and {w} are not whole {_LANES}-lane tiles"
    itemsize = jnp.dtype(w_dtype).itemsize
    tm, _ = _row_tiles(pairs)
    if (_col_tile(tm, d, w, 2, itemsize, itemsize) is None
            or _col_tile(tm, w, d, 1, itemsize, 4) is None):
        return (f"a block over the whole of {d} x {w} does not fit "
                f"{_BLOCK_BYTES >> 20} MiB of VMEM")
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _visit_plan(sizes, tiles, tm):
    """The visits of ``tiles`` row tiles of ``tm`` by the groups of
    ``sizes``: (group of each visit, tile of each visit, first row of each
    group and the last one's end, count of visits). A group is visited once
    a tile it has a row in, the groups in order, so a tile's visits are
    successive and so are a group's."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(count)
    visit = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(upto, visit, side="right", method="compare_all"),
        g - 1).astype(jnp.int32)
    tile = first[group] + visit - (upto - count)[group]
    # slots past the count are never run; they name a tile there is
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    bounds = jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32)
    return group, tile, bounds, upto[-1].astype(jnp.int32)


def _kernel(group_ref, tile_ref, bounds_ref, x_ref, *refs, tm, sub, act):
    *rhs_refs, o_ref = refs
    visit = pl.program_id(1)
    g = group_ref[visit]
    lo, hi = bounds_ref[g], bounds_ref[g + 1]
    row0 = tile_ref[visit] * tm

    def rows_of(s, masked):
        at = slice(s * sub, (s + 1) * sub)
        xs = x_ref[at, :]
        prods = [jnp.dot(xs, r[...], preferred_element_type=jnp.float32)
                 for r in rhs_refs]
        y = prods[0] if act is None else act(prods[0]) * prods[1]
        y = y.astype(o_ref.dtype)
        if masked:
            row = row0 + s * sub + lax.broadcasted_iota(jnp.int32, y.shape, 0)
            y = jnp.where((row >= lo) & (row < hi), y, o_ref[at, :])
        o_ref[at, :] = y

    # the tile in stretches of ``sub`` rows: one the group fills is stored
    # whole, one it shares is stored under a mask, one it has no row in is
    # not computed
    for s in range(tm // sub):
        a, b = row0 + s * sub, row0 + (s + 1) * sub
        whole = (a >= lo) & (b <= hi)
        pl.when(whole)(functools.partial(rows_of, s, False))
        pl.when(~whole & (a < hi) & (b > lo))(functools.partial(rows_of, s, True))


def _call(plan, x, rhs, act, out_dtype, tm, sub, name, interpret):
    group, tile, bounds, visits = plan
    m, k = x.shape
    n = rhs[0].shape[2]
    itemsize = jnp.dtype(x.dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype).itemsize
    tn = _col_tile(tm, k, n, len(rhs), itemsize, out_itemsize)
    if tn is None:
        raise ValueError(f"no column tile of {n} fits VMEM with rows of {k}")
    held = rhs[0].shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, sub=sub, act=act),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # column tiles outermost: a group's visits stay successive
            grid=(n // tn, visits),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, g, t, b: (t[v], 0))]
            + [pl.BlockSpec((None, k, tn), lambda j, v, g, t, b: (g[v], 0, j))
               for _ in rhs],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, g, t, b: (t[v], j))),
        name=name,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(rhs), transcendentals=0,
            bytes_accessed=(m * k * itemsize + m * n * out_itemsize
                            + len(rhs) * held * k * n * itemsize)),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_block_bytes(tm, k, tn, len(rhs), itemsize,
                                          out_itemsize) + (16 << 20)),
    )(group, tile, bounds, x, *rhs)


def _tiles(m, row_tile, sub_rows):
    """(rows a tile, rows a stretch) of ``m`` rows: :func:`_row_tiles`, or
    what a test or the tuning asks for."""
    tm, sub = _row_tiles(m)
    if row_tile is not None:
        tm = sub = row_tile
    if sub_rows is not None:
        sub = min(sub_rows, tm)
    if tm % sub or sub % 16:
        raise ValueError(f"row tile {tm} is not whole stretches of {sub} rows "
                         "of whole sublane tiles")
    return tm, sub


def _tiled(x, sizes, tm):
    """(rows padded to whole tiles, the visits' plan)."""
    m = x.shape[0]
    tiles = -(-m // tm)
    if tiles * tm != m:
        x = jnp.pad(x, ((0, tiles * tm - m), (0, 0)))
    return x, _visit_plan(sizes.astype(jnp.int32), tiles, tm)


def _check(x, rhs, sizes):
    for r in rhs:
        if r.ndim != 3 or r.shape[0] != sizes.shape[0] or r.shape[1] != x.shape[1]:
            raise ValueError(f"weights {r.shape} do not take rows {x.shape} in "
                             f"{sizes.shape[0]} groups")
        if r.dtype != x.dtype:
            raise ValueError(f"rows in {x.dtype} against weights in {r.dtype}")


def grouped_matmul(x, rhs, sizes, *, row_tile=None, sub_rows=None,
                   interpret=None):
    """``x[rows of group g] @ rhs[g]`` for every group: ``x`` ``[m, k]``
    sorted by group, ``rhs`` ``[groups, k, n]``, ``sizes`` ``[groups]`` int32
    with a sum of ``m`` or less. Returns ``[m, n]`` float32; the rows past
    the sizes' sum belong to no group and are UNWRITTEN. ``k`` and ``n``
    are whole 128-lane tiles."""
    _check(x, (rhs,), sizes)
    m = x.shape[0]
    tm, sub = _tiles(m, row_tile, sub_rows)
    xp, plan = _tiled(x, sizes, tm)
    return _call(plan, xp, (rhs,), None, jnp.float32, tm, sub,
                 "grouped_matmul", _resolve_interpret(interpret))[:m]


def grouped_glu_ffn(x, w_gate, w_up, w_down, sizes, activation, *,
                    row_tile=None, sub_rows=None, interpret=None):
    """A gated feed-forward of every group's own expert:
    ``(act(x @ w_gate[g]) * (x @ w_up[g])).astype(x.dtype) @ w_down[g]`` for
    the rows of group ``g``, as :func:`grouped_matmul` reads them: two
    calls over one plan of visits, the first reading the rows once for both
    products. Returns ``[m, d]`` float32, unwritten past the sizes' sum."""
    _check(x, (w_gate, w_up), sizes)
    _check(jax.ShapeDtypeStruct((x.shape[0], w_gate.shape[2]), x.dtype),
           (w_down,), sizes)
    tm, sub = _tiles(x.shape[0], row_tile, sub_rows)
    return _glu_ffn(x, w_gate, w_up, w_down, sizes, activation=activation,
                    tm=tm, sub=sub, interpret=_resolve_interpret(interpret))


# jitted so that a program's layers of one shape are ONE traced and lowered
# function: unjitted, each layer's two kernels were lowered to Mosaic anew
# (56 in SmallThinker's six serving programs: 10 s of a warm set-up of 55,
# PERF.md, PR 36), and that lowering comes before any compile cache is asked
@functools.partial(jax.jit,
                   static_argnames=("activation", "tm", "sub", "interpret"))
def _glu_ffn(x, w_gate, w_up, w_down, sizes, *, activation, tm, sub,
             interpret):
    m = x.shape[0]
    xp, plan = _tiled(x, sizes, tm)
    mid = _call(plan, xp, (w_gate, w_up), ACTIVATIONS[activation], x.dtype,
                tm, sub, "grouped_matmul_gate_up", interpret)
    return _call(plan, mid, (w_down,), None, jnp.float32, tm, sub,
                 "grouped_matmul_down", interpret)[:m]
