"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): a recurrent
matrix state a head, ``S`` (dk, dv), advanced a position at a time ::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

One token a row (decode): :func:`gdn_decode_step`, ONE Pallas kernel
(``gdn_decode_step`` in a trace) that reads a live row's state once, forms
``S^T k``, the update and ``S^T q`` and writes the state back in place; a
dead row's state is not moved. Off the TPU the XLA form
:func:`gdn_decode_xla` (the kernel's oracle); :func:`gdn_decode_refusal`
says which a program takes and why.

A whole prompt (prefill): :func:`gdn_chunk_prefill`, the chunked form in
XLA: within a block of ``chunk`` positions the delta rule's triangular
system is solved by matrix products, and a ``lax.scan`` over the blocks
carries the state from one to the next. No scan over positions.

**The same family without the delta term** (Lightning Attention,
arXiv:2401.04658: ``S_t = lambda S_(t-1) + k_t v_t^T`` with a fixed decay a
head): ``delta=False`` of :func:`gdn_decode_step` / :func:`gdn_decode_xla`
is the same kernel with the read ``S^T k`` and the write's correction left
out (``lightning_decode_step`` in a trace), and
:func:`lightning_chunk_prefill` its chunked prefill, which stops decaying
and writing at the prompt's length.

**The state's layout.** ``(slots, dk, H * dv)`` float32: key width on the
sublanes, the heads' value widths side by side on the lanes (head ``h`` in
lanes ``h * dv ..``), so a row's state is whole (8, 128) tiles with nothing
padded (30 heads of 96 x 192: 96 x 5,760) and the two sums over the key
width are sums over sublanes whose results lie as ``o`` does.
:func:`state_rows` / :func:`state_heads` turn a row's state to and from
``(H, dk, dv)``.
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

_HI = lax.Precision.HIGHEST
_VMEM_HEADROOM = 8 * 1024 * 1024


# -- layout -----------------------------------------------------------------
def state_rows(s):
    """``(..., H, dk, dv)`` -> ``(..., dk, H * dv)``: as the engine holds it."""
    *lead, h, dk, dv = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, dk, h * dv)


def state_heads(rows, heads):
    """``(..., dk, H * dv)`` -> ``(..., H, dk, dv)``."""
    *lead, dk, hv = rows.shape
    return jnp.moveaxis(rows.reshape(*lead, dk, heads, hv // heads), -2, -3)


def _heads_a_group(heads, dv):
    """Heads whose value widths fill whole lane tiles together (two of 192:
    384 lanes); every head where no such count divides ``heads`` (toy
    widths, the interpreter's)."""
    for n in range(1, heads + 1):
        if heads % n == 0 and (n * dv) % _LANES == 0:
            return n
    return heads


# -- one token a row --------------------------------------------------------
def gdn_decode_refusal(state, q, v):
    """Why the kernel does NOT advance ``state`` ``(B, dk, H * dv)`` for
    ``q`` ``(B, H, dk)`` and ``v`` ``(B, H, dv)`` (anything with ``.shape``
    and ``.dtype``), or None when it does; callers take
    :func:`gdn_decode_xla` then. The first condition that fails is named."""
    if not _on_tpu():
        return "the backend is not a TPU"
    _, heads, dk = q.shape
    dv = v.shape[2]
    if state.dtype != jnp.float32:
        return f"state dtype {jnp.dtype(state.dtype).name} is not float32"
    if dk % 8 or (_heads_a_group(heads, dv) * dv) % _LANES:
        return (f"{heads} heads of {dk} x {dv} are not whole (8, {_LANES}) "
                "tiles a group of heads")
    if heads > _LANES:
        return f"{heads} heads do not fit one lane tile"
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _decode_kernel(block_ref, live_ref, s_ref, kt_ref, qt_ref, ax_ref, bv_ref,
                   ab_ref, o_ref, out_ref, *, heads, dv, group, delta=True):
    """One row a grid step. ``block_ref`` names the row whose state the step
    holds: a dead row's step holds a live neighbour's and does nothing, so
    its own state is neither read nor written."""
    b = pl.program_id(0)
    dk = s_ref.shape[1]
    width = group * dv

    @pl.when(live_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live_ref[b] != 0)
    def _():
        kt, qt = kt_ref[0], qt_ref[0]                       # (dk, H)
        lane = lax.broadcasted_iota(jnp.int32, (dk, width), 1)

        def spread(cols, first):
            """(dk, width): head ``first + i``'s column over its dv lanes."""
            out = cols[:, first:first + 1]
            for i in range(1, group):
                out = jnp.where(lane < i * dv, out,
                                cols[:, first + i:first + i + 1])
            return jnp.broadcast_to(out, (dk, width))

        for g in range(heads // group):
            lanes = pl.ds(g * width, width)
            s = s_ref[0, :, lanes]                          # (dk, width)
            kx, qx = spread(kt, g * group), spread(qt, g * group)
            if delta:
                read = jnp.sum(s * kx, axis=0, keepdims=True)   # S^T k
                write = bv_ref[0, :, lanes] - ab_ref[0, :, lanes] * read
            else:
                write = bv_ref[0, :, lanes]
            s = ax_ref[0, :, lanes] * s + kx * write
            out_ref[0, :, lanes] = s
            o_ref[0, :, lanes] = jnp.sum(s * qx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _decode_call(state, kt, qt, ax, bv, ab, live, heads, interpret,
                 delta=True):
    b, dk, hv = state.shape
    dv = hv // heads
    group = _heads_a_group(heads, dv)
    live = live.astype(jnp.int32)
    # the row whose state a step holds: its own if live, else the next live
    # row's, else the last live row's (steps that hold one block follow one
    # another, so the block is fetched once and written once)
    rows = jnp.arange(b, dtype=jnp.int32)
    nxt = lax.cummin(jnp.where(live > 0, rows, b), axis=0, reverse=True)
    last = jnp.max(jnp.where(live > 0, rows, 0))
    block = jnp.where(nxt < b, nxt, last).astype(jnp.int32)
    held = lambda i, blk, _: (blk[i], 0, 0)  # noqa: E731
    own = lambda i, *_: (i, 0, 0)  # noqa: E731
    small = lambda w: pl.BlockSpec((1, 1, w), own)  # noqa: E731
    o, new = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, dv=dv, group=group,
                          delta=delta),
        out_shape=(jax.ShapeDtypeStruct((b, 1, hv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, dk, hv), held),
                      pl.BlockSpec((1, dk, heads), own),
                      pl.BlockSpec((1, dk, heads), own),
                      small(hv), small(hv), small(hv)],
            out_specs=(small(hv), pl.BlockSpec((1, dk, hv), held))),
        input_output_aliases={2: 1},   # the state, behind the two scalars
        name="gdn_decode_step" if delta else "lightning_decode_step",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=6 * dk * hv * 4 + _VMEM_HEADROOM),
    )(block, live, state, kt, qt, ax, bv, ab)
    return o.reshape(b, heads, dv), new


def _decode_operands(q, k, v, alpha, beta, live):
    """What both forms read, float32: the keys and queries with the key
    width leading (dk, H), alpha, beta * v and alpha * beta spread over each
    head's dv lanes (1, H * dv), and the live flags."""
    b, heads, _ = q.shape
    dv = v.shape[2]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    wide = lambda x: jnp.repeat(f32(x), dv, axis=1).reshape(b, 1, heads * dv)  # noqa: E731
    return (jnp.swapaxes(f32(k), 1, 2), jnp.swapaxes(f32(q), 1, 2),
            wide(alpha), (f32(beta)[:, :, None] * f32(v)).reshape(b, 1, -1),
            wide(alpha * beta), jnp.asarray(live, bool))


def gdn_decode_step(state, q, k, v, alpha, beta, live, interpret=None,
                    delta=True):
    """Advance the live rows' state one position: ``state`` ``(B, dk, H *
    dv)`` float32 (written in place where the caller donates it), ``q``, ``k`` ``(B, H,
    dk)``, ``v`` ``(B, H, dv)``, ``alpha``, ``beta`` ``(B, H)``, ``live``
    ``(B,)`` bool. Returns ``(o (B, H, dv) float32, state')``; a dead row's
    state is untouched bit for bit (its bytes are not moved) and its ``o``
    is zero. With ``delta=False`` the rule is ``S <- alpha S + beta k v^T``
    (no delta term: Lightning Attention's state, ``beta`` 1). Callers gate
    via :func:`gdn_decode_refusal`."""
    kt, qt, ax, bv, ab, live = _decode_operands(q, k, v, alpha, beta, live)
    none = ~jnp.any(live)
    first = jnp.arange(live.shape[0]) == 0
    # no live row: row 0 is held and multiplied by one (S * 1 + k * 0)
    hold = (none & first)[:, None, None]
    ax, bv, ab = (jnp.where(hold, fill, x)
                  for x, fill in ((ax, 1.0), (bv, 0.0), (ab, 0.0)))
    o, state = _decode_call(state, kt, qt, ax, bv, ab, live | (none & first),
                            q.shape[1], _resolve_interpret(interpret), delta)
    return jnp.where(live[:, None, None], o, 0.0), state


def gdn_decode_xla(state, q, k, v, alpha, beta, live, delta=True):
    """:func:`gdn_decode_step` in XLA: the same arithmetic in the same
    order, every row's state read and written."""
    b, heads, _ = q.shape
    kt, qt, ax, bv, ab, live = _decode_operands(q, k, v, alpha, beta, live)
    dv = v.shape[2]
    spread = lambda cols: jnp.repeat(cols, dv, axis=2)     # (B, dk, H*dv)  # noqa: E731
    kx, qx = spread(kt), spread(qt)
    if delta:
        read = jnp.sum(state * kx, axis=1, keepdims=True)
        new = ax * state + kx * (bv - ab * read)
    else:
        new = ax * state + kx * bv
    o = jnp.sum(new * qx, axis=1)
    keep = live[:, None, None]
    return (jnp.where(keep[:, 0], o, 0.0).reshape(b, heads, dv),
            jnp.where(keep, new, state))


# -- a whole prompt ---------------------------------------------------------
def gdn_chunk_prefill(q, k, v, g, beta, chunk=64):
    """The gated delta rule over one sequence from a zero state, in blocks of
    ``chunk`` positions: ``q``, ``k`` ``(T, H, dk)``, ``v`` ``(T, H, dv)``,
    ``g`` = log alpha and ``beta`` ``(T, H)``, float32. A position with
    ``g`` 0 and ``beta`` 0 leaves the state as it is: a prefill's padding,
    and what a last block is filled up with here. Returns ``(o (T, H, dv), S
    (H, dk, dv))``: every position's read and the state behind the last.

    Within a block, with ``G_i`` the running sum of ``g`` and ``A`` the
    strictly lower triangle of ``beta_i (k_i . k_j) exp(G_i - G_j)``, the
    rows ``u = (I + A)^-1 beta v`` and ``w = (I + A)^-1 beta exp(G) k`` give
    the block's writes against the state ``S`` it starts from as ``u - w
    S`` (``I + A`` is unit lower triangular: one forward substitution for
    every block and head at once); every other step is a matrix product at
    ``highest``."""
    length, heads, dk = q.shape
    c = min(chunk, length)
    whole = lambda x: jnp.pad(  # noqa: E731
        x, ((0, -length % c),) + ((0, 0),) * (x.ndim - 1))
    q, k, v, g, beta = whole(q), whole(k), whole(v), whole(g), whole(beta)
    t = q.shape[0]
    n = t // c
    blocks = lambda x: jnp.moveaxis(  # (T, H, ...) -> (n, H, c, ...)  # noqa: E731
        x.reshape(n, c, *x.shape[1:]), 1, 2)
    q, k, v = blocks(q), blocks(k), blocks(v)
    g, beta = blocks(g), blocks(beta)                       # (n, H, c)
    run = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((c, c), bool), -1)
    upto = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_i - G_j) where j <= i (the masked entries' exponents may overflow)
    decay = jnp.exp(jnp.where(upto, run[..., :, None] - run[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    a = jnp.where(lower, jnp.einsum("nhik,nhjk->nhij", kb, k, precision=_HI)
                  * decay, 0.0)
    # (I + A) x = [beta v | beta exp(G) k]: unit lower triangular, solved by
    # forward substitution (every block and head at once)
    solved = jax.scipy.linalg.solve_triangular(
        jnp.eye(c, dtype=jnp.float32) + a,
        jnp.concatenate([v * beta[..., None],
                         kb * jnp.exp(run)[..., None]], axis=-1),
        lower=True, unit_diagonal=True)
    u, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    qk = jnp.where(upto, jnp.einsum("nhik,nhjk->nhij", q, k, precision=_HI)
                   * decay, 0.0)
    q_in = q * jnp.exp(run)[..., None]
    tail = jnp.exp(run[..., -1:] - run)                     # to the block's end
    k_out = k * tail[..., None]
    carry = jnp.exp(run[..., -1])                           # (n, H)

    def block(s, xs):
        u_b, w_b, qk_b, q_b, k_b, decay_b = xs
        new = u_b - jnp.einsum("hik,hkv->hiv", w_b, s, precision=_HI)
        o = jnp.einsum("hik,hkv->hiv", q_b, s, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_b, new, precision=_HI)
        s = s * decay_b[:, None, None] \
            + jnp.einsum("hik,hiv->hkv", k_b, new, precision=_HI)
        return s, o

    s, o = lax.scan(block, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                    (u, w, qk, q_in, k_out, carry))
    return jnp.moveaxis(o, 1, 2).reshape(t, heads, -1)[:length], s


def lightning_chunk_prefill(q, k, v, log_decay, length, chunk=128, emit=None,
                            state=None):
    """``S_t = lambda S_(t-1) + k_t v_t^T``, ``o_t = S_t^T q_t`` over one
    sequence from a zero state (or from ``state`` ``(H, dk, dv)``: a long
    sequence a stretch at a time), in blocks of ``chunk`` positions: ``q``,
    ``k`` ``(T, H, dk)``, ``v`` ``(T, H, dv)`` in any float dtype (a block
    is turned to float32 as the scan reaches it), ``log_decay`` ``(H,)`` =
    log lambda, ``length`` the positions that are real (a traced scalar,
    negative or zero for none: the rest is a prefill's padding, which
    neither decays nor writes the state).
    Returns ``(o (T, H, dv) float32, S (H, dk, dv))``: every real
    position's read and the state behind position ``length - 1``. With
    ``emit`` a block's reads ``(chunk, H, dv)`` leave the scan as
    ``emit(reads)`` ``(chunk, ...)``: what follows a read position by
    position (a norm, a cast) then holds no float32 array of the sequence's
    length.

    Within a block ``(Q K^T * D) V`` with ``D_ij = lambda^(i-j)`` for ``i >=
    j``, plus ``diag(lambda^(i+1)) Q S_in``, and ``S_out = lambda^C S_in +
    sum_j lambda^(C-1-j) k_j v_j^T``; every exponent is a sum of log-decays
    over REAL positions, none is positive, and every product is at
    ``highest``."""
    t, heads, dk = q.shape
    c = min(chunk, t)
    whole = lambda x: jnp.pad(  # noqa: E731
        x, ((0, -t % c),) + ((0, 0),) * (x.ndim - 1))
    q, k, v = whole(q), whole(k), whole(v)
    n = q.shape[0] // c
    real = jnp.arange(n * c) < length
    blocks = lambda x: x.reshape(n, c, *x.shape[1:])  # noqa: E731
    upto = jnp.tril(jnp.ones((c, c), bool))
    log_decay = jnp.asarray(log_decay, jnp.float32)
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 0, 1)  # noqa: E731

    def block(s, xs):
        q_b, k_b, v_b, real_b = xs                          # (c, H, d), (c,)
        q_b, v_b = f32(q_b), f32(v_b)                       # (H, c, d)
        k_b = jnp.where(real_b[None, :, None], f32(k_b), 0.0)
        run = jnp.cumsum(real_b.astype(jnp.float32))[None, :] \
            * log_decay[:, None]                            # (H, c)
        decay = jnp.exp(jnp.where(upto, run[:, :, None] - run[:, None, :],
                                  -jnp.inf))
        qk = jnp.einsum("hik,hjk->hij", q_b, k_b, precision=_HI) * decay
        o = jnp.einsum("hij,hjv->hiv", qk, v_b, precision=_HI) \
            + jnp.einsum("hik,hkv->hiv", q_b * jnp.exp(run)[..., None], s,
                         precision=_HI)
        k_out = k_b * jnp.exp(run[:, -1:] - run)[..., None]
        s = s * jnp.exp(run[:, -1])[:, None, None] \
            + jnp.einsum("hik,hiv->hkv", k_out, v_b, precision=_HI)
        o = jnp.moveaxis(o, 0, 1)
        return s, o if emit is None else emit(o)

    if state is None:
        state = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
    s, o = lax.scan(block, state,
                    (blocks(q), blocks(k), blocks(v), blocks(real)))
    return o.reshape(n * c, *o.shape[2:])[:t], s
