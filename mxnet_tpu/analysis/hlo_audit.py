"""Structural analysis of lowered StableHLO / compiled HLO programs.

The framework's correctness story rests on *structural* properties of the
programs XLA is asked to run — bf16 dots under the AMP policy, f32 master
updates, donated carries, exactly-(buckets+1) serving programs. Before this
module those were checked by ad-hoc regexes scattered over the test suite;
here the program text is parsed ONCE into a :class:`ProgramReport` that
every test, tool and gate queries structurally.

Two text dialects are understood, matching the two stages a jitted program
passes through:

  - **stablehlo** — ``jax.jit(f).lower(...).as_text()``: MLIR, one
    ``stablehlo.<op>`` per line, donation as ``tf.aliasing_output`` /
    ``jax.buffer_donor`` arg attributes, layouts as ``sdy.sharding`` (or,
    from a jax that still partitions with GSPMD, ``mhlo.sharding``). This
    is *the program XLA is asked to run* — dtype assertions (bf16 dots, no
    f64 leaks) belong here, because the CPU backend legalizes
    low-precision GEMMs back to f32 at compile time.
  - **hlo** — ``...compile().as_text()``: post-optimization HLO, donation
    in the ``input_output_alias`` module header, GSPMD-inserted collectives
    (``all-reduce`` et al. with ``replica_groups``). Collective/fusion/
    memory structure belongs here.

Also here: the :class:`Fingerprint` of a program's input signature
(shapes, dtypes, static args) and the :class:`RecompileGuard` that diffs
fingerprints to explain *why* a recompile happened — the cause ("shape" /
"dtype" / static args) lands in the observability event log and a
``reason``-labelled counter, not just a bare count.

See docs/ANALYSIS.md for the schema and a how-to.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import Counter as _Counter
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Op", "Collective", "DonationReport", "ProgramReport",
           "ProgramAudit", "audit_text", "audit_lowered", "audit_compiled",
           "Fingerprint", "fingerprint_diff", "RecompileGuard",
           "ShardingInfo", "parse_sharding", "parse_sdy_meshes",
           "parse_sdy_shardings", "sharding_info", "ValueDef", "DTYPE_BYTES"]

#: element width in bytes per HLO dtype token (pred stored as one byte).
#: Lives here (not comm.py, which re-exports it) because both the comm
#: cost model and the buffer-liveness pass size tensors with it.
DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "i1": 1, "i8": 1, "i16": 2, "i32": 4, "i64": 8, "ui8": 1, "ui16": 2,
    "ui32": 4, "ui64": 8,
}


def tensor_bytes(dtype: Optional[str], shape: Sequence[int]) -> int:
    """Logical bytes of one tensor (4-byte fallback for unknown dtypes)."""
    n = 1
    for d in shape:
        n *= d
    return n * DTYPE_BYTES.get(dtype or "", 4)

# ops that move data between host and device (either dialect's spelling,
# normalized): the serving/training hot loops must never contain one
HOST_TRANSFER_OPS = frozenset({
    "infeed", "outfeed", "send", "send_done", "recv", "recv_done",
    "copy_to_host", "copy_from_host",
})

# collective ops (normalized names)
COLLECTIVE_OPS = frozenset({
    "all_reduce", "all_gather", "reduce_scatter", "collective_permute",
    "all_to_all", "collective_broadcast",
})

# dot-like ops: everything that lands on the MXU
DOT_OPS = frozenset({"dot", "dot_general", "convolution"})

_FLOAT_DTYPES = ("f64", "f32", "f16", "bf16", "f8e4m3fn", "f8e5m2")


# the -done half of an async collective pair: dropped by the parsers so
# one start/done pair counts as ONE collective (send/recv keep their done
# ops — they are distinct host-transfer instructions)
_ASYNC_DONE = frozenset({
    "all_reduce_done", "all_gather_done", "collective_permute_done",
    "all_to_all_done", "copy_done",
})


def _normalize_op(name: str) -> str:
    """Canonical op name across dialects: ``stablehlo.dot_general`` /
    ``mhlo.dot_general`` / HLO ``all-reduce-start`` all collapse to a bare
    underscore form (``dot_general``, ``all_reduce``)."""
    name = name.rsplit(".", 1)[-1].replace("-", "_")
    # async pairs count as the base op once: -start carries the payload
    # (replica groups included) and becomes the base op; -done is dropped
    # at parse time (_ASYNC_DONE)
    if name.endswith("_start") and name[:-6] in {
            "all_reduce", "all_gather", "collective_permute",
            "all_to_all", "copy"}:
        return name[:-6]
    return name


@dataclasses.dataclass(frozen=True)
class ShardingInfo:
    """One parsed sharding annotation — the layout of a tensor.

    Every spelling normalizes here: the lowered dialect's GSPMD attribute
    ``mhlo.sharding = "{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}"``,
    its Shardy attribute ``sdy.sharding = #sdy.sharding<@mesh, [{"fsdp"}, {}]>``
    (axis names a dimension, sized by the module's ``sdy.mesh``; what jax
    writes since the Shardy partitioner became its default), the compiled
    dialect's ``sharding={...}`` parameter attribute, and a
    ``jax.sharding.Sharding`` the compiler hands back. ``tile_dims`` is the
    number of shards along each *tensor* dimension (the subgroup-replication
    tile — ``last_tile_dim_replicate`` — already stripped), so "is this
    tensor laid out the way the rules declared" is a per-dim integer
    comparison, never a device-list diff.
    """

    kind: str  # "replicated" | "tiled" | "maximal" | "manual" | "unknown"
    tile_dims: Tuple[int, ...] = ()  # shards per tensor dim (tiled only)
    replicate_last: bool = False  # subgroup replication was present
    raw: str = ""

    @property
    def is_replicated(self) -> bool:
        """Fully materialized on every device (maximal — one device holds
        the whole tensor — counts: nothing is partitioned)."""
        return self.kind in ("replicated", "maximal") or (
            self.kind == "tiled" and all(d == 1 for d in self.tile_dims))

    def describe(self) -> str:
        if self.kind == "tiled" and not self.is_replicated:
            return f"sharded devices={list(self.tile_dims)}"
        if self.kind == "unknown":
            return f"unknown {self.raw!r}"
        return "replicated" if self.is_replicated else self.kind


_SHARDING_DEVICES = re.compile(r"devices=\[([0-9,]+)\]")


def parse_sharding(raw: str) -> ShardingInfo:
    """Parse one HLO sharding attribute value (either dialect's spelling,
    braces/quotes tolerated) into a :class:`ShardingInfo`."""
    body = raw.strip().strip('"').strip()
    if body.startswith("{") and body.endswith("}"):
        body = body[1:-1].strip()
    if body.startswith("{"):
        # tuple sharding ({{..}, {..}}): per-element layouts — not a
        # single-tensor annotation, keep raw
        return ShardingInfo("unknown", raw=raw)
    if body == "replicated":
        return ShardingInfo("replicated", raw=raw)
    if body.startswith("maximal"):
        return ShardingInfo("maximal", raw=raw)
    if body == "manual":
        return ShardingInfo("manual", raw=raw)
    m = _SHARDING_DEVICES.search(body)
    if m:
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        rep_last = "last_tile_dim_replicate" in body
        if rep_last and dims:
            dims = dims[:-1]
        return ShardingInfo("tiled", tile_dims=dims, replicate_last=rep_last,
                            raw=raw)
    return ShardingInfo("unknown", raw=raw)


def _tiled(tiles: Tuple[int, ...], n_devices: int, raw: str) -> ShardingInfo:
    """Per-dim shard counts over ``n_devices`` as a :class:`ShardingInfo`."""
    if all(t == 1 for t in tiles):
        return ShardingInfo("replicated", raw=raw)
    return ShardingInfo("tiled", tile_dims=tiles,
                        replicate_last=math.prod(tiles) < n_devices, raw=raw)


# Shardy: `sdy.mesh @mesh = <["dp"=2, "fsdp"=4]>` once a module, then
# `<@mesh, [{"dp", "fsdp"}, {}]>` wherever a tensor is laid out — inside
# `#sdy.sharding<...>` on an argument, `#sdy.sharding_per_value<[...]>` on
# an op, bare on `sdy.sharding_constraint`. A dimension lists the axes it
# is split over (`"x":(2)4` is a sub-axis of size 4, `?` leaves it open)
_SDY_MESH = re.compile(r'sdy\.mesh\s+@([\w.$-]+)\s*=\s*<\[([^\]]*)\]')
_SDY_MESH_AXIS = re.compile(r'"([^"]+)"\s*=\s*(\d+)')
_SDY_LAYOUT = re.compile(r'<@([\w.$-]+),\s*\[([^\]]*)\]')
_SDY_DIM = re.compile(r'\{([^}]*)\}')
_SDY_AXIS_REF = re.compile(r'"([^"]+)"(?::\(\d+\)(\d+))?')


def _sdy_tiles(dims: str, axes: Dict[str, int]) -> Optional[Tuple[int, ...]]:
    """Shards per dimension of one Shardy dimension list, None where it
    names an axis the mesh does not have."""
    tiles = []
    for dim in _SDY_DIM.findall(dims):
        n = 1
        for name, sub in _SDY_AXIS_REF.findall(dim):
            size = int(sub) if sub else axes.get(name)
            if size is None:
                return None
            n *= size
        tiles.append(n)
    return tuple(tiles)


def parse_sdy_shardings(text: str, meshes: Dict[str, Dict[str, int]]
                        ) -> List[ShardingInfo]:
    """Every Shardy tensor layout spelled in ``text``, in order, sized by
    ``meshes`` (``{mesh name: {axis: size}}``, from :func:`parse_sdy_meshes`).
    A layout over a mesh or an axis the module does not declare is
    ``unknown``, never silently replicated."""
    out = []
    for m in _SDY_LAYOUT.finditer(text):
        raw, axes = m.group(0) + ">", meshes.get(m.group(1))
        tiles = None if axes is None else _sdy_tiles(m.group(2), axes)
        out.append(ShardingInfo("unknown", raw=raw) if tiles is None
                   else _tiled(tiles, math.prod(axes.values()), raw))
    return out


def parse_sdy_meshes(text: str) -> Dict[str, Dict[str, int]]:
    """``{mesh name: {axis: size}}`` of a module's ``sdy.mesh`` lines."""
    return {m.group(1): {a: int(n) for a, n in
                         _SDY_MESH_AXIS.findall(m.group(2))}
            for m in _SDY_MESH.finditer(text)}


def sharding_info(sharding, shape: Sequence[int]) -> ShardingInfo:
    """A ``jax.sharding.Sharding`` (what ``Compiled.input_shardings``
    holds) laid over a tensor of global ``shape``."""
    shard = sharding.shard_shape(tuple(shape))
    return _tiled(tuple(-(-g // l) if l else 1 for g, l in zip(shape, shard)),
                  len(sharding.device_set), str(sharding))


@dataclasses.dataclass
class Op:
    """One program instruction: normalized name, result dtype/shape, and
    every dtype mentioned on its line (operands included)."""

    name: str
    dtype: Optional[str]  # result element dtype ("f32", "bf16", ...)
    shape: Tuple[int, ...]  # result shape ( () for scalars/unknown )
    dtypes: Tuple[str, ...]  # all dtypes on the line, operands included
    line: int
    shapes: Tuple[Tuple[int, ...], ...] = ()  # shapes paired with `dtypes`
    sharding: Optional[ShardingInfo] = None  # per-op sharding annotation
    # dot/convolution contraction structure (both dialects), feeding the
    # analytic FLOPs model (observability.goodput.program_flops):
    #   dot_general:  {"lhs_contracting": (dims,), "lhs_batching": (dims,)}
    #   convolution:  {"kernel_out_dim": i, "batch_groups": g}
    # None for every other op, or when the attributes could not be parsed.
    dot_meta: Optional[dict] = None

    def __repr__(self):
        dims = "x".join(map(str, self.shape)) or "scalar"
        return f"Op({self.name}: {self.dtype}[{dims}] @L{self.line})"


@dataclasses.dataclass
class Collective(Op):
    """A collective op plus its replica grouping. ``groups`` is the
    normalized tuple-of-tuples of device ids, or None when the grouping
    could not be parsed (``raw_groups`` always keeps the source text).
    ``operand_info``/``result_info`` split the line's tensors by side of
    the op — the communication cost model reads payload sizes from them
    (an all-gather's operand is the shard, its result the full tensor)."""

    raw_groups: str = ""
    groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    operand_info: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    result_info: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()

    @property
    def group_size(self) -> Optional[int]:
        """Devices per replica group — the axis span of this collective."""
        if self.groups:
            return len(self.groups[0])
        return None


@dataclasses.dataclass
class ValueDef:
    """One SSA value definition — the def/use record the buffer-liveness
    pass (:mod:`~mxnet_tpu.analysis.memory`) sweeps. Unlike :class:`Op`
    (the census view, which filters structural noise), every instruction
    that *defines* a value lands here — constants, copies, tuples,
    get-tuple-elements included — because each is a potential allocation.

    ``bytes`` is the full result allocation: tuple results (async
    collective starts, variadic all-reduces, ``while`` carries) sum every
    element, with the per-element ``(dtype, shape)`` list kept in
    ``results`` so donated-alias exclusion can subtract exactly the
    carried element that shares a donated input's buffer."""

    vid: str                   # SSA id, no leading % ("" for return lines)
    op: str                    # normalized op name
    bytes: int                 # full result allocation, tuple elems summed
    results: Tuple[Tuple[str, Tuple[int, ...]], ...]  # per result element
    uses: Tuple[str, ...]      # SSA ids this instruction reads
    line: int
    callees: Tuple[str, ...] = ()   # subcomputations (while body, calls=)
    param: Optional[int] = None     # parameter number (op == "parameter")
    gte_index: Optional[int] = None  # get_tuple_element tuple index

    def __repr__(self):
        return f"ValueDef(%{self.vid}: {self.op} {self.bytes}B @L{self.line})"


@dataclasses.dataclass
class DonationReport:
    """Which flat program inputs are aliased to outputs (donation made it
    through to the executable)."""

    n_inputs: int
    # flat input index -> "may-alias" | "must-alias" | "buffer-donor" (the
    # lowered dialect's donated argument whose output the compiler picks)
    aliased: Dict[int, str]
    # flat OUTPUT index -> flat input index it aliases (the direction the
    # liveness pass needs: a donated carry's output element costs zero
    # extra bytes because it writes the input's buffer in place)
    out_alias: Dict[int, int] = dataclasses.field(default_factory=dict)

    @property
    def n_aliased(self) -> int:
        return len(self.aliased)

    def coverage(self, indices: Optional[Sequence[int]] = None) -> float:
        """Fraction of ``indices`` (default: all inputs) that are aliased —
        1.0 means every donated carry buffer is updated in place."""
        idx = range(self.n_inputs) if indices is None else list(indices)
        n = len(idx)
        if n == 0:
            return 1.0
        hit = sum(1 for i in idx if i in self.aliased)
        return hit / n

    def missing(self, indices: Sequence[int]) -> List[int]:
        return [i for i in indices if i not in self.aliased]


# -- text parsing ------------------------------------------------------------
# stablehlo: `%2 = stablehlo.dot_general %0, %1, ...` or `"stablehlo.case"(`
# (`sdy.sharding_constraint`, `sdy.manual_computation`: Shardy's own ops
# define values like any other; the look-arounds keep the ATTRIBUTE
# `sdy.sharding = #sdy.sharding<..>` of a line from reading as its op)
_MLIR_OP = re.compile(r'"?(?<![#\w.])(?:stablehlo|mhlo|chlo|sdy)\.'
                      r'([a-z0-9_]+)(?![a-z0-9_])"?(?!\s*=)')
# HLO: `%name.3 = bf16[4,2]{1,0} op-name(` — result type optional, and may
# be a TUPLE `(f32[4]{0}, u32[], u32[])` (async collective starts, variadic
# all-reduces) nesting one level (`((f32[4]{0}), token[])`, infeed)
_HLO_OP = re.compile(
    r"=\s*(?:\((?:[^()]|\([^()]*\))*\)\s+"
    r"|[a-z0-9]+\[[^\]]*\][^ ]*\s+)?([a-z][a-z0-9-]*)\(")
# tensor<4x8xbf16> / tensor<f32> / tensor<4x!quant...> (ignore non-builtin)
_MLIR_TENSOR = re.compile(r"tensor<([0-9x]*)((?:[a-z][a-z0-9]*))>")
# f32[4,8]{1,0} dtype[shape] tokens in HLO text
_HLO_TENSOR = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_DTYPES = frozenset({"pred", "s4", "s8", "s16", "s32", "s64", "u4", "u8",
                         "u16", "u32", "u64", "f8e4m3fn", "f8e5m2", "bf16",
                         "f16", "f32", "f64", "c64", "c128", "token"})
# donation, lowered: %arg0: tensor<...> {..., tf.aliasing_output = 0 : i32}
# NB: the attr dict is scanned up to the NEXT %arg, not with a `[^}]*`
# group — quoted attr values like `mhlo.sharding = "{replicated}"` contain
# `}` and would truncate the capture before tf.aliasing_output
_MLIR_ARG = re.compile(r"%arg(\d+):\s*tensor<([^>]*)>")
_MLIR_ALIAS = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
# donated, the output left to the compiler (jax writes this where it cannot
# pair the argument with a result itself)
_MLIR_DONOR = re.compile(r"jax\.buffer_donor\s*=\s*true")
# donation, compiled: input_output_alias={ {0}: (0, {}, may-alias), ... }
# — the brace key is the OUTPUT tuple index, the first paren int the
# input. A single-(non-tuple)-output program spells the key `{}` (empty
# index path = the output itself), so the digits are optional and an
# empty capture means output 0
_HLO_ALIAS_ENTRY = re.compile(r"\{\s*(\d*)[\d,\s]*\}:\s*"
                              r"\((\d+),\s*\{[^}]*\},\s*"
                              r"(may-alias|must-alias)\)")


def _alias_header_body(line: str) -> str:
    """The balanced-brace body of ``input_output_alias={...}`` (nested
    braces — ``{0}: (0, {}, may-alias)`` — defeat a non-greedy regex)."""
    start = line.find("input_output_alias={")
    if start < 0:
        return ""
    i = line.index("{", start)
    depth = 0
    for j in range(i, len(line)):
        if line[j] == "{":
            depth += 1
        elif line[j] == "}":
            depth -= 1
            if depth == 0:
                return line[i + 1:j]
    return line[i + 1:]
# replica groups, compiled: [1,8]<=[8] (iota) or {{0,1},{2,3}} (explicit)
_RG = re.compile(r"replica_groups=(\[[^\]]*\]<=\[[^\]]*\](?:T\([^)]*\))?"
                 r"|\{\{[^=]*?\}\})")
# replica groups, stablehlo: replica_groups = dense<[[0, 1, ..]]> : tensor<..>
_RG_MLIR = re.compile(r"replica_groups\s*=\s*dense<(\[\[.*?\]\]|\d+)>")
# ...and the whole clause incl. the attribute's own tensor type, which
# must never be mistaken for a collective operand/result
_RG_MLIR_CLAUSE = re.compile(
    r"replica_groups\s*=\s*dense<(?:\[\[.*?\]\]|\d+)>\s*:\s*tensor<[^>]*>")
# sharding annotations: lowered args/ops carry a quoted mhlo.sharding attr
# (GSPMD) or an sdy.sharding one (Shardy: parse_sdy_shardings); compiled
# HLO parameters/ops carry a bare sharding={...} (the negative
# lookbehind keeps `mhlo.sharding` and header fields like
# allow_spmd_sharding_propagation_to_parameters from matching)
_MLIR_SHARDING = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_HLO_SHARDING = re.compile(r"(?<![.\w])sharding=")


def _hlo_sharding_attr(line: str) -> Optional[str]:
    """The balanced-brace body of a compiled-dialect ``sharding={...}``
    attribute (tuple shardings nest braces), or None."""
    m = _HLO_SHARDING.search(line)
    if m is None or m.end() >= len(line) or line[m.end()] != "{":
        return None
    depth = 0
    for j in range(m.end(), len(line)):
        if line[j] == "{":
            depth += 1
        elif line[j] == "}":
            depth -= 1
            if depth == 0:
                return line[m.end():j + 1]
    return None
_IOTA_RG = re.compile(r"\[([0-9,]+)\]<=\[([0-9,]+)\]"
                      r"(?:T\(([0-9,\s]+)\))?$")


def _iota_ids(reshape_dims: Sequence[int],
              perm: Sequence[int]) -> List[int]:
    """The V2 iota device list: ``arange(n).reshape(reshape_dims)
    .transpose(perm)`` flattened — pure-stdlib (no numpy) index walk."""
    n = 1
    for d in reshape_dims:
        n *= d
    t_shape = [reshape_dims[p] for p in perm]
    out = []
    for i in range(n):
        rem, t = i, []
        for d in reversed(t_shape):
            t.append(rem % d)
            rem //= d
        t.reverse()
        orig = [0] * len(reshape_dims)
        for k, p in enumerate(perm):
            orig[p] = t[k]
        v = 0
        for d, c in zip(reshape_dims, orig):
            v = v * d + c
        out.append(v)
    return out


def _parse_groups(raw: str) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """Normalize a replica-group spec to a tuple of device-id tuples.
    Handles the explicit list form and the V2 iota form — plain
    ``[g,s]<=[n]`` AND the reshaped/transposed ``[g,s]<=[a,b]T(1,0)``
    GSPMD emits for collectives over a non-trailing mesh axis; anything
    fancier keeps groups=None (raw preserved)."""
    raw = raw.strip()
    m = _IOTA_RG.match(raw)
    if m:
        dims = [int(d) for d in m.group(1).split(",") if d]
        reshape = [int(d) for d in m.group(2).split(",") if d]
        perm = ([int(p) for p in m.group(3).replace(" ", "").split(",") if p]
                if m.group(3) else list(range(len(reshape))))
        n = 1
        for d in reshape:
            n *= d
        total = 1
        for d in dims:
            total *= d
        if len(dims) != 2 or total != n or sorted(perm) != \
                list(range(len(reshape))):
            return None
        g, s = dims
        ids = _iota_ids(reshape, perm)
        return tuple(tuple(ids[i * s:(i + 1) * s]) for i in range(g))
    if raw.startswith("{{") or raw.startswith("[["):
        body = raw.strip("{}[]")
        groups = []
        for part in re.split(r"\}\s*,\s*\{|\]\s*,\s*\[", body):
            ids = [int(t) for t in re.findall(r"-?\d+", part)]
            if ids:
                groups.append(tuple(ids))
        return tuple(groups) or None
    return None


# -- dot/conv contraction attributes (FLOPs model inputs) --------------------
# stablehlo pretty form: `contracting_dims = [1] x [0]`, `batching_dims =
# [0] x [0]`; generic form: `lhs_contracting_dimensions = [1]` inside a
# #stablehlo.dot<...> attribute
_DOT_CONTRACT_MLIR = re.compile(
    r"contracting_dims\s*=\s*\[([0-9,\s]*)\]\s*x\s*\[[0-9,\s]*\]")
_DOT_BATCH_MLIR = re.compile(
    r"batching_dims\s*=\s*\[([0-9,\s]*)\]\s*x\s*\[[0-9,\s]*\]")
_DOT_CONTRACT_GENERIC = re.compile(
    r"lhs_contracting_dimensions\s*=\s*\[([0-9,\s]*)\]")
_DOT_BATCH_GENERIC = re.compile(
    r"lhs_batching_dimensions\s*=\s*\[([0-9,\s]*)\]")
# compiled HLO: `lhs_contracting_dims={1}`, `lhs_batch_dims={0}`
_DOT_CONTRACT_HLO = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DOT_BATCH_HLO = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
# convolution kernel layout: stablehlo `dim_numbers = [b, f, 1, 0]x[o, i,
# 1, 0]->[...]` / HLO `dim_labels=bf01_oi01->bf01`; the position of `o` in
# the kernel spec is the output-feature dim of the rhs
_CONV_KERNEL_MLIR = re.compile(r"x\[([^\]]*)\]\s*->")
_CONV_LABELS_HLO = re.compile(r"dim_labels=[^_\s,]+_([^-\s,]+)->")
_GROUP_COUNT = re.compile(r"batch_group_count\s*=\s*(\d+)")


def _ints(csv: str) -> Tuple[int, ...]:
    return tuple(int(t) for t in re.findall(r"\d+", csv))


def _dot_meta(line: str, dialect: str) -> Optional[dict]:
    if dialect == "stablehlo":
        cm = _DOT_CONTRACT_MLIR.search(line) or \
            _DOT_CONTRACT_GENERIC.search(line)
        bm = _DOT_BATCH_MLIR.search(line) or _DOT_BATCH_GENERIC.search(line)
    else:
        cm = _DOT_CONTRACT_HLO.search(line)
        bm = _DOT_BATCH_HLO.search(line)
    if cm is None:
        return None
    return {"lhs_contracting": _ints(cm.group(1)),
            "lhs_batching": _ints(bm.group(1)) if bm else ()}


def _conv_meta(line: str, dialect: str) -> Optional[dict]:
    if dialect == "stablehlo":
        km = _CONV_KERNEL_MLIR.search(line)
        labels = [t.strip() for t in km.group(1).split(",")] if km else []
    else:
        km = _CONV_LABELS_HLO.search(line)
        labels = list(km.group(1)) if km else []
    if "o" not in labels:
        return None
    gm = _GROUP_COUNT.search(line)
    return {"kernel_out_dim": labels.index("o"),
            "batch_groups": int(gm.group(1)) if gm else 1}


def _mlir_line_op(line: str) -> Optional[str]:
    m = _MLIR_OP.search(line)
    return m.group(1) if m else None


def _mlir_tensors(line: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dims, dt in _MLIR_TENSOR.findall(line):
        shape = tuple(int(d) for d in dims.split("x") if d) if dims else ()
        out.append((dt, shape))
    return out


def _hlo_tensors(line: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _HLO_TENSOR.findall(line):
        if dt not in _HLO_DTYPES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append((dt, shape))
    return out


@dataclasses.dataclass
class ProgramReport:
    """Structured view of one lowered/compiled program (docs/ANALYSIS.md).

    Query helpers, not raw text: ``count("dot_general")``,
    ``dot_dtypes()["bf16"]``, ``ops_with_dtype("f64")``,
    ``collective_counts()``, ``report.donation.coverage(range(18))``.
    """

    dialect: str  # "stablehlo" | "hlo"
    ops: List[Op]
    collectives: List[Collective]
    custom_calls: List[str]  # call targets, in program order
    donation: DonationReport
    inputs: List[Tuple[str, Tuple[int, ...]]]  # (dtype, shape) per flat input
    n_lines: int
    # flat input index -> parsed sharding annotation (both dialects: the
    # lowered mhlo.sharding arg attr / the compiled parameter sharding=)
    arg_shardings: Dict[int, ShardingInfo] = \
        dataclasses.field(default_factory=dict)
    # -- def/use tables for the buffer-liveness pass (analysis.memory) ------
    # main-computation (ENTRY / @main) value defs in program order; the
    # compiled dialect is scheduled text, so this order IS the schedule
    values: List[ValueDef] = dataclasses.field(default_factory=list)
    # every other computation (fusion bodies, while body/cond regions,
    # func.call targets) keyed by name, leading % stripped
    subcomputations: Dict[str, List[ValueDef]] = \
        dataclasses.field(default_factory=dict)
    # the returned SSA tokens per flat output, in output order; MLIR
    # tuple-element refs keep their "#k" suffix ("1#2")
    output_ids: Tuple[str, ...] = ()

    # -- census --------------------------------------------------------------
    def op_census(self) -> Dict[str, int]:
        return dict(_Counter(o.name for o in self.ops))

    def count(self, op: str) -> int:
        op = _normalize_op(op)
        return sum(1 for o in self.ops if o.name == op)

    def has(self, op: str) -> bool:
        return self.count(op) > 0

    def dtype_census(self) -> Dict[str, int]:
        """How many instructions *mention* each dtype (operands included) —
        the f64-promotion-leak detector reads this."""
        c: _Counter = _Counter()
        for o in self.ops:
            for dt in set(o.dtypes):
                c[dt] += 1
        return dict(c)

    def ops_with_dtype(self, dtype: str) -> List[Op]:
        return [o for o in self.ops if dtype in o.dtypes]

    # -- dots (MXU coverage) -------------------------------------------------
    def dots(self) -> List[Op]:
        return [o for o in self.ops if o.name in DOT_OPS]

    def dot_dtypes(self) -> Dict[str, int]:
        """Result-dtype census of every dot-like op — the AMP coverage
        check (`dot_dtypes()["bf16"] == len(dots())` means every matmul
        lowered low-precision)."""
        return dict(_Counter(o.dtype for o in self.dots() if o.dtype))

    # -- collectives ---------------------------------------------------------
    def collective_counts(self) -> Dict[str, int]:
        return dict(_Counter(c.name for c in self.collectives))

    def collectives_named(self, name: str) -> List[Collective]:
        name = _normalize_op(name)
        return [c for c in self.collectives if c.name == name]

    def replica_group_specs(self) -> Dict[str, int]:
        """Distinct raw replica-group spec -> number of collectives using
        it. One entry = every collective spans the same device grouping."""
        return dict(_Counter(c.raw_groups for c in self.collectives
                             if c.raw_groups))

    # -- host traffic --------------------------------------------------------
    def host_transfers(self) -> List[Op]:
        return [o for o in self.ops if o.name in HOST_TRANSFER_OPS]

    # -- shardings -----------------------------------------------------------
    def arg_sharding(self, idx: int) -> Optional[ShardingInfo]:
        """Parsed sharding annotation of flat input ``idx`` (None when the
        program carries no annotation for it — mesh-less programs)."""
        return self.arg_shardings.get(idx)

    def sharded_inputs(self) -> List[int]:
        """Flat input indices whose annotation actually partitions the
        tensor (replicated/maximal annotations excluded)."""
        return [i for i, s in sorted(self.arg_shardings.items())
                if not s.is_replicated and s.kind == "tiled"]

    # -- shape queries -------------------------------------------------------
    def has_tensor(self, shape: Tuple[int, ...],
                   dtype: Optional[str] = None,
                   suffix: bool = False) -> bool:
        """Does any instruction mention a tensor of exactly ``shape`` (or,
        with ``suffix=True``, any tensor whose trailing dims equal it)?
        The flash-attention memory contract check: no [.., L, L] buffer."""
        shape = tuple(shape)
        n = len(shape)
        for o in self.ops:
            for dt, s in zip(o.dtypes, o.shapes):
                if dtype is not None and dt != dtype:
                    continue
                if s == shape or (suffix and len(s) >= n
                                  and tuple(s[-n:]) == shape):
                    return True
        return False

    def summary(self) -> dict:
        """JSON-safe digest (tools/audit.py prints this)."""
        return {
            "dialect": self.dialect,
            "n_ops": len(self.ops),
            "op_census": self.op_census(),
            "dtype_census": self.dtype_census(),
            "dots": self.dot_dtypes(),
            "collectives": self.collective_counts(),
            "replica_groups": self.replica_group_specs(),
            "custom_calls": list(self.custom_calls),
            "host_transfers": [o.name for o in self.host_transfers()],
            "donation": {"n_inputs": self.donation.n_inputs,
                         "n_aliased": self.donation.n_aliased},
            "sharded_inputs": len(self.sharded_inputs()),
        }


# MLIR value-def syntax: `%2 = ...` / `%8:2 = ...` (multi-result)
_MLIR_RESULT = re.compile(r"^%([A-Za-z0-9_$.]+)(?::(\d+))?\s*=")
# region-arg bindings in a while header: `%iterArg_1 = %arg0`
_MLIR_REGION_ARG = re.compile(r"%([A-Za-z0-9_$.]+)\s*=\s*%[A-Za-z0-9_$.]+")
_MLIR_USE = re.compile(r"%([A-Za-z0-9_$.]+)")
# output tokens on a bare `return %1#2, %5 : ...` line keep the #k suffix
_MLIR_OUT_TOKEN = re.compile(r"%([A-Za-z0-9_$.]+(?:#\d+)?)")
_MLIR_CALLEE = re.compile(r"call\s+@([A-Za-z0-9_$.]+)")
_FUNC_NAME = re.compile(r"func\.func\s+(?:public\s+|private\s+)?"
                        r"@([A-Za-z0-9_$.]+)")


def _mlir_result_tensors(s: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """The result-type tensors of one MLIR op line: everything after the
    last ``->`` (functional form), else after the last `` : `` (pretty
    form — ``%1 = stablehlo.tanh %0 : tensor<4x16xf32>``, a ``while``'s
    trailing carry-type list)."""
    arrow = s.rfind("->")
    if arrow >= 0:
        return _mlir_tensors(s[arrow:])
    colon = s.rfind(" : ")
    if colon >= 0:
        return _mlir_tensors(s[colon:])
    return []


def _parse_stablehlo(text: str) -> ProgramReport:
    ops: List[Op] = []
    collectives: List[Collective] = []
    custom_calls: List[str] = []
    inputs: List[Tuple[str, Tuple[int, ...]]] = []
    aliased: Dict[int, str] = {}
    out_alias: Dict[int, int] = {}
    arg_shardings: Dict[int, ShardingInfo] = {}
    funcs: Dict[str, List[ValueDef]] = {}
    fn_outputs: Dict[str, Tuple[str, ...]] = {}
    cur_fn: Optional[str] = None
    lines = text.splitlines()
    meshes = parse_sdy_meshes(text)
    in_sig = False
    sig_fn: Optional[str] = None
    sig_buf: List[str] = []
    main_sig = ""

    def _sharding_of(s: str) -> Optional[ShardingInfo]:
        """The one layout an argument's or an op's text carries (several
        values' layouts on one op stay unknown, as a tuple sharding does)."""
        m = _MLIR_SHARDING.search(s)
        if m:
            return parse_sharding(m.group(1))
        found = parse_sdy_shardings(s, meshes)
        if len(found) > 1:
            return ShardingInfo("unknown", raw=s)
        return found[0] if found else None

    def _close_sig(i: int):
        """Sig buffered to completion: emit parameter ValueDefs for the
        function (zero-cost aliases for callees; the liveness pass pins
        @main's inputs separately via ``report.inputs``)."""
        nonlocal main_sig
        sig = " ".join(sig_buf)
        if sig_fn == "main":
            main_sig = sig
        vals = funcs.setdefault(sig_fn or "?", [])
        for m in _MLIR_ARG.finditer(sig):
            idx = int(m.group(1))
            tm = re.match(r"([0-9x]*)((?:[a-z][a-z0-9]*))$", m.group(2))
            if tm:
                dims, dt = tm.groups()
                shape = tuple(int(d) for d in dims.split("x") if d) \
                    if dims else ()
            else:
                dt, shape = "?", ()
            vals.append(ValueDef(vid=f"arg{idx}", op="parameter",
                                 bytes=tensor_bytes(dt, shape),
                                 results=((dt, shape),), uses=(), line=i,
                                 param=idx))

    def _value_of(s: str, i: int, name: str) -> None:
        """Record the def/use ValueDef(s) of one op line."""
        vals = funcs.setdefault(cur_fn or "?", [])
        rm = _MLIR_RESULT.match(s)
        rest = s[rm.end():] if rm else s
        region_defs = list(dict.fromkeys(_MLIR_REGION_ARG.findall(rest)))
        uses = tuple(u for u in _MLIR_USE.findall(rest)
                     if u not in region_defs)
        callees = tuple(_MLIR_CALLEE.findall(s))
        results = tuple(_mlir_result_tensors(s))
        if rm is None:
            # region/return lines define nothing but their uses still
            # extend operand live ranges
            vals.append(ValueDef(vid="", op=name, bytes=0, results=(),
                                 uses=uses, line=i))
            return
        vals.append(ValueDef(
            vid=rm.group(1), op=name,
            bytes=sum(tensor_bytes(dt, sh) for dt, sh in results),
            results=results, uses=uses, line=i, callees=callees))
        for g in region_defs:
            vals.append(ValueDef(vid=g, op="region_arg", bytes=0,
                                 results=(), uses=(), line=i))

    for i, line in enumerate(lines, 1):
        s = line.strip()
        # a func signature may span lines; buffer until the body opens
        if "func.func" in s:
            in_sig = True
            fm = _FUNC_NAME.search(s)
            sig_fn = fm.group(1) if fm else "?"
            sig_buf = []
            cur_fn = sig_fn
        if in_sig:
            sig_buf.append(s)
            if s.endswith("{"):
                in_sig = False
                _close_sig(i)
            continue
        if s.startswith("return"):
            # the function's own return: record output tokens (tuple-
            # element refs keep their #k suffix for alias exclusion)
            if cur_fn is not None:
                fn_outputs[cur_fn] = tuple(_MLIR_OUT_TOKEN.findall(s))
            continue
        if not s or s.startswith(("module", "func.func", "}", "^",
                                  "sdy.mesh")):
            continue
        name = _mlir_line_op(s)
        if name is None:
            # func.call defines values and reaches a subcomputation, but
            # is not a stablehlo op — value table only, census untouched
            if _MLIR_CALLEE.search(s):
                _value_of(s, i, "call")
            continue
        name = _normalize_op(name)
        _value_of(s, i, name)
        if name in _ASYNC_DONE:
            continue
        tensors = _mlir_tensors(s)
        # result type: MLIR puts it last (`-> tensor<..>` or `: tensor<..>`)
        rdt, rshape = (tensors[-1] if tensors else (None, ()))
        dtypes = tuple(dt for dt, _ in tensors)
        shapes = tuple(sh for _, sh in tensors)
        op_sharding = (_sharding_of(s) if name == "sharding_constraint"
                       or "sharding = " in s else None)
        if name == "custom_call":
            m = re.search(r'call_target_name\s*=\s*"([^"]+)"', s)
            custom_calls.append(m.group(1) if m else "?")
        if name in COLLECTIVE_OPS:
            m = _RG_MLIR.search(s)
            raw = m.group(1) if m else ""
            # payload sizing must not read the replica_groups attribute's
            # own `dense<...> : tensor<NxMxi64>` type as a tensor — strip
            # the clause, THEN split operands/results at the trailing type
            # signature (`: (operands) -> result`). Region-form
            # collectives keep their types on the closing line, so after
            # the strip nothing may remain — payload 0 (best effort; the
            # comm model primarily reads the compiled dialect) beats
            # pricing the group table.
            sc = _RG_MLIR_CLAUSE.sub("", s)
            ctensors = _mlir_tensors(sc)
            crdt, crshape = (ctensors[-1] if ctensors else (None, ()))
            arrow = sc.rfind("->")
            res_info = tuple(_mlir_tensors(sc[arrow:])) if arrow >= 0 else ()
            opd_info = (tuple(_mlir_tensors(sc[:arrow])) if arrow >= 0
                        else tuple(ctensors))
            c = Collective(name, crdt, crshape,
                           tuple(dt for dt, _ in ctensors), i,
                           shapes=tuple(sh for _, sh in ctensors),
                           sharding=op_sharding, raw_groups=raw,
                           groups=_parse_groups(raw) if raw else None,
                           operand_info=opd_info, result_info=res_info)
            collectives.append(c)
            ops.append(c)
            continue
        meta = None
        if name in ("dot_general", "dot"):
            meta = _dot_meta(s, "stablehlo")
        elif name == "convolution":
            meta = _conv_meta(s, "stablehlo")
        ops.append(Op(name, rdt, rshape, dtypes, i, shapes=shapes,
                      sharding=op_sharding, dot_meta=meta))
    sig = main_sig
    matches = list(_MLIR_ARG.finditer(sig))
    args_end = sig.rfind(") ->")
    if not matches or args_end < matches[-1].end():
        args_end = len(sig)
    for k, m in enumerate(matches):
        idx = int(m.group(1))
        tdesc = m.group(2)
        tm = re.match(r"([0-9x]*)((?:[a-z][a-z0-9]*))$", tdesc)
        if tm:
            dims, dt = tm.groups()
            shape = tuple(int(d) for d in dims.split("x") if d) if dims else ()
        else:
            dt, shape = "?", ()
        while len(inputs) <= idx:
            inputs.append(("?", ()))
        inputs[idx] = (dt, shape)
        # this arg's attrs: everything up to the next %arg (or the body
        # opening) — quoted values (mhlo.sharding = "{replicated}") hold
        # braces, so a brace-bounded capture would truncate before
        # tf.aliasing_output
        # (the last argument's end where the results' attributes begin)
        end = matches[k + 1].start() if k + 1 < len(matches) else args_end
        am = _MLIR_ALIAS.search(sig, m.end(), end)
        if am:
            aliased[idx] = "may-alias"
            out_alias[int(am.group(1))] = idx
        elif _MLIR_DONOR.search(sig, m.end(), end):
            aliased[idx] = "buffer-donor"
        sh = _sharding_of(sig[m.end():end])
        if sh is not None:
            arg_shardings[idx] = sh
    values = funcs.pop("main", [])
    return ProgramReport(
        dialect="stablehlo", ops=ops, collectives=collectives,
        custom_calls=custom_calls,
        donation=DonationReport(n_inputs=len(inputs), aliased=aliased,
                                out_alias=out_alias),
        inputs=inputs, n_lines=len(lines), arg_shardings=arg_shardings,
        values=values, subcomputations=funcs,
        output_ids=fn_outputs.get("main", ()))


# HLO value-def syntax: `%add.5 = ...` / `ROOT %tuple.3 = ...` (names may
# contain dots and dashes: `%dynamic-slice_bitcast_fusion`)
_HLO_RESULT = re.compile(r"^(ROOT\s+)?%([\w.\-]+)\s*=")
_HLO_USE = re.compile(r"%([\w.\-]+)")
_HLO_CALLEE = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
# computation header: `%region_0.19 (args...) -> type {` / `ENTRY %main (..`
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")


def _parse_hlo(text: str) -> ProgramReport:
    ops: List[Op] = []
    collectives: List[Collective] = []
    custom_calls: List[str] = []
    inputs: List[Tuple[str, Tuple[int, ...]]] = []
    aliased: Dict[int, str] = {}
    out_alias: Dict[int, int] = {}
    arg_shardings: Dict[int, ShardingInfo] = {}
    comps: Dict[str, List[ValueDef]] = {}
    entry_name: Optional[str] = None
    cur_comp: Optional[str] = None
    output_ids: Tuple[str, ...] = ()
    lines = text.splitlines()
    entry_params: Dict[int, Tuple[str, Tuple[int, ...]]] = {}
    in_entry = False
    for i, line in enumerate(lines, 1):
        s = line.strip()
        if s.startswith("HloModule"):
            for onum, pnum, kind in _HLO_ALIAS_ENTRY.findall(
                    _alias_header_body(s)):
                aliased[int(pnum)] = kind
                out_alias[int(onum) if onum else 0] = int(pnum)
            continue
        if s.endswith("{") and _HLO_RESULT.match(s) is None and \
                (s.startswith("%") or s.startswith("ENTRY")):
            cm = _HLO_COMP.match(s)
            cur_comp = cm.group(1) if cm else "?"
            if s.startswith("ENTRY"):
                in_entry = True
                entry_name = cur_comp
            continue
        if s == "}":
            cur_comp = None
            in_entry = False
            continue
        if not s or s.startswith(("//", "#")):
            continue
        m = _HLO_OP.search(s)
        if m is None:
            continue
        name = m.group(1)
        norm = _normalize_op(name)
        # -- value table (liveness pass): EVERY defining instruction,
        # before the census filters drop the structural ops — a copy IS
        # an allocation, a big constant IS resident bytes
        rm = _HLO_RESULT.match(s)
        if rm is not None:
            callees = tuple(_HLO_CALLEE.findall(s))
            bm = _HLO_BRANCHES.search(s)
            if bm:
                callees += tuple(_HLO_USE.findall(bm.group(1)))
            results = tuple(_hlo_tensors(s[rm.end():m.start(1)]))
            uses = tuple(u for u in _HLO_USE.findall(s[m.end(1):])
                         if u not in callees)
            pm_ = re.search(r"parameter\((\d+)\)", s)
            gm_ = (re.search(r"index=(\d+)", s)
                   if norm == "get_tuple_element" else None)
            v = ValueDef(
                vid=rm.group(2), op=norm,
                bytes=sum(tensor_bytes(dt, sh) for dt, sh in results),
                results=results, uses=uses, line=i, callees=callees,
                param=int(pm_.group(1)) if pm_ else None,
                gte_index=int(gm_.group(1)) if gm_ else None)
            comps.setdefault(cur_comp or "?", []).append(v)
            if rm.group(1) and cur_comp == entry_name:
                # the ENTRY root: output j = operand j of the root tuple
                # (or the root itself for single-output programs)
                output_ids = uses if norm == "tuple" else (v.vid,)
        if name in ("parameter",):
            tensors = _hlo_tensors(s)
            if in_entry and tensors:
                pm = re.search(r"parameter\((\d+)\)", s)
                if pm:
                    entry_params[int(pm.group(1))] = tensors[0]
                    sh = _hlo_sharding_attr(s)
                    if sh is not None:
                        arg_shardings[int(pm.group(1))] = parse_sharding(sh)
            continue
        name = norm
        if name in ("constant", "tuple", "get_tuple_element", "bitcast",
                    "copy"):
            # structural noise: layout/plumbing ops drown the census —
            # filtered AFTER normalization so an async copy-start is
            # dropped exactly like the sync copy spelling
            continue
        if name in _ASYNC_DONE:
            continue
        tensors = _hlo_tensors(s)
        # result type: HLO puts it first (`%x = f32[4,8]{1,0} op(...)`)
        rdt, rshape = (tensors[0] if tensors else (None, ()))
        dtypes = tuple(dt for dt, _ in tensors)
        shapes = tuple(sh for _, sh in tensors)
        sh_attr = _hlo_sharding_attr(s)
        op_sharding = parse_sharding(sh_attr) if sh_attr is not None else None
        if name == "custom_call":
            cm = re.search(r'custom_call_target="([^"]+)"', s)
            custom_calls.append(cm.group(1) if cm else "?")
        if name in COLLECTIVE_OPS:
            gm = _RG.search(s)
            raw = gm.group(1) if gm else ""
            # split the line's tensors by side of the op name: result
            # type(s) precede it, operand types live in the call parens —
            # payload sizing for the comm cost model
            res_info = tuple(_hlo_tensors(s[:m.start(1)]))
            opd_info = tuple(_hlo_tensors(s[m.end(1):]))
            c = Collective(name, rdt, rshape, dtypes, i, shapes=shapes,
                           sharding=op_sharding, raw_groups=raw,
                           groups=_parse_groups(raw) if raw else None,
                           operand_info=opd_info, result_info=res_info)
            collectives.append(c)
            ops.append(c)
            continue
        meta = None
        if name in ("dot_general", "dot"):
            meta = _dot_meta(s, "hlo")
        elif name == "convolution":
            meta = _conv_meta(s, "hlo")
        ops.append(Op(name, rdt, rshape, dtypes, i, shapes=shapes,
                      sharding=op_sharding, dot_meta=meta))
    n_inputs = (max(entry_params) + 1) if entry_params else 0
    for idx in range(n_inputs):
        inputs.append(entry_params.get(idx, ("?", ())))
    values = comps.pop(entry_name, []) if entry_name else []
    return ProgramReport(
        dialect="hlo", ops=ops, collectives=collectives,
        custom_calls=custom_calls,
        donation=DonationReport(n_inputs=n_inputs, aliased=aliased,
                                out_alias=out_alias),
        inputs=inputs, n_lines=len(lines), arg_shardings=arg_shardings,
        values=values, subcomputations=comps, output_ids=output_ids)


@dataclasses.dataclass
class ProgramAudit:
    """Paired reports over one program: the *lowered* StableHLO (dtype
    truth — what XLA is asked to run) and the *compiled* HLO (collective/
    donation truth — what the backend will run), plus the flat input
    indices of the donated carry so coverage is a one-call check.
    Returned by ``TrainStep.audit()`` / ``GenerationEngine.audit()``."""

    lowered: ProgramReport
    compiled: Optional[ProgramReport]
    carry_indices: Tuple[int, ...] = ()
    # sharding-contract violations (analysis.contract.ContractViolation):
    # declared layout != compiled layout, [] when the contract holds or no
    # mesh is involved
    contract: List = dataclasses.field(default_factory=list)
    # communication cost model over the program's collectives
    # (analysis.comm.CommReport), None when not computed
    comm: Optional[object] = None
    # buffer-liveness residency estimate (analysis.memory.MemoryReport):
    # peak bytes, timeline, category attribution, materializations
    memory: Optional[object] = None

    def carry_donation(self) -> float:
        """Donation coverage of the carry (params/opt-state for TrainStep,
        KV buffers for the decode engine): 1.0 = every carry buffer is
        updated in place. Reads the compiled executable when available."""
        rep = self.compiled if self.compiled is not None else self.lowered
        return rep.donation.coverage(self.carry_indices)

    def carry_missing(self) -> List[int]:
        rep = self.compiled if self.compiled is not None else self.lowered
        return rep.donation.missing(self.carry_indices)

    def summary(self) -> dict:
        out = {"lowered": self.lowered.summary(),
               "carry": {"n": len(self.carry_indices),
                         "donation_coverage": self.carry_donation(),
                         "missing": self.carry_missing()},
               "contract": [str(v) for v in self.contract]}
        if self.compiled is not None:
            out["compiled"] = self.compiled.summary()
        if self.comm is not None:
            out["comm"] = self.comm.summary()
        if self.memory is not None:
            out["memory"] = self.memory.summary()
        return out


def audit_text(text: str) -> ProgramReport:
    """Parse program text in either dialect (auto-detected)."""
    if "stablehlo." in text or "func.func" in text or "mhlo." in text:
        return _parse_stablehlo(text)
    return _parse_hlo(text)


def audit_lowered(lowered) -> ProgramReport:
    """``jax.jit(f).lower(...)`` -> report over the *requested* program
    (dtype assertions live here: CPU legalizes bf16 away at compile)."""
    return audit_text(lowered.as_text())


def audit_compiled(compiled) -> ProgramReport:
    """``lowered.compile()`` (or anything with ``as_text``) -> report over
    the optimized executable (collectives, fusion, donation live here).
    Where the executable itself says how its inputs are laid out
    (``jax.stages.Compiled.input_shardings``), that is taken over the
    text's ``sharding={...}``: it is the same fact without a parser."""
    report = audit_text(compiled.as_text())
    shardings = getattr(compiled, "input_shardings", None)
    if shardings is not None:
        import jax

        # arguments the compiler dropped hold None and have no parameter
        kept = [(s, a.shape) for s, a in zip(
            jax.tree_util.tree_leaves(shardings, is_leaf=lambda x: x is None),
            jax.tree_util.tree_leaves(compiled.in_avals)) if s is not None]
        if len(kept) == len(report.inputs):
            report.arg_shardings = {
                i: sharding_info(s, shape)
                for i, (s, shape) in enumerate(kept)}
    return report


# -- program fingerprints & the recompile guard ------------------------------
@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """Stable identity of one program signature: per-array shapes/dtypes +
    the static arguments folded into the compiled program as constants.
    Two equal fingerprints hit the same executable; the *diff* between two
    unequal ones is the recompile cause."""

    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    static: Tuple[Tuple[str, str], ...]  # sorted (name, repr) pairs

    @classmethod
    def of(cls, arrays: Sequence, **static) -> "Fingerprint":
        shapes, dtypes = [], []
        for a in arrays:
            shapes.append(tuple(getattr(a, "shape", ())))
            dtypes.append(str(getattr(a, "dtype", type(a).__name__)))
        return cls(tuple(shapes), tuple(dtypes),
                   tuple(sorted((str(k), repr(v)) for k, v in static.items())))

    def describe(self) -> dict:
        return {"shapes": [list(s) for s in self.shapes],
                "dtypes": list(self.dtypes),
                "static": {k: v for k, v in self.static}}


def fingerprint_diff(old: Fingerprint, new: Fingerprint):
    """Explain ``old -> new``: returns ``(cause, detail)`` where cause is
    ``"shape"`` | ``"dtype"`` | ``"static"`` | ``"arity"`` (first
    difference wins in that order of specificity) and detail is a short
    human string naming exactly what changed."""
    if len(old.shapes) != len(new.shapes):
        return "arity", (f"{len(old.shapes)} -> {len(new.shapes)} "
                         "batch arrays")
    for i, (a, b) in enumerate(zip(old.shapes, new.shapes)):
        if a != b:
            return "shape", f"arg{i}: {list(a)} -> {list(b)}"
    for i, (a, b) in enumerate(zip(old.dtypes, new.dtypes)):
        if a != b:
            return "dtype", f"arg{i}: {a} -> {b}"
    do, dn = dict(old.static), dict(new.static)
    for k in sorted(set(do) | set(dn)):
        if do.get(k) != dn.get(k):
            return "static", f"{k}: {do.get(k)} -> {dn.get(k)}"
    return "identical", ""


class RecompileGuard:
    """Fingerprint-keyed recompile detector with *causes*.

    ``observe(fp)`` returns None for a signature already seen; for a new
    one it diffs against the closest previous fingerprint, increments
    ``<counter>{reason=<cause>}`` and writes a ``recompile`` event whose
    ``cause``/``detail`` fields say exactly what changed (the fingerprint
    diff) — a shape-change recompile is *explained*, not just counted.

    ``label_map`` renames causes for the counter label (TrainStep maps
    ``static`` -> its historical ``hyperparams`` label); ``reason=``
    overrides the diffed cause entirely (the window/prefill paths have
    fixed labels by contract).
    """

    def __init__(self, counter_name: str, help: str = "",
                 label_map: Optional[Dict[str, str]] = None,
                 event: str = "recompile"):
        self.counter_name = counter_name
        self.help = help
        self.label_map = label_map or {}
        self.event = event
        self._seen: List[Tuple[Optional[str], Fingerprint]] = []
        self._seen_set = set()

    def __len__(self):
        return len(self._seen)

    def seen(self, fp: Fingerprint, group: Optional[str] = None) -> bool:
        return (group, fp) in self._seen_set

    def diff_cause(self, fp: Fingerprint, group: Optional[str] = None):
        """(cause, detail) of ``fp`` vs the closest seen fingerprint of
        the same ``group`` (program family: step vs window vs decode) —
        closest = the candidate reachable by the smallest class of edit
        (static-args-only beats dtype-only beats shape beats arity), so
        the reported cause is the minimal change that forced the
        recompile. Cross-family diffs would manufacture phantom causes
        (a step batch vs a window's stacked batch 'differ in shape'
        without any input ever changing), hence the grouping."""
        candidates = [f for g, f in self._seen if g == group]
        if not candidates:
            return "first", ""
        best = None
        # closest = smallest change: a candidate differing only in static
        # args beats one differing in dtypes, which beats shapes, which
        # beats arity — so the reported cause is the minimal edit that
        # forced the recompile
        rank = {"static": 0, "dtype": 1, "shape": 2, "arity": 3}
        for prev in candidates:
            cause, detail = fingerprint_diff(prev, fp)
            r = rank.get(cause, 4)
            if best is None or r < best[0]:
                best = (r, cause, detail)
        return best[1], best[2]

    def observe(self, fp: Fingerprint, reason: Optional[str] = None,
                group: Optional[str] = None,
                **event_fields) -> Optional[str]:
        if (group, fp) in self._seen_set:
            return None
        cause, detail = self.diff_cause(fp, group)
        self._seen_set.add((group, fp))
        self._seen.append((group, fp))
        label = reason if reason is not None else \
            self.label_map.get(cause, cause)
        from .. import observability as _obs

        _obs.counter(self.counter_name, self.help).inc(reason=label)
        _obs.emit(self.event, reason=label, cause=cause, detail=detail,
                  **{**fp.describe(), **event_fields})
        return label
