"""Named scopes on device operations: from an operation's ``op_name`` to the
scope it was traced under, and from a compiled program's text to the scope of
every instruction (docs/OBSERVABILITY.md "Named scopes").

``TrainStep`` opens ``jax.named_scope`` around the parts of its traced step
(:data:`SCOPES`) and ``gluon.Block.__call__`` around every block, so each
operation's ``op_name`` reads
``jit(step)/jvp(forward)/<block>/<block>/dot_general``; under differentiation
jax itself writes the forward pass as ``jvp(forward)`` and the backward pass
as ``transpose(jvp(forward))``. A device trace carries instruction names, not
``op_name``s, so the join key is the instruction name and the table comes
from the compiled executable's text (:func:`op_scopes_from_hlo`).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional

__all__ = ["SCOPES", "BACKWARD", "MIXED", "UNSCOPED", "ScopeTable", "scope_path",
           "op_scopes_from_hlo", "scoped_text", "instruction_name", "at_depth"]

#: the scopes ``parallel.TrainStep`` opens in its traced step
SCOPES = ("forward", "loss", "optimizer", "amp", "grad_norm", "accumulate")
#: what ``transpose(jvp(forward))`` is called here
BACKWARD = "backward"
#: a fusion whose operations no one scope holds most of
MIXED = "mixed"
#: an operation traced under none of :data:`SCOPES`
UNSCOPED = "unscoped"

# transformations jax wraps a scope's name in: jvp(forward), transpose(jvp(..))
_WRAPPED = re.compile(r"^(transpose|jvp|vmap|pmap|shard_map|remat|checkpoint|"
                      r"custom_jvp|custom_vjp)\((.*)\)$")
# path components that are program structure, not a scope someone named
_STRUCTURE = re.compile(
    r"^(jit|pjit|closed_call|core_call|custom_jvp_call|custom_vjp_call|"
    r"custom_vjp_call_jaxpr|checkpoint|remat|rematted_computation|while|scan|"
    r"cond|body|body_fun|cond_fun|branch_\d+_fun)(\(.*\))?$")


def scope_path(op_name: str, scopes: Iterable[str] = SCOPES) -> Optional[str]:
    """``jit(step)/transpose(jvp(forward))/bert/enc/layer3/attn/dot_general``
    -> ``backward/bert/enc/layer3/attn``. The path starts at the first
    component that is one of ``scopes`` (unwrapped from ``jvp(...)`` and the
    like); a transposed one reads :data:`BACKWARD` (``backward`` for
    ``forward``, ``backward/loss`` for ``loss``). The last component is the
    primitive and is dropped; structural components (``jit(...)``,
    ``while``, ``body``, ``checkpoint`` ...) are skipped. None when the
    operation was traced under none of ``scopes``."""
    out = None
    for part in op_name.split("/")[:-1]:
        transposed = False
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            transposed |= m.group(1) == "transpose"
            part = m.group(2)
        if out is None:
            if part in scopes:
                out = [BACKWARD] if transposed else [part]
                if transposed and part != "forward":
                    out.append(part)
        elif part and not _STRUCTURE.match(part):
            out.append(part)
    return "/".join(out) if out else None


def at_depth(path: str, depth: Optional[int]) -> str:
    """The first ``depth`` components of a scope path (all when None)."""
    return path if depth is None else "/".join(path.split("/")[:depth])


def instruction_name(event_name: str) -> str:
    """The HLO instruction's name from a trace row's name: a TPU row is the
    instruction's text (``%fusion.3 = bf16[..] fusion(..)``), a CPU row the
    bare name."""
    return event_name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPCODE = re.compile(r"\b([a-z][\w\-]*)\(")
_PRODUCTS = ("convolution", "dot")


def _held_most(paths):
    """The longest scope path that more than half of ``paths`` lie under,
    or :data:`MIXED` when even the first component has no such majority."""
    best, depth = None, 1
    while True:
        counts: Dict[str, int] = {}
        for p in paths:
            parts = p.split("/")
            if len(parts) >= depth:
                key = "/".join(parts[:depth])
                counts[key] = counts.get(key, 0) + 1
        top = max(counts.items(), key=lambda kv: kv[1], default=None)
        if top is None or 2 * top[1] <= len(paths):
            return best or MIXED
        best, depth = top[0], depth + 1


class ScopeTable(dict):
    """{HLO instruction name: scope path}. ``shared`` holds, for each fusion
    whose operations come from more than one depth-1 scope, {scope:
    operations}: the path says where the fusion's time is counted, ``shared``
    says what else is inside it (XLA fuses Adam's update into the
    weight-gradient product it consumes: that time is one number)."""

    def __init__(self):
        super().__init__()
        self.shared: Dict[str, Dict[str, int]] = {}


def scoped_text(lowered) -> str:
    """The compiled text of ``lowered`` with every scope in it. jax leaves
    metadata out of its compile cache's key, so the running executable may
    have been cached before the scopes existed, and its text then names none
    of them (its instruction names are the same). This compiles once more:
    an explicit compiler option, set to its default, keeps the compile from
    being handed that executable, and the metadata is made part of the key."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile(compiler_options={
            "xla_embed_ir_in_executable": False}).as_text()
    finally:
        jax.config.update(flag, was)


def op_scopes_from_hlo(text: str, scopes: Iterable[str] = SCOPES
                       ) -> ScopeTable:
    """The :class:`ScopeTable` of a compiled program's text
    (``compiled.as_text()``): every instruction that was traced under a
    scope. An instruction goes by its own ``op_name``; a fusion by the
    operations inside the computation it calls. If matrix products
    (``convolution``, ``dot``) are among them the fusion goes by those: its
    time is mostly theirs, and XLA pulls cheap elementwise operations of
    OTHER scopes into it by the dozen (a backward product recomputes the
    forward pass's activation function inside its fusion, and takes the
    optimizer's update of the weight as its epilogue). Otherwise it goes by
    all of them. Either way the path is the longest that holds most of the
    operations counted (a fusion of attention's and the feed-forward's
    operations in one layer reads ``.../layer3``), :data:`MIXED` when no
    scope holds most. Instructions under no scope are left out."""
    scopes = tuple(scopes)
    # computation -> scope paths of its operations: [all, products only]
    inside: Dict[str, tuple] = {}
    entries = []                    # (instruction, own path, called computation)
    current = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            current = head.group(1)
            inside[current] = ([], [])
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = _INSTRUCTION.match(line) if current is not None else None
        if inst is None:
            continue
        name, rest = inst.groups()
        found = _OP_NAME.search(rest)
        path = scope_path(found.group(1), scopes) if found else None
        opcode = _OPCODE.search(rest.split(", metadata=", 1)[0])
        opcode = opcode.group(1) if opcode else ""
        if path is not None and opcode != "parameter":
            inside[current][0].append(path)
            if opcode in _PRODUCTS:
                inside[current][1].append(path)
        called = _CALLS.search(rest)
        entries.append((name, path, called.group(1) if called else None))
    out = ScopeTable()
    for name, path, called in entries:
        fused, products = inside.get(called, ((), ()))
        if fused:
            out[name] = _held_most(products or fused)
            tops: Dict[str, int] = {}
            for p in fused:
                tops[at_depth(p, 1)] = tops.get(at_depth(p, 1), 0) + 1
            if len(tops) > 1:
                out.shared[name] = tops
        elif path is not None:
            out[name] = path
    return out
