"""From a profiler trace to busy time, idle gaps, per-operation time and
exposed collective time.

The arithmetic works on plain lists of ``(name, start_s, duration_s)`` so
that it can be checked by hand; :func:`load` is the only part that knows the
trace file. Times are seconds on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
import re

# where a platform's trace keeps device operations and whole programs
SOURCES = {
    "tpu": {"plane": "/device:TPU:", "ops": ("XLA Ops",),
            "modules": ("XLA Modules",)},
    # only the CPU tests read this one: XLA:CPU writes its operations on
    # the host plane, on the client's own threads
    "cpu": {"plane": "/host:CPU", "ops": ("tf_XLAPjRtCpuClient",),
            "modules": ()},
}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
HOST_SPAN_PREFIX = "bench."


def op_base(name):
    """``%fusion.123 = ...`` -> ``fusion``: one name for all of a kind."""
    name = name.lstrip("%").split(" ", 1)[0].split("(", 1)[0]
    return re.sub(r"[.\d]+$", "", name) or name


def is_collective(name):
    return op_base(name).startswith(COLLECTIVES)


def op_code(text):
    """The opcode of an operation's text as the trace records it, ``%name =
    <result shape> <opcode>(<operands>), <attributes>``: ``fusion``,
    ``custom-call``, ``copy-done``. None where the record is a bare name."""
    _, eq, rest = text.partition(" = ")
    if not eq:
        return None
    if rest.startswith("("):  # a tuple's shape: skip to its closing bracket
        depth = 0
        for at, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[at + 1:]
                break
    else:  # a shape has no space in it
        rest = rest.partition(" ")[2]
    found = re.match(r"\s*([a-z][\w-]*)\(", rest)
    return found.group(1) if found else None


def is_custom_call(name):
    """A Pallas kernel, whatever its instruction is called: by the
    operation's own text where the trace records it (the opcode
    ``custom-call`` with ``custom_call_target="tpu_custom_call"``; XLA's own
    custom calls have other targets), and only for a bare name by the name
    (``custom-call*``/``custom_call*``/``tpu_custom_call*``)."""
    code = op_code(name)
    if code is not None:
        return code == "custom-call" and (
            'custom_call_target="tpu_custom_call"' in name)
    return bool(re.match(r"(tpu_)?custom[-_]call", op_base(name)))


def clip(events, lo, hi):
    """The parts of ``events`` inside [lo, hi], as (name, start, end)."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def merge(intervals):
    """Sorted, disjoint (start, end) covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals):
    return sum(b - a for a, b in merge(intervals))


def subtract(intervals, holes):
    """The parts of ``intervals`` that no hole covers (both get merged)."""
    out, holes = [], merge(holes)
    for a, b in merge(intervals):
        for ha, hb in holes:
            if hb <= a or ha >= b:
                continue
            if ha > a:
                out.append((a, ha))
            a = max(a, hb)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def self_times(events):
    """(name, start, end, self seconds, covers another) per event of one
    line. A line nests: a ``while`` covers the operations of its body, and
    only the time no child covers is the parent's own."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, a, b in order:
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
            stack[-1][4] |= b <= stack[-1][2]
        rec = [name, a, b, b - a, False]
        out.append(rec)
        stack.append(rec)
    return [(n, a, b, max(s, 0.0), parent) for n, a, b, s, parent in out]


def per_op_seconds(events, lo, hi):
    """{base name: seconds of its own} inside the window."""
    out = {}
    for name, _, _, own, _ in self_times(clip(events, lo, hi)):
        key = op_base(name)
        out[key] = out.get(key, 0.0) + own
    return out


def leaves(events, lo, hi):
    """Events that cover no other event: the operations that ran."""
    return [(n, a, b) for n, a, b, _, parent
            in self_times(clip(events, lo, hi)) if not parent]


def exposed_collective_seconds(events, lo, hi):
    """Seconds in which a collective ran on this chip and no other
    operation did."""
    ops = leaves(events, lo, hi)
    coll = [(a, b) for n, a, b in ops if is_collective(n)]
    comp = [(a, b) for n, a, b in ops if not is_collective(n)]
    return measure(subtract(coll, comp))


def idle_gaps(events, host_spans, lo, hi, between_ops_s=2e-6):
    """{label: idle seconds}: each gap in which no operation ran goes to the
    benchmark's host span (``bench.*``) that overlaps it most, the innermost
    on a tie; a gap too short for the host to matter is
    ``device.between_ops``."""
    busy = merge([(a, b) for _, a, b in clip(events, lo, hi)])
    spans = [(n, a, b) for n, a, b in clip(host_spans, lo, hi)
             if n.startswith(HOST_SPAN_PREFIX)]
    out = {}
    for a, b in subtract([(lo, hi)], busy):
        label = "host.unattributed"
        if b - a <= between_ops_s:
            label = "device.between_ops"
        else:
            best = (0.0, 0.0)
            for n, sa, sb in spans:
                cover = min(b, sb) - max(a, sa)
                if cover > 0 and (cover, -(sb - sa)) > best:
                    best, label = (cover, -(sb - sa)), n
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def find_trace(directory):
    found = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path, platform):
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]},
    every event (name, start_s, duration_s)."""
    from jax.profiler import ProfileData

    if platform not in SOURCES:
        raise KeyError(f"no trace layout on record for platform {platform!r}")
    src = SOURCES[platform]
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(src["plane"]):
            ops, modules = [], []
            for line in plane.lines:
                into = (ops if line.name.startswith(src["ops"]) else
                        modules if line.name.startswith(src["modules"] or ("\0",))
                        else None)
                if into is None:
                    continue
                for e in line.events:
                    if e.duration_ns > 0 and not e.name.startswith("end: "):
                        into.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
            if ops:
                devices[plane.name] = {"ops": ops, "modules": modules}
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def reduce(trace, window_span="bench.window"):
    """The numbers of one traced window. The window is the host span
    ``window_span`` (the profiler starts before it and stops after it);
    per-chip quantities are averaged over the chips that ran operations."""
    marks = [(a, a + d) for n, a, d in trace["host"] if n == window_span]
    if not marks:
        raise ValueError(f"the trace holds no host span {window_span!r}")
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    chips = sorted(trace["devices"])
    if not chips:
        raise ValueError("the trace holds no device operations")
    busy, ops, exposed, custom = [], {}, [], []
    for chip in chips:
        ev = trace["devices"][chip]["ops"]
        busy.append(measure([(a, b) for _, a, b in clip(ev, lo, hi)]))
        exposed.append(exposed_collective_seconds(ev, lo, hi))
        custom.append(sum(b - a for n, a, b in leaves(ev, lo, hi)
                          if is_custom_call(n)))
        for k, v in per_op_seconds(ev, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(chips)
    first = trace["devices"][chips[0]]
    modules = {}
    for name, a, b in clip(first["modules"], lo, hi):
        key = name.split("(", 1)[0]
        n, s = modules.get(key, (0, 0.0))
        modules[key] = (n + 1, s + (b - a))
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    return {"window_s": hi - lo, "busy_s": mean(busy), "chips": len(chips),
            "ops": ops, "collective_exposed_s": mean(exposed),
            "custom_call_s": mean(custom), "modules": modules,
            "idle_gaps": idle_gaps(
                first["ops"], [s for s in trace["host"] if s[0] != window_span],
                lo, hi)}
