"""From a `jax.profiler` trace (`.xplane.pb`) to numbers: device busy time,
the time of each device program and operation, and the device's idle gaps
named by what the host was doing in them. Read with `jax.profiler.
ProfileData` alone. The process that calls this imports JAX, so it is the
launcher (or a test), never the benchmark's parent.

What a TPU trace holds (looked at by hand, PERF.md §6): a plane
`/device:TPU:<n>` for each chip with the lines `XLA Modules` (one event a
run of a compiled program), `XLA Ops` (one an operation) and `Steps`; and
`/host:CPU` with a line a thread, where `TraceAnnotation`s appear by name.
A CPU trace has no device plane: every device number is then absent.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench/"
UNNAMED_GAP = "no annotated host span (asleep until the tick's edge, or other host work)"


def _union_s(intervals: list[tuple[int, int]]) -> tuple[float, list[tuple[int, int]]]:
    """Seconds covered by (start, end) ns intervals, and the merged list."""
    merged: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return sum(b - a for a, b in merged) / 1e9, merged


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """`%copy.7 = pred[64,16]{...} copy(pred[...] %x)` → `%copy.7 copy`: an
    operation's event is named by its whole HLO line, operands and all."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:120]
    op = _OPCODE.search(" " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(filter(None, (head, op and op.group(1), target and target.group(1))))[:120]


def _events(line) -> list[tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(path: str | Path, top: int = 10) -> dict:
    """The trace's numbers. Device keys are present only where a device
    plane with operations on it is: a reader that finds nothing says nothing.
    `idle_gaps` gives, for the first chip, the idle seconds that fell inside
    each host span and those inside none; spans on two threads overlap (the
    next tick is staged while the device call waits), so the named seconds
    can add up to more than the idle time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, spans = [], []                        # per chip: {line name: events}
    first, last = None, None
    for plane in data.planes:
        lines: dict[str, list] = {}              # threads share a line name
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
        for evs in lines.values():
            for _, a, b in evs:
                first = a if first is None or a < first else first
                last = b if last is None or b > last else last
        if plane.name.startswith(DEVICE_PLANE):
            device.append(lines)
        elif plane.name.startswith("/host:"):
            spans += [e for evs in lines.values() for e in evs
                      if e[0].startswith(SPAN_PREFIX)]
    out: dict = {"planes": [p.name for p in data.planes],
                 "window_s": 0.0 if first is None else (last - first) / 1e9,
                 "host_spans": {}}
    for name, a, b in spans:
        n, s = out["host_spans"].get(name, (0, 0.0))
        out["host_spans"][name] = (n + 1, s + (b - a) / 1e9)
    busy, ops, modules = [], {}, {}
    gaps: dict[str, float] = {}
    for lines in device:
        op_events = lines.get(OP_LINE) or [
            e for name, evs in lines.items() if name not in (MODULE_LINE, "Steps")
            for e in evs]
        if not op_events:
            continue
        busy_s, merged = _union_s([(a, b) for _, a, b in op_events])
        busy.append(busy_s)
        for name, a, b in op_events:
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        for name, a, b in lines.get(MODULE_LINE, ()):
            n, s = modules.get(name, (0, 0.0))
            modules[name] = (n + 1, s + (b - a) / 1e9)
        if len(busy) == 1:                       # name the first chip's gaps
            edges = [(first, first)] + merged + [(last, last)]
            for (_, a), (b, _) in zip(edges, edges[1:]):
                if b <= a:
                    continue
                laps = [(max(a, s0), min(b, s1), name) for name, s0, s1 in spans
                        if min(b, s1) > max(a, s0)]
                for s0, s1, name in laps:
                    gaps[name] = gaps.get(name, 0.0) + (s1 - s0) / 1e9
                covered, _ = _union_s([(s0, s1) for s0, s1, _ in laps])
                gaps[UNNAMED_GAP] = gaps.get(UNNAMED_GAP, 0.0) + (b - a) / 1e9 - covered
    if busy:
        out["chips_traced"] = len(busy)
        out["busy_s"] = sum(busy) / len(busy)
        out["device_ops"] = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        out["modules"] = {k: list(v) for k, v in modules.items()}
        out["idle_gaps"] = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return out


def tick_program(reduced: dict, pattern: str) -> tuple[str, int, float] | None:
    """(name, runs, seconds) of the traced device program whose name holds
    `pattern` and which took the most device time; None where none does."""
    found = [(name, n, s) for name, (n, s) in reduced.get("modules", {}).items()
             if pattern in name]
    return max(found, key=lambda m: m[2]) if found else None
