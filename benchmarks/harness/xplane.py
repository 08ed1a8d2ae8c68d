"""From a profiler trace to numbers. Reads the ``.xplane.pb`` that
``jax.profiler`` writes with ``jax.profiler.ProfileData`` (nothing but JAX),
and reduces it to what the metric readers use:

  busy_s            per device, the union of the intervals in which an
                    operation ran (so nested and parallel lines count once)
  window_s          the traced window
  op_self_s         per operation name, time in the operation and in none of
                    the operations nested inside it (a ``while`` that spans a
                    scan over the layers is charged only what its body leaves)
  exposed_collective_s   per device, time inside collective operations during
                    which no other operation ran on that device
  gaps              the idle intervals of device 0, each attributed to the
                    host annotation (``jax.profiler.TraceAnnotation`` named
                    ``bench.*``) that covers most of it, else "unattributed"

Which lines of a device plane hold operations is decided by name (OP_LINES);
``describe()`` prints what a trace holds, for the day the names change.
"""

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# lines of a device plane whose events are operations that occupy the core
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."
# the host annotation that brackets the traced window; never blamed for a gap
WINDOW_EVENT = "bench.traced_window"
# Both are matched against an operation's short name (its own instruction name,
# opcode and custom-call target), never against the whole HLO text: that also
# names the operands, and a fusion that reads an all-gather is not a collective.
COLLECTIVE = re.compile(
    r" (all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)(-start|-done)?$")
# a Mosaic kernel is a custom call whose target is tpu_custom_call
PALLAS = re.compile(r" custom-call:tpu_custom_call$")


_INSTR = re.compile(r"^%?([^\s=]+?)(?:\.\d+)*(?:\.(?:clone|remat\d*))*(?:\.\d+)* = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"\bkind=(k\w+)")


def short_name(event_name: str) -> str:
    """The trace names a device operation by its whole HLO instruction. Keep
    what identifies its kind: the instruction's name without its numeric
    suffixes, its opcode, and a custom call's target:
    ``%checkpoint.93 = (...) custom-call(...), custom_call_target="tpu_custom_call"``
    -> ``checkpoint custom-call:tpu_custom_call``; a fusion keeps its kind
    (``fusion fusion:kOutput``). Instances of one kind then
    add up under one name."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name[:80]
    rest = event_name[m.end():]
    op = _OPCODE.search(" " + rest)
    out = m.group(1) + (" " + op.group(1) if op else "")
    t = _TARGET.search(rest) or _KIND.search(rest)
    if t:
        out += ":" + t.group(1)
    return out[:80]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union ``a`` that union ``b`` does not cover (both sorted,
    disjoint: the output of ``union``)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_pieces(events: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """Flatten one line's events, which overlap only by nesting, into disjoint
    pieces ``(name, start, end)``: each instant belongs to the innermost event
    that covers it."""
    pieces: List[Tuple[str, float, float]] = []
    stack: List[List] = []  # [name, end, cursor]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                pieces.append((name, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end in sorted(events, key=lambda e: (e[1], -(e[2] - e[1]))):
        close(start)
        if stack:
            pname, pend, pcur = stack[-1]
            if start > pcur:
                pieces.append((pname, pcur, start))
            stack[-1][2] = max(pcur, start)
            end = min(end, pend)  # a child never outlives its parent
        stack.append([name, end, start])
    close(float("inf"))
    return pieces


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Dict:
    """{"devices": {id: {line: [(name, start_s, end_s)]}}, "host": [(name, s, e)]}
    with times in seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = devices.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                evs = lines.setdefault(line.name, [])
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 12) -> str:
    """What a trace holds: planes, lines, event counts and the commonest
    names. For a human, before trusting OP_LINES on a new runtime."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names = Counter()
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                n += 1
            if n:
                common = ", ".join(f"{k} x{v}" for k, v in names.most_common(top))
                out.append(f"  line {line.name!r}: {n} events: {common}")
    return "\n".join(out)


def reduce_trace(trace: Dict, window: Optional[Interval] = None, top: int = 10) -> Dict:
    """The numbers the metric readers use, from ``load()``'s output.
    ``window``: the traced interval on the trace's clock; by default from the
    first to the last device event."""
    devices = trace["devices"]
    op_events = {
        dev: {ln: evs for ln, evs in lines.items() if ln in OP_LINES and evs}
        for dev, lines in devices.items()
    }
    op_events = {dev: lines for dev, lines in op_events.items() if lines}
    if not op_events:
        return {}
    if window is None:
        starts = [e[1] for lines in op_events.values() for evs in lines.values() for e in evs]
        ends = [e[2] for lines in op_events.values() for evs in lines.values() for e in evs]
        window = (min(starts), max(ends))
    lo, hi = window
    busy_s, exposed_s, pallas_s = {}, {}, {}
    op_self: Dict[str, float] = {}
    gaps: List[Interval] = []
    for dev in sorted(op_events):
        pieces = [p for evs in op_events[dev].values() for p in self_pieces(evs)]
        names = {n: short_name(n) for n in {p[0] for p in pieces}}
        pieces = [(names[n], max(a, lo), min(b, hi)) for n, a, b in pieces
                  if min(b, hi) > max(a, lo)]
        busy = union((a, b) for _, a, b in pieces)
        busy_s[dev] = total(busy)
        coll = union((a, b) for n, a, b in pieces if COLLECTIVE.search(n))
        other = union((a, b) for n, a, b in pieces if not COLLECTIVE.search(n))
        exposed_s[dev] = total(subtract(coll, other))
        pallas_s[dev] = total(union((a, b) for n, a, b in pieces if PALLAS.search(n)))
        if dev == min(op_events):
            for n, a, b in pieces:
                op_self[n] = op_self.get(n, 0.0) + (b - a)
            gaps = subtract([(lo, hi)], busy)
    host = [h for h in trace.get("host", []) if h[0] != WINDOW_EVENT]
    by_host: Dict[str, float] = {}
    for a, b in gaps:
        best, best_cover = "unattributed", 0.0
        for name, s, e in host:
            cover = min(b, e) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
        # a gap counts for the annotation only if that covers most of it
        if best_cover < 0.5 * (b - a):
            best = "unattributed"
        by_host[best] = by_host.get(best, 0.0) + (b - a)
    n_dev = len(busy_s)
    return {
        "window_s": hi - lo,
        "n_devices": n_dev,
        "busy_s": sum(busy_s.values()) / n_dev,
        "busy_s_by_device": busy_s,
        "exposed_collective_s": exposed_s[min(exposed_s)],
        "pallas_s": pallas_s[min(pallas_s)],
        "device_ops": sorted(op_self.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_host.items(), key=lambda kv: -kv[1])[:top],
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0),
    }
