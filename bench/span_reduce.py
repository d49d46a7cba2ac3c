"""Reduction of the host spans of a profiler trace, nested ones too: each
span's count, total and self time inside the window, and idle time of the
device attributed to the innermost span that covered it.

It reads the same plain planes as ``trace_reduce`` (``trace_reduce.load``
makes them) and leaves ``trace_reduce.reduce`` as it is: that one
attributes idle to the harness's spans, which never overlap; the spans
here nest (the program's inside the harness's ``sched.step``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

# The serving loop's own spans (repro.serving.scheduler, GenServer.run_group).
PROGRAM_SPANS = ("sched.admit", "sched.wait", "sched.launch",
                 "serve.inputs", "serve.dispatch", "sched.block",
                 "sched.outputs")
NO_SPAN = "(no span)"

Span = Tuple[str, float, float]


def device_ops(plane: dict) -> List[dict]:
    return [ev for line in plane["lines"]
            if line["name"] == trace_reduce.OPS_LINE
            for ev in line["events"]]


def window_of(planes: Sequence[dict]) -> Tuple[float, float]:
    for plane in planes:
        if trace_reduce.is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev["name"] == trace_reduce.WINDOW_SPAN:
                    return ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
    raise ValueError(f"trace has no {trace_reduce.WINDOW_SPAN!r} span")


def host_lines(planes: Sequence[dict], names: Sequence[str]
               ) -> List[List[Span]]:
    """The spans named in ``names`` on each host line, clipped to the
    window; lines that hold none are left out."""
    lo, hi = window_of(planes)
    lines = []
    for plane in planes:
        if trace_reduce.is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            spans = []
            for ev in line["events"]:
                if ev["name"] in names:
                    a, b = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
                    if min(b, hi) > max(a, lo):
                        spans.append((ev["name"], max(a, lo), min(b, hi)))
            if spans:
                lines.append(spans)
    return lines


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Cuts nested spans into pieces that do not overlap, each named by
    the innermost span that covers it.  A span that outlasts the one it
    starts inside is cut at that one's end."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []          # (name, end)
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        if stack:
            close_until(a)
        if stack and a > t:
            out.append((stack[-1][0], t, a))
        t = a
        stack.append((name, min(b, stack[-1][1]) if stack else b))
    if stack:
        close_until(float("inf"))
    return out


def span_table(planes: Sequence[dict], names: Sequence[str]
               ) -> Dict[str, dict]:
    """For each span name: ``count``, ``total_s`` and ``self_s`` (its time
    less what its children on the same line cover) inside the window."""
    table: Dict[str, dict] = {}
    for spans in host_lines(planes, names):
        for name, a, b in spans:
            row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += (b - a) / 1e9
        for name, a, b in innermost(spans):
            table[name]["self_s"] += (b - a) / 1e9
    return table


def idle_gaps(planes: Sequence[dict]) -> Optional[List[Tuple[float, float]]]:
    """The window's intervals in which no op ran on any device, or None
    when the trace holds no device ops."""
    lo, hi = window_of(planes)
    busy, devices = [], 0
    for plane in planes:
        if not trace_reduce.is_device_plane(plane["name"]):
            continue
        ops = device_ops(plane)
        devices += bool(ops)
        for ev in ops:
            a, b = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
            if min(b, hi) > max(a, lo):
                busy.append((max(a, lo), min(b, hi)))
    if not devices:
        return None
    gaps, t = [], lo
    for a, b in trace_reduce.union(busy):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_span(planes: Sequence[dict], names: Sequence[str]
                 ) -> Optional[List[list]]:
    """Idle seconds of the device by the innermost span named in
    ``names`` that covered them, ``(no span)`` for the rest, largest
    first; None when the trace holds no device ops.  Spans of all host
    lines are taken together, as the serving loop runs on one thread."""
    gaps = idle_gaps(planes)
    if gaps is None:
        return None
    pieces = innermost([s for spans in host_lines(planes, names)
                        for s in spans])
    idle: Dict[str, float] = {}
    covered = 0.0
    pi = 0
    for ga, gb in gaps:
        while pi < len(pieces) and pieces[pi][2] <= ga:
            pi += 1
        j = pi
        while j < len(pieces) and pieces[j][1] < gb:
            name, a, b = pieces[j]
            d = min(b, gb) - max(a, ga)
            if d > 0:
                idle[name] = idle.get(name, 0.0) + d
                covered += d
            j += 1
    rest = sum(gb - ga for ga, gb in gaps) - covered
    if rest > 0:
        idle[NO_SPAN] = rest
    return [[k, v / 1e9] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])]
