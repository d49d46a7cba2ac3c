"""Reduction of a profiler trace to device busy time, op times and idle
gaps attributed to the harness's host spans.

The reduction works on plain data, so a test can feed it a synthetic
trace: a list of planes ``{"name", "lines": [{"name", "events": [ev]}]}``
with events ``{"name", "start_ns", "dur_ns", "stats"}``.  ``load`` makes
that from the ``.xplane.pb`` that ``jax.profiler`` writes.

Device planes are named ``/device:<KIND>:<n>``; their ``XLA Ops`` line
holds one event per executed HLO op and their ``XLA Modules`` line one
per executed program.  A Pallas kernel is an op whose name or HLO
category says custom call.  Host spans are the harness's
``TraceAnnotation`` names, recorded on the host planes on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
TOP = 10                      # entries in each list of the breakdown
# An op event is named by its HLO text: "%name = type{layout} opcode(...".
_HLO = re.compile(r"^%?(\S+) = (\S+?)(?:\{[^}]*\})? ([\w-]+)\(")


def load(trace_dir: str) -> List[dict]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            keep_stats = device and line.name == OPS_LINE
            events = [{"name": ev.name, "start_ns": ev.start_ns,
                       "dur_ns": ev.duration_ns,
                       "stats": dict(ev.stats) if keep_stats else {}}
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(name: str) -> str:
    """``<instruction> <opcode> <type>`` of an HLO op event, or the name."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def is_custom_call(ev: dict) -> bool:
    cat = str(ev["stats"].get("hlo_category", ""))
    return "custom-call" in ev["name"] or "custom" in cat.lower()


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _line(plane: dict, name: str) -> List[dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(planes: Sequence[dict], model_modules: Sequence[str] = (),
           host_spans: Sequence[str] = ()) -> Optional[dict]:
    """Reduce a trace to what the per-layer metrics read.

    The window is the harness's ``bench.window`` span.  Returns None when
    the trace holds no device ops (a backend that is not traced), else a
    dict of seconds: ``window_s``, ``busy_s`` (union of op intervals,
    averaged over devices), ``model_custom_s`` and ``model_xla_s`` (op
    time inside programs whose name starts with one of ``model_modules``),
    ``device_ops`` (top op names by time) and ``idle_gaps`` (idle time
    by the host span that covered it)."""
    window = None
    spans: List[Tuple[str, float, float]] = []
    for plane in planes:
        if is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                a, b = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
                if ev["name"] == WINDOW_SPAN:
                    window = (a, b)
                elif ev["name"] in host_spans:
                    spans.append((ev["name"], a, b))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = window

    busy_ns, custom_ns, xla_ns = [], 0.0, 0.0
    by_op: Dict[str, float] = {}
    busy_all: List[Tuple[float, float]] = []
    devices = 0
    for plane in planes:
        if not is_device_plane(plane["name"]):
            continue
        ops = _line(plane, OPS_LINE)
        if not ops:
            continue
        devices += 1
        modules = sorted(
            (m["start_ns"], m["start_ns"] + m["dur_ns"], m["name"])
            for m in _line(plane, MODULES_LINE))
        mi = 0
        intervals = []
        for ev in sorted(ops, key=lambda e: e["start_ns"]):
            iv = _clip(ev["start_ns"], ev["start_ns"] + ev["dur_ns"], lo, hi)
            if iv is None:
                continue
            intervals.append(iv)
            dur = iv[1] - iv[0]
            name = op_name(ev["name"])
            by_op[name] = by_op.get(name, 0.0) + dur
            while mi < len(modules) and modules[mi][1] < ev["start_ns"]:
                mi += 1
            module = (modules[mi][2] if mi < len(modules)
                      and modules[mi][0] <= ev["start_ns"] else "")
            if any(module.startswith(m) for m in model_modules):
                if is_custom_call(ev):
                    custom_ns += dur
                else:
                    xla_ns += dur
        u = union(intervals)
        busy_ns.append(sum(b - a for a, b in u))
        busy_all.extend(u)
    if not devices:
        return None

    gaps, t = [], lo
    for a, b in union(busy_all):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    # The harness's spans follow one another without overlap, so one pass
    # over the gaps and the spans, both in time order, attributes them.
    idle: Dict[str, float] = {}
    spans.sort(key=lambda s: s[1])
    si = 0
    for ga, gb in gaps:
        while si < len(spans) and spans[si][2] <= ga:
            si += 1
        covered = 0.0
        j = si
        while j < len(spans) and spans[j][1] < gb:
            name, a, b = spans[j]
            iv = _clip(a, b, ga, gb)
            if iv is not None:
                idle[name] = idle.get(name, 0.0) + (iv[1] - iv[0])
                covered += iv[1] - iv[0]
            j += 1
        rest = (gb - ga) - covered
        if rest > 0:
            idle["(no span)"] = idle.get("(no span)", 0.0) + rest

    def top_list(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / devices / 1e9,
            "model_custom_s": custom_ns / devices / 1e9,
            "model_xla_s": xla_ns / devices / 1e9,
            "device_ops": top_list(by_op),
            "idle_gaps": top_list(idle)}
