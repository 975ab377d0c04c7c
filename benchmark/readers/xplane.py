"""Reader ``xplane``: the profiler's trace of a few seconds of the window.

``load`` turns the ``.xplane.pb`` under a trace directory into plain rows
``[plane, line, name, start_ns, duration_ns]`` (jax.profiler.ProfileData,
nothing but JAX). ``reduce`` works on rows alone, so the reduction is
checked on a small recorded trace (tests/data): per device the union of
the intervals in which an operation ran, the programs by name with their
durations, and the idle gaps between them.

source args: ``{"kind": "idle_pct"}``; ``{"kind": "kernel_us", "match":
"<part of a program's name>"}`` for the mean device time of one such
program; ``{"kind": "roofline", "match": ..., "work": "<function of
work.py>"}`` for the least time the chip could take over the time it
took, in percent."""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
# Lines of a TPU plane, as the profiler names them: whole programs
# (one event per executed jit program) and the operations inside them.
PROGRAM_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
MARKER = "bench_trace_open"


def load(trace_dir: str) -> List[list]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    rows: List[list] = []
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for ev in line.events:
                # Host planes are kept for the marker alone.
                if device or ev.name == MARKER:
                    rows.append([plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


def outline(trace_dir: str) -> Dict[str, Dict[str, int]]:
    """{plane: {line: events}}: what to look at by hand before trusting
    the reduction on a new device or JAX version."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}
    return {plane.name: {line.name: len(list(line.events))
                         for line in plane.lines}
            for plane in ProfileData.from_file(paths[-1]).planes}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(rows: List[list]) -> Optional[Dict]:
    """{"devices", "busy_s" (mean over devices), "span_ns" (first start,
    last end over devices), "programs": {name: [seconds]}, "gaps":
    [(start_ns, end_ns)] of the first device, "marker_ns"}; None where no
    operation ran on a device."""
    planes: Dict[str, Dict[str, list]] = {}
    marker = None
    for plane, line, name, start, dur in rows:
        if name == MARKER and not plane.startswith(DEVICE_PLANE):
            marker = start if marker is None else min(marker, start)
        if plane.startswith(DEVICE_PLANE):
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (name, start, dur))
    busy, programs, gaps, lo, hi = [], {}, [], None, None
    for plane in sorted(planes):
        lines = planes[plane]
        ops = [ev for ln in OP_LINES for ev in lines.get(ln, ())]
        progs = [ev for ln in PROGRAM_LINES for ev in lines.get(ln, ())]
        if not ops:
            # A plane without an operations line: programs stand for them.
            ops = progs or [ev for evs in lines.values() for ev in evs]
        if not ops:
            continue
        union = _union([(s, s + d) for _n, s, d in ops])
        busy.append(sum(e - s for s, e in union) / 1e9)
        lo = union[0][0] if lo is None else min(lo, union[0][0])
        hi = union[-1][1] if hi is None else max(hi, union[-1][1])
        for name, _s, d in (progs or ops):
            programs.setdefault(name, []).append(d / 1e9)
        if not gaps:
            gaps = [(a[1], b[0]) for a, b in zip(union, union[1:])]
    if not busy:
        return None
    return {"devices": len(busy), "busy_s": sum(busy) / len(busy),
            "span_ns": (lo, hi), "programs": programs, "gaps": gaps,
            "marker_ns": marker}


def matching(trace: Dict, part: str) -> List[float]:
    return [d for name, durs in trace["programs"].items()
            if part in name for d in durs]


def read(args, ctx):
    trace = ctx.trace
    if not trace:
        return None
    kind = args["kind"]
    if kind == "idle_pct":
        if not ctx.trace_window_s:
            return None
        return 100.0 * (1.0 - trace["busy_s"] / ctx.trace_window_s)
    durs = matching(trace, args["match"])
    if not durs:
        return None
    if kind == "kernel_us":
        return 1e6 * sum(durs) / len(durs)
    if kind == "roofline":
        from benchmark import work

        least = work.least_seconds(
            args["work"], ctx.device_kind, ctx.node_bucket,
            ctx.trace_widths)
        if least is None:
            return None
        return 100.0 * least / sum(durs)
    raise ValueError(f"unknown xplane kind {kind!r}")
