"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are the ``/device:...`` planes; on a TPU their ``XLA Ops``
line holds one event per executed HLO op. Busy time is the union of
those intervals inside the traced window, averaged over the device
planes. The window is the span of the harness's host step annotations
(``round`` events, one per ``Trainer.step``). Idle gaps are the holes
in the busy union inside the window, each labelled by the innermost
host event open at the gap's midpoint on the thread that carries the
step annotations.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
STEP_EVENT = "round"

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float                   # first step start .. last step end
    busy_s: float                     # device op union, mean over chips
    steps: int                        # step annotations in the window
    devices: int
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(paths)}")
    return paths[0]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals; returns them sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Holes in sorted, disjoint ``busy`` inside [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def short(name: str) -> str:
    """An HLO op's name, kind and result shape, from the instruction text
    a TPU trace gives as its name (``%fusion.3 = f32[8,4]{...} fusion(...)``
    becomes ``%fusion.3 fusion f32[8,4]``)."""
    m = re.match(r"(%[\w.\-]+) = (\(|[\w\[\],]*)", name)
    if not m:
        return name
    kind = re.search(r" ([a-z][\w\-]*)\(", name[m.end():])
    shape = "tuple" if m.group(2) == "(" else m.group(2)
    return " ".join(x for x in (m.group(1), kind and kind.group(1), shape)
                    if x)


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def summarize(path: str, top: int = 10) -> TraceSummary:
    """Read one trace file into a :class:`TraceSummary`."""
    pd = _load(path)
    device_ops: List[List[Tuple[str, float, float]]] = []
    host_lines: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line)]
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append(list(_events(line)))
    if not device_ops:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")

    step_line = max(host_lines, key=lambda evs: sum(
        1 for n, _, _ in evs if n == STEP_EVENT), default=[])
    steps = [(s, e) for n, s, e in step_line if n == STEP_EVENT]
    if not steps:
        raise ValueError(f"{path}: no host {STEP_EVENT!r} annotations")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)

    busy_ns, per_op = 0.0, defaultdict(float)
    gap_labels: Dict[str, float] = defaultdict(float)
    for ops in device_ops:
        ivs = clip(union([(s, e) for _, s, e in ops]), lo, hi)
        busy_ns += sum(e - s for s, e in ivs)
        for name, s, e in ops:
            if e > lo and s < hi:
                per_op[short(name)] += min(e, hi) - max(s, lo)
        for s, e in gaps(ivs, lo, hi):
            gap_labels[_label(step_line, (s + e) / 2)] += e - s
    n = len(device_ops)
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gap_labels.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns / n * 1e-9,
        steps=len(steps), devices=n,
        top_ops=[(k, v / n * 1e-9) for k, v in ranked],
        idle_gaps=[(k, v / n * 1e-9) for k, v in idle])


def _label(line_events, t: float) -> str:
    """Name of the innermost (shortest) event on the line open at ``t``."""
    best: Optional[Tuple[float, str]] = None
    for name, s, e in line_events:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside any host span"
