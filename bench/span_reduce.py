"""Reduce a JAX profiler trace (``.xplane.pb``) to the round's stages,
its host spans and its host-to-device transfers.

Reads the file :mod:`bench.trace_reduce` reads, over the same window
(the span of the harness's ``round`` step annotations):

* stages: device self time of the ops whose ``op_name`` metadata names
  a ``scala.<stage>`` scope (the program's ``jax.named_scope``s), each
  op attributed to the rightmost such component, and the self time of
  every other op (``unscoped_s``). The op names come from xprof's
  ``framework_op_stats``, which joins the trace's device ops with the
  HLO the trace carries. It reads the whole trace, which the harness
  opens and closes around the window's rounds. It writes a cache file
  beside the trace it reads, so it reads a copy in a temporary
  directory;
* span idle: the device's idle time (holes in the busy union) inside
  the host events named ``trainer.*`` (the program's
  ``jax.profiler.TraceAnnotation`` spans), per name;
* transfers: the union of the runtime's host-to-device events on the
  host planes: the host-side relayout of a buffer into the device's
  tiled layout (``XlaLinearize``), its dispatch and the DMA
  (``tpu::System::TransferToDevice`` and its sub-events, whose
  ``size`` stat gives the bytes).

Times are seconds, per device plane (mean over chips), like
``trace_reduce``'s.

    python3 bench/span_reduce.py TRACE.xplane.pb [--rounds N]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace_reduce as TR  # noqa: E402

SPAN_PREFIX = "trainer."
# the TPU runtime's host-to-device path, per buffer (read off a v5e trace
# of jax 0.9 / libtpu 0.0.34): relayout, dispatch, DMA and its sub-events
H2D_EVENTS = ("XlaLinearize", "H2D Dispatch")
H2D_TRANSFER = "tpu::System::TransferToDevice"

# a whole ``scala.<stage>`` name-stack component of an op name
_STAGE_RE = re.compile(r"(?:^|[/(])scala\.(\w+)(?=$|[/)])")


@dataclass
class SpanSummary:
    stage_s: Dict[str, float] = field(default_factory=dict)
    unscoped_s: float = 0.0           # device self time outside every stage
    # the largest ops of each stage ("" for the unscoped), by self time
    stage_ops: Dict[str, List[Tuple[str, float]]] = field(
        default_factory=dict)
    span_idle_s: Dict[str, float] = field(default_factory=dict)
    h2d_s: float = 0.0                # union of host-to-device transfers
    h2d_events: int = 0
    h2d_bytes: int = 0


def stage_of(op_name: str) -> Optional[str]:
    """The rightmost ``scala.<stage>`` component of an op name."""
    found = _STAGE_RE.findall(op_name)
    return found[-1] if found else None


def overlap(a: List[TR.Interval], b: List[TR.Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def framework_op_self_times(path: str) -> List[Tuple[str, float]]:
    """(op name, device self seconds) of every device op, summed over
    the trace's devices, from xprof's ``framework_op_stats``."""
    from xprof.convert import raw_to_tool_data

    tmp = tempfile.mkdtemp(prefix="span_reduce_")
    try:
        copy = os.path.join(tmp, os.path.basename(path))
        shutil.copyfile(path, copy)
        raw, _ = raw_to_tool_data.xspace_to_tool_data(
            [copy], "framework_op_stats", {})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    table = json.loads(raw)[0]
    col = {c["id"]: i for i, c in enumerate(table["cols"])}
    out = []
    for row in table["rows"]:
        v = [c.get("v") for c in row["c"]]
        if v[col["host_or_device"]] == "Device" and v[col["type"]] != "IDLE":
            out.append((v[col["operation"]],
                        v[col["total_self_time"]] * 1e-6))
    return out


def _stat(ev, name: str):
    for k, v in ev.stats:
        if k == name:
            return v
    return None


def summarize(path: str, top: int = 5) -> SpanSummary:
    """Read one trace file into a :class:`SpanSummary`."""
    pd = TR._load(path)
    device_ops: List[List[TR.Interval]] = []
    host_lines: List[list] = []
    spans: Dict[str, List[TR.Interval]] = defaultdict(list)
    h2d: List[TR.Interval] = []
    out = SpanSummary()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(s, e) for line in plane.lines if line.name == TR.OPS_LINE
                   for _, s, e in TR._events(line)]
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    evs.append((ev.name, s, e))
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name].append((s, e))
                    elif (ev.name in H2D_EVENTS
                          or ev.name.startswith(H2D_TRANSFER)):
                        h2d.append((s, e))
                    if ev.name == H2D_TRANSFER:
                        out.h2d_events += 1
                        size = _stat(ev, "size")
                        out.h2d_bytes += int(size) if size else 0
                host_lines.append(evs)
    if not device_ops:
        raise ValueError(f"{path}: no device plane with an "
                         f"{TR.OPS_LINE!r} line")
    step_line = max(host_lines, key=lambda evs: sum(
        1 for n, _, _ in evs if n == TR.STEP_EVENT), default=[])
    steps = [(s, e) for n, s, e in step_line if n == TR.STEP_EVENT]
    if not steps:
        raise ValueError(f"{path}: no host {TR.STEP_EVENT!r} annotations")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    n = len(device_ops)

    span_ivs = {k: TR.clip(TR.union(v), lo, hi) for k, v in spans.items()}
    idle = defaultdict(float)
    for ops in device_ops:
        holes = TR.gaps(TR.clip(TR.union(ops), lo, hi), lo, hi)
        for k, ivs in span_ivs.items():
            idle[k] += overlap(holes, ivs)
    out.span_idle_s = {k: v / n * 1e-9 for k, v in sorted(idle.items())}
    h2d_ivs = TR.clip(TR.union(h2d), lo, hi)
    out.h2d_s = sum(e - s for s, e in h2d_ivs) * 1e-9

    by_stage = defaultdict(list)
    for name, secs in framework_op_self_times(path):
        by_stage[stage_of(name) or ""].append((name, secs / n))
    for st, rows in sorted(by_stage.items()):
        total = sum(t for _, t in rows)
        if st:
            out.stage_s[st] = total
        else:
            out.unscoped_s = total
        out.stage_ops[st] = sorted(rows, key=lambda r: -r[1])[:top]
    return out


def span_ms_per_round(ctx, seconds_of) -> Optional[float]:
    """A metric reader's ms per round of the traced window, of the
    seconds ``seconds_of`` takes from ``ctx["spans"]``. None where the
    span reduction failed, no round ran, or ``seconds_of`` returns None
    (the trace has nothing of it: not measured)."""
    s = ctx.get("spans")
    secs = None if s is None or not ctx["rounds"] else seconds_of(s)
    return None if secs is None else 1000.0 * secs / ctx["rounds"]


def stage_ms_per_round(ctx, stage: Optional[str]) -> Optional[float]:
    """``span_ms_per_round`` of one stage's device self time (``None``:
    the unscoped remainder). None where the trace carries no stage at all
    (a program, or a cached executable, compiled without the scopes); 0.0
    for a stage absent while others show (its ops fused into theirs)."""
    def seconds(s):
        if not s.stage_s:
            return None
        return s.unscoped_s if stage is None else s.stage_s.get(stage, 0.0)
    return span_ms_per_round(ctx, seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file")
    ap.add_argument("--rounds", type=int, default=1,
                    help="divide every time by this many rounds")
    args = ap.parse_args(argv)
    s = summarize(args.trace)
    busy = TR.summarize(args.trace)
    k = 1000.0 / args.rounds
    print(json.dumps({
        "stage_ms": {st: v * k for st, v in s.stage_s.items()},
        "unscoped_ms": s.unscoped_s * k,
        "busy_ms": busy.busy_s * k,
        "span_idle_ms": {sp: v * k for sp, v in s.span_idle_s.items()},
        "idle_ms": (busy.window_s - busy.busy_s) * k,
        "h2d_ms": s.h2d_s * k, "h2d_events": s.h2d_events,
        "h2d_bytes": s.h2d_bytes,
        "stage_ops_ms": {st: [(op, v * k) for op, v in rows]
                         for st, rows in s.stage_ops.items()}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
