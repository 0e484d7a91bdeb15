"""Reduction from a profiler trace to the benchmark's per-layer numbers.

The harness wraps every timed call in a host span
(``jax.profiler.TraceAnnotation(CALL_SPAN)``); the profiler writes those
spans and the device's operations into one ``.xplane.pb`` on one clock.
This module reads that file with ``jax.profiler.ProfileData`` and
reduces it:

* device busy time: the union of the intervals in which the device runs
  a program (a scan's loop is one program, so time between its ops
  counts as busy);
* idle gaps: the stretches of the traced window in which the device
  runs no program, each labelled by what the host was doing (the host
  preparing a call's inputs, assembling its results, or the harness
  between calls);
* per call: host preparation (span start to the call's first device
  program), device busy time, and result assembly (the end of the
  call's last device program to span end);
* the device operations that took most time, by op name.

The functions below :func:`read_trace` work on plain tuples, so they
are tested on synthetic intervals as well as on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

CALL_SPAN = "bench.call"

Interval = Tuple[int, int]               # [start_ns, end_ns)


def tpu_lines(plane: str, line: str) -> Tuple[str, ...]:
    """Roles of a TPU trace's lines: each chip's program executions
    (``XLA Modules``) make its busy time, its per-op line (``XLA Ops``)
    the operation breakdown."""
    if not plane.startswith("/device:TPU:"):
        return ()
    return {"XLA Modules": ("busy",), "XLA Ops": ("ops",)}.get(line, ())


def cpu_lines(plane: str, line: str) -> Tuple[str, ...]:
    """The CPU backend's stand-in for a device (its XLA worker threads);
    used only to test the reduction without a chip."""
    if plane == "/host:CPU" and line.startswith("tf_XLA"):
        return ("busy", "ops")
    return ()


def trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


#: a host thread whose first events hold no call span holds none: the
#: profiler starts right before the first call
SPAN_LOOKAHEAD = 64


def op_name(name: str) -> str:
    """An XLA op event's name without its HLO text (``%while.288``)."""
    return name.split(" = ", 1)[0]


def read_trace(path: str, lines: Callable[[str, str], Tuple[str, ...]] = tpu_lines):
    """``(spans, busy, op_ns)`` from one trace file: the harness's call
    spans as ``(start, end)``; per device, the intervals in which it ran
    a program; and the time of each device operation inside the spans,
    by op name (a loop's time includes its body's).  Events are read as
    a stream, so a trace of millions of operations stays small."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Interval] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if lines(plane.name, line.name):
                continue
            for n, ev in enumerate(line.events):
                if ev.name == CALL_SPAN:
                    s = int(ev.start_ns)
                    spans.append((s, s + int(ev.duration_ns)))
                elif n >= SPAN_LOOKAHEAD and not spans:
                    break
    spans.sort()
    lo, hi = (spans[0][0], spans[-1][1]) if spans else (0, 0)
    busy: Dict[str, List[Interval]] = {}
    op_ns: Dict[str, int] = {}
    for plane in pd.planes:
        for line in plane.lines:
            roles = lines(plane.name, line.name)
            if not roles:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= lo or s >= hi or e <= s:
                    continue
                if "busy" in roles:
                    busy.setdefault(plane.name, []).append((s, e))
                if "ops" in roles:
                    name = op_name(ev.name)
                    op_ns[name] = op_ns.get(name, 0) + min(e, hi) - max(s, lo)
    return spans, busy, op_ns


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping ``[start, end)`` intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(merged: Sequence[Interval]) -> int:
    return sum(e - s for s, e in merged)


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that no merged interval covers."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def per_call(spans: Sequence[Interval],
             merged: Sequence[Interval]) -> List[Dict[str, Optional[int]]]:
    """For each call span: host preparation before its first device
    program, device busy time inside it, and assembly after its last
    (``None`` for a call in which the device ran nothing)."""
    out = []
    for s, e in spans:
        inside = clip(merged, s, e)
        if not inside:
            out.append({"span_ns": e - s, "prep_ns": None, "busy_ns": 0,
                        "assemble_ns": None})
            continue
        out.append({"span_ns": e - s, "prep_ns": inside[0][0] - s,
                    "busy_ns": busy_ns(inside),
                    "assemble_ns": e - inside[-1][1]})
    return out


def label_gap(gap: Interval, spans: Sequence[Interval],
              merged: Sequence[Interval]) -> str:
    """What the host was doing in an idle gap: ``prep`` before a call's
    first device program, ``assemble`` after its last, ``in_call``
    between two of its programs, ``harness`` outside every call."""
    mid = (gap[0] + gap[1]) // 2
    for s, e in spans:
        if s <= mid < e:
            inside = clip(merged, s, e)
            if not inside or mid < inside[0][0]:
                return "prep"
            if mid >= inside[-1][1]:
                return "assemble"
            return "in_call"
    return "harness"


def reduce(spans: Sequence[Interval], busy: Dict[str, List[Interval]],
           op_ns: Dict[str, int]) -> dict:
    """The whole reduction over the window the call spans cover."""
    if not spans:
        raise ValueError("the trace holds no call span")
    lo, hi = spans[0][0], spans[-1][1]
    merged_dev = {d: clip(union(v), lo, hi) for d, v in busy.items()}
    all_merged = clip(union([iv for v in busy.values() for iv in v]), lo, hi)
    n_dev = len(merged_dev)
    idle = sorted(gaps(all_merged, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "window_ns": hi - lo,
        "busy_ns": sum(busy_ns(m) for m in merged_dev.values()) / n_dev if n_dev else 0.0,
        "calls": per_call(spans, all_merged),
        "top_ops": sorted(op_ns.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": [(label_gap(g, spans, all_merged), g[1] - g[0])
                      for g in idle[:10]],
    }
