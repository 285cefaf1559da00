"""From a profiler trace to device busy time, idle share, device time per
benchmark span and the breakdown.

A trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per device (``/device:TPU:<i>``) and one for the host
(``/host:CPU``).  Device planes carry a line of XLA operations (``XLA
Ops``) and one of whole programs (``XLA Modules``); busy time is the
union of the operation intervals (the module intervals where a plane has
no operation line).  Host lines carry the benchmark's own
``TraceAnnotation`` spans, named ``bench/<label>``; both sit on the
trace's one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from tracing import PREFIX

Interval = Tuple[float, float]


@dataclass
class Trace:
    #: device index -> [(start_ns, end_ns, op name)]
    ops: Dict[int, List[Tuple[float, float, str]]] = field(default_factory=dict)
    #: [(label, start_ns, end_ns)] of the benchmark's host spans
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def _device_index(plane_name: str) -> Optional[int]:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    return from_planes(data.planes)


def from_planes(planes) -> Trace:
    """Build a :class:`Trace` from objects shaped like ``ProfilePlane``
    (``name``, ``lines`` of ``name`` and ``events`` with ``name``,
    ``start_ns`` and ``duration_ns``)."""
    tr = Trace()
    for plane in planes:
        dev = _device_index(plane.name)
        lines = {line.name: line for line in plane.lines}
        if dev is not None:
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                tr.ops[dev] = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        tr.spans.append((e.name[len(PREFIX):], e.start_ns,
                                         e.start_ns + e.duration_ns))
    tr.spans.sort(key=lambda s: (s[1], -s[2]))
    return tr


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Total length of the intersection of two merged interval lists."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += max(0.0, min(b, ys[k][1]) - max(a, ys[k][0]))
            k += 1
    return total


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists, merged, in one sweep."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            out.append((max(a, ys[k][0]), min(b, ys[k][1])))
            k += 1
    return merge(out)


def clip(intervals: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def window(tr: Trace, label: str = "window") -> Interval:
    w = [s for s in tr.spans if s[0] == label]
    if not w:
        raise ValueError(f"trace has no bench/{label} span")
    return w[0][1], w[0][2]


def busy(tr: Trace, t0: float, t1: float) -> Dict[int, List[Interval]]:
    """Merged busy intervals of each device inside ``[t0, t1]``."""
    return {d: clip(merge([(a, b) for a, b, _ in ops]), t0, t1)
            for d, ops in tr.ops.items()}


def spans_of(spans, labels: Sequence[str], t0: float, t1: float) -> List[Interval]:
    """Merged intervals of the ``(label, start, end)`` spans with one of
    ``labels``, clipped to ``[t0, t1]``."""
    return clip(merge([(a, b) for l, a, b in spans if l in labels]), t0, t1)


class Innermost:
    """The innermost benchmark span open at a time, as a ``>``-joined
    path of labels (``run_until>place_batch>wave_scan``)."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]):
        cuts = sorted({t for _, a, b in spans for t in (a, b)})
        self.cuts = cuts
        labels: List[str] = []
        # Spans of one thread nest: walk the cut points with a stack.
        events = sorted([(a, 1, -b, l) for l, a, b in spans]
                        + [(b, 0, 0, l) for l, a, b in spans])
        stack: List[str] = []
        i = 0
        for c in cuts:
            while i < len(events) and events[i][0] <= c:
                t, opening, _, l = events[i]
                if opening:
                    stack.append(l)
                elif l in stack:
                    stack.reverse()
                    stack.remove(l)
                    stack.reverse()
                i += 1
            labels.append(">".join(stack) if stack else "")
        self.labels = labels

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.cuts, t) - 1
        return self.labels[i] if i >= 0 else ""


def breakdown(tr: Trace, t0: float, t1: float, per_dev: Dict[int, List[Interval]],
              top: int = 10) -> dict:
    """The device operations that took most time, by enclosing span, and
    the longest idle gaps (between ``per_dev``'s busy intervals), by the
    host span open during each."""
    inner = Innermost([s for s in tr.spans if s[0] != "window"])
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev, ops in tr.ops.items():
        for a, b, name in ops:
            if b <= t0 or a >= t1:
                continue
            key = f"{inner.at(a) or 'no span'}:{name}"
            op_s[key] = op_s.get(key, 0.0) + (min(b, t1) - max(a, t0)) * 1e-9
        edge = t0
        for a, b in per_dev[dev]:
            if a > edge:
                gaps.append((inner.at((edge + a) / 2) or "no span", (a - edge) * 1e-9))
            edge = max(edge, b)
        if t1 > edge:
            gaps.append((inner.at((edge + t1) / 2) or "no span", (t1 - edge) * 1e-9))
    ops_top = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in ops_top],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def reduce(tr: Trace, calls_ns: Sequence[int] = (), ns0: int = 0) -> dict:
    """Window, busy time averaged over devices, and device time inside
    each label's spans (and inside scans outside their mirror syncs).

    ``calls_ns`` are the host-clock starts of the window's device calls
    and ``ns0`` the window's start on that clock.  Where a call starts
    after the device's last recorded operation, the profiler stopped
    recording the device's operations before the window closed, and the
    window ends with that operation: the time after it would read as
    idle."""
    t0, t1 = window(tr)
    full_s = (t1 - t0) * 1e-9
    ends = [b for ops in tr.ops.values() for a, b, _ in ops if a < t1]
    if not ends:
        raise ValueError("trace has no device plane with operations")
    if any(t0 + (c - ns0) > max(ends) for c in calls_ns):
        t1 = max(ends)
    per_dev = busy(tr, t0, t1)
    n = len(per_dev)
    busy_s = sum(sum(b - a for a, b in iv) for iv in per_dev.values()) * 1e-9 / n
    labels = sorted({l for l, _, _ in tr.spans} - {"window"})
    device_s = {
        l: sum(overlap(iv, spans_of(tr.spans, [l], t0, t1)) for iv in per_dev.values())
        * 1e-9 / n
        for l in labels
    }
    sync = spans_of(tr.spans, ["mirror_sync"], t0, t1)
    kernel_s = {}
    for scan in ("wave_scan", "col_scan"):
        sp = spans_of(tr.spans, [scan], t0, t1)
        inside_sync = intersect(sp, sync)
        kernel_s[scan] = sum(overlap(iv, sp) - overlap(iv, inside_sync)
                             for iv in per_dev.values()) * 1e-9 / n
    return {
        "window_s": (t1 - t0) * 1e-9,
        "traced_s": full_s,
        "busy_s": busy_s,
        "devices": n,
        "device_s": device_s,
        "kernel_s": kernel_s,
        "breakdown": breakdown(tr, t0, t1, per_dev),
    }
