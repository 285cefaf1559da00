"""Shared arithmetic of the metric readers in ``bench/metrics/``.

A reader gets the run's record (``harness.run_cell`` builds it) and
returns a number, or ``None`` when the run holds nothing to read; the
harness then leaves the metric out of the result line.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import peaks
import trace_reduce
import tracing


def counter(rec: dict, name: str) -> float:
    return rec["counters"].get(name, 0)


def device_calls(rec: dict) -> float:
    """Device program calls: compiled buckets built plus reused."""
    return counter(rec, "ts_plan_device.traces") + counter(rec, "ts_plan_device.cache_hits")


def share_pct(part: float, whole: float) -> Optional[float]:
    return 100.0 * part / whole if whole else None


def percentile_ms(rec: dict, q: float) -> Optional[float]:
    lat = rec.get("latency_s")
    return float(np.percentile(lat, q)) * 1e3 if lat else None


def controller_self_pct(rec: dict) -> Optional[float]:
    """Share of the window spent in the controller's entry points but not
    in the placement engines below them (host spans)."""
    if rec.get("spans") is None:
        return None
    t0, t1 = rec["span_window"]
    spans = rec["spans"]
    outer = trace_reduce.spans_of(spans, tracing.CONTROLLER, t0, t1)
    inner = trace_reduce.spans_of(spans, tracing.PLANNERS, t0, t1)
    own = sum(b - a for a, b in outer) - trace_reduce.overlap(outer, inner)
    return share_pct(own, t1 - t0)


def device_idle_pct(rec: dict) -> Optional[float]:
    red = rec.get("trace")
    if red is None or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def scan_roofline_pct(rec: dict, scan: str) -> Optional[float]:
    """Least time the chip could take for the ``scan`` calls of the traced
    window (their bytes over peak HBM bandwidth) over the device time
    spent inside those calls outside their mirror syncs."""
    red = rec.get("trace")
    if red is None or not rec.get("calls"):
        return None
    kernel_s = red["kernel_s"].get(scan, 0.0)
    end = rec["span_window"][0] + red["window_s"] * 1e9
    moved = sum(peaks.scan_bytes(n, l, w) for lab, n, l, w, t in rec["calls"]
                if lab == scan and t <= end)
    if kernel_s <= 0.0 or moved == 0:
        return None
    bound_s = moved / peaks.peak(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound_s / kernel_s
