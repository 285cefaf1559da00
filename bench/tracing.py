"""Spans around the calls into each layer, installed only for a traced run.

Each wrapped call records a ``jax.profiler.TraceAnnotation`` named
``bench/<label>`` (so the profiler's trace carries it on its own clock)
and a host span ``(label, start_ns, end_ns)`` on ``perf_counter_ns``.
The planning scans also record their unpadded call shape, which the
roofline reader prices.  ``uninstall`` puts every original back.
"""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

PREFIX = "bench/"

#: The controller's entry points; every other label is a layer below.
CONTROLLER = ("submit", "run_until", "fail_link", "recover_link")
#: The placement engines, whose time is not the controller's own.
PLANNERS = ("place_batch", "reroute")
SCANS = ("wave_scan", "col_scan")


def _wave_shape(ledger, pad, caps, sz, t0c, sizes, w, first_secs):
    return int(pad.shape[0]), int(pad.shape[1]), int(w)


def _col_shape(ledger, pad, cols, caps, secs, sizes):
    return int(pad.shape[0]), int(pad.shape[1]), int(cols.shape[1])


class Tracer:
    def __init__(self):
        self.spans: List[Tuple[str, int, int]] = []
        #: label, n, L, W and the call's start (``perf_counter_ns``)
        self.calls: List[Tuple[str, int, int, int, int]] = []
        self._undo: List[Tuple[object, str, object]] = []

    def _wrap(self, owner, attr: str, label: str) -> None:
        import jax

        orig = getattr(owner, attr)
        spans, name = self.spans, PREFIX + label

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter_ns()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return orig(*args, **kwargs)
            finally:
                spans.append((label, t0, time.perf_counter_ns()))

        self._set(owner, attr, wrapped)

    def _shapes(self, owner, attr: str, label: str, shape: Callable) -> None:
        """Record the call shape of each call that reaches the device."""
        orig = getattr(owner, attr)
        calls = self.calls

        def wrapped(*args, **kwargs):
            calls.append((label,) + shape(*args, **kwargs) + (time.perf_counter_ns(),))
            return orig(*args, **kwargs)

        self._set(owner, attr, wrapped)

    def _set(self, owner, attr: str, wrapped) -> None:
        self._undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, wrapped)

    def install(self) -> "Tracer":
        from repro.core.controller import ClusterController
        from repro.core.reroute import RerouteEngine
        from repro.core.wavefront import WavefrontPlanner
        from repro.kernels import ts_plan, ts_plan_device

        for attr in CONTROLLER:
            self._wrap(ClusterController, attr, attr)
        self._wrap(WavefrontPlanner, "place_batch", "place_batch")
        self._wrap(RerouteEngine, "run", "reroute")
        for scan in SCANS:
            self._wrap(ts_plan, scan, scan)
        self._wrap(ts_plan, "wave_select", "wave_select")
        self._wrap(ts_plan_device.DeviceMirror, "sync", "mirror_sync")
        # Only calls that the dispatch sends to the device are priced.
        self._shapes(ts_plan_device, "wave_scan", "wave_scan", _wave_shape)
        self._shapes(ts_plan_device, "col_scan", "col_scan", _col_shape)
        return self

    def uninstall(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)

