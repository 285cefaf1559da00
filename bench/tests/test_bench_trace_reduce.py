"""The reduction from a profiler trace to busy time, idle share, device
time per span and the breakdown, on a trace with known intervals."""
from types import SimpleNamespace as NS

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

import trace_reduce


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    # Window [0, 1000].  Host spans: run_until [100, 900] holding
    # place_batch [150, 850] holding wave_scan [200, 600] holding
    # mirror_sync [200, 300].  Device ops: [50, 80] (no span),
    # [220, 280] (in the sync), [300, 500] (in the scan, outside the
    # sync), [450, 550] (overlaps the previous op) and [950, 1100]
    # (runs past the window's end).
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench/window", 0, 1000),
        ev("bench/run_until", 100, 800),
        ev("bench/place_batch", 150, 700),
        ev("bench/wave_scan", 200, 400),
        ev("bench/mirror_sync", 200, 100),
        ev("not_ours", 0, 5),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_f", 0, 2000)]),
        NS(name="XLA Ops", events=[
            ev("gather", 50, 30), ev("scatter", 220, 60), ev("while", 300, 200),
            ev("fusion", 450, 100), ev("copy", 950, 150),
        ]),
    ])
    return [host, dev, NS(name="/host:metadata", lines=[])]


def test_busy_idle_and_span_device_time():
    red = trace_reduce.reduce(trace_reduce.from_planes(planes()))
    assert red["window_s"] == pytest.approx(1000e-9)
    # union: 30 + 60 + 250 ([300, 550]) + 50 (clipped to the window)
    assert red["busy_s"] == pytest.approx(390e-9)
    assert red["devices"] == 1
    assert red["device_s"]["wave_scan"] == pytest.approx(60e-9 + 250e-9)
    assert red["device_s"]["mirror_sync"] == pytest.approx(60e-9)
    assert red["device_s"]["run_until"] == pytest.approx(310e-9)
    # the scan's own time leaves out its nested sync
    assert red["kernel_s"]["wave_scan"] == pytest.approx(250e-9)
    assert red["kernel_s"]["col_scan"] == 0.0


def test_breakdown_labels_ops_and_gaps_by_enclosing_span():
    red = trace_reduce.reduce(trace_reduce.from_planes(planes()))
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["run_until>place_batch>wave_scan:while"] == pytest.approx(200e-9)
    assert ops["run_until>place_batch>wave_scan>mirror_sync:scatter"] == pytest.approx(60e-9)
    assert ops["no span:gather"] == pytest.approx(30e-9)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["run_until>place_batch", pytest.approx(400e-9)]  # [550, 950]
    assert sum(g for _, g in gaps) == pytest.approx(1000e-9 - red["busy_s"])
    assert len(red["breakdown"]["device_ops"]) <= 10


def test_innermost_span_and_interval_helpers():
    tr = trace_reduce.from_planes(planes())
    inner = trace_reduce.Innermost([s for s in tr.spans if s[0] != "window"])
    assert inner.at(250) == "run_until>place_batch>wave_scan>mirror_sync"
    assert inner.at(700) == "run_until>place_batch"
    assert inner.at(950) == ""
    assert trace_reduce.merge([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    assert trace_reduce.overlap([(0, 10)], [(2, 3), (8, 12)]) == 3


def test_a_trace_without_a_device_plane_is_refused():
    host_only = [p for p in planes() if not p.name.startswith("/device")]
    with pytest.raises(ValueError):
        trace_reduce.reduce(trace_reduce.from_planes(host_only))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_intersect_is_the_pairwise_overlap_in_one_sweep(seed):
    import random

    rng = random.Random(seed)

    def intervals(n):
        return trace_reduce.merge([(a, a + rng.randint(1, 30))
                                   for a in (rng.randint(0, 1000) for _ in range(n))])

    xs, ys = intervals(60), intervals(80)
    pairwise = trace_reduce.merge([(max(a, c), min(b, d)) for a, b in xs for c, d in ys
                                   if min(b, d) > max(a, c)])
    assert trace_reduce.intersect(xs, ys) == pairwise
    assert trace_reduce.intersect(ys, xs) == pairwise


def test_window_ends_with_the_device_trace_where_the_profiler_stopped_it():
    # the device's last recorded operation ends at 500
    trimmed = [NS(name=p.name, lines=[NS(name=l.name, events=[
        e for e in l.events if not p.name.startswith("/device") or e.start_ns < 400])
        for l in p.lines]) for p in planes()]
    tr = trace_reduce.from_planes(trimmed)
    # no device call after its last operation: a device idle to the end
    assert trace_reduce.reduce(tr, [20, 300], 0)["window_s"] == pytest.approx(1000e-9)
    # a call at 700 left no operation: the profiler stopped recording them
    red = trace_reduce.reduce(tr, [20, 300, 700], 0)
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["traced_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(30e-9 + 60e-9 + 200e-9)
    gaps = red["breakdown"]["idle_gaps"]
    assert sum(g for _, g in gaps) == pytest.approx(500e-9 - red["busy_s"])
