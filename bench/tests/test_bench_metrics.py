"""Metric readers: percentiles over every submit from its due time,
rates over the whole window, shares of a roofline, and nothing read
where nothing is there."""
import bench_tiny
import pytest

import harness
import readers


def read(name, rec):
    return harness.metric_module(name).read(rec)


def test_percentiles_cover_every_submit_from_its_due_time():
    lat = [0.010] * 90 + [0.100] * 9 + [1.0]
    rec = {"latency_s": lat}
    assert readers.percentile_ms(rec, 50.0) == pytest.approx(10.0)
    assert readers.percentile_ms(rec, 95.0) == pytest.approx(100.0)
    assert readers.percentile_ms({"latency_s": []}, 50.0) is None


def test_rates_take_all_work_over_all_the_window():
    assert read("place_rate", {"tasks": 3072, "window_s": 1.5}) == pytest.approx(2048.0)
    assert read("reroute_rate", {"victims": 300, "window_s": 12.0}) == pytest.approx(25.0)
    assert read("place_rate", {"tasks": 0, "window_s": 1.0}) is None
    assert read("setup_s", {"setup_s": 31.5}) == 31.5


def test_counter_ratios():
    rec = {"tasks": 2000, "victims": 50, "counters": {
        "wavefront.hits": 30, "wavefront.misses": 10,
        "reroute.hits": 0, "reroute.misses": 0,
        "ts_plan_device.traces": 2, "ts_plan_device.cache_hits": 98,
        "ts_plan_device.mirror_cells": 5000, "ts_plan_device.mirror_uploads": 3}}
    assert read("wave_hit_pct.place", rec) == pytest.approx(75.0)
    assert read("reroute_hit_pct.reroute", rec) is None
    assert read("device_calls_per_ktask.place", rec) == pytest.approx(50.0)
    assert read("device_calls_per_victim.reroute", rec) == pytest.approx(2.0)
    assert read("mirror_cells_per_task.place", rec) == pytest.approx(2.5)
    assert read("mirror_uploads.reroute", rec) == 3


def test_controller_self_time_leaves_out_the_engines():
    spans = [("run_until", 100, 600), ("place_batch", 200, 500),
             ("wave_scan", 250, 300), ("submit", 700, 750), ("run_until", 800, 1200)]
    rec = {"spans": spans, "span_window": (0, 1000)}
    assert read("controller_self_pct.place", rec) == pytest.approx(45.0)
    assert read("controller_self_pct.place", {"spans": None}) is None


def test_roofline_and_idle_shares_from_the_trace():
    # the last call starts after the traced window, which ends at 1 s
    calls = [("wave_scan", 8, 4, 64, 10), ("wave_scan", 16, 4, 256, 900_000_000),
             ("col_scan", 8, 6, 32, 20), ("wave_scan", 64, 4, 64, 1_000_000_001)]
    moved = 8 * 8 * 64 * 7 + 8 * 16 * 256 * 7
    rec = {"calls": calls, "device_kind": "TPU v5 lite", "span_window": (0, 2 * 10**9),
           "trace": {"kernel_s": {"wave_scan": 1e-3, "col_scan": 0.0},
                     "busy_s": 0.25, "window_s": 1.0}}
    assert read("wave_scan_roofline.place", rec) == pytest.approx(
        100.0 * moved / 819e9 / 1e-3)
    assert read("col_scan_roofline.reroute", rec) is None  # no device time
    assert read("device_idle_pct.place", rec) == pytest.approx(75.0)
    assert read("device_idle_pct.place", {"trace": None}) is None
    with pytest.raises(KeyError):
        readers.scan_roofline_pct(dict(rec, device_kind="cpu"), "wave_scan")
