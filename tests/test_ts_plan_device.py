"""Device-backend contract tests: bit-pattern pipeline parity with numpy,
shape-bucket sweeps (interpret mode — no TPU needed), winner-selection
tie-breaking, the ledger mirror's journal/sync protocol, the compile
cache, and the auto-selection rule."""
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.timeslot import TimeSlotLedger, TransferPlan
from repro.core.topology import two_tier_fabric
from repro.kernels import ts_plan, ts_plan_device


@pytest.fixture(autouse=True)
def _device_backend():
    """Force the device dispatch path and an enabled mirror for every test
    here; restore the process-wide defaults afterwards."""
    prev = ts_plan.get_backend()
    ts_plan.set_backend("pallas")
    ts_plan_device.set_mirror(True)
    yield
    ts_plan.set_backend(prev)
    ts_plan_device.set_mirror(None)


def _inputs(seed, n, L, W, dyadic=False):
    rng = np.random.default_rng(seed)
    if dyadic:
        booked = rng.integers(0, 9, size=(n, L, W)) / 8.0
        caps = 2.0 ** rng.integers(0, 5, size=n)
        secs = np.ones((n, W))
        secs[:, 0] = 0.5
        sizes = rng.integers(1, 40, size=n).astype(np.float64)
    else:
        booked = rng.random((n, L, W))
        caps = rng.uniform(1.0, 37.0, size=n)
        secs = rng.uniform(0.0, 1.3, size=(n, W))
        sizes = rng.uniform(0.5, 60.0, size=n)
    return booked, caps, secs, sizes


def _assert_same(ref, got):
    for name, r, g in zip(("resid", "bw", "cum", "hit"), ref, got):
        assert np.array_equal(
            np.asarray(r, np.float64), np.asarray(g, np.float64)
        ), name


# -- fused f64 pipeline: bit-exact on arbitrary inputs -----------------------


@pytest.mark.parametrize("n", [1, 7, 8, 9, 33])
@pytest.mark.parametrize("L", [1, 8, 9])
@pytest.mark.parametrize("W", [1, 64, 200])
def test_f64_pipeline_bitwise_any_input(n, L, W):
    booked, caps, secs, sizes = _inputs(3 * n + L + W, n, L, W)
    ref = ts_plan.plan_scan_numpy(booked, caps, secs, sizes)
    got = ts_plan_device.plan_scan(booked, caps, secs, sizes)
    _assert_same(ref, got)


@pytest.mark.parametrize("cap", [None, 16.0, 3.7])
def test_f64_pipeline_overlay_and_cap_combos(cap):
    booked, caps, secs, sizes = _inputs(11, 9, 3, 48)
    rng = np.random.default_rng(99)
    overlay = (rng.random(booked.shape) < 0.2).astype(np.float64)
    ref = ts_plan.plan_scan_numpy(booked, caps, secs, sizes, cap, overlay)
    got = ts_plan_device.plan_scan(booked, caps, secs, sizes, cap, overlay)
    _assert_same(ref, got)


# -- exact binary64 routines on bit patterns --------------------------------


def _operand_pairs(seed, n=1 << 14):
    """Non-negative finite operand pairs: arbitrary patterns (subnormals
    and huge exponents included), ledger-like fractions, zeros, and
    exact and near rounding ties."""
    rng = np.random.default_rng(seed)
    top = np.uint64(0x7FF0000000000000)

    def any_pattern():
        return rng.integers(0, top, size=n, dtype=np.uint64).view(np.float64)

    a = rng.uniform(1.0, 2.0, n) * 2.0 ** rng.integers(-30, 30, n)
    tie = np.ldexp(rng.integers(1, 64, n).astype(np.float64),
                   np.frexp(a)[1] - 59)
    sub = np.ldexp(rng.random(n), -1022)
    pairs = [
        (any_pattern(), any_pattern()),
        (rng.random(n), rng.random(n)),
        (rng.uniform(0.4, 0.8, n), rng.uniform(1.0, 37.0, n)),
        (a, tie),
        (a, a * (1.0 - np.ldexp(rng.random(n), -40))),
        (sub, sub * rng.random(n)),
        (sub, rng.random(n)),
        (np.zeros(n), any_pattern()),
    ]
    x = np.concatenate([p[0] for p in pairs])
    y = np.concatenate([p[1] for p in pairs])
    return x, y


@pytest.mark.parametrize("op", ["add", "sub", "mul"])
def test_bit_pattern_arithmetic_is_binary64(op):
    x, y = _operand_pairs(17)
    if op == "sub":
        x, y = np.maximum(x, y), np.minimum(x, y)
    ref_op = {"add": np.add, "sub": np.subtract, "mul": np.multiply}[op]
    with np.errstate(over="ignore", under="ignore"):
        ref = ref_op(x, y)
    fn = jax.jit(getattr(ts_plan_device, f"_{op}"))
    with jax.enable_x64(True):
        got = np.asarray(fn(ts_plan_device._bits(x), ts_plan_device._bits(y)))
    bad = np.flatnonzero(got != ts_plan_device._bits(ref))
    assert bad.size == 0, (
        f"{op}({x[bad[0]]!r}, {y[bad[0]]!r}) = "
        f"{got[bad[0:1]].view(np.float64)[0]!r}, want {ref[bad[0]]!r}"
    )


# -- Pallas kernel (interpret): shape buckets on float64-safe inputs ---------


@pytest.mark.parametrize(
    "n,L,W",
    [
        (7, 3, 127),   # below every pad boundary
        (8, 8, 128),   # exactly on the BN / L-pad / lane boundaries
        (9, 9, 129),   # just past all three
        (24, 4, 256),  # multi-block grid, two full lanes
    ],
)
@pytest.mark.parametrize("cap", [None, 16.0])
def test_pallas_kernel_shape_buckets(n, L, W, cap):
    booked, caps, secs, sizes = _inputs(n + L + W, n, L, W, dyadic=True)
    ref = ts_plan.plan_scan_numpy(booked, caps, secs, sizes, cap)
    got = ts_plan.plan_scan_pallas(
        booked, caps, secs, sizes, cap, interpret=True
    )
    _assert_same(ref, got)


def test_pallas_kernel_overlay_bitwise():
    booked, caps, secs, sizes = _inputs(21, 9, 3, 130, dyadic=True)
    overlay = np.zeros_like(booked)
    overlay[::2, 0, ::3] = 1.0
    ref = ts_plan.plan_scan_numpy(booked, caps, secs, sizes, None, overlay)
    got = ts_plan.plan_scan_pallas(
        booked, caps, secs, sizes, None, overlay, interpret=True
    )
    _assert_same(ref, got)


# -- satellites: _pad_to fast path, searchsorted hit, compile cache ----------


def test_pad_to_identity_fast_path():
    x = np.ones((4, 5))
    assert ts_plan._pad_to(x, (4, 5)) is x
    y = ts_plan._pad_to(x, (6, 5))
    assert y.shape == (6, 5) and (y[4:] == 0).all()


@pytest.mark.parametrize(
    "n,W", [(1, 4096), (2, 300), (8, 64), (40, 16), (7, 1)]
)
def test_hit_count_matches_historical_full_count(n, W):
    # Both _hit_count regimes (per-row searchsorted for few long rows,
    # vectorized count otherwise) must pin the pre-optimization counts.
    booked, caps, secs, sizes = _inputs(n * W, n, 2, W)
    sizes = np.concatenate([sizes[: n - 1], [1e9]])  # one never-fitting row
    _r, _b, cum, hit = ts_plan.plan_scan_numpy(booked, caps, secs, sizes)
    legacy = (cum < (sizes - ts_plan.EPS)[:, None]).sum(axis=1)
    assert np.array_equal(hit, legacy)


def test_compile_cache_buckets_trace_once():
    ts_plan_device.reset_cache()
    booked, caps, secs, sizes = _inputs(1, 5, 2, 32)
    ts_plan_device.plan_scan(booked, caps, secs, sizes)
    t1 = ts_plan_device.stats["traces"]
    assert t1 == 1
    ts_plan_device.plan_scan(booked * 0.5, caps, secs, sizes)
    assert ts_plan_device.stats["traces"] == t1  # same bucket: no retrace
    assert ts_plan_device.stats["cache_hits"] >= 1
    ts_plan_device.plan_scan(booked[:, :, :16], caps, secs[:, :16], sizes)
    assert ts_plan_device.stats["traces"] == t1 + 1  # new W bucket


# -- winner selection: tie-breaking parity -----------------------------------


def test_wave_select_tie_parity():
    rng = np.random.default_rng(7)
    counts = [1, 2, 5, 8, 3]
    nc = sum(counts)
    # Exact float ties on purpose: draw ends from a tiny dyadic pool.
    end = rng.integers(0, 3, size=nc) / 4.0
    end[4] = np.inf  # whole-segment unfit ties on rank alone
    end[5] = np.inf
    lens = rng.integers(1, 4, size=nc)
    srcs = rng.integers(0, 3, size=nc).astype(str)
    ranks = np.empty(nc, dtype=np.int64)
    expect = []
    pos = 0
    for cnt in counts:
        order = sorted(
            range(cnt), key=lambda c: (lens[pos + c], srcs[pos + c], c)
        )
        for r, c in enumerate(order):
            ranks[pos + c] = r
        expect.append(
            min(
                range(cnt),
                key=lambda c: (
                    end[pos + c], lens[pos + c], srcs[pos + c], c
                ),
            )
        )
        pos += cnt
    host = ts_plan.wave_select_numpy(end, ranks, counts)
    dev = ts_plan_device.wave_select(end, ranks, counts)
    assert np.array_equal(host, np.array(expect))
    assert np.array_equal(dev, np.array(expect))


# -- ledger mirror: journal/sync protocol ------------------------------------


def _ledger(horizon=64):
    fab = two_tier_fabric(2, 4, 100.0, 100.0)
    return TimeSlotLedger(fab, 1.0, horizon)


def _plan(led, rows, slot_fracs):
    start = slot_fracs[0][0] * led.slot_duration
    end = (slot_fracs[-1][0] + 1) * led.slot_duration
    return TransferPlan(tuple(rows), start, end, tuple(slot_fracs))


def _check(mirror, led):
    mirror.sync()
    assert np.array_equal(mirror.host_view(), led.reserved)


def test_mirror_tracks_api_mutations():
    led = _ledger()
    mirror = led.device_mirror()
    rows = led.path_rows("H0", "H5")
    _check(mirror, led)  # initial upload

    p1 = _plan(led, rows, [(2, 0.5), (3, 0.25)])
    led.commit(p1)
    p2 = _plan(led, rows, [(4, 1.0)])  # scalar fast path
    led.commit(p2)
    _check(mirror, led)
    assert ts_plan_device.stats["mirror_cells"] > 0

    led.occupy(rows[:2], 6.0, 9.0, 0.25)
    _check(mirror, led)

    led.release(p1)
    _check(mirror, led)

    p3 = _plan(led, rows, [(5, 0.5), (6, 0.5), (7, 0.5)])
    led.commit(p3)
    led.release_after(p3, 6.0)
    _check(mirror, led)

    other = led.path_rows("H1", "H6")
    led.commit_batch(
        [_plan(led, other, [(8, 0.5)]), _plan(led, rows, [(9, 0.25)])]
    )
    _check(mirror, led)


def test_mirror_survives_growth_and_origin_shift():
    led = _ledger(16)
    mirror = led.device_mirror()
    rows = led.path_rows("H0", "H5")
    led.commit(_plan(led, rows, [(3, 0.5)]))
    _check(mirror, led)

    led.commit(_plan(led, rows, [(40, 0.5)]))  # grows the window
    _check(mirror, led)

    led.commit(_plan(led, rows, [(700, 0.25)]))  # beyond the 256 bucket
    _check(mirror, led)

    led.retire_to(39)  # partial retire: origin shift, no invalidation
    led.commit(_plan(led, rows, [(41, 0.125)]))
    _check(mirror, led)
    assert mirror.base == 39

    led.retire_to(2000)  # full-past: reset through the setter → re-upload
    up0 = ts_plan_device.stats["mirror_uploads"]
    _check(mirror, led)
    assert ts_plan_device.stats["mirror_uploads"] == up0 + 1


def test_mirror_invalidated_by_direct_assignment():
    led = _ledger()
    mirror = led.device_mirror()
    rows = led.path_rows("H0", "H5")
    led.commit(_plan(led, rows, [(2, 0.5)]))
    _check(mirror, led)
    snap = led.reserved.copy()
    led.commit(_plan(led, rows, [(3, 0.5)]))
    led.reserved = snap  # controller restore(): setter must invalidate
    _check(mirror, led)
    led.reserved[list(rows), 5] = 0.5  # out-of-contract direct write...
    led.mirror_invalidate()            # ...declared, as reroute's paths do
    _check(mirror, led)


def test_wave_and_col_scan_parity_through_mirror():
    led = _ledger()
    rng = np.random.default_rng(5)
    rows_a = led.path_rows("H0", "H5")
    rows_b = led.path_rows("H2", "H7")
    for s in range(12):
        led.commit(_plan(led, rows_a, [(s, float(rng.integers(1, 7)) / 8.0)]))
    pad = np.array([rows_a, rows_b, rows_b], dtype=np.intp)
    caps = np.array([100.0, 50.0, 100.0])
    sz = np.array([0, 2, 5], dtype=np.int64)
    t0c = np.array([0.0, 2.25, 5.0])
    sizes = np.array([120.0, 60.0, 0.0])
    first = np.array([1.0, 0.75, 1.0])
    w = 16
    ref = ts_plan.wave_scan_numpy(led, pad, caps, sz, t0c, sizes, w, first)
    got = ts_plan_device.wave_scan(led, pad, caps, sz, t0c, sizes, w, first)
    for name, r, g in zip(("resid", "bw", "cum", "hit", "end"), ref, got):
        assert np.array_equal(np.asarray(r), np.asarray(g)), name

    cols = np.array(
        [[0, 1, 5, 9, 13], [2, 3, 4, 8, 20], [5, 6, 7, 30, 31]],
        dtype=np.int64,
    )
    secs = np.ones((3, 5))
    booked = led.reserved[pad[:, :, None], (cols - led.base_slot)[:, None, :]]
    ref = ts_plan.plan_scan_numpy(booked, caps, secs, sizes + 1.0)
    got = ts_plan_device.col_scan(led, pad, cols, caps, secs, sizes + 1.0)
    _assert_same(ref, got)


# -- persistent compile cache placement --------------------------------------


@pytest.mark.parametrize("preset", [None, "elsewhere"])
def test_compile_cache_placement(preset, tmp_path):
    """An explicitly configured cache directory (``JAX_COMPILATION_CACHE_DIR``
    lands in this config value) is kept; otherwise the fixed repo-root
    ``.jax_cache`` is used.  Either way every entry is kept."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    want = str(tmp_path / preset) if preset else str(ts_plan_device.CACHE_DIR)
    try:
        jax.config.update(keys[0], want if preset else None)
        ts_plan_device.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert ts_plan_device.CACHE_DIR.parent == Path(__file__).resolve().parents[1]


# -- auto rule ---------------------------------------------------------------


def test_auto_rule_resolution(monkeypatch):
    monkeypatch.setattr(ts_plan, "_backend", "auto")
    # The first call resolves the rule, whatever its size: on an
    # accelerator every call plans on the device.
    monkeypatch.setattr(ts_plan, "_auto", None)
    assert ts_plan._use_device(1) == (ts_plan_device.platform() != "cpu")
    assert ts_plan._auto is not None
    # On CPU the resolved answer is numpy...
    if ts_plan_device.platform() == "cpu":
        assert not ts_plan._use_device(1 << 20)
        assert ts_plan._auto == (False, 0)
        # ...unless REPRO_TS_PLAN_AUTO_CELLS opts big calls in.
        monkeypatch.setenv("REPRO_TS_PLAN_AUTO_CELLS", "100000")
        monkeypatch.setattr(ts_plan, "_auto", None)
        assert ts_plan._use_device(1 << 20)
        assert not ts_plan._use_device(50_000)
    # Forced backends bypass the probe entirely.
    monkeypatch.setattr(ts_plan, "_backend", "numpy")
    assert not ts_plan._use_device(1 << 30)
    monkeypatch.setattr(ts_plan, "_backend", "pallas")
    assert ts_plan._use_device(1)


def test_auto_answers_numpy_only_without_jax(monkeypatch):
    """A missing jax is the one reason ``auto`` plans on numpy; any other
    failure to start the device is raised, never swallowed."""
    real = ts_plan.importlib.util.find_spec
    monkeypatch.setattr(
        ts_plan.importlib.util, "find_spec",
        lambda name, *a: None if name == "jax" else real(name, *a),
    )
    assert ts_plan._resolve_auto() == (False, 0)
    monkeypatch.setattr(ts_plan.importlib.util, "find_spec", real)

    def broken():
        raise RuntimeError("backend failed to start")

    monkeypatch.setattr(ts_plan_device, "platform", broken)
    with pytest.raises(RuntimeError, match="failed to start"):
        ts_plan._resolve_auto()
