"""Device-side fused planning pipelines and the ledger mirror (jax).

This module is the implementation behind ``ts_plan``'s device backend —
it is only ever imported lazily, from inside ``ts_plan`` entry points or
directly by device-contract tests, so the numpy scheduling path never
pays the jax import.

Three layers live here:

* **Compile cache** (:func:`_cached`): every jitted pipeline is built
  once per *shape bucket* — candidate counts round up to the next power
  of two (≥ 8), window widths arrive already exact (the engines escalate
  in powers of 4) — and reused for the rest of the process.  ``stats``
  counts built buckets (``traces``) vs reuses (``cache_hits``);
  ``bench_sched_scale`` reports the hit rate.  Across processes, jax's
  persistent compilation cache keeps the compiled buckets on an
  accelerator (:func:`platform` places it).

* **Fused binary64 pipelines on bit patterns**: residue → bandwidth →
  sequential-scan cumsum → hit count, fed by the wavefront's mirror
  gather (:func:`wave_scan`) or the reroute compressed-column gather
  (:func:`col_scan`), plus the per-wave winner selection
  (:func:`wave_select`).  Values travel as ``uint64`` float64 bit
  patterns and the arithmetic is the integer round-to-nearest-even
  routines below; the cumsum is a ``lax.scan`` in numpy's order.  Every
  output is **bit-identical to the numpy reference on any non-negative
  float64 input**, on every platform — the TPU's own f64 is not
  binary64, so ``chip_smoke.py`` holds the chip's schedules
  byte-identical to the numpy reference.  The mirror array is donated
  (``donate_argnums``) only by the operations that consume it
  (reindex/scatter), never by gathers.

* **Ledger mirror** (:class:`DeviceMirror`): a device-resident copy of
  ``TimeSlotLedger.reserved`` kept in step by a journal of cell writes
  (the ledger's mutators call ``note_*`` with *final* cell values), so
  per-wave gathers read device memory instead of re-uploading the
  window.  See DESIGN.md §8 for the sync/invalidation contract.

The float32 Pallas kernel (:func:`pallas_scan`) is off the planning path:
it is reachable only through ``ts_plan.plan_scan_pallas``, agrees with
numpy only on float64-safe inputs, and its whole-window block exceeds
the TPU's VMEM at windows of 16,384 slots and more.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import default_registry
from . import ts_plan

EPS = ts_plan.EPS

#: Built buckets / reuses of the compile cache, plus mirror traffic.
#: A live ``repro.obs`` counter group in the process-wide registry —
#: dict-style access (`stats["traces"] += 1`, iteration, ``dict(stats)``)
#: is unchanged from the plain dict it replaced.
stats = default_registry().group(
    "ts_plan_device",
    ("traces", "cache_hits", "mirror_syncs", "mirror_cells", "mirror_uploads"),
)

#: Default home of jax's persistent compilation cache: a fixed path,
#: because the directory is part of what a later run must find again.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_cache: dict = {}
_platform: Optional[str] = None
_mirror_flag: Optional[bool] = None


def place_compile_cache() -> None:
    """Keep compiled buckets across processes: where no cache directory
    was configured (jax reads ``JAX_COMPILATION_CACHE_DIR`` itself), use
    :data:`CACHE_DIR`; and keep every entry, since the per-bucket
    programs compile faster than jax's default one-second floor."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def platform() -> str:
    """The default jax platform, resolved once per process.  Off the CPU
    the first call also places the persistent compile cache, before any
    program compiles; XLA:CPU programs are cheap to rebuild and are not
    kept."""
    global _platform
    if _platform is None:
        _platform = jax.default_backend()
        if _platform != "cpu":
            place_compile_cache()
    return _platform


def set_mirror(value: Optional[bool]) -> None:
    """Force the ledger mirror on/off (``None`` = re-derive from
    ``REPRO_TS_PLAN_MIRROR`` / platform)."""
    global _mirror_flag
    _mirror_flag = value


def mirror_enabled() -> bool:
    if _mirror_flag is not None:
        return _mirror_flag
    env = os.environ.get("REPRO_TS_PLAN_MIRROR")
    if env is not None:
        return env not in ("", "0")
    # On CPU a device_put is a real copy, so the mirror only pays off
    # where device memory is actually separate (and gathers are fast).
    return platform() != "cpu"


def reset_cache() -> None:
    """Drop compiled buckets and zero the counters (tests/benchmarks)."""
    _cache.clear()
    for k in stats:
        stats[k] = 0


def _cached(key, build):
    fn = _cache.get(key)
    if fn is None:
        fn = _cache[key] = build()
        stats["traces"] += 1
    else:
        stats["cache_hits"] += 1
    return fn


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


# -- exact binary64 arithmetic on bit patterns ------------------------------
#
# The TPU has no float64 unit: XLA splits an f64 into a pair of f32s, which
# cannot hold every 53-bit mantissa, so neither f64 storage nor f64
# arithmetic is IEEE binary64 there.  The device therefore never holds a
# float64: every value travels as its uint64 bit pattern (exact under
# integer emulation on every platform) and the pipelines compute with the
# integer routines below — round-to-nearest-even add, subtract and
# multiply of non-negative finite operands, the only values the ledger
# path carries (reserved fractions, capacities, seconds, sizes, times).
# For non-negative values the bit order is the value order, so max, min
# and comparisons act on the patterns directly.

_MANT = (1 << 52) - 1
_HIDDEN = 1 << 52
_ONE = 0x3FF0000000000000
_INF = 0x7FF0000000000000


def _bits(x, shape=None) -> np.ndarray:
    """Float64 values → IEEE-754 bit patterns (uint64), zero-padded to
    ``shape`` (a zero pattern is +0.0)."""
    b = np.asarray(x, np.float64).view(np.uint64)
    return b if shape is None else ts_plan._pad_to(b, shape)


def _floats(a, n: int) -> np.ndarray:
    """Device bit patterns → the first ``n`` rows as float64."""
    return np.asarray(a)[:n].view(np.float64)


def _unpack(x):
    """Bit patterns → ``(m, e)`` with value ``m * 2**(e - 1075)``;
    subnormals and zero carry ``e = 1`` and no hidden bit."""
    e = (x >> 52).astype(jnp.int64)
    f = x & _MANT
    return jnp.where(e == 0, f, f | _HIDDEN), jnp.maximum(e, 1)


def _shr_jam(x, n):
    """``x >> n`` with every shifted-out bit OR-ed into bit 0, so that
    rounding still sees an inexact tail (``n`` is clipped to [0, 63])."""
    n = jnp.clip(n, 0, 63).astype(jnp.uint64)
    lost = (x & ((jnp.uint64(1) << n) - 1)) != 0
    return (x >> n) | lost.astype(jnp.uint64)


def _round_pack(m3, e):
    """Round ``m3`` (mantissa with 3 guard bits, leading bit at most 55)
    at exponent ``e >= 1`` to nearest-even and pack it.  A rounding carry
    out of the mantissa lands in the exponent field; overflow gives inf."""
    low = m3 & 7
    m = m3 >> 3
    up = (low > 4) | ((low == 4) & ((m & 1) == 1))
    bits = ((e - 1).astype(jnp.uint64) << 52) + m + up.astype(jnp.uint64)
    return jnp.minimum(bits, _INF)


def _add(a, b):
    """Binary64 ``a + b`` for non-negative operands."""
    mh, eh = _unpack(jnp.maximum(a, b))
    ml, el = _unpack(jnp.minimum(a, b))
    s = (mh << 3) + _shr_jam(ml << 3, eh - el)
    carry = (s >> 56).astype(jnp.int64)
    s = jnp.where(carry == 1, _shr_jam(s, 1), s)
    return _round_pack(s, eh + carry)


def _sub(a, b):
    """Binary64 ``a - b`` for ``a >= b >= 0``."""
    ma, ea = _unpack(a)
    mb, eb = _unpack(b)
    s = (ma << 3) - _shr_jam(mb << 3, ea - eb)
    # Renormalize so bit 55 leads, stopping at the subnormal exponent.
    sh = jnp.minimum(lax.clz(s).astype(jnp.int64) - 8, ea - 1)
    s = s << sh.astype(jnp.uint64)
    return jnp.where(s == 0, jnp.uint64(0), _round_pack(s, ea - sh))


def _mul(a, b):
    """Binary64 ``a * b`` for non-negative operands: the 106-bit mantissa
    product from 32-bit limbs, then one rounding."""
    ma, ea = _unpack(a)
    mb, eb = _unpack(b)
    za = lax.clz(ma).astype(jnp.int64) - 11  # subnormal operands: normalize
    zb = lax.clz(mb).astype(jnp.int64) - 11
    ma, ea = ma << za.astype(jnp.uint64), ea - za
    mb, eb = mb << zb.astype(jnp.uint64), eb - zb
    al, ah = ma & 0xFFFFFFFF, ma >> 32
    bl, bh = mb & 0xFFFFFFFF, mb >> 32
    ll = al * bl
    mid = al * bh + ah * bl
    lo = ll + (mid << 32)
    hi = ah * bh + (mid >> 32) + (lo < ll).astype(jnp.uint64)
    top = (hi >> 41).astype(jnp.int64)  # product leads at bit 104 + top
    k = 49 + top
    s = (hi << (64 - k).astype(jnp.uint64)) | _shr_jam(lo, k)
    e = ea + eb - 1023 + top
    s = jnp.where(e < 1, _shr_jam(s, 1 - e), s)
    out = _round_pack(s, jnp.maximum(e, 1))
    return jnp.where((ma == 0) | (mb == 0), jnp.uint64(0), out)


def _seq_cumsum(d):
    """Sequential inclusive cumsum along axis 1 — numpy's order, so the
    sums are bit-identical (``jnp.cumsum`` reduces in tree order)."""
    def step(c, x):
        c = _add(c, x)
        return c, c

    _, cum = lax.scan(step, jnp.zeros(d.shape[0], d.dtype), d.T)
    return cum.T


# -- fused scan pipelines (bit patterns in, bit patterns out) ----------------


def _scan_tail(bk, cp, sc, tgt, cap, has_cap):
    """residue → bandwidth → cumsum → hit count; ``tgt`` holds the
    patterns of ``max(size - EPS, 0)``, since no cumsum is below zero."""
    resid = _sub(jnp.uint64(_ONE), jnp.max(bk, axis=1))
    bw = _mul(resid, cp[:, None])
    if has_cap:
        bw = jnp.minimum(bw, cap)
    cum = _seq_cumsum(_mul(bw, sc))
    hit = jnp.sum(cum < tgt[:, None], axis=1)
    return resid, bw, cum, hit


def _donate():
    # Donating the consumed mirror saves an allocation on a real device;
    # on CPU jax cannot use np-backed donations and warns.
    return (0,) if platform() != "cpu" else ()


def _build_scan(NP, L, W, has_cap):
    def f(bk, cp, sc, tgt, cap):
        return _scan_tail(bk, cp, sc, tgt, cap, has_cap)

    return jax.jit(f)


def _build_wave_mirror(NP, WL, W, Wb, dur):
    def f(M, padp, off, cp, fs, tgt):
        iota = jnp.arange(W)
        bk = M[padp[:, :, None], off[:, None, None] + iota[None, None, :]]
        sc = jnp.full((NP, W), dur, jnp.uint64).at[:, 0].set(fs)
        return _scan_tail(bk, cp, sc, tgt, None, False)

    return jax.jit(f)  # M is the live mirror: never donated by gathers


def _build_col(NP, WL, Wm, Wb):
    def f(M, padp, colp, cp, sc, tgt):
        bk = M[padp[:, :, None], colp[:, None, :]]
        return _scan_tail(bk, cp, sc, tgt, None, False)

    return jax.jit(f)


def _build_select(NC, NS):
    def f(end, rank, seg):
        emin = jax.ops.segment_min(
            end, seg, num_segments=NS + 1, indices_are_sorted=True
        )
        tie = end == emin[seg]
        big = jnp.iinfo(rank.dtype).max
        rmin = jax.ops.segment_min(
            jnp.where(tie, rank, big),
            seg,
            num_segments=NS + 1,
            indices_are_sorted=True,
        )
        pos = jnp.arange(NC)
        cand = jnp.where(tie & (rank == rmin[seg]), pos, NC)
        return jax.ops.segment_min(
            cand, seg, num_segments=NS + 1, indices_are_sorted=True
        )[:NS]

    return jax.jit(f)


def _targets(sizes, shape):
    return _bits(np.maximum(np.asarray(sizes, np.float64) - EPS, 0.0), shape)


def _scan_out(outs, n):
    resid, bw, cum, hit = outs
    return (
        _floats(resid, n), _floats(bw, n), _floats(cum, n),
        np.asarray(hit)[:n],
    )


def plan_scan(booked, caps, secs, sizes, bandwidth_cap=None, overlay=None):
    """Fused device scan; bit-identical to ``plan_scan_numpy``."""
    if overlay is not None:
        booked = np.maximum(booked, overlay)
    n, L, W = booked.shape
    NP = _bucket(n)
    has_cap = bandwidth_cap is not None
    fn = _cached(
        ("scan", NP, L, W, has_cap), lambda: _build_scan(NP, L, W, has_cap)
    )
    cap = _bits(0.0 if bandwidth_cap is None else float(bandwidth_cap))
    with jax.enable_x64(True):
        return _scan_out(
            fn(
                _bits(booked, (NP, L, W)), _bits(caps, (NP,)),
                _bits(secs, (NP, W)), _targets(sizes, (NP,)), cap,
            ),
            n,
        )


def wave_scan(ledger, pad, caps, sz, t0c, sizes, w, first_secs):
    """Device wave pipeline: mirror gather (when live) → scan, one fused
    jit call per shape bucket; the O(n) plan-end extraction (one divide
    per candidate) runs on the host, exactly as the reference does."""
    dur = float(ledger.slot_duration)
    mir = _mirror_for(ledger)
    if mir is None:
        booked = ledger.booked_window(pad, sz, w)
        secs = np.full((len(caps), w), dur)
        secs[:, 0] = first_secs
        resid, bw, cum, hit = plan_scan(booked, caps, secs, sizes)
    else:
        n, wl = pad.shape
        NP = _bucket(n)
        padp = ts_plan._pad_to(np.asarray(pad, np.int64), (NP, wl))
        szp = ts_plan._pad_to(np.asarray(sz, np.int64), (NP,))
        ledger._ensure(int(szp.max()) + w - 1)
        mir.sync()
        off = np.maximum(szp - mir.base, 0)  # pad rows clamp to in-bounds
        durb = int(_bits(dur))
        fn = _cached(
            ("wave_m", NP, wl, w, mir.width, durb),
            lambda: _build_wave_mirror(NP, wl, w, mir.width, durb),
        )
        with jax.enable_x64(True):
            resid, bw, cum, hit = _scan_out(
                fn(
                    mir.arr, padp, off, _bits(caps, (NP,)),
                    _bits(first_secs, (NP,)), _targets(sizes, (NP,)),
                ),
                n,
            )
    end = ts_plan._extract_end(dur, t0c, sizes, sz, cum, bw, hit, w)
    return resid, bw, cum, hit, end


def col_scan(ledger, pad, cols, caps, secs, sizes):
    """Device compressed-column round for the reroute engine."""
    mir = _mirror_for(ledger)
    if mir is None:
        booked = ledger.reserved[
            pad[:, :, None], (cols - ledger.base_slot)[:, None, :]
        ]
        return plan_scan(booked, caps, secs, sizes)
    n, wl = pad.shape
    m = cols.shape[1]
    ledger._ensure(int(cols.max()))
    mir.sync()
    NP = _bucket(n)
    padp = ts_plan._pad_to(np.asarray(pad, np.int64), (NP, wl))
    colp = ts_plan._pad_to(np.asarray(cols - mir.base, np.int64), (NP, m))
    fn = _cached(
        ("col", NP, wl, m, mir.width), lambda: _build_col(NP, wl, m, mir.width)
    )
    with jax.enable_x64(True):
        return _scan_out(
            fn(
                mir.arr, padp, colp, _bits(caps, (NP,)),
                _bits(secs, (NP, m)), _targets(sizes, (NP,)),
            ),
            n,
        )


def wave_select(
    end: np.ndarray, rank: np.ndarray, counts: Sequence[int]
) -> np.ndarray:
    """Fused per-segment argmin of ``(end, rank)`` — three sorted
    ``segment_min`` passes (min end; min rank among exact end ties; the
    unique position carrying both minima) over the ends' bit patterns
    (ends are non-negative or inf).  Exactly the host loop: pattern
    equality is float equality and ranks are unique within a segment."""
    nc = len(end)
    ns = len(counts)
    NC = _bucket(nc)
    NS = _bucket(ns)
    seg = np.full(NC, NS, np.int64)
    seg[:nc] = np.repeat(np.arange(ns, dtype=np.int64), counts)
    ep = np.full(NC, _INF, np.uint64)
    ep[:nc] = _bits(end)
    rp = np.full(NC, np.iinfo(np.int64).max, np.int64)
    rp[:nc] = rank
    fn = _cached(("sel", NC, NS), lambda: _build_select(NC, NS))
    with jax.enable_x64(True):
        win = np.asarray(fn(ep, rp, seg))[:ns]
    starts = np.zeros(ns, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    return win - starts


# -- Pallas kernel (float32), compile-cached --------------------------------


def _build_pallas(NP, LP, WP, W, cap, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BN = 8

    def kernel(bk_ref, cp_ref, sc_ref, sz_ref, resid_ref, bw_ref, cum_ref, hit_ref):
        resid = 1.0 - jnp.max(bk_ref[...], axis=1)
        bw = resid * cp_ref[...]
        if cap is not None:
            bw = jnp.minimum(bw, cap)
        cum = bw * sc_ref[...]
        k = 1
        while k < WP:  # Hillis–Steele inclusive prefix sum along the lanes
            shifted = jnp.concatenate(
                [jnp.zeros((BN, k), jnp.float32), cum[:, : WP - k]], axis=1
            )
            cum = cum + shifted
            k *= 2
        lane = jax.lax.broadcasted_iota(jnp.int32, (BN, WP), 1)
        below = (cum < (sz_ref[...] - np.float32(EPS))) & (lane < W)
        resid_ref[...] = resid
        bw_ref[...] = bw
        cum_ref[...] = cum
        hit_ref[...] = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)

    call = pl.pallas_call(
        kernel,
        grid=(NP // BN,),
        in_specs=[
            pl.BlockSpec((BN, LP, WP), lambda i: (i, 0, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, WP), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, WP), lambda i: (i, 0)),
            pl.BlockSpec((BN, WP), lambda i: (i, 0)),
            pl.BlockSpec((BN, WP), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((NP, WP), jnp.float32),
            jax.ShapeDtypeStruct((NP, WP), jnp.float32),
            jax.ShapeDtypeStruct((NP, WP), jnp.float32),
            jax.ShapeDtypeStruct((NP, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    # jit so each bucket traces once (interpret mode re-runs the python
    # kernel body per call otherwise — the dominant per-call cost).
    return jax.jit(call)


def pallas_scan(booked, caps, secs, sizes, bandwidth_cap, interpret):
    """Padded, compile-cached entry behind ``ts_plan.plan_scan_pallas``.
    ``bandwidth_cap`` is baked into the kernel body as a static constant,
    so its value is part of the cache key."""
    n, L, W = booked.shape
    BN, LP = 8, max(8, L)
    WP = max(128, -(-W // 128) * 128)
    NP = -(-n // BN) * BN
    bk = ts_plan._pad_to(np.asarray(booked, np.float32), (NP, LP, WP))
    cp = ts_plan._pad_to(np.asarray(caps, np.float32)[:, None], (NP, 1))
    sc = ts_plan._pad_to(np.asarray(secs, np.float32), (NP, WP))
    sz = ts_plan._pad_to(np.asarray(sizes, np.float32)[:, None], (NP, 1))
    cap = None if bandwidth_cap is None else float(bandwidth_cap)
    fn = _cached(
        ("pallas", NP, LP, WP, W, cap, bool(interpret)),
        lambda: _build_pallas(NP, LP, WP, W, cap, interpret),
    )
    resid, bw, cum, hit = fn(bk, cp, sc, sz)
    return (
        np.asarray(resid)[:n, :W],
        np.asarray(bw)[:n, :W],
        np.asarray(cum)[:n, :W],
        np.asarray(hit)[:n, 0],
    )


# -- device-resident ledger mirror ------------------------------------------


def _build_reindex(Win, Wb):
    def f(a, drop):
        return jnp.take(
            a, drop + jnp.arange(Wb), axis=1, mode="fill", fill_value=0
        )

    # The old mirror array is consumed here: donate it (off-CPU) when the
    # width is unchanged, the only case its buffer can be reused.
    return jax.jit(f, donate_argnums=_donate() if Win == Wb else ())


def _build_scatter(Wb, K):
    def f(a, r, c, v):
        return a.at[r, c].set(v, mode="drop")

    # The old mirror array is consumed here: donate it (off-CPU).
    return jax.jit(f, donate_argnums=_donate())


def _mirror_for(ledger):
    if not mirror_enabled():
        return None
    return ledger.device_mirror()


class DeviceMirror:
    """Device-resident copy of a ledger's live ``reserved`` window, held
    as float64 bit patterns (uint64) like every device-side value.

    The ledger's mutators journal every cell write (``note_flat`` /
    ``note_grid``) with the *final* post-clamp value; :meth:`sync` folds
    the journal into the device array with one keep-last dedup and one
    donated scatter, re-basing for origin shifts (DESIGN.md §7) with a
    donated ``take``.  Direct writes that bypass the mutators must call
    :meth:`invalidate` (``TimeSlotLedger.mirror_invalidate``) — the next
    sync then re-uploads the full window.  See DESIGN.md §8.
    """

    def __init__(self, ledger):
        self._ledger = ledger
        self._arr = None
        self._base = 0
        self._width = 0  # device width (pow-2 bucket of the ledger width)
        self._rows: list = []
        self._slots: list = []
        self._vals: list = []
        self._cells = 0
        self._stale = True

    @property
    def base(self) -> int:
        return self._base

    @property
    def width(self) -> int:
        return self._width

    @property
    def arr(self):
        return self._arr

    # -- journal hooks (ledger mutators; slots are absolute) ----------------
    def note_flat(self, rows, slots, vals) -> None:
        if self._stale:
            return
        rows = np.asarray(rows, np.int64).ravel()
        self._rows.append(rows)
        self._slots.append(np.asarray(slots, np.int64).ravel())
        self._vals.append(np.asarray(vals, np.float64).ravel())
        self._cells += rows.size
        # Pressure valve: past a quarter of the window, one upload is
        # cheaper than the journal bookkeeping.
        if self._cells * 4 > self._ledger.reserved.size:
            self.invalidate()

    def note_grid(self, rows, slots, vals) -> None:
        """An outer-product write: ``reserved[rows][:, slots] = vals``
        with ``vals`` of shape ``[len(rows), len(slots)]``."""
        if self._stale:
            return
        rows = np.asarray(rows, np.int64).ravel()
        slots = np.asarray(slots, np.int64).ravel()
        self.note_flat(
            np.repeat(rows, slots.size),
            np.tile(slots, rows.size),
            np.asarray(vals, np.float64).ravel(),
        )

    def invalidate(self) -> None:
        self._rows.clear()
        self._slots.clear()
        self._vals.clear()
        self._cells = 0
        self._stale = True

    # -- sync ---------------------------------------------------------------
    def sync(self) -> None:
        """Bring the device window up to date with the ledger (journal
        replay, or full re-upload after invalidation / shrink)."""
        led = self._ledger
        res = led.reserved
        nrows, W = res.shape
        base = led.base_slot
        Wb = _bucket(W, 256)
        stats["mirror_syncs"] += 1
        if (
            self._stale
            or self._arr is None
            or Wb < self._width
            or self._arr.shape[0] != nrows
            or base < self._base
        ):
            self._upload(res, Wb, base)
            return
        arr = self._arr
        if base != self._base or Wb != self._width:
            drop = base - self._base
            fn = _cached(
                ("reidx", self._width, Wb),
                lambda: _build_reindex(self._width, Wb),
            )
            with jax.enable_x64(True):
                arr = fn(arr, np.int64(drop))
        if self._rows:
            rows = np.concatenate(self._rows)
            cc = np.concatenate(self._slots) - base
            vals = np.concatenate(self._vals)
            keep = cc >= 0  # retired cells fell off the window
            if not keep.all():
                rows, cc, vals = rows[keep], cc[keep], vals[keep]
            if rows.size:
                # Keep-last dedup: the journal holds final values, so the
                # latest note for a cell wins.
                keys = rows * np.int64(Wb) + cc
                _u, idx = np.unique(keys[::-1], return_index=True)
                sel = keys.size - 1 - idx
                K = _bucket(sel.size, 64)
                rp = np.zeros(K, np.int64)
                cp = np.full(K, Wb, np.int64)  # pad cols drop in-scatter
                vp = np.zeros(K, np.uint64)
                rp[: sel.size] = rows[sel]
                cp[: sel.size] = cc[sel]
                vp[: sel.size] = _bits(vals[sel])
                fn = _cached(
                    ("scat", Wb, K), lambda: _build_scatter(Wb, K)
                )
                with jax.enable_x64(True):
                    arr = fn(arr, rp, cp, vp)
                stats["mirror_cells"] += int(sel.size)
            self._rows.clear()
            self._slots.clear()
            self._vals.clear()
            self._cells = 0
        self._arr = arr
        self._base = base
        self._width = Wb

    def _upload(self, res, Wb, base) -> None:
        buf = np.zeros((res.shape[0], Wb), np.uint64)
        buf[:, : res.shape[1]] = _bits(res)
        with jax.enable_x64(True):
            self._arr = jax.device_put(buf)
        self._base = base
        self._width = Wb
        self._rows.clear()
        self._slots.clear()
        self._vals.clear()
        self._cells = 0
        self._stale = False
        stats["mirror_uploads"] += 1

    def host_view(self) -> np.ndarray:
        """Host copy of the device window, trimmed to the ledger width
        (test hook: must equal ``ledger.reserved`` after ``sync``)."""
        W = self._ledger.reserved.shape[1]
        return np.asarray(self._arr)[:, :W].view(np.float64)
