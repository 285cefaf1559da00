"""Time-slot planning scan — the ledger/wavefront inner kernel.

The greedy paper-policy transfer plan (``TimeSlotLedger.plan_transfer``)
reduces, per candidate path, to a fixed four-step scan over a slot window:

1. **residue**   ``resid = 1 - max over path links of booked``  (path residue
   per slot — the "cummax" over the link axis of the gathered window),
2. **bandwidth** ``bw = resid * bottleneck_capacity`` (optionally capped),
3. **cumsum**    ``cum = cumsum(bw * secs)`` (cumulative deliverable,
   first slot possibly partial),
4. **searchsorted** ``hit = #{j : cum[j] < size - EPS}`` (first slot at
   which the transfer completes; ``hit == W`` means "does not fit").

:func:`plan_scan` runs that scan for *every* candidate in one array pass
over a ``[n_cand, n_links_padded, window]`` gather of the ledger; the
fused entry points :func:`wave_scan` (gather → scan → plan-end extraction
→ winner selection, the wavefront engine's per-wave pipeline) and
:func:`col_scan` (compressed-column gather → scan, the reroute engine's
escalation rounds) extend the same contract to whole pipelines.

**Backends.**

* ``numpy`` (the **reference**): bit-identical to a ``plan_transfer``
  loop — ``repro.core`` relies on this for the paper-semantics guarantee,
  and every other backend is property-tested against it.
* ``pallas`` (the **device** backend, forced): a shape-bucketed,
  compile-cached jax pipeline (``ts_plan_device``) with the
  device-resident ledger mirror, the same on every platform.  XLA's
  float64 on the TPU is a pair of float32s and is not binary64, so the
  device holds every value as its float64 bit pattern and computes with
  integer round-to-nearest-even add/sub/mul (``lax.scan`` sequential
  cumsum); the one divide per candidate (the plan end) runs on the host.
  The result is **bit-identical to numpy on any non-negative input**,
  on the CPU and on the chip (``chip_smoke.py``).
* ``auto`` (the **default**): resolves once, at the first planning call
  of the process.  When a non-CPU jax backend is present the device
  pipeline plans every call; on CPU the reference numpy kernel stays
  (XLA-on-one-socket cannot beat it), unless
  ``REPRO_TS_PLAN_AUTO_CELLS=<n>`` opts calls of ≥ n cells in.  Only a
  missing jax makes ``auto`` answer through numpy; any other failure to
  start the device raises.  ``pallas`` raises at first use without jax.

:func:`plan_scan_pallas` is the float32 Pallas kernel (Hillis–Steele
prefix sum).  It is on no planning path: it agrees with numpy bit-wise
only on *float64-safe* inputs (dyadic fractions of moderate magnitude,
pow-2 capacities, integer sizes), and its whole-window block exceeds the
TPU's VMEM at windows of 16,384 slots and more.  Off-TPU it runs in
interpret mode.

Select with ``set_backend(...)`` or ``REPRO_TS_PLAN_BACKEND=...``.
``REPRO_TS_PLAN_MIRROR=1/0`` forces the device-resident ledger mirror on
or off (default: on for non-CPU platforms — see DESIGN.md §8).

Both backends are **origin-free**: ``booked`` arrives as an already-
gathered window (or absolute slots translated against ``base_slot`` right
at the gather), so the rolling-horizon coordinate map (DESIGN.md §7) is
applied entirely by the callers, and a compacted ledger feeds
bit-identical windows to either backend.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Optional, Sequence, Tuple

import numpy as np

EPS = 1e-9  # must equal repro.core.timeslot._EPS


def _hit_count(cum: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """``hit[k] = #{j : cum[k, j] < sizes[k] - EPS}`` — searchsorted-left
    on each row.  Rows are nondecreasing by construction (reserved
    fractions ≤ 1 ⇒ ``bw ≥ 0``, and ``secs ≥ 0``), so a per-row binary
    search returns the identical count; it wins when the batch is a few
    long rows (escalated windows), while the vectorized count wins when
    the batch is wide and the rows short (wave scans).  A regression test
    pins the two bit-identical on both regimes."""
    n, w = cum.shape
    targets = sizes - EPS
    if n * 8 <= w:
        out = np.empty(n, dtype=np.int64)
        for k in range(n):
            out[k] = np.searchsorted(cum[k], targets[k])
        return out
    return (cum < targets[:, None]).sum(axis=1)


def plan_scan_numpy(
    booked: np.ndarray,        # [n_cand, L, W] reserved fractions (gathered)
    caps: np.ndarray,          # [n_cand] bottleneck capacity per candidate
    secs: np.ndarray,          # [n_cand, W] usable seconds per slot
    sizes: np.ndarray,         # [n_cand] bytes (capacity-units·sec) to move
    bandwidth_cap: Optional[float] = None,
    overlay: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Reference scan; row ``k`` is bit-identical to ``plan_transfer`` run
    on candidate ``k`` alone (same expressions, numpy ``cumsum`` is a
    sequential accumulation per row).

    ``overlay`` (same shape as ``booked``, or broadcastable) is an extra
    reserved-fraction layer folded in as an elementwise max — a masked
    scan for callers that want cells priced as busier than the ledger
    records without mutating it (liveness masks, what-if overlays).
    ``max`` is exact in floating point, so an overlay of 0/1 cells
    reproduces the overlaid ledger bit-for-bit.  (The reroute engine
    ultimately prices its phantom-full view by *enumerating* only
    owner-clean columns — see ``core/reroute.py`` — so nothing in the
    scheduling core depends on this parameter; it is contract-tested on
    both backends.)
    """
    if overlay is not None:
        booked = np.maximum(booked, overlay)
    resid = 1.0 - booked.max(axis=1)
    bw = resid * caps[:, None]
    if bandwidth_cap is not None:
        bw = np.minimum(bw, bandwidth_cap)
    cum = np.cumsum(bw * secs, axis=1)
    hit = _hit_count(cum, sizes)
    return resid, bw, cum, hit


def _pad_to(x: np.ndarray, shape) -> np.ndarray:
    if tuple(x.shape) == tuple(shape):
        return x  # already aligned: no copy
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    return np.pad(x, pads)


def plan_scan_pallas(
    booked: np.ndarray,
    caps: np.ndarray,
    secs: np.ndarray,
    sizes: np.ndarray,
    bandwidth_cap: Optional[float] = None,
    overlay: Optional[np.ndarray] = None,
    interpret: Optional[bool] = None,
):
    """Pallas-TPU kernel (float32).  Agrees with :func:`plan_scan_numpy`
    bit-wise on float64-safe inputs (module docstring); lazy jax import so
    the numpy scheduling path never touches jax.  Each padded
    ``(NP, LP, WP)`` shape bucket lowers and compiles **once** (the
    ``ts_plan_device`` compile cache); ``interpret`` defaults to on
    exactly when the platform is not a TPU.  The ``overlay`` layer is
    folded in on the host (one exact elementwise max) — it feeds the same
    padded gather, so the kernel body is unchanged."""
    from . import ts_plan_device

    if interpret is None:
        interpret = ts_plan_device.platform() != "tpu"
    if overlay is not None:
        booked = np.maximum(booked, overlay)
    return ts_plan_device.pallas_scan(
        booked, caps, secs, sizes, bandwidth_cap, interpret
    )


# -- backend selection -------------------------------------------------------

_VALID_BACKENDS = ("numpy", "pallas", "auto")
_backend = os.environ.get("REPRO_TS_PLAN_BACKEND", "auto")

#: ``auto``'s resolution, made at the first planning call of the process
#: (importing ``repro.kernels`` alone never imports jax).
_auto: Optional[Tuple[bool, int]] = None  # (use device?, min cells)


def set_backend(name: str) -> None:
    if name not in _VALID_BACKENDS:
        raise ValueError(
            f"unknown ts_plan backend {name!r} (want {sorted(_VALID_BACKENDS)})"
        )
    global _backend
    _backend = name


def get_backend() -> str:
    return _backend


def _resolve_auto() -> Tuple[bool, int]:
    if importlib.util.find_spec("jax") is None:
        return (False, 0)  # no jax installed: auto answers through numpy
    from . import ts_plan_device

    if ts_plan_device.platform() != "cpu":
        return (True, 0)
    env = os.environ.get("REPRO_TS_PLAN_AUTO_CELLS")
    if env:
        return (True, int(env))
    # XLA on the host CPU cannot beat the numpy kernel it would stand in
    # for: the reference stays the default off-accelerator.
    return (False, 0)


def _use_device(cells: int) -> bool:
    if _backend == "numpy":
        return False
    if _backend == "pallas":
        return True
    global _auto
    if _auto is None:
        _auto = _resolve_auto()
    dev, floor = _auto
    return dev and cells >= floor


def device_stats() -> dict:
    """Compile-cache / mirror counters of the device backend (empty when
    it was never engaged) — reported by ``bench_sched_scale``."""
    import sys

    mod = sys.modules.get(__package__ + ".ts_plan_device")
    return dict(mod.stats) if mod is not None else {}


def plan_scan(booked, caps, secs, sizes, bandwidth_cap=None, overlay=None):
    """Dispatch to the selected backend (module docstring: the auto rule)."""
    if _use_device(booked.size):
        from . import ts_plan_device

        return ts_plan_device.plan_scan(
            booked, caps, secs, sizes, bandwidth_cap, overlay
        )
    return plan_scan_numpy(booked, caps, secs, sizes, bandwidth_cap, overlay)


# -- fused pipelines ---------------------------------------------------------


def _extract_end(dur, t0c, sizes, sz, cum, bw, hit, w):
    """Plan-end extraction from scan curves — the exact tail arithmetic of
    ``plan_transfer`` vectorized over candidates (``end = t_in +
    remaining / bw[hit]``; unfit rows → inf, empty transfers → t0)."""
    n = len(sizes)
    ar = np.arange(n)
    hidx = np.minimum(hit, w - 1)
    before = np.where(hit > 0, cum[ar, np.maximum(hit - 1, 0)], 0.0)
    t_in = np.maximum(t0c, (sz + hit) * dur)
    with np.errstate(divide="ignore", invalid="ignore"):
        end = t_in + (sizes - before) / bw[ar, hidx]
    end = np.where(hit < w, end, np.inf)
    end = np.where(sizes <= 0, t0c, end)
    return end


def wave_scan_numpy(ledger, pad, caps, sz, t0c, sizes, w, first_secs):
    """Reference wave pipeline: host gather (``booked_window``) → scan →
    plan-end extraction.  ``sz`` is the per-candidate (frontier-skipped)
    absolute scan-base slot, ``first_secs`` the usable seconds of each
    candidate's first scanned slot."""
    booked = ledger.booked_window(pad, sz, w)
    n = len(caps)
    secs = np.full((n, w), ledger.slot_duration)
    secs[:, 0] = first_secs
    resid, bw, cum, hit = plan_scan_numpy(booked, caps, secs, sizes)
    end = _extract_end(ledger.slot_duration, t0c, sizes, sz, cum, bw, hit, w)
    return resid, bw, cum, hit, end


def wave_scan(ledger, pad, caps, sz, t0c, sizes, w, first_secs):
    """The wavefront engine's fused per-wave pipeline: gather the
    ``[n_cand, L, w]`` window (device-side from the ledger mirror when one
    is live), scan, and extract plan ends — one call per wave.  Returns
    ``(resid, bw, cum, hit, end)``, bit-identical across backends."""
    if _use_device(pad.shape[0] * pad.shape[1] * w):
        from . import ts_plan_device

        return ts_plan_device.wave_scan(
            ledger, pad, caps, sz, t0c, sizes, w, first_secs
        )
    return wave_scan_numpy(ledger, pad, caps, sz, t0c, sizes, w, first_secs)


def col_scan(ledger, pad, cols, caps, secs, sizes):
    """The reroute engine's compressed-column round: gather each
    candidate's collected joint columns (``cols`` holds *absolute* slots)
    and scan.  Device path gathers from the ledger mirror; the numpy path
    is the reference gather expression, bit for bit."""
    if _use_device(pad.shape[0] * pad.shape[1] * cols.shape[1]):
        from . import ts_plan_device

        return ts_plan_device.col_scan(ledger, pad, cols, caps, secs, sizes)
    booked = ledger.reserved[
        pad[:, :, None], (cols - ledger.base_slot)[:, None, :]
    ]
    return plan_scan_numpy(booked, caps, secs, sizes)


def wave_select_numpy(
    end: np.ndarray, rank: np.ndarray, counts: Sequence[int]
) -> np.ndarray:
    """Per-segment argmin of ``(end, rank)`` — the host winner loop.
    ``rank`` is each candidate's precomputed position in its segment's
    tie-break order, so minimizing ``(end, rank)`` equals minimizing the
    scorer's full lexicographic key ``(end, hops, src, index)`` exactly
    (float equality is exact; ranks are unique within a segment).
    Returns the winner's *local* index per segment."""
    out = np.empty(len(counts), dtype=np.int64)
    pos = 0
    for s, cnt in enumerate(counts):
        best = pos
        for c in range(pos + 1, pos + cnt):
            if end[c] < end[best] or (
                end[c] == end[best] and rank[c] < rank[best]
            ):
                best = c
        out[s] = best - pos
        pos += cnt
    return out


def wave_select(
    end: np.ndarray, rank: np.ndarray, counts: Sequence[int]
) -> np.ndarray:
    """Winner selection over a wave's candidate segments — fused on
    device (three ``segment_min`` passes) when the device backend is
    forced, the host loop otherwise; tie-breaking parity is
    contract-tested."""
    if _use_device(len(end)):
        from . import ts_plan_device

        return ts_plan_device.wave_select(end, rank, counts)
    return wave_select_numpy(end, rank, counts)
