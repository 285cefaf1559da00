"""Chip smoke test: the BASS planning path on one TPU, held to the numpy
reference.

Three phases, all in this one process:

1. the exact binary64 routines the device pipelines compute with
   (add, subtract, multiply on float64 bit patterns) against numpy;
2. the 16,384-host fleet instance (100,000 tasks) placed through
   ``ClusterController`` in 1,024-task submits, as ``bench_sched_scale``
   does;
3. the k=8 fat-tree spine-kill storm of ``bench_failover_scale``
   (10,000 in-flight transfers rerouted by the batched engine).

Phases 2 and 3 run twice: with the default ``auto`` planning backend,
which plans on the TPU, and with the numpy reference.  Each pair of
schedules must be byte-identical, ``replay_online`` must accept the
device run, and the device must have done the planning (compiled
buckets and ledger-mirror syncs).  Wall times are printed for
orientation only: they are not benchmark metrics.

    python chip_smoke.py

Exits nonzero, with no result line, when jax's default backend is not a
TPU or any phase fails.  On success the last line of output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import io
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

FLEET = (64, 256, 100_000)  # pods, hosts per pod, tasks: 16,384 hosts
STORM = (8, 10_000)         # fat-tree arity, tasks: bench_failover_scale
BATCH = 1024                # tasks per submit, as bench_sched_scale


class SmokeFailure(Exception):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_arithmetic(seed: int = 0, n: int = 1 << 17) -> None:
    """Phase 1: the device's binary64 routines equal numpy bit for bit."""
    import jax
    import numpy as np

    from repro.kernels import ts_plan_device as dev

    rng = np.random.default_rng(seed)
    top = np.uint64(0x7FF0000000000000)
    x = np.concatenate([
        rng.integers(0, top, n, dtype=np.uint64).view(np.float64),
        rng.random(n),
        rng.uniform(0.4, 0.8, n),
    ])
    y = np.concatenate([
        rng.integers(0, top, n, dtype=np.uint64).view(np.float64),
        rng.random(n),
        np.full(n, 0.1),
    ])
    with np.errstate(over="ignore", under="ignore"):
        refs = {
            "add": x + y,
            "sub": np.maximum(x, y) - np.minimum(x, y),
            "mul": x * y,
        }
    for op, ref in refs.items():
        a, b = (np.maximum(x, y), np.minimum(x, y)) if op == "sub" else (x, y)
        with jax.enable_x64(True):
            got = np.asarray(
                jax.jit(getattr(dev, f"_{op}"))(dev._bits(a), dev._bits(b))
            )
        bad = np.flatnonzero(got != dev._bits(ref))
        require(
            bad.size == 0,
            f"device {op} differs from numpy on {bad.size} of {ref.size} "
            f"pairs, first {a[bad[:1]]!r} {b[bad[:1]]!r}",
        )
        print(f"arithmetic {op}: {ref.size} pairs bit-identical to numpy")


def _dump(sched) -> str:
    from benchmarks.tools.dump_schedules import dump_schedule

    buf = io.StringIO()
    dump_schedule(buf, "x", sched)
    return buf.getvalue()


def _place_fleet():
    from benchmarks.bench_sched_scale import fleet_instance
    from repro.core.controller import ClusterController

    inst = fleet_instance(*FLEET)
    ctrl = ClusterController.from_instance(inst)
    for i in range(0, len(inst.tasks), BATCH):
        ctrl.submit(inst.tasks[i:i + BATCH], at=0.0)
        ctrl.run_until(0.0)
    return ctrl, [(0.0, inst.tasks)], inst.idle


def _spine_kill():
    from benchmarks.bench_failover_scale import run_reroute_leg, storm_setup

    _fab, _workers, tasks, idle = storm_setup(*STORM)
    ctrl, _dt, _in_flight, victims = run_reroute_leg(*STORM, "batched")
    require(victims > 0, "spine-kill storm rerouted no transfer")
    return ctrl, [(0.0, tasks)], idle


def run_pair(name: str, workload) -> None:
    """Run ``workload`` on the device (``auto``) and on numpy; require
    byte-identical schedules, a clean replay and real device work."""
    from repro.core.simulator import replay_online
    from repro.kernels import ts_plan, ts_plan_device as dev

    dumps = {}
    for backend in ("auto", "numpy"):
        ts_plan.set_backend(backend)
        dev.reset_cache()
        t0 = time.perf_counter()
        ctrl, jobs, idle = workload()
        wall = time.perf_counter() - t0
        sched = ctrl.schedule()
        dumps[backend] = _dump(sched)
        print(f"{name} [{backend}]: {len(sched.assignments)} assignments, "
              f"wall time {wall:.3f} s (not a benchmark metric)")
        if backend == "auto":
            st = dict(dev.stats)
            print(f"{name} [auto] device counters: {json.dumps(st)}")
            require(st["traces"] > 0, f"{name}: no device program ran")
            require(st["mirror_syncs"] > 0, f"{name}: ledger mirror never synced")
            mirror = ctrl.state.ledger.device_mirror().arr
            print(f"{name} [auto] ledger mirror: {mirror.shape} "
                  f"{mirror.dtype}, {mirror.nbytes} bytes on device")
            rep = replay_online(jobs, sched, idle)
            require(rep.ok, f"{name}: replay_online rejects the device "
                            f"schedule: {rep.violations[:3]}")
            print(f"{name} [auto] replay_online ok")
    ts_plan.set_backend("auto")
    require(dumps["auto"] == dumps["numpy"],
            f"{name}: device schedule differs from the numpy reference")
    print(f"{name}: device schedule byte-identical to numpy "
          f"({len(dumps['auto'])} bytes)")


def _cache_entries() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return len(list(pathlib.Path(d).glob("*"))) if d else 0


def main() -> int:
    from repro.kernels import ts_plan_device as dev

    plat = dev.platform()  # starts the backend; on a TPU, places the cache
    if plat != "tpu":
        print(f"chip_smoke: jax default backend is {plat!r}, not a TPU",
              file=sys.stderr)
        return 1
    import jax

    d0 = jax.devices()[0]
    print(f"device: {d0.platform} {d0.device_kind}, {len(jax.devices())} "
          f"visible; compile cache {jax.config.jax_compilation_cache_dir} "
          f"({_cache_entries()} entries)")
    try:
        check_arithmetic()
        run_pair("fleet_16384h_100000t", _place_fleet)
        run_pair("spine_kill_k8_10000t", _spine_kill)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    mem = d0.memory_stats() or {}
    print(f"peak device bytes in use: {mem.get('peak_bytes_in_use')}; "
          f"compile cache now {_cache_entries()} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
