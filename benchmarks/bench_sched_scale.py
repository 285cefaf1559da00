"""Benchmark: scheduler scalability (beyond-paper; §VI's "much larger
network cluster" future work, delivered).

BASS as a central controller for a TPU fleet: tasks = input-shard fetches
over the DCN fabric.  Derived value = scheduled tasks/second.  The 1000+
node requirement means the controller must place tens of thousands of
flows per epoch in seconds — the wavefront placement engine
(``repro.core.wavefront``) plans batches against the TS ledger with fused
frontier-skipped scans instead of per-candidate window re-scans, byte-
identical to the sequential greedy loop.  CSV: ``name,us_per_call,derived``
where ``derived`` packs sustained throughput plus the per-batch placement
latency tail: ``tasks_s=…,p50_us=…,p99_us=…,p999_us=…`` (per-task µs
percentiles over 1024-task submit batches — the fleet's actual arrival
granularity, so tail regressions in the decision loop are visible, not
averaged away).

``--smoke`` runs the small config only and enforces a coarse tasks/s
floor (CI guard against decision-loop regressions); ``--json PATH``
appends machine-readable rows (see ``benchmarks/run.py --json``).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.controller import ClusterController
from repro.core.tasks import Instance, Task
from repro.core.topology import tpu_dcn_fabric

CONFIGS = [
    (2, 128, 4000),      # 256 hosts
    (4, 256, 10000),     # 1 024 hosts
    (16, 256, 40000),    # 4 096 hosts — the ≥5× acceptance config
    (64, 256, 100000),   # 16 384 hosts — fleet scale, completes in seconds
]

#: Coarse CI floor for the smoke config (pre-wavefront: ~6.7k tasks/s on a
#: dev box; wavefront: ~15k).  Set far below both so only a real
#: decision-loop regression (or a hopeless runner) trips it.
SMOKE_FLOOR_TASKS_PER_S = 2500.0


def git_sha() -> str:
    import subprocess

    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — best-effort provenance
        return "unknown"


def write_json(rows, path: str) -> None:
    """Machine-readable benchmark rows: name, us_per_call, derived, git
    sha — the perf-trajectory artifact CI uploads per run."""
    import json

    sha = git_sha()
    out = [
        {"name": r[0], "us_per_call": float(r[1]),
         "derived": r[2] if isinstance(r[2], str) else float(r[2]),
         "git_sha": sha}
        for r in rows
    ]
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


def append_json(rows, path: str) -> None:
    """Merge benchmark rows into an existing artifact, deduping by
    (name, git sha): a re-run at the same commit *replaces* its old rows
    instead of growing the file unboundedly, while rows from other
    commits (the perf trajectory) and other benches are preserved.
    Backend variants keep distinct names (``…_numpy``/``…_pallas``), so
    the (name, sha) key already separates them."""
    import json
    import os

    sha = git_sha()
    new = [
        {"name": r[0], "us_per_call": float(r[1]),
         "derived": r[2] if isinstance(r[2], str) else float(r[2]),
         "git_sha": sha}
        for r in rows
    ]
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    fresh = {(r["name"], r["git_sha"]) for r in new}
    out = [
        r for r in existing
        if (r.get("name"), r.get("git_sha")) not in fresh
    ] + new
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


def fleet_instance(pods: int, hosts: int, n_tasks: int) -> Instance:
    n_hosts = pods * hosts
    fab = tpu_dcn_fabric(n_pods=pods, hosts_per_pod=hosts)
    workers = [f"pod{p}/host{h}" for p in range(pods) for h in range(hosts)]
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n_hosts, size=(n_tasks, 3))
    tasks = [
        Task(
            tid=i,
            size=float(256e6 + (i % 7) * 64e6),     # 256–640 MB shards
            compute=float(0.05),
            replicas=tuple(workers[j] for j in idx[i]),
        )
        for i in range(n_tasks)
    ]
    idle = {w: float(rng.uniform(0, 2.0)) for w in workers}
    return Instance(fabric=fab, workers=workers, idle=idle, tasks=tasks,
                    slot_duration=0.1)


def run(configs=None, backend: str = "both") -> list:
    from repro.kernels import ts_plan

    rows = []
    prev = ts_plan.get_backend()
    try:
        # ``both`` always includes the device leg: when jax cannot run
        # it, the run fails instead of dropping the leg.
        for be in ["numpy", "pallas"] if backend == "both" else [backend]:
            ts_plan.set_backend(be)
            for pods, hosts, n_tasks in (
                configs if configs is not None else CONFIGS
            ):
                n_hosts = pods * hosts
                inst = fleet_instance(pods, hosts, n_tasks)
                # Stream the instance through the online controller in
                # 1024-task submit batches (the greedy order and hence the
                # schedule bytes are unchanged — the wavefront planner is
                # batch-size invariant), timing each batch so the derived
                # column carries per-task latency percentiles, not just
                # the mean.
                ctrl = ClusterController.from_instance(inst)
                batch = 1024
                lat_us = []
                t0 = time.perf_counter()
                for i in range(0, n_tasks, batch):
                    chunk = inst.tasks[i:i + batch]
                    c0 = time.perf_counter()
                    ctrl.submit(chunk, at=0.0)
                    ctrl.run_until(0.0)
                    lat_us.append(
                        (time.perf_counter() - c0) / len(chunk) * 1e6
                    )
                dt = time.perf_counter() - t0
                p50, p99, p999 = np.percentile(lat_us, [50.0, 99.0, 99.9])
                rows.append(
                    (
                        f"sched_scale_{n_hosts}hosts_{n_tasks}tasks_{be}",
                        dt / n_tasks * 1e6,
                        f"tasks_s={n_tasks / dt:.0f},p50_us={p50:.1f},"
                        f"p99_us={p99:.1f},p999_us={p999:.1f}",
                    )
                )
                assert len(ctrl.schedule().assignments) == n_tasks
            if be == "pallas":
                st = ts_plan.device_stats()
                calls = st.get("traces", 0) + st.get("cache_hits", 0)
                rate = st.get("cache_hits", 0) / calls if calls else 0.0
                rows.append(
                    (
                        "sched_scale_compile_cache",
                        0.0,
                        f"hit_rate={rate:.4f},traces={st.get('traces', 0)},"
                        f"hits={st.get('cache_hits', 0)},"
                        f"mirror_syncs={st.get('mirror_syncs', 0)}",
                    )
                )
    finally:
        ts_plan.set_backend(prev)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small config only + coarse tasks/s floor")
    ap.add_argument("--json", metavar="PATH",
                    help="also write machine-readable rows (JSON)")
    ap.add_argument("--backend", choices=["numpy", "pallas", "both"],
                    default="both",
                    help="ts_plan backend leg(s) to measure")
    args = ap.parse_args()
    configs = CONFIGS[:1] if args.smoke else CONFIGS
    rows = run(configs, backend=args.backend)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        append_json(rows, args.json)
    if args.smoke:
        name, _us, derived = rows[0]  # the numpy leg guards the floor
        tasks_s = float(str(derived).split("tasks_s=")[1].split(",")[0])
        if tasks_s < SMOKE_FLOOR_TASKS_PER_S:
            raise SystemExit(
                f"{name}: {tasks_s} tasks/s below the "
                f"{SMOKE_FLOOR_TASKS_PER_S} floor"
            )


if __name__ == "__main__":
    main()
