"""Schedule-dump tool: byte-exact dumps of the paper + fleet workloads.

Run before and after a scheduler change; an empty diff proves the change
is byte-identical (floats serialized via ``float.hex``).  Used to verify
the wavefront placement engine (DESIGN.md §5) emits the same bytes as
the sequential greedy loop on the Fig. 2, Table-I and fleet workloads,
and the batched reroute engine (DESIGN.md §6) on a failure-storm fleet
workload (schedules **and** reroute log; the storm section is emitted
per reroute engine, so the two blocks must be byte-identical to each
other within one dump as well as across code changes).

The ``compaction_*`` / ``failstorm_compacted`` sections run the same
arrival stream through an aggressively-compacting controller
(``retire_stride = 4``) and a never-compacted twin
(``retire_stride = None``): the paired blocks must be byte-identical
within one dump — the rolling-horizon origin shift (DESIGN.md §7) is
invisible in every emitted coordinate.

The ``faultstorm_*`` sections run a seeded host-kill + straggler storm
(``FaultPlan``, DESIGN.md §10) with retries and LATE speculation on,
once per reroute engine: the paired blocks must be byte-identical to
each other within one dump as well as across code changes, and every
section *above* them runs fault-free and must stay byte-identical to
main.

The ``backend_*`` sections emit the same workloads under the numpy
reference and the forced device ``ts_plan`` backend (DESIGN.md §8):
paired blocks must be byte-identical within one dump, pinning the device
pipeline's bit-exactness end to end.

    PYTHONPATH=src python benchmarks/tools/dump_schedules.py OUTFILE
"""
from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.bench_sched_scale import CONFIGS, fleet_instance  # noqa: E402
from repro.core import SCHEDULERS  # noqa: E402
from repro.core.examples_fig import example1_instance  # noqa: E402
from repro.core.workloads import SORT, WORDCOUNT, make_instance  # noqa: E402


def fx(v):
    if v is None:
        return "None"
    return float(v).hex()


def dump_schedule(out, label, sched):
    out.write(f"== {label}\n")
    for a in sorted(sched.assignments, key=lambda a: a.tid):
        t = a.transfer
        if t is None:
            tr = "-"
        else:
            fr = ";".join(f"{s}:{fx(f)}" for s, f in t.slot_fracs)
            tr = f"links={','.join(map(str, t.links))} start={fx(t.start)} end={fx(t.end)} fracs={fr}"
        out.write(
            f"{a.tid} node={a.node} src={a.source} start={fx(a.start)} "
            f"finish={fx(a.finish)} bw={fx(a.bw_needed)} {tr}\n"
        )


def main() -> None:
    path = sys.argv[1]
    with open(path, "w") as out:
        fig2 = example1_instance()
        for name in ("bass", "prebass", "hds", "bar"):
            dump_schedule(out, f"fig2_{name}", SCHEDULERS[name](fig2))
        for jobname, job in (("wordcount", WORDCOUNT), ("sort", SORT)):
            for mb in (150, 600):
                for seed in (0, 1):
                    inst, _, _ = make_instance(job, mb, seed=seed)
                    for name in ("bass", "prebass", "hds", "bar"):
                        dump_schedule(
                            out,
                            f"table1_{jobname}_{mb}_{seed}_{name}",
                            SCHEDULERS[name](inst),
                        )
        for pods, hosts, n in CONFIGS[:3]:  # fleet configs up to 4 096 hosts
            inst = fleet_instance(pods, hosts, n)
            dump_schedule(out, f"fleet_{pods * hosts}h_{n}t_bass",
                          SCHEDULERS["bass"](inst))
        for engine in ("batched", "sequential"):
            dump_failure_storm(out, engine)
        dump_compaction(out)
        # Same storm under aggressive vs no compaction: the two blocks
        # (and the default-stride ``failstorm_batched`` one above) must
        # be byte-identical to each other.
        dump_failure_storm(out, "batched", stride=4,
                           label="failstorm_compacted")
        dump_failure_storm(out, "batched", stride=None,
                           label="failstorm_uncompacted")
        dump_backend_parity(out)
        # Seeded fault storm (DESIGN.md §10) under both reroute engines:
        # the paired blocks must be byte-identical to each other within
        # one dump (host kills, retries, blacklisting and LATE
        # speculation are engine-invariant) as well as across code
        # changes.  Everything above this line runs fault-free and must
        # stay byte-identical to main.
        for engine in ("batched", "sequential"):
            dump_fault_storm(out, engine)
        # Crash-recovery equivalence (DESIGN.md §11): the same fault storm
        # dumped from a never-killed journaled controller and from a twin
        # rebuilt via snapshot bytes + journal replay — the paired blocks
        # are asserted byte-identical before they are written.
        dump_recovery(out)
        # Flat vs sharded control plane (DESIGN.md §12): the same arrival
        # streams through the flat ClusterController and the exact-mode
        # HierarchicalController — the paired ``hierarchy_*`` blocks are
        # asserted byte-identical before they are written (single-pod AND
        # cross-pod workloads, rebalancer off).
        dump_hierarchy(out)


def dump_recovery(out):
    """Mid-storm checkpoint + kill: the ``recovery_uncrashed`` twin runs
    the journaled storm straight through; the ``recovery_crashed`` twin is
    rebuilt from the checkpoint's snapshot bytes plus a replay of the
    journal suffix.  Schedules, fault counters and ha counters must match
    byte-for-byte (asserted here, not just diffed across runs)."""
    import io  # noqa: E402

    from benchmarks.bench_faults import (  # noqa: E402
        MTTR, SEED, SLOW, T0, T1, storm_setup,
    )
    from repro.core.controller import (  # noqa: E402
        BassPolicy, ClusterController, RetryPolicy,
    )
    from repro.core.faults import FaultPlan  # noqa: E402
    from repro.core.journal import ControllerSnapshot, Journal  # noqa: E402

    fab, workers, tasks = storm_setup(4, 16)
    ctrl = ClusterController(
        fab, workers, BassPolicy(multipath=True), slot_duration=0.1,
        retry=RetryPolicy(max_attempts=4, backoff_s=0.5),
        speculation=True,
    )
    ctrl.attach_journal()
    ctrl.submit(tasks, at=0.0)
    ctrl.run_until(0.0)
    # The bench_faults storm plus one in-sim controller crash, so the
    # dumped bytes also cover the headless window + mailbox drain path.
    FaultPlan.generate(
        SEED, workers, T0, T1, n_crashes=2, mttr=MTTR,
        n_stragglers=4, slow_factor=SLOW,
        n_ctrl_crashes=1, ctrl_mttr=1.0,
    ).apply(ctrl)
    ctrl.run_until(1.5)          # mid-storm checkpoint: the kill point
    snap = ctrl.snapshot()
    ctrl.run()                   # never-killed twin finishes the storm

    rec = ClusterController.recover_from(
        fab, ControllerSnapshot.from_bytes(snap.to_bytes()),
        Journal.from_bytes(ctrl.journal.to_bytes()),
    )

    bodies = []
    for c in (ctrl, rec):
        buf = io.StringIO()
        dump_schedule(buf, "x", c.schedule())
        body = buf.getvalue().split("\n", 1)[1]
        for key in sorted(c.fault_stats):
            body += f"{key}={fx(c.fault_stats[key])}\n"
        for key in sorted(c.ha_stats):
            body += f"{key}={fx(c.ha_stats[key])}\n"
        bodies.append(body)
    assert bodies[0] == bodies[1], (
        "recovery dump pair diverged: snapshot+replay is not equivalent"
    )
    for label, body in (("recovery_uncrashed", bodies[0]),
                        ("recovery_crashed", bodies[1])):
        out.write(f"== {label}\n")
        out.write(body)


def dump_hierarchy(out):
    """Flat vs pod-sharded controller on identical arrival streams: the
    paired ``hierarchy_<case>_flat`` / ``hierarchy_<case>_sharded`` blocks
    must be byte-identical within one dump — the exact-mode parity
    contract of ``core.hierarchy`` (lazy minnow, per-pod ledger shards and
    the boundary shard are all invisible in every emitted coordinate)."""
    import io  # noqa: E402
    import random  # noqa: E402

    from repro.core.controller import ClusterController  # noqa: E402
    from repro.core.hierarchy import HierarchicalController  # noqa: E402
    from repro.core.tasks import Task  # noqa: E402
    from repro.core.topology import storage_hosts, tpu_dcn_fabric  # noqa: E402
    from repro.net.fattree import fat_tree_fabric  # noqa: E402

    def stream(hosts, seed, pod=None):
        rng = random.Random(seed)
        pool = [h for h in hosts if pod is None or h.startswith(pod + "/")]
        jobs = []
        for j in range(8):
            jobs.append((
                [
                    Task(
                        j * 100 + i,
                        size=rng.uniform(40, 400),
                        compute=rng.uniform(1, 20),
                        replicas=tuple(rng.sample(pool, 3)),
                    )
                    for i in range(rng.randint(1, 10))
                ],
                j * 2.5,
            ))
        return jobs

    cases = [
        ("fattree_cross_pod", fat_tree_fabric(4), None, 11),
        ("fattree_single_pod", fat_tree_fabric(4), "pod2", 23),
        ("tpu_dcn_cross_pod", tpu_dcn_fabric(n_pods=4, hosts_per_pod=8),
         None, 7),
    ]
    for case, fab, pod, seed in cases:
        hosts = storage_hosts(fab)
        jobs = stream(hosts, seed, pod)
        bodies = []
        for ctl in (ClusterController(fab, hosts, "bass"),
                    HierarchicalController(fab, hosts)):
            for tasks, at in jobs:
                ctl.submit(tasks, at=at)
            ctl.run()
            buf = io.StringIO()
            dump_schedule(buf, "x", ctl.schedule())
            bodies.append(buf.getvalue().split("\n", 1)[1])
        assert bodies[0] == bodies[1], (
            f"hierarchy dump pair diverged on {case}: sharded control "
            "plane is not byte-identical to flat"
        )
        for mode, body in (("flat", bodies[0]), ("sharded", bodies[1])):
            out.write(f"== hierarchy_{case}_{mode}\n")
            out.write(body)


def dump_fault_storm(out, engine):
    """Seeded host-kill + straggler storm: schedule + fault counters
    under one reroute engine, speculation on."""
    from benchmarks.bench_faults import (  # noqa: E402
        MTTR, SEED, SLOW, T0, T1, storm_setup,
    )
    from repro.core.controller import (  # noqa: E402
        BassPolicy, ClusterController, RetryPolicy,
    )
    from repro.core.faults import FaultPlan  # noqa: E402

    fab, workers, tasks = storm_setup(4, 16)
    ctrl = ClusterController(
        fab, workers, BassPolicy(multipath=True), slot_duration=0.1,
        retry=RetryPolicy(max_attempts=4, backoff_s=0.5),
        speculation=True,
    )
    ctrl.reroute_engine = engine
    ctrl.submit(tasks, at=0.0)
    ctrl.run_until(0.0)
    FaultPlan.generate(
        SEED, workers, T0, T1, n_crashes=2, mttr=MTTR,
        n_stragglers=4, slow_factor=SLOW,
    ).apply(ctrl)
    ctrl.run()
    label = f"faultstorm_{engine}"
    dump_schedule(out, label, ctrl.schedule())
    out.write(f"== {label}_counters\n")
    for key in sorted(ctrl.fault_stats):
        out.write(f"{key}={fx(ctrl.fault_stats[key])}\n")


def dump_backend_parity(out):
    """The same workloads under the numpy reference and the forced device
    ``ts_plan`` backend (fused f64 pipeline + ledger mirror): paired
    ``backend_*`` blocks must be byte-identical within one dump — the
    device pipeline's bit-exactness contract, end to end through the
    scheduler."""
    from repro.kernels import ts_plan, ts_plan_device  # noqa: E402

    pods, hosts, n = CONFIGS[0]
    prev = ts_plan.get_backend()
    try:
        for be in ("numpy", "pallas"):
            ts_plan.set_backend(be)
            if be == "pallas":
                ts_plan_device.set_mirror(True)  # exercise the mirror too
            dump_schedule(
                out, f"backend_{be}_fig2_bass",
                SCHEDULERS["bass"](example1_instance()),
            )
            dump_schedule(
                out, f"backend_{be}_fleet_{pods * hosts}h_{n}t",
                SCHEDULERS["bass"](fleet_instance(pods, hosts, n)),
            )
    finally:
        ts_plan.set_backend(prev)
        ts_plan_device.set_mirror(None)


def dump_compaction(out):
    """Fig-2 and Table-I streams through a live controller, compacted
    (retire_stride=4) vs never-compacted: paired blocks byte-identical."""
    from dataclasses import replace  # noqa: E402

    from repro.core.controller import ClusterController  # noqa: E402

    cases = [("fig2", example1_instance())]
    inst, _, _ = make_instance(SORT, 150, seed=0)
    cases.append(("table1_sort_150_0", inst))
    for label, inst in cases:
        for mode, stride in (("compacted", 4), ("uncompacted", None)):
            ctrl = ClusterController.from_instance(inst)
            ctrl.state.ledger.retire_stride = stride
            half = len(inst.tasks) // 2
            ctrl.submit(inst.tasks[:half], at=0.0)
            # The second half arrives a compaction-stride later, so the
            # compacting controller has already shifted its origin.
            ctrl.submit(
                [replace(t, tid=t.tid + 10_000) for t in inst.tasks[half:]],
                at=40.0,
            )
            ctrl.run()
            dump_schedule(out, f"compaction_{label}_{mode}",
                          ctrl.schedule())


def dump_failure_storm(out, engine, stride=256, label=None):
    """Spine-kill fleet storm: schedule + reroute log under one engine."""
    from benchmarks.bench_failover_scale import (  # noqa: E402
        DEAD_CORE, T_KILL, _controller, storm_setup,
    )

    fab, workers, tasks, idle = storm_setup(4, 600)
    ctrl = _controller(fab, workers, idle, engine)
    ctrl.state.ledger.retire_stride = stride
    ctrl.submit(tasks, at=0.0)
    ctrl.fail_switch(DEAD_CORE, at=T_KILL)
    ctrl.fail_link("ea/p3e0a0", at=1.0)
    ctrl.run_until(2.0)
    label = label or f"failstorm_{engine}"
    dump_schedule(out, label, ctrl.schedule())
    out.write(f"== {label}_reroute_log\n")
    for r in ctrl.reroute_log:
        out.write(
            f"{r.flow} at={fx(r.at)} dead={','.join(r.dead_links)} "
            f"{r.src}->{r.dst} old={'/'.join(r.old_path)} "
            f"new={'/'.join(r.new_path)} delivered={fx(r.delivered)} "
            f"remaining={fx(r.remaining)} old_end={fx(r.old_end)} "
            f"new_end={fx(r.new_end)}\n"
        )


if __name__ == "__main__":
    main()
