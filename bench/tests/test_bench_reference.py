"""The comparison that decides ``correct`` fails what it must: the
reference in float32 (the control), and the program with a fault
planted underneath the timed path."""
import bench_tiny
import numpy as np
import pytest

import harness
import reference

CELLS = [c["name"] for c in bench_tiny.SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_float32_reference_is_told_apart(workload):
    sink = {}
    result, _ = bench_tiny.run(workload, seed=11, sink=sink)
    assert result["correct"]
    drv = sink["program"]
    counts = drv.ref.compare(harness.replay(drv, sink["cfg"], np.float32), sink["want"])
    assert any(c["value"] > c["limit"]
               for c in harness.checks(counts, drv.ref).values())


def _no_commit(monkeypatch):
    from repro.core.timeslot import TimeSlotLedger

    monkeypatch.setattr(TimeSlotLedger, "commit", lambda self, plan: None)
    monkeypatch.setattr(TimeSlotLedger, "commit_batch", lambda self, plans: None)


def _half_batch(monkeypatch):
    from repro.core.wavefront import WavefrontPlanner

    orig = WavefrontPlanner.place_batch
    monkeypatch.setattr(WavefrontPlanner, "place_batch",
                        lambda self, tasks, **kw: orig(self, tasks[: len(tasks) // 2], **kw))


def _altered_scan(monkeypatch):
    from repro.kernels import ts_plan

    def nudge(fn):
        def wrapped(*args, **kwargs):
            out = list(fn(*args, **kwargs))
            out[0] = out[0] * (1.0 - 2.0**-30)  # residual fraction per slot
            out[1] = out[1] * (1.0 - 2.0**-30)  # bandwidth per slot
            return tuple(out)
        return wrapped

    monkeypatch.setattr(ts_plan, "wave_scan", nudge(ts_plan.wave_scan))
    monkeypatch.setattr(ts_plan, "col_scan", nudge(ts_plan.col_scan))


FAULTS = {"state_unchanged": _no_commit, "half_batch": _half_batch,
          "answer_altered": _altered_scan}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_reads_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result, lines = bench_tiny.run(workload, seed=13)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_reference_routes_like_the_program_with_and_without_dead_links():
    import random

    from repro.net.paths import PathEngine

    cfg = {"k": 4, "link_capacity": 100.0}
    mod = harness.fabric_module("fat_tree")
    ref = mod.reference(cfg)
    net = reference.Net(ref["links"], None, 4)
    engine = PathEngine(mod.program(cfg), 4)
    hosts = ref["hosts"]
    core = sorted(n for n, *_ in ref["links"] if n.startswith(("ac/", "ea/")))
    rng = random.Random(3)
    for trial in range(30):
        dead = frozenset(rng.sample(core, rng.choice([0, 1, 2, 6])))
        for src in rng.sample(hosts, 5):
            for dst in rng.sample(hosts, 5):
                if src == dst:
                    continue
                try:
                    want = engine.route(src, dst, dead)
                except ValueError:
                    want = ()
                assert net.candidates(src, dst, dead) == want, (src, dst, dead)


def test_reference_tree_walk_is_the_programs_path():
    cfg = {"n_pods": 3, "hosts_per_pod": 4, "nic_bytes_per_s": 25e9,
           "pod_trunk_bytes_per_s": 400e9}
    mod = harness.fabric_module("tpu_dcn")
    ref = mod.reference(cfg)
    fab = mod.program(cfg)
    net = reference.Net(ref["links"], ref["parent"], 4)
    for src in ref["hosts"]:
        for dst in ref["hosts"] + ["dcn-core", "pod1/agg"]:
            assert net.path(src, dst) == fab.path(src, dst)
    assert harness.fabric_mismatch(fab, ref) == 0
    bad = dict(ref, links=ref["links"][:-1] + [ref["links"][-1][:3] + (1.0,)])
    assert harness.fabric_mismatch(fab, bad) == 1


def test_a_fabric_unlike_the_references_is_refused(monkeypatch):
    cfg, _ = bench_tiny.tiny("hadoop_yahoo.saturate")
    mod = harness.fabric_module("tpu_dcn")
    build = mod.reference
    monkeypatch.setattr(mod, "reference",
                        lambda c: dict(build(c), hosts=build(c)["hosts"][:-1]))
    with pytest.raises(RuntimeError, match="differs"):
        harness.Program(cfg, 1)
