"""One tiny run of each cell on the CPU: the result line's schema, the
program held equal to the reference, and files found by name."""
import json
import re
import shutil
import time

import bench_tiny
import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [c["name"] for c in bench_tiny.SPEC["workloads"]])
def test_tiny_run_is_correct_and_prints_the_contract_line(workload):
    result, lines = bench_tiny.run(workload)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    want = {m["name"] for m in bench_tiny.SPEC["end_to_end"] if harness.applies(m, workload)}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert all(c["value"] == 0 and c["limit"] == 0 for c in result["checks"].values())
    assert lines[-len(result["checks"]):] == [
        f"check {k}: 0 (limit 0)" for k in result["checks"]]
    assert ("reroutes_wrong" in result["checks"]) == ("storm" in workload)
    json.dumps(result)


def test_traced_run_reads_per_layer_metrics(monkeypatch):
    # The CPU has no device plane: stand one in from the benchmark spans.
    import trace_reduce

    def fake_load(_dir):
        tr = trace_reduce.Trace()
        tr.spans = [("window", 0, 10**9)]
        tr.ops = {0: [(0, 10**6, "op")]}
        return tr

    monkeypatch.setattr(trace_reduce, "load", fake_load)
    monkeypatch.setitem(__import__("peaks").PEAKS, "cpu", {"hbm_bytes_per_s": 819e9})
    result, _ = bench_tiny.run("hadoop_yahoo.saturate", trace=True)
    assert result["correct"]
    assert result["device"]["busy_s"] == pytest.approx(1e-3)
    assert result["device"]["window_s"] == pytest.approx(1.0)
    assert "controller_self_pct.place" in result["metrics"]
    assert result["metrics"]["device_idle_pct.place"]["value"] == pytest.approx(99.9)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_traced_run_prices_only_scans_that_reach_the_device(monkeypatch, backend):
    import trace_reduce
    import tracing
    from repro.kernels import ts_plan

    monkeypatch.setattr(trace_reduce, "load", lambda _dir: None)
    monkeypatch.setattr(trace_reduce, "reduce", lambda _tr, *_: {
        "window_s": 1.0, "traced_s": 1.0, "busy_s": 0.5, "kernel_s": {}, "breakdown": {}})
    monkeypatch.setattr(ts_plan, "_backend", backend)
    seen = []
    install = tracing.Tracer.install
    monkeypatch.setattr(tracing.Tracer, "install",
                        lambda self: seen.append(self) or install(self))
    result, _ = bench_tiny.run("hadoop_yahoo.saturate", trace=True)
    assert result["correct"]
    (tracer,) = seen
    labels = {s[0] for s in tracer.spans}
    assert {"submit", "run_until", "place_batch", "wave_scan"} <= labels
    if backend == "numpy":
        assert tracer.calls == []
    else:
        assert tracer.calls and all(
            c[0] == "wave_scan" and all(isinstance(x, int) and x > 0 for x in c[1:])
            for c in tracer.calls)
    assert ts_plan.wave_scan.__name__ == "wave_scan"  # put back after the window


def test_benchmark_json_keeps_to_the_contract():
    spec = bench_tiny.SPEC
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in spec[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        layers.setdefault(m["layer"], set()).add(m["name"])
        (harness.BENCH / "metrics" / f"{m['name']}.py").resolve(strict=True)
    for cell in spec["workloads"]:
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        reports = {m["name"] for m in spec["end_to_end"] if harness.applies(m, cell["name"])}
        assert "setup_s" in reports and len(reports) >= 2
        assert any(harness.applies(m, cell["name"]) and m["moves"] in reports
                   for m in spec["per_layer"])
        harness.cell_parts(spec, cell["name"])
    for c in spec["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert set(c["reduced"]) == set(cfg["reduced"])


#: A reference of its own for a new configuration: every submitted task
#: placed once, on a worker.
PLACED_ONCE = (
    "LIMITS = {'tasks_misplaced': 0}\n"
    "ONLY_WHERE = {}\n"
    "def program_schedule(ctrl):\n"
    "    return sorted((a.tid, a.node) for r in ctrl.jobs.values()\n"
    "                  for a in r.assignments)\n"
    "def replay(ref_fab, cfg, dep, ops, dtype=None):\n"
    "    tids = sorted(t[0] for op in ops if op[0] == 'submit' for t in op[3])\n"
    "    return tids, set(dep.workers)\n"
    "def compare(got, want):\n"
    "    tids, workers = want\n"
    "    wrong = sum(n not in workers for _, n in got)\n"
    "    wrong += len(set(tids) ^ {t for t, _ in got}) + len(got) - len(set(got))\n"
    "    return {'tasks': len(tids), 'tasks_misplaced': wrong}\n")


def new_cell_checkout(tmp_path, reference_source: str):
    """A checkout with a new configuration (with its own reference and
    controller), a new mix and a new metric: new files and new entries
    only.  Returns its root and its ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(bench_tiny.SPEC))
    # a new configuration, a new mix and a new metric: new files ...
    cfg, traffic = bench_tiny.tiny("hadoop_yahoo.saturate")
    cfg.update(n_pods=2, hosts_per_pod=6, controller="flat_marked", reference="own")
    (root / "bench" / "configs" / "fleet_small.json").write_text(json.dumps(cfg))
    (root / "bench" / "references" / "own.py").write_text(reference_source)
    (root / "bench" / "controllers" / "flat_marked.py").write_text(
        "import harness\n"
        "def build(fab, dep, cfg):\n"
        "    dep.built_by = 'flat_marked'\n"
        "    return harness.controller_module('flat').build(fab, dep, cfg)\n")
    traffic["arrival"] = {"every_s": 0.2}
    (root / "bench" / "traffic" / "sparse.json").write_text(json.dumps(traffic))
    (root / "bench" / "metrics" / "jobs_in_window.py").write_text(
        "def read(rec):\n    return rec.get('jobs')\n")
    # ... and new entries
    spec["configs"].append({"name": "fleet_small", "source": "test",
                            "file": "bench/configs/fleet_small.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "fleet_small.sparse", "config": "fleet_small",
                              "traffic": "sparse", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "jobs_in_window", "unit": "count",
                               "better": "higher", "bound": 0.25, "source": "host_clock",
                               "workloads": ["fleet_small.sparse"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, harness.load_spec(root)


def test_new_cell_mix_and_metric_are_files_and_entries_only(tmp_path):
    root, spec = new_cell_checkout(tmp_path, PLACED_ONCE)
    sink = {}
    result, _ = harness.run_cell(spec, "fleet_small.sparse", 3, 0.2, False, 0.0, root,
                                 sink=sink)
    assert result["correct"]
    assert result["checks"] == {"tasks_misplaced": {"value": 0, "limit": 0}}
    assert sink["counts"]["tasks"] > 0
    assert sink["program"].dep.built_by == "flat_marked"
    assert set(result["metrics"]) == {"jobs_in_window", "setup_s"}
    assert result["metrics"]["jobs_in_window"]["value"] >= 1
    with pytest.raises(FileNotFoundError):
        harness.metric_module("no_such_metric", root / "bench")


@pytest.mark.parametrize("compare, only_where", [
    ("{}", "{}"),  # the comparison counts nothing
    ("{'tasks': 0, 'tasks_misplaced': 0}", "{'tasks_misplaced': 'tasks'}"),  # none due
])
def test_a_run_that_compares_nothing_is_not_correct(tmp_path, compare, only_where):
    source = (PLACED_ONCE.replace("ONLY_WHERE = {}", f"ONLY_WHERE = {only_where}")
              + f"def compare(got, want):\n    return {compare}\n")
    root, spec = new_cell_checkout(tmp_path, source)
    if compare == "{}":
        with pytest.raises(ValueError, match="tasks_misplaced"):
            harness.run_cell(spec, "fleet_small.sparse", 3, 0.2, False, 0.0, root)
        return
    result, lines = harness.run_cell(spec, "fleet_small.sparse", 3, 0.2, False, 0.0, root)
    assert result["correct"] is False and result["checks"] == {}
    assert lines[-1] == "check: nothing was compared"


def test_open_loop_times_each_submit_from_its_due_time(monkeypatch):
    """A stalled submit delays the ones due behind it; their latency
    counts the wait, and the window closes on the last submit due."""

    class Stub:
        def __init__(self):
            self.jobs_at = {}

        def submit(self, at, tasks):
            self.jobs_at[len(self.jobs_at)] = at

        def run_until(self, t):
            time.sleep(0.25 if len(self.jobs_at) == 2 else 0.0)

    monkeypatch.setattr(harness, "warm_jobs",
                        lambda drv, cfg, traffic, seed: iter([(0.0, [])] * 100))
    out = harness.loop_module("open").run(Stub(), {}, {"rate_per_s": 20.0}, 1, 0.5,
                                          lambda: harness.Window(None, None))
    assert out["attempted"] == 10 and out["failed"] == 0 and out["jobs"] == 10
    lat = out["latency_s"]
    assert lat[1] >= 0.25  # the stalled submit
    assert lat[2] >= 0.25 - 0.05  # due 50 ms later, sent after the stall
    assert out["lag_s"][2] >= 0.15 and out["lag_s"][0] < 0.05
    assert all(x < 0.1 for x in lat[7:])  # caught up again
