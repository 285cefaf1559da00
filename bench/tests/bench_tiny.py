"""Tiny cells for the harness tests: the benchmark's own configurations
and mixes, cut to a size a CPU test can run in a second."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SPEC = harness.load_spec(ROOT)


def tiny(workload: str):
    """(configuration, mix) of ``workload`` at a CPU test's size."""
    _cell, cfg, traffic = harness.cell_parts(SPEC, workload, ROOT)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    if cfg["fabric"] == "tpu_dcn":
        cfg.update(n_pods=3, hosts_per_pod=8)
        if "fixed" in traffic.get("job_tasks", {}):
            traffic["job_tasks"] = {"fixed": 48}
        traffic["warmup_jobs"] = 2
    else:
        cfg.update(k=4, backlog_tasks=120)
        traffic.update(warmup_events=2, window_events=6, traced_events=3)
    return cfg, traffic


def run(workload: str, seed: int = 5, seconds: float = 0.3, trace: bool = False,
        sink=None):
    cfg, traffic = tiny(workload)
    return harness.run_cell(SPEC, workload, seed, seconds, trace,
                            time.perf_counter(), ROOT, cfg, traffic, sink)
