"""Off a TPU the command exits nonzero and prints no result; with
--trace 0 it installs no wrapper and no profiler."""
import os
import subprocess
import sys

import bench_tiny
import pytest

import harness
import tracing


def test_cli_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "hadoop_yahoo.saturate", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_untraced_run_installs_no_wrapper_and_no_profiler(monkeypatch):
    import jax

    from repro.core.controller import ClusterController
    from repro.kernels import ts_plan

    before = (ClusterController.run_until, ts_plan.wave_scan)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: pytest.fail("profiler started"))
    seen = []
    orig = harness.Program.run_until

    def spy(self, t):
        seen.append((ClusterController.run_until, ts_plan.wave_scan))
        return orig(self, t)

    monkeypatch.setattr(harness.Program, "run_until", spy)
    monkeypatch.setattr(tracing.Tracer, "install", lambda self: pytest.fail("wrapped"))
    result, _ = bench_tiny.run("hadoop_yahoo.saturate")
    assert result["correct"] and seen
    assert all(s == before for s in seen)
    assert "breakdown" not in result and "busy_s" not in result["device"]
