"""Closed loop: one client submits a job and waits for its
``run_until``, back to back, until the window's time is up."""
import time

import harness


def run(drv, cfg, traffic, seed, seconds, window):
    gen = harness.warm_jobs(drv, cfg, traffic, seed)
    out = {"tasks": 0, "jobs": 0, "first_job": len(drv.jobs_at)}
    with window() as w:
        while True:
            at, tasks = next(gen)
            drv.submit(at, tasks)
            drv.run_until(at)
            out["tasks"] += len(tasks)
            out["jobs"] += 1
            if time.perf_counter() - w.t0 >= seconds:
                break
    out["attempted"], out["failed"] = out["tasks"], 0
    return out
