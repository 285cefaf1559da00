"""Open loop: job ``k`` is due at ``k / rate_per_s`` wall seconds after
the window opens; the client sleeps until it is due, and its latency
runs from the due time to the return of its ``run_until``.  Submits not
yet sent ``GIVE_UP_S`` past the close count as failed."""
import math
import time

import harness

GIVE_UP_S = 60.0


def run(drv, cfg, traffic, seed, seconds, window):
    gen = harness.warm_jobs(drv, cfg, traffic, seed)
    rate = float(traffic["rate_per_s"])
    due_n = math.ceil(seconds * rate)
    lat, lag = [], []
    out = {"tasks": 0, "jobs": 0, "first_job": len(drv.jobs_at)}
    with window() as w:
        for k in range(due_n):
            due = w.t0 + k / rate
            now = time.perf_counter()
            if now < due:
                time.sleep(due - now)
            start = time.perf_counter()
            if start - w.t0 > seconds + GIVE_UP_S:
                break
            lag.append(start - due)
            at, tasks = next(gen)
            drv.submit(at, tasks)
            drv.run_until(at)
            lat.append(time.perf_counter() - due)
            out["tasks"] += len(tasks)
            out["jobs"] += 1
    out.update(latency_s=lat, lag_s=lag, attempted=due_n,
               failed=due_n - len(lat), offered_s=seconds)
    return out
