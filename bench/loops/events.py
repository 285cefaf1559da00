"""Failure events: a backlog placed in set-up, then events back to
back.  Event ``i`` at ``first_s + every_s * i`` simulated seconds
recovers the link event ``i - 1`` failed, fails the next link of the
seeded order, and runs the controller to that instant.  The first
``warmup_events`` are set-up; the window ends with the first event that
finishes past its time."""
import time

import generator
import harness


def run(drv, cfg, traffic, seed, seconds, window):
    harness.place_backlog(drv, cfg)
    ev = traffic["events"]
    order = generator.failing_links([l[0] for l in drv.ref_fabric["links"]],
                                    traffic, seed)
    state = {"i": 0, "prev": None}

    def event():
        at = ev["first_s"] + ev["every_s"] * state["i"]
        if state["prev"] is not None:
            drv.recover_link(state["prev"], at)
        state["prev"] = next(order)
        drv.fail_link(state["prev"], at)
        drv.run_until(at)
        state["i"] += 1

    for _ in range(traffic["warmup_events"]):
        event()
    out = {"events": 0, "victims": 0, "first_job": len(drv.jobs_at)}
    with window() as w:
        while True:
            n0 = len(drv.ctrl.reroute_log)
            event()
            out["victims"] += len(drv.ctrl.reroute_log) - n0
            out["events"] += 1
            if time.perf_counter() - w.t0 >= seconds:
                break
    out["attempted"], out["failed"] = out["victims"], 0
    return out
