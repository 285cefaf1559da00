"""Failure events: a backlog placed in set-up, then events back to
back.  Event ``i`` at ``first_s + every_s * i`` simulated seconds
recovers the link event ``i - 1`` failed, fails the next link, and runs
the controller to that instant.  The warm-up's events are set-up.  The
window is a fixed piece of work, the same links on every seed in a
seeded order (``generator.failing_links``), and ends with its last
event: the mix sizes it (``window_events``) to take about a run's
seconds, so that what a window replans never depends on how fast the
program is.  A traced run's window is the first ``traced_events`` of
those links, short enough for the profiler to record all of it."""
import generator
import harness


def run(drv, cfg, traffic, seed, seconds, window):
    harness.place_backlog(drv, cfg)
    ev = traffic["events"]
    warmup, timed, traced = generator.failing_links(
        [l[0] for l in drv.ref_fabric["links"]], traffic, cfg["k"], seed)
    state = {"i": 0, "prev": None}

    def event(link):
        at = ev["first_s"] + ev["every_s"] * state["i"]
        if state["prev"] is not None:
            drv.recover_link(state["prev"], at)
        state["prev"] = link
        drv.fail_link(link, at)
        drv.run_until(at)
        state["i"] += 1

    for link in warmup:
        event(link)
    out = {"events": 0, "first_job": len(drv.jobs_at)}
    n0 = len(drv.ctrl.reroute_log)
    with window() as w:
        for link in timed if w.tracer is None else traced:
            event(link)
            out["events"] += 1
    out["victims"] = len(drv.ctrl.reroute_log) - n0
    out["attempted"], out["failed"] = out["victims"], 0
    return out
