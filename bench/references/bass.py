"""The BASS control plane's semantics, named by a configuration's
``"reference": "bass"``: the schedule and the reroute log, compared
exactly with the sequential replay of ``bench/reference.py``.

A configuration with other semantics (flow groups, tenants, recovery)
brings a file of its own beside this one, with the same five names:
``LIMITS``, ``ONLY_WHERE``, ``program_schedule``, ``replay`` and
``compare``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import reference

#: The numbers compared, each with its limit: exact comparisons, so 0.
LIMITS = {"assignments_wrong": 0, "reroutes_wrong": 0}
#: A number compared only where the count named beside it is above 0: a
#: cell without failures has no reroute to compare.
ONLY_WHERE = {"reroutes_wrong": "reroutes"}

Output = Tuple[Dict[int, tuple], List[tuple]]  # (schedule by task, reroute log)


def program_schedule(ctrl) -> Output:
    """What the program produced, in the reference's canonical form: every
    assignment of every job, and every reroute record in fire order."""
    names = ctrl.state.ledger.link_names
    schedule = {}
    for rec in ctrl.jobs.values():
        for a in rec.assignments:
            plan = None
            if a.transfer is not None:
                t = a.transfer
                plan = reference.Plan(names(t.links), t.start, t.end, t.slot_fracs)
            schedule[a.tid] = reference.canon(a.node, a.source, plan, a.start,
                                              a.finish)
    log = [(r.flow[2], tuple(r.old_path), tuple(r.new_path), float(r.delivered),
            float(r.remaining), float(r.new_end)) for r in ctrl.reroute_log]
    return schedule, log


def replay(ref_fab: dict, cfg: dict, dep, ops, dtype=np.float64) -> Output:
    """The reference's own output for the logged operations; ``dtype`` is
    its precision (``numpy.float32`` is the control)."""
    net = reference.Net(ref_fab["links"], ref_fab["parent"], cfg["k_paths"])
    ref = reference.Reference(net, dep.workers, dep.idle, cfg["slot_s"],
                              cfg["policy"].get("multipath", False), dtype)
    for op in ops:
        ref.apply(op)
    return ref.schedule(), ref.log


def compare(got: Output, want: Output) -> dict:
    """Counts of what differs, and of what was compared."""
    return reference.compare(got[0], want[0], got[1], want[1])
