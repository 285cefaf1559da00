"""The one traffic generator: a deployment and its arrivals from a seed.

Everything a run submits comes from here, as plain values, so the same
stream feeds the program and the reference.  What varies between runs
is only the seed; what a cell is made of comes from two data files:

* the configuration (``bench/configs/<config>.json``): the fabric, who
  stores data and who computes, the task shape and the initial load;
* the traffic mix (``bench/traffic/<mix>.json``): how jobs arrive, how
  large they are, and which failures strike.

Provenance of the shapes, copied so that the yardstick does not move
with the program: the task shape (``size_base + (tid % size_steps) *
size_step`` bytes per task, replicas drawn uniformly, initial idle
times uniform on ``[0, idle_max_s)``) is
``benchmarks/bench_sched_scale.py:fleet_instance``; the storm set-up
(sources in the lower half of the hosts, workers in the upper half, so
every transfer crosses the core) is
``benchmarks/bench_failover_scale.py:storm_setup``.  Both fixed their
seed at 0; here it is the run's ``--seed``.

Sizes and gaps that are random are drawn as a block of quantiles of
their distribution, shuffled by the seed: every seed offers the same
work in another order, so seeds do not change how much is asked.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

Task = Tuple[int, float, float, Tuple[str, ...]]  # tid, size, compute, replicas


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def select(hosts: Sequence[str], which: str) -> List[str]:
    """``all``, ``lower_half`` or ``upper_half`` of the hosts, in order."""
    half = len(hosts) // 2
    return {"all": list(hosts), "lower_half": list(hosts[:half]),
            "upper_half": list(hosts[half:])}[which]


class Deployment:
    """Workers, data sources, initial idle times and the task stream."""

    def __init__(self, cfg: dict, hosts: Sequence[str], seed: int):
        self.cfg = cfg
        self.workers = select(hosts, cfg["workers"])
        self.sources = select(hosts, cfg["sources"])
        idle = _rng(seed, 0).uniform(0.0, cfg["idle_max_s"], len(self.workers))
        self.idle: Dict[str, float] = {
            w: float(x) for w, x in zip(self.workers, idle)
        }
        self._rep = _rng(seed, 1)
        self._next_tid = 0

    def tasks(self, n: int) -> List[Task]:
        t = self.cfg["task"]
        idx = self._rep.integers(0, len(self.sources), size=(n, t["replicas"]))
        out = []
        for row in idx:
            tid = self._next_tid
            self._next_tid += 1
            size = float(t["size_base"] + (tid % t["size_steps"]) * t["size_step"])
            out.append((tid, size, float(t["compute_s"]),
                        tuple(self.sources[j] for j in row)))
        return out


def _quantile_blocks(rng, block: int, inv_cdf) -> Iterator[float]:
    """Quantiles ``inv_cdf((i + 0.5) / block)`` for ``i < block``, each
    block in a fresh seeded order."""
    q = [inv_cdf((i + 0.5) / block) for i in range(block)]
    while True:
        for i in rng.permutation(block):
            yield q[i]


def job_sizes(traffic: dict, seed: int) -> Iterator[int]:
    spec = traffic["job_tasks"]
    if "fixed" in spec:
        while True:
            yield int(spec["fixed"])
    lo, hi = spec["loguniform"]
    yield from (int(round(x)) for x in _quantile_blocks(
        _rng(seed, 2), spec["block"], lambda u: lo * (hi / lo) ** u))


def mean_job_tasks(traffic: dict) -> float:
    spec = traffic["job_tasks"]
    if "fixed" in spec:
        return float(spec["fixed"])
    lo, hi = spec["loguniform"]
    b = spec["block"]
    return sum(round(lo * (hi / lo) ** ((i + 0.5) / b)) for i in range(b)) / b


def arrivals(traffic: dict, seed: int) -> Iterator[float]:
    """Simulated arrival times of the jobs, from 0."""
    spec = traffic["arrival"]
    if "every_s" in spec:
        j = 0
        while True:
            yield j * spec["every_s"]
            j += 1
    rate = spec["poisson_tasks_per_s"] / mean_job_tasks(traffic)
    t = 0.0
    for gap in _quantile_blocks(_rng(seed, 3), spec["block"],
                                lambda u: -math.log(1.0 - u) / rate):
        yield t
        t += gap


def jobs(dep: Deployment, traffic: dict, seed: int) -> Iterator[Tuple[float, List[Task]]]:
    """``(simulated arrival, tasks)`` for each job, forever."""
    for at, n in zip(arrivals(traffic, seed), job_sizes(traffic, seed)):
        yield at, dep.tasks(n)


def link_order(links: Sequence[str], traffic: dict, k: int) -> List[str]:
    """The mix's failing links in one fixed order, the same for every
    seed.  The pool is every link whose name starts with ``fail_links``,
    in the fabric's construction order; link ``i`` of it falls in group
    (sum of the digits of ``i`` in base k/2) mod k/2, and the order is
    group 0, then group 1, and so on.  In a k-ary fat-tree, whose
    aggregation-core links are built pod by pod, switch by switch, core
    by core, each group takes one link of every aggregation switch, pod
    by pod, its core rotating with pod and switch."""
    h = k // 2
    pool = [l for l in links if l.startswith(traffic["events"]["fail_links"])]
    groups: List[List[str]] = [[] for _ in range(h)]
    for i, name in enumerate(pool):
        digits, x = 0, i
        while x:
            digits, x = digits + x % h, x // h
        groups[digits % h].append(name)
    return [name for g in groups for name in g]


def failing_links(links: Sequence[str], traffic: dict, k: int,
                  seed: int) -> Tuple[List[str], List[str], List[str]]:
    """``(warm-up, window, traced window)``: the last ``warmup_events``
    links of :func:`link_order`, its first ``window_events`` and its
    first ``traced_events``, each in a seeded order, so every seed fails
    the same links in another order."""
    order = link_order(links, traffic, k)
    n_warm, n_win = traffic["warmup_events"], traffic["window_events"]
    if n_warm + n_win > len(order) or traffic["traced_events"] > n_win:
        raise ValueError(f"{n_warm} warm-up and {n_win} window events need "
                         f"distinct links, and the pool has {len(order)}")
    rng = _rng(seed, 4)

    def shuffled(names):
        return [names[i] for i in rng.permutation(len(names))]

    return (shuffled(order[len(order) - n_warm:]), shuffled(order[:n_win]),
            shuffled(order[:traffic["traced_events"]]))
