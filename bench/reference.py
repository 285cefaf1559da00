"""Plain reference of the BASS control plane (paper Algorithm 1 on a
time-slot ledger), written apart from the program under test.

It imports nothing of the program and takes nothing the program made: it
builds its own graph from the configuration's link list, routes with its
own tree walk or hop-count Dijkstra and Yen search, books a plain
``[links, slots]`` array that never retires, and replays the same
operations the benchmark drove (``submit``, ``run_until``, ``fail_link``,
``recover_link``) one task and one victim at a time.  The semantics it
follows are the paper's and the repository's documented ones (DESIGN.md
§1, §4):

* a task runs on the least-loaded available replica holder when that is
  no later than the least-idle worker (``minnow``); otherwise its data
  moves to ``minnow`` when the greedy transfer finishes earlier, and
  with no holder among the workers it always moves;
* a transfer starts at the destination's idle time and takes, slot by
  slot, the whole residue of its path until its bytes are delivered;
* single-path routing picks the replica whose path has the most
  residual bandwidth at that time; multipath routing plans every
  (replica, candidate path) pair and keeps the earliest end;
* a link failure releases every in-flight transfer's tail from the
  failure slot on and replans its remaining bytes on the best surviving
  candidate, never earlier than it was to start; the touched nodes'
  compute timelines are then replayed.

``dtype`` sets the precision of the ledger and of the transfer
arithmetic.  ``numpy.float64`` is the reference; ``numpy.float32`` is
the control that a sound comparison has to reject.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EPS = 1e-9
BIG = 1 << 30


@dataclass
class Plan:
    links: Tuple[str, ...]  # link names in path order
    start: float
    end: float
    fracs: Tuple[Tuple[int, float], ...]  # (absolute slot, fraction)


@dataclass
class Placed:
    tid: int
    node: str
    source: Optional[str]
    plan: Optional[Plan]
    start: float
    finish: float


@dataclass
class Job:
    jid: int
    at: float
    tasks: List[tuple]  # (tid, size, compute, replicas)
    placed: List[Placed] = field(default_factory=list)


class Net:
    """Links, adjacency and routing of one fabric.

    ``links`` is ``[(name, a, b, capacity), ...]`` in construction order;
    ``parent`` maps a tree member to ``(parent, uplink name)`` when every
    link is an uplink, and is ``None`` for a graph routed by Dijkstra."""

    def __init__(self, links, parent=None, k_paths: int = 4):
        self.ends = {n: (a, b) for n, a, b, _ in links}
        self.adj: Dict[str, List[str]] = {}
        for n, a, b, _ in links:
            self.adj.setdefault(a, []).append(n)
            self.adj.setdefault(b, []).append(n)
        self.names = sorted(self.ends)
        self.row = {n: i for i, n in enumerate(self.names)}
        caps = {n: c for n, _, _, c in links}
        self.capacity = np.array([caps[n] for n in self.names], np.float64)
        self.parent = parent
        self.k = k_paths
        #: node -> (its one neighbour, the link to it) for degree-1 nodes
        self.leaf = {n: (self.other(ls[0], n), ls[0])
                     for n, ls in self.adj.items() if len(ls) == 1}
        self._paths: Dict[tuple, Tuple[Tuple[str, ...], ...]] = {}

    def other(self, name: str, node: str) -> str:
        a, b = self.ends[name]
        return b if node == a else a

    def tree_path(self, src: str, dst: str) -> Tuple[str, ...]:
        """Up from ``src`` to the lowest common ancestor, then down."""
        depth = {dst: 0}
        down = []
        n = dst
        while n in self.parent:
            n, l = self.parent[n]
            down.append(l)
            depth[n] = len(down)
        up = []
        n = src
        while n not in depth:
            n, l = self.parent[n]
            up.append(l)
        return tuple(up + list(reversed(down[: depth[n]])))

    def dijkstra(self, src, dst, banned_links=frozenset(), banned_nodes=frozenset()):
        if src == dst:
            return ()
        dist = {src: 0}
        prev = {}
        pq = [(0, src)]
        while pq:
            d, u = heapq.heappop(pq)
            if u == dst:
                break
            if d > dist.get(u, BIG):
                continue
            for name in sorted(self.adj[u]):
                if name in banned_links:
                    continue
                v = self.other(name, u)
                if v in banned_nodes:
                    continue
                if d + 1 < dist.get(v, BIG):
                    dist[v] = d + 1
                    prev[v] = (u, name)
                    heapq.heappush(pq, (d + 1, v))
        if dst not in prev:
            return None
        out = []
        n = dst
        while n != src:
            n, via = prev[n]
            out.append(via)
        return tuple(reversed(out))

    def yen(self, src, dst, k, banned=frozenset()):
        """Up to ``k`` loop-free min-hop paths; the pool orders by
        (hops, link-name sequence).

        Between two leaves (hosts), every path starts and ends with their
        one link and no spur search can use either again, so the search
        runs between the switches they hang from, and is kept per
        switch pair."""
        if src in self.leaf and dst in self.leaf and src != dst:
            (a, la), (b, lb) = self.leaf[src], self.leaf[dst]
            if la in banned or lb in banned:
                return ()
            key = (a, b, k, banned)
            if key not in self._paths:
                self._paths[key] = self._yen(a, b, k, banned)
            return tuple((la,) + p + (lb,) for p in self._paths[key])
        return self._yen(src, dst, k, banned)

    def _yen(self, src, dst, k, banned):
        first = self.dijkstra(src, dst, banned)
        if first is None:
            return ()
        found = [first]
        seen = {first}
        pool = []
        while len(found) < k:
            last = found[-1]
            nodes = [src]
            for name in last:
                nodes.append(self.other(name, nodes[-1]))
            for j in range(len(last)):
                root = last[:j]
                ban = set(banned)
                for p in found:
                    if len(p) > j and p[:j] == root:
                        ban.add(p[j])
                spur = self.dijkstra(nodes[j], dst, frozenset(ban),
                                     frozenset(nodes[:j]))
                if spur is None:
                    continue
                cand = root + spur
                if cand not in seen:
                    seen.add(cand)
                    heapq.heappush(pool, (len(cand), len(cand), cand))
            if not pool:
                break
            found.append(heapq.heappop(pool)[2])
        return tuple(found)

    def path(self, src: str, dst: str, dead=frozenset()) -> Tuple[str, ...]:
        """The one min-hop path single-path routing uses (``()`` when
        every path crosses a dead link)."""
        if self.parent is not None and not dead:
            return self.tree_path(src, dst)
        return (self.candidates(src, dst, dead, 1) or ((),))[0]

    def candidates(self, src, dst, dead, k=None) -> Tuple[Tuple[str, ...], ...]:
        """Surviving candidate paths: the cached ``k`` paths that avoid
        every dead link, or a fresh search around them when none does."""
        k = self.k if k is None else k
        key = (src, dst, k)
        if key not in self._paths:
            self._paths[key] = self.yen(src, dst, k)
        alive = tuple(p for p in self._paths[key] if not dead.intersection(p))
        if alive or not dead:
            return alive
        return self.yen(src, dst, k, frozenset(dead))


class Ledger:
    """``reserved[link row, absolute slot]`` fractions, grown on demand."""

    def __init__(self, net: Net, slot_s: float, dtype=np.float64):
        self.net = net
        self.f = dtype
        self.dur = dtype(slot_s)
        self.cap = net.capacity.astype(dtype)
        self.res = np.zeros((len(net.names), 256), dtype)

    def ensure(self, slot: int) -> None:
        if slot >= self.res.shape[1]:
            width = max(2 * self.res.shape[1], slot + 1)
            grown = np.zeros((self.res.shape[0], width), self.f)
            grown[:, : self.res.shape[1]] = self.res
            self.res = grown

    def rows(self, names) -> List[int]:
        return [self.net.row[n] for n in names]

    def slot_of(self, t) -> int:
        return int(math.floor(t / self.dur + EPS))

    def path_bandwidth(self, names, t) -> float:
        r = self.rows(names)
        p = self.slot_of(t)
        if p >= self.res.shape[1]:
            return self.cap[r].min()
        return ((1.0 - self.res[r, p]) * self.cap[r]).min()

    def plan(self, size, names, t0) -> Plan:
        names = tuple(names)
        if size <= 0 or not names:
            return Plan(names, t0, t0, ())
        r = self.rows(names)
        cap = self.cap[r].min()
        size = self.f(size)
        s0 = self.slot_of(t0)
        window = 64
        while True:
            self.ensure(s0 + window)
            resid = 1.0 - self.res[r, s0 : s0 + window].max(axis=0)
            bw = resid * cap
            secs = np.full(window, self.dur, self.f)
            secs[0] = (s0 + 1) * self.dur - self.f(t0)
            cum = np.cumsum(bw * secs)
            hit = int(np.searchsorted(cum, size - self.f(EPS)))
            if hit < window:
                break
            window *= 4
        used = np.nonzero(bw[: hit + 1] > EPS)[0]
        start = max(t0, (s0 + int(used[0])) * self.dur)
        before = cum[hit - 1] if hit > 0 else self.f(0.0)
        end = max(t0, (s0 + hit) * self.dur) + (size - before) / bw[hit]
        fracs = tuple((s0 + int(j), float(resid[j])) for j in used)
        return Plan(names, float(start), float(end), fracs)

    def commit(self, plan: Plan) -> None:
        if not plan.fracs:
            return
        r = np.asarray(self.rows(plan.links))[:, None]
        slots = np.asarray([s for s, _ in plan.fracs])
        self.ensure(int(slots.max()))
        new = self.res[r, slots] + np.asarray([f for _, f in plan.fracs], self.f)
        if (new > 1.0 + 1e-6).any():
            raise ValueError(f"over-reservation on {plan.links}")
        self.res[r, slots] = np.minimum(new, 1.0)

    def plan_bytes(self, plan: Plan) -> float:
        if not plan.fracs:
            return 0.0
        cap = self.cap[self.rows(plan.links)].min()
        slots = np.array([s for s, _ in plan.fracs])
        fracs = np.array([f for _, f in plan.fracs], self.f)
        lo = np.maximum(self.f(plan.start), slots * self.dur)
        hi = np.minimum(self.f(plan.end), (slots + 1) * self.dur)
        return float((fracs * cap * np.clip(hi - lo, 0.0, None)).sum())

    def release_after(self, plan: Plan, t: float) -> Plan:
        """Free every slot from ``t``'s slot on (that slot whole); keep
        the slots delivered before it."""
        if not plan.fracs or t >= plan.end:
            return plan
        cut = plan.fracs[0][0] if t <= plan.start else self.slot_of(t)
        tail = [(s, f) for s, f in plan.fracs if s >= cut]
        if tail:
            r = np.asarray(self.rows(plan.links))[:, None]
            slots = np.asarray([s for s, _ in tail])
            fr = np.asarray([f for _, f in tail], self.f)
            self.res[r, slots] = np.maximum(self.res[r, slots] - fr, 0.0)
        keep = tuple((s, f) for s, f in plan.fracs if s < cut)
        if not keep:
            return Plan(plan.links, plan.start, plan.start, ())
        return Plan(plan.links, plan.start,
                    float(min(plan.end, cut * self.dur)), keep)


class Reference:
    """Sequential replay of a benchmark's operation log."""

    def __init__(self, net: Net, workers: Sequence[str], idle: Dict[str, float],
                 slot_s: float, multipath: bool, dtype=np.float64):
        self.net = net
        self.ledger = Ledger(net, slot_s, dtype)
        self.multipath = multipath
        self.workers = sorted(workers)
        self.windex = {w: i for i, w in enumerate(self.workers)}
        self.idle = np.array([idle[w] for w in self.workers], np.float64)
        self.idle0 = dict(idle)
        self.now = 0.0        # the controller's clock
        self.state_now = 0.0  # the clock idle times were last clamped to
        self.queue: List[tuple] = []
        self.seq = 0
        self.jobs: Dict[int, Job] = {}
        self.live: Dict[int, float] = {}
        self.dead: set = set()
        self.log: List[tuple] = []

    # -- operations --------------------------------------------------------
    def apply(self, op: tuple) -> None:
        kind = op[0]
        if kind == "submit":
            _, jid, at, tasks = op
            self.jobs[jid] = Job(jid, at, list(tasks))
            self._push(at, "job", jid)
        elif kind == "fail_link":
            self._push(op[2], "down", op[1])
        elif kind == "recover_link":
            self._push(op[2], "up", op[1])
        elif kind == "run_until":
            t = op[1]
            while self.queue and self.queue[0][0] <= t + EPS:
                at, _, what, arg = heapq.heappop(self.queue)
                self.now = max(self.now, at)
                self.state_now = max(self.state_now, at)
                np.maximum(self.idle, self.state_now, out=self.idle)
                if what == "job":
                    self._place_job(self.jobs[arg])
                elif what == "down":
                    self.dead.add(arg)
                    self._reroute(at)
                else:
                    self.dead.discard(arg)
            self.now = max(self.now, t)
        else:
            raise ValueError(f"unknown operation {kind!r}")

    def _push(self, at, what, arg) -> None:
        heapq.heappush(self.queue, (at, self.seq, what, arg))
        self.seq += 1

    # -- Algorithm 1 --------------------------------------------------------
    def _minnow(self) -> str:
        return self.workers[int(np.argmin(self.idle))]

    def _idle(self, node: str) -> float:
        return float(self.idle[self.windex[node]])

    def _set_idle(self, node: str, t: float) -> None:
        self.idle[self.windex[node]] = t

    def _source(self, replicas, dst, at, size):
        """(source, plan) for moving a task's data to ``dst`` at ``at``."""
        led = self.ledger
        if self.multipath:
            best = None
            for rep in replicas:
                if rep == dst:
                    continue
                for i, p in enumerate(self.net.candidates(rep, dst, self.dead)):
                    plan = led.plan(size, p, at)
                    key = (plan.end, len(p), rep, i)
                    if best is None or key < best[0]:
                        best = (key, rep, plan)
            if best is None:
                raise RuntimeError(f"no surviving path to {dst}")
            return best[1], best[2]
        best = None
        for rep in replicas:
            if rep == dst:
                continue
            p = self.net.path(rep, dst, self.dead)
            if not p:
                continue
            key = (-led.path_bandwidth(p, at), len(p), rep)
            if best is None or key < best[0]:
                best = (key, rep, p)
        if best is None:
            raise RuntimeError(f"no surviving path to {dst}")
        return best[1], led.plan(size, best[2], at)

    def _remote(self, tid, compute, node, src, plan) -> Placed:
        self.ledger.commit(plan)
        start = plan.end if plan.fracs else self._idle(node)
        finish = start + compute
        self._set_idle(node, finish)
        return Placed(tid, node, src, plan, start, finish)

    def place(self, task) -> Placed:
        tid, size, compute, replicas = task
        minnow = self._minnow()
        holders = [n for n in replicas if n in self.windex]
        loc = min(holders, key=lambda n: (self._idle(n), n)) if holders else None
        t_min = self._idle(minnow)
        if loc is not None:
            t_loc = self._idle(loc)
            if minnow == loc or t_loc <= t_min + EPS:
                self._set_idle(loc, t_loc + compute)
                return Placed(tid, loc, None, None, t_loc, t_loc + compute)
            src, plan = self._source(replicas, minnow, t_min, size)
            tm = plan.end - plan.start if plan.fracs else 0.0
            if (compute + 0.0 + t_min) + tm < (compute + 0.0 + t_loc) - EPS:
                return self._remote(tid, compute, minnow, src, plan)
            self._set_idle(loc, t_loc + compute)
            return Placed(tid, loc, None, None, t_loc, t_loc + compute)
        src, plan = self._source(replicas, minnow, t_min, size)
        return self._remote(tid, compute, minnow, src, plan)

    def _place_job(self, job: Job) -> None:
        for task in job.tasks:
            a = self.place(task)
            job.placed.append(a)
            if a.plan is not None and a.plan.fracs:
                self.live[job.jid] = max(self.live.get(job.jid, 0.0), a.plan.end)

    # -- failures ---------------------------------------------------------
    def _reroute(self, at: float) -> None:
        led = self.ledger
        touched, moved = set(), set()
        for jid, latest in list(self.live.items()):
            job = self.jobs[jid]
            if latest <= at + EPS:
                del self.live[jid]
                continue
            tasks = {t[0]: t for t in job.tasks}
            for a in job.placed:
                plan = a.plan
                if plan is None or not plan.fracs:
                    continue
                if plan.end <= at + EPS or not self.dead.intersection(plan.links):
                    continue
                total = led.plan_bytes(plan)
                kept = led.release_after(plan, at)
                delivered = led.plan_bytes(kept)
                remaining = max(total - delivered, 0.0)
                src, new = self._source(tasks[a.tid][3], a.node,
                                        max(at, plan.start), remaining)
                led.commit(new)
                self.log.append((a.tid, plan.links, new.links, delivered,
                                 remaining, new.end))
                a.source, a.plan = src, new
                touched.add(a.node)
                moved.add(a.tid)
                self.live[jid] = max(self.live.get(jid, 0.0), new.end)
        if touched:
            self._retime(touched, moved)

    def _retime(self, nodes, moved) -> None:
        """Replay each touched node's compute timeline in committed order."""
        by_node = {n: [] for n in nodes}
        for job in self.jobs.values():
            for a in job.placed:
                if a.node in by_node:
                    by_node[a.node].append((job.at, a))
        for node, items in by_node.items():
            items.sort(key=lambda x: (x[1].start, x[1].tid))
            t = self.idle0.get(node, 0.0)
            for at, a in items:
                ready = at
                if a.plan is not None and a.plan.fracs:
                    ready = max(ready, a.plan.end)
                compute = a.finish - a.start
                start = max(t, ready)
                if a.tid not in moved:
                    start = max(start, a.start)
                a.start = start
                a.finish = start + compute
                t = a.finish
            self._set_idle(node, max(t, self.state_now))

    # -- results ----------------------------------------------------------
    def schedule(self) -> Dict[int, tuple]:
        return {a.tid: canon(a.node, a.source, a.plan, a.start, a.finish)
                for job in self.jobs.values() for a in job.placed}


def canon(node, source, plan: Optional[Plan], start, finish) -> tuple:
    """One assignment as plain, exactly comparable values."""
    tr = None
    if plan is not None:
        tr = (tuple(plan.links), float(plan.start), float(plan.end),
              tuple((int(s), float(f)) for s, f in plan.fracs))
    return (node, source, float(start), float(finish), tr)


def compare(got: Dict[int, tuple], want: Dict[int, tuple],
            got_log: Sequence[tuple], want_log: Sequence[tuple]) -> dict:
    """Counts of assignments and reroute records that differ (``reroutes``
    is the longer of the two logs)."""
    tids = set(got) | set(want)
    wrong = sum(1 for t in tids if got.get(t) != want.get(t))
    n = max(len(got_log), len(want_log))
    log_wrong = sum(
        1 for i in range(n)
        if i >= len(got_log) or i >= len(want_log) or got_log[i] != want_log[i]
    )
    return {"assignments": len(want), "assignments_wrong": wrong,
            "reroutes": n, "reroutes_wrong": log_wrong}
