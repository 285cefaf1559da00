"""One run of one benchmark cell: build, warm up, drive the window, check.

Everything about a cell is found by name: its configuration in
``bench/configs/<config>.json``, whose ``fabric`` is built by
``bench/fabrics/<fabric>.py`` and whose ``controller`` by
``bench/controllers/<controller>.py``, and whose ``reference`` (the
semantics the program is held to) is ``bench/references/<reference>.py``;
its traffic mix in
``bench/traffic/<mix>.json``, whose ``loop`` (closed, open, failure
events) is ``bench/loops/<loop>.py``; and each metric's reader in
``bench/metrics/<metric>.py``.  A new cell, mix or metric is new files
plus new entries in ``BENCHMARK.json``; nothing here changes.

Every call into the program is logged as a plain operation.  After the
window, once the device's peak memory is read and the program's state
is freed, the configuration's reference replays the log and what the
program produced is compared with its own output, each number compared
under the reference's limit.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import statistics
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

import generator
import trace_reduce
import tracing

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# -- finding the pieces by name ---------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def entry(entries: List[dict], name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}")


_modules: Dict[Path, object] = {}


def module_at(path: Path):
    """Import a file by path (metric files have dots in their names)."""
    path = Path(path)
    mod = _modules.get(path)
    if mod is None:
        if not path.is_file():
            raise FileNotFoundError(path)
        name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[path] = mod
    return mod


def fabric_module(name: str, bench: Path = BENCH):
    return module_at(bench / "fabrics" / f"{name}.py")


def metric_module(name: str, bench: Path = BENCH):
    return module_at(bench / "metrics" / f"{name}.py")


def controller_module(name: str, bench: Path = BENCH):
    return module_at(bench / "controllers" / f"{name}.py")


def loop_module(name: str, bench: Path = BENCH):
    return module_at(bench / "loops" / f"{name}.py")


def reference_module(name: str, bench: Path = BENCH):
    """The semantics a configuration is held to: ``LIMITS``,
    ``ONLY_WHERE``, ``program_schedule(ctrl)``, ``replay(ref_fabric, cfg,
    dep, ops, dtype)`` and ``compare(got, want)``."""
    return module_at(bench / "references" / f"{name}.py")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell_parts(spec: dict, workload: str, root: Path = ROOT) -> Tuple[dict, dict, dict]:
    """(cell entry, configuration, traffic mix) of one workload."""
    cell = entry(spec["workloads"], workload)
    cfg = load_json(root / entry(spec["configs"], cell["config"])["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, traffic


# -- the program side -------------------------------------------------------

def _links_of(fab) -> List[tuple]:
    return [(n, l.a, l.b, float(l.capacity)) for n, l in fab.links.items()]


def fabric_mismatch(fab, ref: dict) -> int:
    """Links and hosts on which the program's fabric and the reference's
    own build disagree (they must agree before anything is compared)."""
    from repro.core.topology import storage_hosts

    got, want = _links_of(fab), [tuple(l[:3]) + (float(l[3]),) for l in ref["links"]]
    hosts, want_hosts = storage_hosts(fab), ref["hosts"]
    wrong = sum(1 for a, b in zip(got, want) if a != b) + abs(len(got) - len(want))
    wrong += sum(1 for a, b in zip(hosts, want_hosts) if a != b)
    return wrong + abs(len(hosts) - len(want_hosts))


class Program:
    """The program under test, with every call logged as an operation."""

    def __init__(self, cfg: dict, seed: int, bench: Path = BENCH):
        self.ref = reference_module(cfg["reference"], bench)
        fab_mod = fabric_module(cfg["fabric"], bench)
        self.ref_fabric = fab_mod.reference(cfg)
        fab = fab_mod.program(cfg)
        wrong = fabric_mismatch(fab, self.ref_fabric)
        if wrong:
            raise RuntimeError(f"the program's fabric differs from the reference's "
                               f"in {wrong} links or hosts: nothing can be compared")
        self.dep = generator.Deployment(cfg, self.ref_fabric["hosts"], seed)
        self.ctrl = controller_module(cfg["controller"], bench).build(fab, self.dep, cfg)
        self.ops: List[tuple] = []
        self.jobs_at: Dict[int, float] = {}

    def submit(self, at: float, tasks) -> int:
        from repro.core.tasks import Task

        jid = self.ctrl.submit([Task(*t) for t in tasks], at=at)
        self.ops.append(("submit", jid, at, tasks))
        self.jobs_at[jid] = at
        return jid

    def run_until(self, t: float) -> None:
        self.ctrl.run_until(t)
        self.ops.append(("run_until", t))

    def fail_link(self, name: str, at: float) -> None:
        self.ctrl.fail_link(name, at=at)
        self.ops.append(("fail_link", name, at))

    def recover_link(self, name: str, at: float) -> None:
        self.ctrl.recover_link(name, at=at)
        self.ops.append(("recover_link", name, at))

    def counters(self) -> Dict[str, float]:
        """The device backend's counters and every counter and counter
        group of the controller's ``repro.obs`` registry."""
        from repro.kernels import ts_plan

        out = {f"ts_plan_device.{k}": v for k, v in ts_plan.device_stats().items()}
        values = self.ctrl.obs.dump_values()
        out.update(values["counters"])
        for group, cells in values["groups"].items():
            for k, v in cells.items():
                out[f"{group}.{k}"] = v
        return out


# -- set-up shared by the loops (``bench/loops/<loop>.py``) ----------------

def place_backlog(drv: Program, cfg: dict) -> None:
    if cfg.get("backlog_tasks"):
        drv.submit(0.0, drv.dep.tasks(cfg["backlog_tasks"]))
        drv.run_until(0.0)


def warm_jobs(drv, cfg, traffic, seed):
    """The backlog, then the mix's warm-up jobs back to back.  Returns
    the job stream, positioned after them."""
    place_backlog(drv, cfg)
    gen = generator.jobs(drv.dep, traffic, seed)
    for _ in range(traffic["warmup_jobs"]):
        at, tasks = next(gen)
        drv.submit(at, tasks)
        drv.run_until(at)
    return gen


class Window:
    """The measured window: host clock always, and with ``tracer`` the
    spans and the profiler too."""

    def __init__(self, tracer: Optional[tracing.Tracer], trace_dir: Optional[str]):
        self.tracer, self.trace_dir = tracer, trace_dir
        self.t0 = self.t1 = 0.0
        self.ns = (0, 0)

    def __enter__(self):
        if self.tracer is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # The benchmark's annotations only: the runtime's own host
            # events share the trace's 2 GB with the device's, which loses
            # its later operations once the trace is full.
            opts.host_tracer_level = 1
            self.tracer.install()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(tracing.PREFIX + "window")
            self._ann.__enter__()
        self._ns0 = time.perf_counter_ns()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self.ns = (self._ns0, time.perf_counter_ns())
        if self.tracer is not None:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.tracer.uninstall()
        return False


# -- one run ----------------------------------------------------------------

def chip_refusal(chips: int) -> Optional[str]:
    """Why this process cannot measure ``chips`` TPU chips, or ``None``
    when it can.  The program places its own compile cache in the
    checkout at its first device call."""
    import jax

    backend, n = jax.default_backend(), len(jax.devices())
    if backend != "tpu" or n < chips:
        return (f"needs {chips} TPU chip(s); jax's default backend is "
                f"{backend!r} with {n} device(s)")
    return None


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def check(drv: Program, cfg: dict) -> Tuple[dict, dict]:
    """Replay the logged operations in the configuration's reference and
    count what differs.  The program's controller is released before the
    replay.  Returns the counts and what both sides produced."""
    got = drv.ref.program_schedule(drv.ctrl)
    drv.ctrl = None
    gc.collect()
    want = replay(drv, cfg)
    return drv.ref.compare(got, want), {"got": got, "want": want}


def checks(counts: dict, ref) -> dict:
    """The compared numbers beside their limits.  The comparison has to
    count every number of the reference's ``LIMITS``; one that its
    ``ONLY_WHERE`` names is compared only where the count named beside it
    is above 0 (a cell without failures has no reroute to compare)."""
    missing = sorted(set(ref.LIMITS) - set(counts))
    if missing:
        raise ValueError(f"the reference's comparison did not count {missing}")

    def due(k):
        return k not in ref.ONLY_WHERE or counts[ref.ONLY_WHERE[k]] > 0

    return {k: {"value": counts[k], "limit": lim} for k, lim in ref.LIMITS.items()
            if due(k)}


def replay(drv: Program, cfg: dict, dtype=np.float64):
    """The reference's output for the run's operations, in ``dtype``."""
    return drv.ref.replay(drv.ref_fabric, cfg, drv.dep, drv.ops, dtype)


def mean_jct(ctrl, jobs_at: Dict[int, float]) -> Optional[float]:
    jcts = [max(a.finish for a in ctrl.jobs[j].assignments) - at
            for j, at in jobs_at.items() if ctrl.jobs[j].assignments]
    return statistics.fmean(jcts) if jcts else None


def run_cell(spec: dict, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, cfg: Optional[dict] = None,
             traffic: Optional[dict] = None,
             sink: Optional[dict] = None) -> Tuple[dict, List[str]]:
    """One run.  Returns the result line's object and the lines for
    standard error (the compared numbers last).  ``cfg``/``traffic``
    replace the files named by the cell (tests use tiny ones); ``sink``
    receives the program and both sides' outputs (the control reads
    them)."""
    _cell, cfg_file, traffic_file = cell_parts(spec, workload, root)
    cfg = cfg_file if cfg is None else cfg
    traffic = traffic_file if traffic is None else traffic
    drv = Program(cfg, seed, root / "bench")
    loop = loop_module(traffic["loop"], root / "bench").run
    tracer = tracing.Tracer() if trace else None
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        holder = {}

        def window():
            holder["w"] = Window(tracer, tdir)
            holder["c0"] = drv.counters()
            holder["setup_s"] = time.perf_counter() - t_start
            return holder["w"]

        out = loop(drv, cfg, traffic, seed, seconds, window)
        w = holder["w"]
        c1 = drv.counters()
        counters = {k: c1[k] - holder["c0"].get(k, 0) for k in c1}
        red, t_trace = None, [time.perf_counter()]
        if trace:
            loaded = trace_reduce.load(tdir)
            t_trace.append(time.perf_counter())
            red = trace_reduce.reduce(loaded, [c[-1] for c in tracer.calls], w.ns[0])
            t_trace.append(time.perf_counter())
    dev = device_info()
    first = out["first_job"]
    jct = mean_jct(drv.ctrl, {j: at for j, at in drv.jobs_at.items() if j >= first})
    t_check = time.perf_counter()
    counts, prog = check(drv, cfg)
    if sink is not None:
        sink.update(prog, program=drv, cfg=cfg, counts=counts)
    t_check = time.perf_counter() - t_check
    rec = dict(out, setup_s=holder["setup_s"], window_s=w.t1 - w.t0,
               counters=counters, trace=red, device_kind=dev["kind"],
               spans=None if tracer is None else tracer.spans,
               calls=None if tracer is None else tracer.calls,
               span_window=w.ns)
    metric_specs = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in metric_specs:
        if not applies(m, workload):
            continue
        value = metric_module(m["name"], root / "bench").read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
    compared = checks(counts, drv.ref)
    # A run in which nothing was compared is not shown correct.
    correct = bool(compared) and all(c["value"] <= c["limit"] for c in compared.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = red["breakdown"]
    result["checks"] = compared
    lines = [
        f"window: {rec['window_s']:.6f} s, set-up {rec['setup_s']:.6f} s, "
        f"attempted {out['attempted']}, failed {out['failed']}",
        f"compiles inside the window: {counters.get('ts_plan_device.traces', 0)}",
        f"the loop's counts: {json.dumps({k: v for k, v in out.items() if not isinstance(v, list)})}",
        f"mean job completion time of the window's jobs: {jct} s (simulated)",
        f"counters over the window: {json.dumps(counters, sort_keys=True)}",
    ]
    if trace:
        lines.append(f"trace: written in {t_trace[0] - w.t1:.3f} s, read in "
                     f"{t_trace[1] - t_trace[0]:.3f} s, reduced in "
                     f"{t_trace[2] - t_trace[1]:.3f} s; device operations recorded "
                     f"over {red['window_s']:.3f} s of the {red['traced_s']:.3f} s window")
    if out.get("lag_s"):
        lag = sorted(out["lag_s"])
        lines.append(f"generator lag: median {lag[len(lag) // 2]:.6f} s, "
                     f"max {lag[-1]:.6f} s over {len(lag)} submits")
    lines.append(f"compared with the reference in {t_check:.3f} s: "
                 + ", ".join(f"{k} {v}" for k, v in counts.items()))
    if not compared:
        lines.append("check: nothing was compared")
    lines += [f"check {k}: {c['value']} (limit {c['limit']})" for k, c in compared.items()]
    return result, lines
