"""The traffic generator repeats under one seed, and every seed offers
the same work in another order."""
import itertools

import bench_tiny
import numpy as np
import pytest

import generator
import harness


def take(it, n):
    return list(itertools.islice(it, n))


def test_same_seed_same_stream_and_deployment():
    cfg, traffic = bench_tiny.tiny("hadoop_yahoo.saturate")
    hosts = [f"h{i}" for i in range(24)]
    a = generator.Deployment(cfg, hosts, 2**31 + 77)
    b = generator.Deployment(cfg, hosts, 2**31 + 77)
    assert a.idle == b.idle
    assert take(generator.jobs(a, traffic, 2**31 + 77), 5) == take(
        generator.jobs(b, traffic, 2**31 + 77), 5)
    c = generator.Deployment(cfg, hosts, 3)
    assert c.idle != a.idle
    assert all(0.0 <= v < cfg["idle_max_s"] for v in a.idle.values())


def test_task_shape_follows_the_configuration():
    cfg, _ = bench_tiny.tiny("hadoop_yahoo.saturate")
    dep = generator.Deployment(cfg, [f"h{i}" for i in range(24)], 1)
    tasks = dep.tasks(20)
    assert [t[0] for t in tasks] == list(range(20))
    t = cfg["task"]
    assert {x[1] for x in tasks} == {t["size_base"] + i * t["size_step"]
                                     for i in range(t["size_steps"])}
    assert all(len(x[3]) == t["replicas"] and x[2] == t["compute_s"] for x in tasks)
    assert dep.tasks(1)[0][0] == 20


def test_storm_hosts_split_sources_from_workers():
    cfg, _ = bench_tiny.tiny("fattree_k8.link_storm")
    hosts = [f"h{i}" for i in range(16)]
    dep = generator.Deployment(cfg, hosts, 9)
    assert dep.sources == hosts[:8] and dep.workers == hosts[8:]
    assert all(set(t[3]) <= set(hosts[:8]) for t in dep.tasks(50))


def test_open_mix_offers_the_same_sizes_and_gaps_to_every_seed():
    traffic = {"job_tasks": {"loguniform": [8, 512], "block": 64},
               "arrival": {"poisson_tasks_per_s": 20480.0, "block": 64}}
    a = take(generator.job_sizes(traffic, 1), 64)
    b = take(generator.job_sizes(traffic, 2), 64)
    assert a != b and sorted(a) == sorted(b)
    assert min(a) == 8 and max(a) <= 512
    assert np.median(a) == pytest.approx(64, rel=0.1)
    t1 = take(generator.arrivals(traffic, 1), 65)
    t2 = take(generator.arrivals(traffic, 2), 65)
    assert t1[0] == 0.0 and all(x < y for x, y in zip(t1, t1[1:]))
    assert t1[-1] == pytest.approx(t2[-1])  # one block: the same total gap
    mean = generator.mean_job_tasks(traffic)
    assert 64 * mean / t1[-1] == pytest.approx(20480.0, rel=0.15)


def test_closed_mix_and_failing_links():
    traffic = {"job_tasks": {"fixed": 1024}, "arrival": {"every_s": 0.05},
               "events": {"fail_links": "ac/"}, "warmup_events": 1,
               "window_events": 2, "traced_events": 1}
    assert take(generator.arrivals(traffic, 4), 3) == [0.0, 0.05, 0.1]
    assert take(generator.job_sizes(traffic, 4), 2) == [1024, 1024]
    links = ["ac/a", "ea/x", "ac/b", "ac/c", "ac/d"]
    # k = 4: the pool a, b, c, d has binary digit sums 0, 1, 1, 2
    assert generator.link_order(links, traffic, 4) == ["ac/a", "ac/d", "ac/b", "ac/c"]
    warm, timed, traced = generator.failing_links(links, traffic, 4, 11)
    assert warm == ["ac/c"] and sorted(timed) == ["ac/a", "ac/d"]
    assert traced == ["ac/a"]
    assert generator.failing_links(links, traffic, 4, 11) == (warm, timed, traced)
    with pytest.raises(ValueError):  # the warm-up would fail a window link
        generator.failing_links(links, dict(traffic, window_events=4), 4, 11)


def test_every_seed_fails_the_same_links_in_another_order():
    _cell, cfg, traffic = harness.cell_parts(bench_tiny.SPEC, "fattree_k8.link_storm")
    links = [l[0] for l in harness.fabric_module(cfg["fabric"]).reference(cfg)["links"]]
    order = generator.link_order(links, traffic, cfg["k"])
    pool = [l for l in links if l.startswith(traffic["events"]["fail_links"])]
    assert sorted(order) == sorted(pool)
    switches = sorted({l[: len("ac/p0a0")] for l in pool})
    group = len(pool) // (cfg["k"] // 2)
    for g in range(0, len(pool), group):  # one link of every aggregation switch
        assert sorted(l[: len("ac/p0a0")] for l in order[g:g + group]) == switches
    n_warm, n_win = traffic["warmup_events"], traffic["window_events"]
    runs = [generator.failing_links(links, traffic, cfg["k"], seed)
            for seed in (1, 2**31 + 77, 4_000_000_007)]
    for warm, timed, traced in runs:
        assert sorted(warm) == sorted(order[-n_warm:])
        assert sorted(timed) == sorted(order[:n_win])
        assert sorted(traced) == sorted(order[:traffic["traced_events"]])
        assert not set(warm) & set(timed)
    assert runs[0][1] != runs[1][1] != runs[2][1] != runs[0][1]


@pytest.mark.parametrize("trace", [False, True])
def test_storm_window_fails_its_fixed_links_at_test_size(monkeypatch, trace):
    """The window fails its fixed links once each, and a traced window
    the first of them, whatever ``--seconds`` asks."""
    import trace_reduce

    monkeypatch.setattr(trace_reduce, "load", lambda _dir: trace_reduce.Trace(
        ops={0: [(0, 10**6, "op")]}, spans=[("window", 0, 10**9)]))
    cfg, traffic = bench_tiny.tiny("fattree_k8.link_storm")
    failed = {}
    for seed in (3, 2**31 + 9):
        sink = {}
        result, _ = bench_tiny.run("fattree_k8.link_storm", seed=seed, seconds=1e-9,
                                   trace=trace, sink=sink)
        assert result["correct"] and result["attempted"] > 0
        links = [l[0] for l in sink["program"].ref_fabric["links"]]
        order = generator.link_order(links, traffic, cfg["k"])
        names = [op[1] for op in sink["program"].ops if op[0] == "fail_link"]
        window = names[traffic["warmup_events"]:]
        n = traffic["traced_events" if trace else "window_events"]
        assert sorted(window) == sorted(order[:n])
        failed[seed] = window
    first, second = failed.values()
    assert first != second
