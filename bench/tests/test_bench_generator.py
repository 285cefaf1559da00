"""The traffic generator repeats under one seed, and every seed offers
the same work in another order."""
import itertools

import bench_tiny
import numpy as np
import pytest

import generator


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
               "events": {"fail_links": "ac/"}}
    assert take(generator.arrivals(traffic, 4), 3) == [0.0, 0.05, 0.1]
    assert take(generator.job_sizes(traffic, 4), 2) == [1024, 1024]
    links = ["ac/a", "ac/b", "ea/x", "ac/c"]
    seq = take(generator.failing_links(links, traffic, 11), 6)
    assert sorted(seq[:3]) == ["ac/a", "ac/b", "ac/c"] and sorted(seq[3:]) == sorted(seq[:3])
    assert seq == take(generator.failing_links(links, traffic, 11), 6)
