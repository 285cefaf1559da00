"""k-ary fat-tree (Al-Fares et al., SIGCOMM 2008): ``k`` pods of ``k/2``
edge and ``k/2`` aggregation switches, ``(k/2)^2`` cores, ``k^3/4``
hosts, one capacity on every link.  Not a tree: routing is hop-count
Dijkstra and Yen's k shortest paths."""


def program(cfg: dict):
    """The fabric as the program builds it."""
    from repro.net.fattree import fat_tree_fabric

    return fat_tree_fabric(cfg["k"], link_mbps=cfg["link_capacity"])


def reference(cfg: dict) -> dict:
    """Links ``(name, a, b, capacity)`` in construction order and the
    hosts in pod, edge, port order, built without the program."""
    k, cap = cfg["k"], cfg["link_capacity"]
    half = k // 2
    links, hosts = [], []
    for p in range(k):
        for a in range(half):
            for j in range(half):
                links.append((f"ac/p{p}a{a}c{j}", f"pod{p}/agg{a}", f"core{a}_{j}", cap))
        for e in range(half):
            for a in range(half):
                links.append((f"ea/p{p}e{e}a{a}", f"pod{p}/edge{e}", f"pod{p}/agg{a}", cap))
            for i in range(half):
                host = f"pod{p}/h{e}_{i}"
                links.append((f"eh/p{p}e{e}h{i}", host, f"pod{p}/edge{e}", cap))
                hosts.append(host)
    return {"links": links, "parent": None, "hosts": hosts}
