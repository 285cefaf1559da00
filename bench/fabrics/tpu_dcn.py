"""Two-level tree (the program's ``tpu_dcn_fabric``): hosts
``pod<p>/host<h>`` behind a rack or pod switch ``pod<p>/agg``, each
trunked to one core ``dcn-core``; every pair has one min-hop path.
Capacities are bytes per second."""


def program(cfg: dict):
    """The fabric as the program builds it."""
    from repro.core.topology import tpu_dcn_fabric

    return tpu_dcn_fabric(cfg["n_pods"], cfg["hosts_per_pod"],
                          cfg["nic_bytes_per_s"], cfg["pod_trunk_bytes_per_s"])


def reference(cfg: dict) -> dict:
    """Links ``(name, child, parent, capacity)`` in construction order,
    the tree's parent map and the hosts, built without the program."""
    links, parent, hosts = [], {}, []
    for p in range(cfg["n_pods"]):
        agg = f"pod{p}/agg"
        links.append((f"pod{p}/trunk", agg, "dcn-core", cfg["pod_trunk_bytes_per_s"]))
        parent[agg] = ("dcn-core", f"pod{p}/trunk")
        for h in range(cfg["hosts_per_pod"]):
            host = f"pod{p}/host{h}"
            links.append((f"pod{p}/nic{h}", host, agg, cfg["nic_bytes_per_s"]))
            parent[host] = (agg, f"pod{p}/nic{h}")
            hosts.append(host)
    return {"links": links, "parent": parent, "hosts": hosts}
