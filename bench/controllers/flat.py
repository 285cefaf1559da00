"""The flat ``ClusterController``: one event loop and one ledger over the
whole fabric, with ``BassPolicy`` set by the configuration's ``policy``."""


def build(fab, dep, cfg: dict):
    from repro.core.controller import BassPolicy, ClusterController

    return ClusterController(fab, dep.workers, BassPolicy(**cfg["policy"]),
                             idle=dep.idle, slot_duration=cfg["slot_s"],
                             k_paths=cfg["k_paths"])
