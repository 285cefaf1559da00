"""Ledger cells written to the device mirror per task placed (program
counter ts_plan_device.mirror_cells)."""
import readers


def read(rec):
    if not rec.get("tasks"):
        return None
    return readers.counter(rec, "ts_plan_device.mirror_cells") / rec["tasks"]
