"""Whole-window uploads of the ledger mirror in the window (program
counter ts_plan_device.mirror_uploads)."""
import readers


def read(rec):
    return readers.counter(rec, "ts_plan_device.mirror_uploads")
