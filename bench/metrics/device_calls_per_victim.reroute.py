"""Device program calls per victim replanned (program counters
ts_plan_device.traces plus cache_hits)."""
import readers


def read(rec):
    if not rec.get("victims"):
        return None
    return readers.device_calls(rec) / rec["victims"]
