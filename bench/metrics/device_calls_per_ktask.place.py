"""Device program calls per thousand tasks placed (program counters
ts_plan_device.traces plus cache_hits)."""
import readers


def read(rec):
    if not rec.get("tasks"):
        return None
    return readers.device_calls(rec) * 1000.0 / rec["tasks"]
