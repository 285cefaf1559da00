"""wave_scan's share of its bytes roofline: the bytes of its calls over
peak HBM bandwidth, over the device time inside them (device trace)."""
import readers


def read(rec):
    return readers.scan_roofline_pct(rec, "wave_scan")
