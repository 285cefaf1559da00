"""Share of the traced window in which no operation ran on the device."""
import readers


def read(rec):
    return readers.device_idle_pct(rec)
