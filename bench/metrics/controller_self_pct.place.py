"""Share of the window in the controller's entry points outside the
placement engines (benchmark spans)."""
import readers


def read(rec):
    return readers.controller_self_pct(rec)
