"""Tasks placed in the window over the window's seconds (host clock)."""


def read(rec):
    return rec["tasks"] / rec["window_s"] if rec.get("tasks") else None
