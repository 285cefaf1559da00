"""Victims replanned in the window over the window's seconds (host clock)."""


def read(rec):
    return rec["victims"] / rec["window_s"] if rec.get("victims") else None
