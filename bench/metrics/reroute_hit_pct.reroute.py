"""Reroute prescan curves reused, over reused plus live re-scores
(program counters reroute.hits and reroute.misses)."""
import readers


def read(rec):
    hits = readers.counter(rec, "reroute.hits")
    return readers.share_pct(hits, hits + readers.counter(rec, "reroute.misses"))
