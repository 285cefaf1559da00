"""Wavefront speculative curves reused, over reused plus re-scored
(program counters wavefront.hits and wavefront.misses)."""
import readers


def read(rec):
    hits = readers.counter(rec, "wavefront.hits")
    return readers.share_pct(hits, hits + readers.counter(rec, "wavefront.misses"))
