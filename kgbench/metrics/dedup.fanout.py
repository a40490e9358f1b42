"""Requests answered per executed row: the server's `served` counter over
its `executed` counter (1.0 when no request repeats within a batch)."""


def read(rec):
    c = rec.counters
    return c["served"] / c["executed"] if c["executed"] else None
