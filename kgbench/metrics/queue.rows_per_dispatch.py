"""Rows executed per engine dispatch: the server's `executed` counter over
its flushes of every reason (full, deadline, drain), after dedup and
before padding."""


def read(rec):
    c = rec.counters
    flushes = c["flush_full"] + c["flush_deadline"] + c["flush_drain"]
    return c["executed"] / flushes if flushes else None
