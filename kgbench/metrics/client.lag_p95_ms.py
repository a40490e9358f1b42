"""How late the open-loop client sent its requests: the 95th percentile of
submit time minus due time, ms. Nothing to read in a closed loop, where a
request is due when it is sent."""
from kgbench.stats import percentile


def read(rec):
    if rec.loop != "open" or not rec.requests:
        return None
    return percentile([(r.submit - r.due) * 1e3 for r in rec.requests], 95)
