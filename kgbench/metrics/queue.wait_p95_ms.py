"""Time in the bucket queue: the 95th percentile, over the window's
requests that were flushed, of the ticket's flush stamp minus the request's
due time (open loop) or submit time (closed loop), ms."""
from kgbench.stats import percentile


def read(rec):
    origin = (lambda r: r.due) if rec.loop == "open" else (lambda r: r.submit)
    waits = [(r.ticket.t_flush - origin(r)) * 1e3 for r in rec.requests
             if r.ticket is not None and r.ticket.t_flush is not None]
    return percentile(waits, 95) if waits else None
