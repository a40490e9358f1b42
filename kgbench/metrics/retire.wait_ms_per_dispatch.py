"""Time the server blocked on the device per dispatch: the summed duration
of the server's `wait` spans (`block_until_ready` on an engine call's
output, whether `_retire` found it ready, `max_inflight` forced it, or a
drain did) over their number, ms."""


def read(rec):
    d = [e["dur"] for e in rec.spans if e.get("name") == "wait"]
    return sum(d) / len(d) * 1e3 if d else None
