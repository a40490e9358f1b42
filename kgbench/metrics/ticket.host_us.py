"""The server's host work per ticket: the summed durations of its `submit`
spans (tracker, cache lookup, enqueue) and `deliver` spans (stamps,
counters, cache fill of each delivered ticket) over the tickets it served,
us."""


def read(rec):
    submit = [e["dur"] for e in rec.spans if e.get("name") == "submit"]
    deliver = [e["dur"] for e in rec.spans if e.get("name") == "deliver"]
    served = rec.counters["served"]
    if not submit or not served:
        return None
    return (sum(submit) + sum(deliver)) / served * 1e6
