"""Host extraction per dispatch: the summed durations of the server's
`fetch` spans (the device-to-host copy of an engine call's table, mask and
overflow flags) and `extract` spans (PPN slice, `np.unique`, fan-out) over
the number of dispatches fetched, ms."""


def read(rec):
    fetch = [e["dur"] for e in rec.spans if e.get("name") == "fetch"]
    extract = [e["dur"] for e in rec.spans if e.get("name") == "extract"]
    if not fetch:
        return None
    return (sum(fetch) + sum(extract)) / len(fetch) * 1e3
