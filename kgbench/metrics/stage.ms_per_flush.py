"""Host staging per flush: the mean duration of the server's `stage` spans
(batch assembly and the start of its host-to-device copy), ms."""


def read(rec):
    d = [e["dur"] for e in rec.spans if e.get("name") == "stage"]
    return sum(d) / len(d) * 1e3 if d else None
