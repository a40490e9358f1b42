"""Bytes copied back per executed row: the server's `d2h_bytes` counter
(engine output fetched to the host, padded batch rows and every shard
included) over its `executed` counter, KB (1,024 bytes)."""


def read(rec):
    c = rec.counters
    if "d2h_bytes" not in c or not c["executed"]:
        return None
    return c["d2h_bytes"] / 1024 / c["executed"]
