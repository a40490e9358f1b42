"""How full the binding tables the engine carries are: 100 x the server's
`table_rows_live` counter (mask-true rows of the executed batch rows, every
shard) over its `table_rows_cap` counter (executed rows x shards x the
bucket's table cap), %."""


def read(rec):
    c = rec.counters
    if not c.get("table_rows_cap"):
        return None
    return 100.0 * c["table_rows_live"] / c["table_rows_cap"]
