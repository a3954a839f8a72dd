"""Host milliseconds per tick in the shard engines' ``engine.emit_wait``
spans, summed over shards: the blocking copy of the gathered rows to the
host, which waits for the tick's step kernel and the gather first.
Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.emit_wait")
    return 1e3 * s / ctx["ticks"] if s is not None and ctx["ticks"] else None
