"""Host milliseconds per tick in the shard engines' ``engine.emit_pull``
spans, summed over shards: which rows to deliver, the lazy h slice, the
row indices' copy to the device and the issue of the row gather.  The
first of ``engine.emit``'s four children.  Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.emit_pull")
    return 1e3 * s / ctx["ticks"] if s is not None and ctx["ticks"] else None
