"""Host milliseconds per tick in the shard engines' ``engine.emit_head``
spans, summed over shards: the classifier head on the host and the event
batch.  Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.emit_head")
    return 1e3 * s / ctx["ticks"] if s is not None and ctx["ticks"] else None
