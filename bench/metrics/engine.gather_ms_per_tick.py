"""Host milliseconds per tick in the shard engines' ``engine.gather`` spans
(the ring gather of one sample per advancing stream), summed over shards.
Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.gather")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
