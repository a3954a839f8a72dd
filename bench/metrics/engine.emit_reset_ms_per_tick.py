"""Host milliseconds per tick in the shard engines' ``engine.emit_reset``
spans, summed over shards: finished rows, the window-reset mask's copy to
the device and the reset's dispatch.  Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.emit_reset")
    return 1e3 * s / ctx["ticks"] if s is not None and ctx["ticks"] else None
