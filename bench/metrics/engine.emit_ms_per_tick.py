"""Host milliseconds per tick in the shard engines' ``engine.emit`` spans
(row pull, head logits, event batch, window resets), summed over shards.
The row pull waits for the tick's step, so this holds the device wait.
Program span."""


def read(ctx):
    s = ctx["spans"].get("engine.emit")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
