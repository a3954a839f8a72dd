"""Host milliseconds per tick in the engine's ``lm.decode`` span: issuing the tick's one batched decode step over all resident slots; the device work itself is waited for in ``lm.sample``.  Program span."""


def read(ctx):
    s = ctx["spans"].get("lm.decode")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
