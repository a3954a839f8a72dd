"""Host milliseconds per tick in the engine's ``lm.prefill`` spans: issuing each admission's prefill into its slot (padded to its bucket); the device work itself is waited for in ``lm.sample``.  Program span."""


def read(ctx):
    s = ctx["spans"].get("lm.prefill")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
