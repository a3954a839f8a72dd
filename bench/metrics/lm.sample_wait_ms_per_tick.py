"""Host milliseconds per tick in the engine's ``lm.sample`` spans: every blocking pull of sampled tokens and of the checked rows, after admission and after decode, which is where the host waits for the device.  Program span."""


def read(ctx):
    s = ctx["spans"].get("lm.sample")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
