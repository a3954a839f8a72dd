"""Host milliseconds per tick in the fleet's ``fleet.feed`` spans: one span
per ``FleetEngine.feed`` call (sample checks, failover journal, the shard's
ring write), summed over the tick's calls.  The benchmark's own loop around
the calls is ``bench.feed`` less this.  Program span."""


def read(ctx):
    s = ctx["spans"].get("fleet.feed")
    return 1e3 * s / ctx["ticks"] if s is not None and ctx["ticks"] else None
