"""Host milliseconds per tick in ``fleet.tick`` that none of its direct
child spans covers: the tick's own bookkeeping between its phases.
Program span."""

CHILDREN = ("fleet.snapshot", "fleet.flush_spill", "fleet.device_wait",
            "fleet.begin", "fleet.dispatch", "fleet.finish", "fleet.deliver",
            "engine.tick")


def read(ctx):
    spans = ctx["spans"]
    if "fleet.tick" not in spans or not ctx["ticks"]:
        return None
    own = spans["fleet.tick"] - sum(spans.get(c, 0.0) for c in CHILDREN)
    return 1e3 * own / ctx["ticks"]
