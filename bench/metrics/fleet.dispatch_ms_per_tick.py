"""Host milliseconds per tick in the fleet's ``fleet.dispatch`` span: the
device-side concat of shard views, x and mask staging, pads and the
kernel's issue.  Program span."""


def read(ctx):
    s = ctx["spans"].get("fleet.dispatch")
    return 1e3 * s / ctx["ticks"] if s and ctx["ticks"] else None
