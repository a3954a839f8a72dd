"""Host-to-device copies per tick the program booked in its transfer
ledger (each one a dispatch on a host-bound tick, whatever its size).
Program counter."""


def read(ctx):
    n = ctx["counters"].get("transfers.h2d_count")
    return n / ctx["ticks"] if n is not None and ctx["ticks"] else None
