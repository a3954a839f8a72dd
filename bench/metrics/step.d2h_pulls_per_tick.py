"""Device-to-host pulls per tick the program booked in its transfer ledger
(each one a blocking copy: the host waits for the device).  Program
counter."""


def read(ctx):
    n = ctx["counters"].get("transfers.d2h_count")
    return n / ctx["ticks"] if n is not None and ctx["ticks"] else None
