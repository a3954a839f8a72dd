"""Host-to-device bytes the program booked in its transfer ledger over the
window, per stream-step advanced.  Program counter."""


def read(ctx):
    b = ctx["counters"].get("transfers.h2d_bytes")
    return b / ctx["stream_steps"] if b and ctx["stream_steps"] else None
