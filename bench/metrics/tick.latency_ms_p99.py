"""99th percentile over every tick of the window of its latency, from when
the tick was due to when its device work was done.  Host clock.

A per-layer figure in the closed-loop (backlog) cells: every tick there
does the same work, so the tail holds the host's rare stalls and not the
fleet's load."""
import numpy as np


def read(ctx):
    lat = ctx["latencies_s"]
    return float(np.percentile(lat, 99)) * 1e3 if lat.size else None
