"""Median over every tick of the window of its latency, from when the tick
was due to when its device work was done.  Host clock."""
import numpy as np


def read(ctx):
    lat = ctx["latencies_s"]
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
