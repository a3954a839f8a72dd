"""How unevenly the routed tokens of the window fell on the experts: in each
MoE layer, the most tokens any expert received over the mean per expert;
the largest over the layers (1 is an even load).  The engine's device
counter of tokens per (layer, expert), prefill and decode, padding
excluded.  Program counter."""
import numpy as np


def read(ctx):
    load = ctx["counters"].get("moe.expert_tokens")
    if load is None:
        return None
    load = np.asarray(load, np.float64)
    mean = load.mean(axis=1)
    if not np.all(mean > 0):
        return None
    return float(np.max(load.max(axis=1) / mean))
