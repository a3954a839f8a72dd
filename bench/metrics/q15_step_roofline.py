"""The Q15 step kernel's share of its roofline, in percent: the least time
the chip could take for the window's work, max(FLOPs / peak FLOP/s,
bytes / HBM bandwidth) counted from the model's shapes and the window's
stream-steps (``work/fastgrnn.py``), over the device time of the kernel's
ops in the trace.  Device trace.

The kernel's op is found by its name or its stats: the step's Pallas
kernel (``_q15_step_kernel``), which is the only custom call a fleet tick
runs (pads, slices, concat, resets and row gathers are plain XLA ops).  A
device trace in which no op matches is an error, not a silent gap: the
kernel was renamed or left the path, and the reader has to follow it."""

KEYS = ("q15_step", "custom-call", "custom_call")


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if not tr or not tr["devices"] or not peak:
        return None
    text = lambda name: (name + " " + tr["op_text"].get(name, "")).lower()
    t = sum(s for name, s in tr["ops"].items() if any(k in text(name) for k in KEYS))
    if t <= 0:
        raise LookupError(f"no step-kernel op ({', '.join(KEYS)}) among the "
                          f"{len(tr['ops'])} device ops of the trace")
    w = ctx["work"]
    bound = max(w["flops"] / peak["flops"], w["hbm_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * bound / (t / tr["devices"])
