"""The absorbed MLA decode attention's share of its roofline, in percent:
the least time the chip could take for the window's decode attention,
max(FLOPs / peak FLOP/s, bytes / HBM bandwidth) counted from the attended
positions (``work/moonlight.py``, ``mla_decode``), over the device time of
the program's ``mla_decode`` Pallas kernel (``kernels/mla_decode``), whose
name the trace's ops carry.  Device trace.

A device trace in which no op matches is an error: the kernel was renamed
or left the path."""

KEY = "mla_decode"


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if not tr or not tr["devices"] or not peak:
        return None
    t = sum(s for name, s in tr["ops"].items() if KEY in name)
    if t <= 0:
        raise LookupError(f"no {KEY} op among the {len(tr['ops'])} device ops of the trace")
    w = ctx["work"][KEY]
    bound = max(w["flops"] / peak["flops"], w["hbm_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * bound / (t / tr["devices"])
