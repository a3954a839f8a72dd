"""The routed experts' share of their roofline, in percent: the least time
the chip could take for the window's routed (token, expert) pairs, the
sum over prefill and decode of max(FLOPs / peak FLOP/s, bytes / HBM
bandwidth) counted from the routed tokens and the experts each call hit
(``work/moonlight.py``, ``moe_experts``), over the device time of the
grouped matmuls that compute them: the chip's ``ragged-dot`` kernels
(XLA's TPU lowering of ``jax.lax.ragged_dot``, named so in the trace; the
program's ``moe_experts`` scope itself does not reach the trace's op
names).  The sort, gathers and combine around them are left out.  Device
trace.

A device trace in which no op matches is an error: the grouped matmul was
renamed or left the path."""

KEY, OPS = "moe_experts", "ragged-dot"


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if not tr or not tr["devices"] or not peak:
        return None
    t = sum(s for name, s in tr["ops"].items() if OPS in name)
    if t <= 0:
        raise LookupError(f"no {OPS} op among the {len(tr['ops'])} device ops of the trace")
    bound = sum(max(w["flops"] / peak["flops"], w["hbm_bytes"] / peak["hbm_bytes_per_s"])
                for w in ctx["work"][KEY].values())
    return 100.0 * bound / (t / tr["devices"])
