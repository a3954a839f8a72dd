"""The whole tick's share of the chips' peak, in percent: stream-steps per
second times model FLOPs per stream-step (``work.py``) over chips times the
published peak FLOP/s.  Host clock and counter."""


def read(ctx):
    if not ctx["peak"] or not ctx["stream_steps"]:
        return None
    rate = ctx["stream_steps"] / ctx["system_s"]
    return 100.0 * rate * ctx["work"]["flops"] / (ctx["chips"] * ctx["peak"]["flops"])
