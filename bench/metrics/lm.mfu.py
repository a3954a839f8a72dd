"""The whole tick's share of the chip's peak, in percent: the window's model
FLOPs (``work/moonlight.py``: every prefilled and decoded token through the
body, attention over the positions attended and the head at each emitted
token) over the system's seconds, over chips times the published peak
FLOP/s.  Host clock and counter."""


def read(ctx):
    if not ctx["peak"] or not ctx["work"]["flops"]:
        return None
    return 100.0 * ctx["work"]["flops"] / ctx["system_s"] / (ctx["chips"] * ctx["peak"]["flops"])
