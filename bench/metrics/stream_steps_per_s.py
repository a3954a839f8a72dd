"""Stream-steps advanced in the window (the fleet's ``stream_steps``
counter) over the system's seconds in it: in a closed loop the sum of the
ticks' latencies, so the time the benchmark spends preparing the next
tick's packets is not charged.  Host clock, counter."""


def read(ctx):
    return ctx["stream_steps"] / ctx["system_s"]
