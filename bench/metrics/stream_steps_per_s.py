"""Stream-steps advanced in the window over the system's seconds in it: in
a closed loop the sum of the ticks' latencies, so the time the benchmark
spends preparing the next tick's inputs is not charged.  Host clock,
counter.

A stream-step is the scheduler's slot-step (``TickReport.advanced``): one
stream advanced by one sample, or one sequence advanced by one token.  The
system's ``stream_steps`` counter counts them (the fleet's own counter for
the FastGRNN cells), so every cell reports this rate."""


def read(ctx):
    return ctx["stream_steps"] / ctx["system_s"]
