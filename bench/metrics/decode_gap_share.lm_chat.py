"""Share of each interval from one pooled decode step's start to the
next in which the device runs nothing (the host round trip and
admission work between steps), from the device trace."""
from bench.lib import lm_trace


def read(ctx):
    if ctx.trace is None:
        return None
    return lm_trace.decode_gap_share(ctx.trace)
