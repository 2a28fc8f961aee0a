"""Roofline share of the ``codr_matmul`` kernel over all its calls in
the traced stretch: each call's least time (``bench/costs``) summed,
over the calls' summed device time."""
from bench.lib import lm_trace


def read(ctx):
    if ctx.trace is None:
        return None
    return lm_trace.codr_roofline(ctx.trace, ctx.device_kind)
