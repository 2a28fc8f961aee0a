"""``codr_gmm`` device time inside the pooled decode step's runs in the
traced stretch, over the number of those runs, in ms: the routed
experts' share of one decode step."""
from bench.lib import lm_moe_trace


def read(ctx):
    if ctx.trace is None:
        return None
    return lm_moe_trace.gmm_ms_per_step(ctx.trace)
