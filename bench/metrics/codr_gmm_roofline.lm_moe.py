"""Roofline share of the grouped ``codr_gmm`` kernel over its calls in
the traced stretch: each call's least time (``bench/costs/codr_gmm``,
its bytes the packed words of the experts the batcher counted as given
rows in that call's program) summed, over the calls' summed device
time."""
from bench.lib import lm_moe_trace


def read(ctx):
    return lm_moe_trace.gmm_roofline(ctx)
