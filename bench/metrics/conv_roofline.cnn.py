"""Roofline share of the chain's convolutions: the least time of the
whole chain's work (every convolution's operations; bytes of the input
map, the weights and the final map, as a chain that keeps its
intermediates on chip would move them) per call, times the calls, over
the summed device time of the convolution ops in the traced stretch."""
from bench.lib import cnn_trace


def read(ctx):
    if ctx.trace is None:
        return None
    return cnn_trace.conv_roofline(ctx)
