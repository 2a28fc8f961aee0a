"""Useful model FLOPs of the prefill and decode work completed inside
the window (each prompt whose first token arrived in it, each later
token that arrived in it), over the window, over the chip's bf16 peak."""
from bench.lib import lm_stats, peaks


def read(ctx):
    if not ctx.requests:
        return None
    flops = lm_stats.useful_flops(ctx, ctx.t0, ctx.t1, prefill=True)
    if not flops:
        return None
    return 100.0 * flops / ctx.seconds / peaks.peak(
        ctx.device_kind)["bf16_flops"]
