"""Images per second of the window (which runs before the profiler
starts) times the FLOPs of one image (both convolutions), over the
chip's bf16 peak."""
from bench.lib import peaks


def read(ctx):
    if not ctx.calls:
        return None
    n = sum(c.items for c in ctx.calls if ctx.t0 <= c.end <= ctx.t1)
    if not n:
        return None
    flops = n * ctx.model["flops_per_image"] / ctx.seconds
    return 100.0 * flops / peaks.peak(ctx.device_kind)["bf16_flops"]
