"""Output tokens that reached their clients inside the window, over the
window."""
from bench.lib import lm_stats


def read(ctx):
    return lm_stats.tokens_in(ctx, ctx.t0, ctx.t1) / ctx.seconds
