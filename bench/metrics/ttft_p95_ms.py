"""95th percentile, over every request due in the window, of the time
from when it was due to when its first token reached its client.  A
request that failed, was refused or never answered counts with the time
it was waited for."""
from bench.lib import lm_stats


def read(ctx):
    v = lm_stats.ttfts(ctx)
    return lm_stats.percentile_ms(v, 95)
