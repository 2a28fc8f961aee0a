"""95th percentile over all gaps between consecutive tokens of every
request whose later token reached its client inside the window."""
from bench.lib import lm_stats


def read(ctx):
    return lm_stats.percentile_ms(lm_stats.gaps(ctx), 95)
