"""Median of the same token gaps as ``itl_p95_ms``: one pooled decode
step plus the host round trip, in the common case."""
from bench.lib import lm_stats


def read(ctx):
    return lm_stats.percentile_ms(lm_stats.gaps(ctx), 50)
