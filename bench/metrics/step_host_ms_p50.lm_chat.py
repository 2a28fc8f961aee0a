"""Median host part of a pooled decode step: each ``batcher.step`` span
that starts inside the window, less the ``batcher.wait`` under it (the
wait for the device): the page-table and input push, the dispatch, the
logits fetch, sampling and delivery.  Program spans, host clock."""
import collections

from bench.lib import lm_stats, spans


def read(ctx):
    steps = spans.in_window(ctx, "batcher.step", "start")
    if not steps:
        return None
    wait = collections.Counter()
    for s in ctx.spans:
        if s.name == "batcher.wait":
            wait[s.parent] += s.dur_ns
    return lm_stats.percentile_ms(
        [(s.dur_ns - wait[s.id]) / 1e9 for s in steps], 50)
