"""95th percentile of a request's prefill as the batcher runs it: the
``batcher.prefill`` spans (prefill, slot write and logits fetch, to the
first token handed to the client's stream) that end inside the
window.  Program spans, host clock."""
from bench.lib import lm_stats, spans


def read(ctx):
    p = spans.in_window(ctx, "batcher.prefill", "end")
    return lm_stats.percentile_ms([s.dur_ns / 1e9 for s in p], 95)
