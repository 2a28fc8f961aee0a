"""95th percentile of the time a request waited for a slot: the
``batcher.queue`` spans (submit to slot reserved) that start inside the
window.  Program spans, host clock; with queue wait and prefill it
splits ``ttft_p95_ms``."""
from bench.lib import lm_stats, spans


def read(ctx):
    q = spans.in_window(ctx, "batcher.queue", "start")
    return lm_stats.percentile_ms([s.dur_ns / 1e9 for s in q], 95)
