"""Useful model FLOPs of the pooled decode steps (every projection and
the unembedding per active slot, attention over each slot's context)
over the decode-step programs' summed device time, over the bf16 peak.
The FLOPs are those of the tokens decoded while the profiler ran."""
from bench.lib import lm_stats, lm_trace, peaks


def read(ctx):
    if ctx.trace is None:
        return None
    runs = lm_trace.decode_runs(ctx.trace)
    if not runs:
        return None
    flops = lm_stats.useful_flops(ctx, ctx.trace_t0, ctx.trace_t1,
                                  prefill=False)
    busy = sum(r.dur for r in runs) / 1e9
    if not flops or not busy:
        return None
    return 100.0 * flops / busy / peaks.peak(ctx.device_kind)["bf16_flops"]
