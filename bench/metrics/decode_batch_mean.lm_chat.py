"""Mean number of slots a pooled decode step served: ``n_active`` of the
``batcher.step`` spans that start inside the window.  Program spans."""
from bench.lib import spans


def read(ctx):
    steps = spans.in_window(ctx, "batcher.step", "start")
    if not steps:
        return None
    return sum(s.attrs["n_active"] for s in steps) / len(steps)
