"""Share of the traced stretch in which the device runs nothing: 1 - the
busy union of its ops over the stretch (the loop runs there at its own
speed: the profiler records no Python calls)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
