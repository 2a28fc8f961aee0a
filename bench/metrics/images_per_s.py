"""Images whose result was ready inside the window, over the window."""


def read(ctx):
    if not ctx.calls:
        return None
    return sum(c.items for c in ctx.calls
               if ctx.t0 <= c.end <= ctx.t1) / ctx.seconds
