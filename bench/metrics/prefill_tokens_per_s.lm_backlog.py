"""Prompt tokens of the requests whose first token arrived inside the
window, over the window."""


def read(ctx):
    n = sum(r.prompt_len for r in ctx.requests
            if r.times and ctx.t0 <= r.times[0] <= ctx.t1)
    return n / ctx.seconds if ctx.requests else None
