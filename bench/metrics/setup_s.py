"""Set-up: from the start of the process to the first request sent."""


def read(ctx):
    return ctx.setup_s
