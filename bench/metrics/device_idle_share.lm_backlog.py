"""1 - the device's busy union over the traced stretch, in percent."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.n_devices:
        return None
    return 100.0 * ctx.trace.idle_share()
