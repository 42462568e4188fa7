"""Device busy time of the chase program in the traced window, over the
dependent steps its traced calls walked, in ns per step."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    steps = ctx.window.traced_calls * ctx.session.work["steps"]
    return ctx.trace.busy_s() * 1e9 / steps
