"""Share of the traced window, in %, in which the device ran no operation:
1 - (union of device operation intervals / traced window).  It reads every
``idle_share.<part>`` metric, one for each end-to-end metric it moves."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
