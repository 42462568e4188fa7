"""Declared bytes of every call completed in the window, over the time from
the first call's start to the last call's end (host clock), in GB/s."""


def read(ctx):
    w = ctx.window
    return w.calls * ctx.session.work["bytes"] / w.span_s / 1e9
