"""Runner points completed in the window, over the time from the first
``Runner.run`` call's start to the last one's end (host clock)."""


def read(ctx):
    w = ctx.window
    return w.calls * ctx.session.work["points"] / w.span_s
