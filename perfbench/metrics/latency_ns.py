"""Window time over every dependent step completed (calls x passes x steps
per pass), in ns per step (host clock)."""


def read(ctx):
    w = ctx.window
    return w.span_s * 1e9 / (w.calls * ctx.session.work["steps"])
