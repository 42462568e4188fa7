"""Seconds from process start to the first timed call: imports, TPU
start-up, inputs, compiles or compile-cache hits, and warm-up."""


def read(ctx):
    return ctx.setup_s
