"""Seconds from process start to the first measured round."""


def read(ctx):
    return ctx.setup_s
