"""Uploads folded into closed rounds per second of the window."""


def read(ctx):
    w = ctx.window
    return w["uploads"] / w["elapsed_s"] if w and "uploads" in w else None
