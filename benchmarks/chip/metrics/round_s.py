"""Seconds per closed round: the window's elapsed time over the whole
rounds it closed (the window ends on a whole round)."""


def read(ctx):
    w = ctx.window
    return w["elapsed_s"] / w["rounds"] if w and w["rounds"] else None
