"""The whole round's share of the chip's peak, in percent: the round's
required floating-point operations (``counts``) over the traced run's
seconds per round times the device's published bf16 peak."""


def read(ctx):
    w, c, p = ctx.window, ctx.counts, ctx.peaks
    if not (ctx.trace and w and w["rounds"] and p
            and c.get("flops_per_round")):
        return None
    per_round = w["elapsed_s"] / w["rounds"]
    return 100.0 * c["flops_per_round"] / (per_round * p["bf16_flops_per_s"])
