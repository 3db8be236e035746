"""Mean of the engine's own ``apply_s`` (host clock around the round's
apply, ended by ``block_until_ready``) over the applied rounds of the
window, in milliseconds."""


def read(ctx):
    a = (ctx.window or {}).get("apply_s")
    return 1000.0 * sum(a) / len(a) if a else None
