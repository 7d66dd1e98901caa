"""Device busy time (union of device-op intervals) in the traced window,
per batch completed in it."""


def read(ctx):
    t = ctx.trace
    if not t or not ctx.kinds:
        return None
    return 1e3 * t["busy_s"] / len(ctx.kinds)
