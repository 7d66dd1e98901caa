"""Compactions the store ran in the window (its ``compactions``
counter), per 1,000 ops."""


def read(ctx):
    if not ctx.ops:
        return None
    return ctx.counter_delta("compactions") / (ctx.ops / 1e3)
