"""Ops completed in the window over the window's seconds."""


def read(ctx):
    return ctx.ops / ctx.window_s if ctx.ops else None
