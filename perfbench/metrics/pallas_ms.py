"""Device time in the Pallas kernels (``tpu_custom_call`` ops) in the
traced window, per batch completed in it."""


def read(ctx):
    t = ctx.trace
    if not t or not ctx.kinds or t["kernel_s"] <= 0:
        return None
    return 1e3 * t["kernel_s"] / len(ctx.kinds)
