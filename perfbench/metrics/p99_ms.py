"""99th percentile of per-op latency, over every op of the window."""


def read(ctx):
    q = ctx.latency_quantile(0.99)
    return None if q is None else q * 1e3
