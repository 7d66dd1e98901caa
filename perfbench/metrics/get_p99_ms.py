"""99th percentile of per-op latency over the window's get ops."""


def read(ctx):
    q = ctx.latency_quantile(0.99, "get")
    return None if q is None else q * 1e3
