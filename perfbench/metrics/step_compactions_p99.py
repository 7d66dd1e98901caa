"""Compactions committed by the window's 99th-percentile engine step: the
upper edge of the store's ``steps_by_compactions`` bucket that holds it
(buckets 0, 1, 2-3, ..., 128-255; the last, from 256 up, reads 256, the
bound a two-tier step's maintenance loop stops at).  None where the
store does not count steps by compactions."""

import numpy as np

EDGES = (0, 1, 3, 7, 15, 31, 63, 127, 255, 256)


def read(ctx):
    name = "steps_by_compactions"
    if name not in ctx.counters0 or name not in ctx.counters1:
        return None
    steps = np.subtract(ctx.counters1[name], ctx.counters0[name])
    n = int(steps.sum())
    if n == 0:
        return None
    rank = int(np.ceil(0.99 * n))
    return EDGES[int(np.searchsorted(np.cumsum(steps), rank))]
