"""Host time of one facade call (``PrismDB.put`` / ``get``): building the
op, copying it to the device and enqueueing the step, per batch."""


def read(ctx):
    if not ctx.call_s:
        return None
    return 1e6 * sum(ctx.call_s) / len(ctx.call_s)
