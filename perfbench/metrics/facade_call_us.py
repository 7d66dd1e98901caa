"""Host time of one facade call (``put`` / ``get`` of ``PrismDB``, or of
``PartitionedDB`` for a configuration with ``partitions``): building the
op, copying it to the device and enqueueing the step, per batch."""


def read(ctx):
    if not ctx.call_s:
        return None
    return 1e6 * sum(ctx.call_s) / len(ctx.call_s)
