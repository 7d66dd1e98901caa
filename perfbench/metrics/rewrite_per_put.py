"""Rows compaction wrote to the slow tier in the window (the store's
``slow_writes`` counter: puts write only the fast tier), per user put."""


def read(ctx):
    if not ctx.puts:
        return None
    return ctx.counter_delta("slow_writes") / ctx.puts
