"""Share of the window's compaction jobs that the watermark hysteresis
triggered (the store's ``jobs_by_trigger``: rate limit, watermark, read
policy).  None where the store does not count jobs by trigger, or ran
none in the window."""

WATERMARK = 1


def read(ctx):
    name = "jobs_by_trigger"
    if name not in ctx.counters0 or name not in ctx.counters1:
        return None
    jobs = [b - a for a, b in zip(ctx.counters0[name], ctx.counters1[name])]
    if sum(jobs) == 0:
        return None
    return 100.0 * jobs[WATERMARK] / sum(jobs)
