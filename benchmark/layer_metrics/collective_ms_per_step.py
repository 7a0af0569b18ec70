"""Milliseconds of collective operations per optimizer step on
device 0, hidden or not."""


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.kind_seconds("collective")
    steps = ctx.trace.steps(ctx)
    return 1e3 * seconds / steps if seconds and steps else None
