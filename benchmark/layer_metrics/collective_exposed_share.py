"""Share of the traced window in which a collective ran on device 0
while no compute operation did, in percent: the gradient all-reduce
that the backward pass did not hide."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.kind_seconds("collective"):
        return None
    return 100.0 * ctx.trace.collective_exposed_s / ctx.trace.window_s
