"""Share of the window in which no dispatch was under way, in percent:
the host's work between two dispatches (serving the epoch's minibatches
to the decision, choosing the next chunk, enqueueing). From the
program's ``xla.dispatch.epoch`` spans."""


def read(ctx):
    if not ctx.dispatches:
        return None
    start, end = ctx.span_window
    inside = sum(d["dur"] for d in ctx.dispatches)
    return max(0.0, 100.0 * (1.0 - inside / (end - start)))
