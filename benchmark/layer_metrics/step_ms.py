"""Milliseconds of one optimizer step: the median warm dispatch of the
window divided by the steps it held. Read from the program's
``xla.dispatch.epoch`` spans, whose clock stops only after the metric
fetch, so the time is the device's plus one host round trip a dispatch.
"""

import statistics


def read(ctx):
    per_step = [d["dur"] / (d["epochs"] * ctx.steps_per_epoch)
                for d in ctx.dispatches if d["warm"]]
    return 1e3 * statistics.median(per_step) if per_step else None
