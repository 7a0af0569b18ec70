"""Model FLOP/s utilization of training, in percent: the operations the
forward and backward passes need for one sample (``costs/<config>.py``,
recomputation not counted) times samples per second over the window,
over chips times the bf16 peak of ``reduce/peaks.py``. An end-to-end
utilization — not a kernel's roofline share, and blind to idle time."""


def read(ctx):
    if not ctx.dispatches or ctx.peaks is None:
        return None
    flops = ctx.costs.train_flops_per_sample(
        ctx.cell["config"]["model"], ctx.cell["traffic"])
    return 100.0 * flops * ctx.span_samples_per_s \
        / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
