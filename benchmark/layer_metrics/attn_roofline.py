"""The attention kernels' share of their roofline, in percent: the
least time the chip could take for the causal attention of the traced
steps — the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from ``costs/lm.py`` — over the time the kernels took,
forward and fused backward together. At S = 8192 the operations bound
it (about 2,000 FLOP a byte against the chip's 240)."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    seconds = ctx.trace.kind_seconds("custom_call")
    steps = ctx.trace.steps(ctx)
    if not seconds or not steps:
        return None
    flops, nbytes = ctx.costs.attention_kernel_cost(
        ctx.cell["config"]["model"], ctx.cell["traffic"])
    least = max(flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"]),
                nbytes / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least * steps / seconds
