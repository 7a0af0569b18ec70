"""The attention kernels' share of their roofline in a model whose
attention layers are some of its layers and grouped-query, in percent:
the least time the chip could take for the causal attention of the
traced window — ``costs.attention_kernel_cost``, which counts the
configuration's attention layers and its query heads — over the time
of the Mosaic kernels under a ``veles.core`` scope (``reduce/scopes.py``),
forward and fused backward together. Only what runs under the attention
units' core is timed: the expert layer's grouped products are Mosaic
kernels too, and are not attention.

The window's work: the traced train steps run forward and backward,
the validation minibatches of the traced epochs (``n_valid`` over
``minibatch`` an epoch, each at the full minibatch shape) forward
alone. At S = 8192 the operations bound it."""

from benchmark.reduce import scopes


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    found = scopes.of(ctx)
    cost = getattr(ctx.costs, "attention_kernel_cost", None)
    if found is None or cost is None:
        return None
    seconds = found.seconds(lambda op: op.sub == "core"
                            and op.kind == "custom_call")
    steps = ctx.trace.steps(ctx)
    if not seconds or not steps:
        return None
    traffic, model = ctx.cell["traffic"], ctx.cell["config"]["model"]
    valid = -(-traffic["n_valid"] // traffic["minibatch"]) \
        * (steps // ctx.steps_per_epoch)
    least = 0.0
    for count, backward in ((steps, True), (valid, False)):
        flops, nbytes = cost(model, traffic, backward=backward)
        least += count * max(
            flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"]),
            nbytes / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
