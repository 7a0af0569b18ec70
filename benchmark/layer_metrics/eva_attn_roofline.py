"""EVA attention's core against its roofline, in percent: the least time
the chip could take for the pairs the DEFINITION has — each window's
causal triangle and every query's summaries of the earlier windows,
``costs.core_cost`` of the configuration's shapes — over the time of
the device's operations under ``veles.eva.local`` and
``veles.eva.remote`` (``reduce/evascopes.py``), forward and backward
together. Counted for the traced steps (forward + backward) and for the
validation minibatches of the traced epochs (forward alone). The work
is the model's and not the tiles': the same count holds whatever
kernels compute it, a tile the definition only cuts is charged its time
and not credited its masked pairs, and the share cannot pass 100%.
Least time: the larger of operations over the bf16 peak and bytes over
the HBM peak; at head 128 the operations bound it."""

from benchmark.reduce import evascopes


def read(ctx):
    cost = getattr(ctx.costs, "core_cost", None)
    if cost is None or ctx.trace is None or ctx.peaks is None:
        return None
    took = evascopes.seconds(
        ctx, lambda op: op.sub in ("eva.local", "eva.remote"))
    if not took:
        return None
    traffic, model = ctx.cell["traffic"], ctx.cell["config"]["model"]
    steps = ctx.trace.steps(ctx)
    valid = -(-traffic["n_valid"] // traffic["minibatch"]) \
        * (steps // ctx.steps_per_epoch)
    least = 0.0
    for count, backward in ((steps, True), (valid, False)):
        flops, nbytes = cost(model, traffic, backward=backward)
        least += count * max(flops / ctx.peaks["bf16_flops_per_s"],
                             nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.chips * took)
