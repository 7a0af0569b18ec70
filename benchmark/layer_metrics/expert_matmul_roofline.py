"""The expert layers' grouped products' share of their roofline, in
percent: the least time the chip could take for the pairs the traced
window computed, over the device's time under ``veles.experts``
(``reduce/subscopes.py``) — whatever implements the products, and
whatever it spends on rows that hold no pair.

Pairs: the program's counters, ``veles_moe_pairs_total{layer}`` over
``veles_moe_steps_total{layer}``, the mean pairs a training step of
each layer, taken for the traced steps (forward + backward,
``costs.expert_matmul_cost``) and for the validation minibatches of
the traced epochs (forward alone, the same mean). Least time: the
larger of operations over the bf16 peak and bytes over the HBM peak;
at a thousand pairs an expert the operations bound it.
"""

from benchmark.reduce import subscopes


def pairs_per_step():
    """{layer: mean pairs a training step}, from the counters."""
    from veles import telemetry
    totals = {}
    for family in telemetry.get_registry().families():
        if family.name in ("veles_moe_pairs_total",
                           "veles_moe_steps_total"):
            for items, child in family.children():
                totals.setdefault(dict(items)["layer"], {})[
                    family.name] = child.value
    return {layer: t["veles_moe_pairs_total"] / t["veles_moe_steps_total"]
            for layer, t in totals.items()
            if t.get("veles_moe_steps_total")}


def read(ctx):
    took = subscopes.seconds(ctx, "experts")
    pairs = pairs_per_step()
    if not took or not pairs or ctx.peaks is None:
        return None
    traffic, model = ctx.cell["traffic"], ctx.cell["config"]["model"]
    steps = ctx.trace.steps(ctx)
    valid = -(-traffic["n_valid"] // traffic["minibatch"]) \
        * (steps // ctx.steps_per_epoch)
    least = 0.0
    for mean in pairs.values():
        for count, backward in ((steps, True), (valid, False)):
            flops, nbytes = ctx.costs.expert_matmul_cost(
                model, mean, backward=backward)
            least += count * max(
                flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.chips * took)
