"""The delta-rule recurrence's share of its roofline, in percent: the
least time the chip could take for the recurrence of the tokens the
traced window ran, over the device's time under ``veles.delta``
(``reduce/deltascopes.py``) — whatever algorithm runs it, and whatever
it runs twice.

Tokens: the program's counters, ``veles_delta_tokens_total{layer}`` over
``veles_delta_steps_total{layer}``, the tokens a training step of each
layer, taken for the traced steps (forward + backward,
``costs.delta_core_cost``) and for the validation minibatches of the
traced epochs (forward alone, a minibatch's tokens each). The work is
the MODEL's — the recurrence token by token as its equations write it —
not the chunked algorithm's, so a later kernel is measured against the
same count and the share cannot pass 100%. Least time: the larger of
operations over the bf16 peak and bytes over the HBM peak.
"""

from benchmark.reduce import deltascopes


def tokens_per_step():
    """{layer: mean tokens a training step}, from the counters."""
    from veles import telemetry
    totals = {}
    for family in telemetry.get_registry().families():
        if family.name in ("veles_delta_tokens_total",
                           "veles_delta_steps_total"):
            for items, child in family.children():
                totals.setdefault(dict(items)["layer"], {})[
                    family.name] = child.value
    return {layer: t["veles_delta_tokens_total"]
            / t["veles_delta_steps_total"]
            for layer, t in totals.items()
            if t.get("veles_delta_steps_total")}


def read(ctx):
    cost = getattr(ctx.costs, "delta_core_cost", None)
    tokens = tokens_per_step()
    if cost is None or not tokens or ctx.trace is None \
            or ctx.peaks is None:
        return None
    took = deltascopes.seconds(ctx, lambda op: op.sub == "delta")
    if not took:
        return None
    traffic, model = ctx.cell["traffic"], ctx.cell["config"]["model"]
    steps = ctx.trace.steps(ctx)
    valid = -(-traffic["n_valid"] // traffic["minibatch"]) \
        * (steps // ctx.steps_per_epoch)
    least = 0.0
    for mean in tokens.values():
        for count, backward in ((steps, True), (valid, False)):
            flops, nbytes = cost(model, mean, backward=backward)
            least += count * max(
                flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.chips * took)
