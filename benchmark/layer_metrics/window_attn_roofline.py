"""The windowed attention kernels' share of their roofline, in percent:
the least time the chip could take for the BAND the traced window
attended, over the time of the Mosaic kernels under ``veles.window``
(``reduce/windowscopes.py``), forward and fused backward together.

Pairs: the program's counters, ``veles_window_pairs_total{layer}`` over
``veles_window_steps_total{layer}`` — the query-key pairs INSIDE the
band a training step of each sliding layer attended, all its heads —
taken for the traced steps (forward + backward,
``costs.window_kernel_cost``) and for the validation minibatches of the
traced epochs (forward alone, the same pairs). The work is the MODEL's,
not the tiles': a kernel that visits a tile the band only cuts is
charged the tile's time and not credited its masked pairs, a later
kernel is held to the same count, and the share cannot pass 100%. Least
time: the larger of operations over the bf16 peak and bytes (q, k, v,
out and their cotangents at the K/V heads the model has) over the HBM
peak; at window 512 and head 128 the operations bound it.
"""

from benchmark.reduce import windowscopes


def window_layers():
    """{layer: mean band pairs a training step}, from the counters."""
    from veles import telemetry
    totals = {}
    for family in telemetry.get_registry().families():
        if family.name in ("veles_window_pairs_total",
                           "veles_window_steps_total"):
            for items, child in family.children():
                totals.setdefault(dict(items)["layer"], {})[
                    family.name] = child.value
    return {layer: t["veles_window_pairs_total"]
            / t["veles_window_steps_total"]
            for layer, t in totals.items()
            if t.get("veles_window_steps_total")}


def read(ctx):
    cost = getattr(ctx.costs, "window_kernel_cost", None)
    pairs = window_layers()
    if cost is None or not pairs or ctx.trace is None \
            or ctx.peaks is None:
        return None
    took = windowscopes.seconds(
        ctx, lambda op: op.sub == "window" and op.kind == "custom_call")
    if not took:
        return None
    traffic, model = ctx.cell["traffic"], ctx.cell["config"]["model"]
    windowed = [own for own in model["operators"].values()
                if own.get("window")]
    if len(windowed) != 1:
        return None
    tokens = traffic["seq_len"] * traffic["minibatch"]
    steps = ctx.trace.steps(ctx)
    valid = -(-traffic["n_valid"] // traffic["minibatch"]) \
        * (steps // ctx.steps_per_epoch)
    least = 0.0
    for mean in pairs.values():
        for count, backward in ((steps, True), (valid, False)):
            flops, nbytes = cost(model, mean, tokens,
                                 windowed[0]["heads"], backward=backward)
            least += count * max(
                flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.chips * took)
