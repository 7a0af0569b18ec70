"""The attention FORWARD kernel's share of its roofline, in percent:
the least time the chip could take for the causal forward of every
sequence that ran forward in the traced window, over the time of the
Mosaic kernels under the ``fwd`` attention units' ``veles.core`` scope
(``reduce/scopes.py``).

Sequences that ran forward: the minibatches of the traced train steps
AND the validation minibatches of the traced epochs, which are part of
every epoch's program (``n_valid`` over ``minibatch`` an epoch, each
run at the full minibatch shape). Operations: ``costs/lm.py``'s
``attention_flops_per_sequence(model, S, passes=1)``. Bytes, the least
of a flash formulation in bf16: q, k, v read and out written — four
(S, dim) tensors a sequence and layer — plus the float32 row
statistics (lse) written once. At S = 8192 the operations bound it.
"""

from benchmark.reduce import scopes


def roofline(ctx, role, passes, tensors, sequences):
    """Percent of the roofline reached by the Mosaic kernels under the
    ``veles.core`` scope of the units of ``role``: ``passes`` and
    ``tensors`` as the docstrings above and in ``attn_bwd_roofline``
    count them, ``sequences`` the number that went through."""
    found = scopes.of(ctx)
    if found is None or ctx.peaks is None:
        return None
    seconds = found.seconds(lambda op: op.role == role
                            and op.sub == "core"
                            and op.kind == "custom_call")
    if not seconds or not sequences:
        return None
    model, seq = ctx.cell["config"]["model"], ctx.cell["traffic"]["seq_len"]
    flops = ctx.costs.attention_flops_per_sequence(model, seq, passes)
    nbytes = model["layers"] * (
        tensors * seq * model["dim"] * ctx.costs.BF16
        + model["heads"] * seq * ctx.costs.F32)
    least = max(flops / (ctx.chips * ctx.peaks["bf16_flops_per_s"]),
                nbytes / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least * sequences / seconds


def read(ctx):
    if ctx.trace is None:
        return None
    traffic = ctx.cell["traffic"]
    minibatch = traffic["minibatch"]
    steps = ctx.trace.steps(ctx)
    valid_steps = -(-traffic["n_valid"] // minibatch) \
        * (steps // ctx.steps_per_epoch)
    return roofline(ctx, "fwd", passes=1, tensors=4,
                    sequences=minibatch * (steps + valid_steps))
