"""The fused attention BACKWARD kernel's share of its roofline, in
percent: as ``attn_fwd_roofline`` over the Mosaic kernels under the
``bwd`` attention units' ``veles.core`` scope. Only the train steps run
backward. Operations: four matmuls (dV, dP, dQ, dK), twice the forward
(``passes=2``); the kernel's recomputed QK^T is not counted. Bytes: q,
k, v, out, dout read and dq, dk, dv written — eight (S, dim) tensors a
sequence and layer — plus lse read once."""

from benchmark.layer_metrics.attn_fwd_roofline import roofline


def read(ctx):
    if ctx.trace is None:
        return None
    return roofline(
        ctx, "bwd", passes=2, tensors=8,
        sequences=ctx.cell["traffic"]["minibatch"] * ctx.trace.steps(ctx))
