"""Share of the device's busy time spent in operations of HLO category
``convolution`` (plain or as the root of a fusion), in percent: the MXU
work of the conv stack (``ops/conv.py``, ``ops/gd_conv.py``). On a TPU a
matrix product is a convolution too, so the three FC products and the
conv backward's im2col products are counted here; ``reduce/trace.py``
tells the two apart (kinds ``convolution`` and ``matmul``) in the
summary it prints."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (ctx.trace.kind_share("convolution")
                    + ctx.trace.kind_share("matmul"))
