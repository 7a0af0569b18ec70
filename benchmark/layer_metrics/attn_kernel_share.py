"""Share of the device's busy time spent in Mosaic kernels
(``tpu_custom_call``), in percent. The only kernels the training step
holds are the attention forward and fused backward
(``parallel/pallas_attention.py``), which the program selects from
S = 1024 up; below that the figure must read 0."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.kind_share("custom_call")
