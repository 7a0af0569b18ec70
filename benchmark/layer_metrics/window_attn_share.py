"""Share of the device's busy time under ``veles.window``, in percent:
the attention proper of the sliding-window layers, forward and backward
— the Mosaic kernels and what surrounds them inside the scope (the V
transpose before the forward kernel, the row sums and the transposed
float32 dq's conversion around the backward one)
(``reduce/windowscopes.py``). It lies inside ``flash_attn_share``,
which reads all of ``veles.core``."""

from benchmark.reduce import windowscopes


def read(ctx):
    return windowscopes.share_percent(ctx, lambda op: op.sub == "window")
