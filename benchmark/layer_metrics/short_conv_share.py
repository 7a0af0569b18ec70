"""Share of the device's busy time in the gated short convolution's
units, in percent: classes ``ShortConv`` and ``GDShortConv`` (pre-norm,
in-projection, gating, the depthwise causal taps, out-projection, their
backward and update)."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.share_percent(
        ctx, lambda op: op.cls in ("ShortConv", "GDShortConv"))
