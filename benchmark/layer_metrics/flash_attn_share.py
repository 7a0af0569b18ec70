"""Share of the device's busy time under a ``veles.core`` scope, in
percent: the attention proper of every attention unit, forward and
backward — below S = 1024 the scan-flash formulation of
``parallel/flash.py`` (score, softmax and context blocks, apart from
the unit's qkv and output projections and head transposes)."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.share_percent(ctx, lambda op: op.sub == "core")
