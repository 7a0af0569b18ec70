"""Share of the device's busy time under ``veles.route``, in percent:
what the expert layer costs that is no product — pre-norm, router,
top-k, the sort of the token-expert pairs, the dispatch into the pair
buffer, the gated activation, weighting and the combine back, forward
and backward (``reduce/subscopes.py``)."""

from benchmark.reduce import subscopes


def read(ctx):
    return subscopes.share_percent(ctx, lambda op: op.sub == "route")
