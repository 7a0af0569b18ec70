"""Share of the device's busy time in operations under no unit scope,
in percent: the gather of the minibatch, the loader's transform, the
packing of the metrics, and what XLA made itself (layout copies,
prefetches, mask packing). The gradient all-reduce of a data-parallel
step is NOT here: the compiled program gives each combined all-reduce
the path of one of the gradient products it reduces, so it counts as
that unit's backward. The guard on the names: a refactor that loses a
scope shows here. The ``device scopes:`` line lists this time by
kind."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.share_percent(ctx, lambda op: op.cls is None)
