"""Share of the device's busy time under ``veles.delta``, in percent:
the delta-rule recurrence proper — the chunks' decayed scores, the
triangular systems, the scan that carries the state — forward, the
backward's repeated forward and the backward itself
(``reduce/deltascopes.py``); what ``kda_share`` holds beside it is
projections, taps, norms and gates."""

from benchmark.reduce import deltascopes


def read(ctx):
    return deltascopes.share_percent(ctx, lambda op: op.sub == "delta")
