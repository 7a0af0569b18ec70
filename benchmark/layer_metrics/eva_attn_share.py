"""Share of the device's busy time under ``veles.eva``, in percent: EVA
attention's core, forward and backward — the summaries
(``veles.eva.summary``), the local windows' and the remote summaries'
kernels and what surrounds them (``veles.eva.local``,
``veles.eva.remote``) and the merge of the two parts
(``reduce/evascopes.py``). The projections and the output product of
the layer lie outside it."""

from benchmark.reduce import evascopes


def read(ctx):
    return evascopes.share_percent(ctx, lambda op: op.sub is not None)
