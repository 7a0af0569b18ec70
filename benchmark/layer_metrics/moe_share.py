"""Share of the device's busy time in the expert layers, in percent:
the units of class ``ExpertFFN`` and ``GDExpertFFN`` — pre-norm, router,
top-k, sort, dispatch, gating, combine, their backward and the solver's
update of the expert weights — and the grouped products, which the TPU
compiler runs as kernels of its own that carry no unit's path
(``reduce/subscopes.py`` knows them by name)."""

from benchmark.reduce import subscopes


def read(ctx):
    return subscopes.share_percent(
        ctx, lambda op: op.sub == "experts"
        or op.cls in ("ExpertFFN", "GDExpertFFN"))
