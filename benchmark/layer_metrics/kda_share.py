"""Share of the device's busy time in the delta-rule linear-attention
layers, in percent: the units of class ``DeltaAttention`` and
``GDDeltaAttention`` — pre-norm, projections, taps, gates, the
recurrence, the output norm and gate, their backward (which runs the
recurrence a second time) and the solver's update
(``reduce/deltascopes.py``)."""

from benchmark.reduce import deltascopes


def read(ctx):
    return deltascopes.share_percent(
        ctx, lambda op: op.cls in ("DeltaAttention", "GDDeltaAttention"))
