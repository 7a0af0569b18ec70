"""Share of the device's busy time in operations of a ``veles.update``
scope, in percent: the solver of the gradient units
(``GradientDescentBase.update_weights_xla`` / ``update_extra_xla``:
momentum SGD over float32 weights, velocities and gradients, and the
layer statistics taken there) AS FAR AS IT RUNS IN OPERATIONS OF ITS
OWN. XLA fuses the update of a weight matrix into the epilogue of the
weight-gradient product, and that fusion carries the product's path
(``reduce/scopes.py``): this metric then reads the update of biases,
layer norms and the embedding only, a lower bound. It rises when
something parts the update from the product, as the gradient
all-reduce of a data-parallel step does (1.6% on four chips against
0.3% on one, PR 25)."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.share_percent(ctx, lambda op: op.sub == "update")
