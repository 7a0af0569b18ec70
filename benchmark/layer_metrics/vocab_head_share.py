"""Share of the device's busy time in the vocabulary head and the
loss, in percent: the units of class ``TokenDense`` and
``GDTokenDense`` (the d x V product forward, and its two backward
products) and the unit of role ``loss`` (softmax cross-entropy over the
tokens x V logits), the head's weight update (``veles.update``) left
out — that is ``solver_update_share``'s."""

from benchmark.reduce import scopes


def read(ctx):
    return scopes.share_percent(
        ctx, lambda op: op.sub != "update" and (
            op.role == "loss" or op.cls in ("TokenDense", "GDTokenDense")))
