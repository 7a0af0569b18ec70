"""Milliseconds a dispatch that device 0 idled under the program's
``veles.dispatch.build`` annotation: ``_epoch_program`` (epoch orders,
the stacked schedules, their ``device_put`` under a mesh, the program's
look-up, the hyperparameters' byte comparison), the epoch-entry copy,
the cost look-up (``reduce/phases.py``)."""

from benchmark.reduce import phases


def read(ctx):
    return phases.idle_ms(ctx, "build")
