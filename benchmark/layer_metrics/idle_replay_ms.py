"""Milliseconds a dispatch that device 0 idled while the host replayed
the chunk: from the end of ``veles.dispatch.fetch`` to the start of the
next ``veles.dispatch.build`` — the workflow's loop serving the chunk's
minibatches to loader, step and decision (``reduce/phases.py``)."""

from benchmark.reduce import phases


def read(ctx):
    return phases.idle_ms(ctx, "replay")
