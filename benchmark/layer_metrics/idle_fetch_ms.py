"""Milliseconds a dispatch that device 0 idled under the program's
``veles.dispatch.fetch`` annotation: the step program had finished and
the host had not yet got its metrics — the host thread's wake-up, the
packing program's launch, the one transfer, the unpacking
(``reduce/phases.py``)."""

from benchmark.reduce import phases


def read(ctx):
    return phases.idle_ms(ctx, "fetch")
