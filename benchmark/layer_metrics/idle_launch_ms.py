"""Milliseconds a dispatch that device 0 idled under the program's
``veles.dispatch.launch`` annotation: from the jit call's entry to the
step program's first operation on the device — the numpy leaves'
transfers, the enqueue on every chip (``reduce/phases.py``)."""

from benchmark.reduce import phases


def read(ctx):
    return phases.idle_ms(ctx, "launch")
