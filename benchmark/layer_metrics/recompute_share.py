"""Share of the device's busy time under ``veles.recompute``, in
percent: the layers' forwards that the looped stack's backward runs a
second time from their saved inputs (``reduce/loopscopes.py``) — time
that is no model work (``train_mfu`` does not count it)."""

from benchmark.reduce import loopscopes


def read(ctx):
    seconds = loopscopes.seconds(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx.trace.per_device[0]["busy_s"]
