"""Device time of a looped layer stack's recomputation (PR 32): the
scope ``veles.recompute`` that ``veles/znicz_tpu/loop.py`` puts AROUND
the units' own scopes where the backward runs a layer's forward again
from its saved input, e.g.

    jit(veles_epoch_scan)/while/body/.../while/body/veles.pass/
        veles.recompute/veles.fwd.GQAttention.GQAttention_3/veles.core/...

``reduce/scopes.py`` finds the unit after it, so every accepted reader
works unchanged, but its ``ScopedOp`` keeps no path; so this module
reads the same xplane file again (with ``reduce/trace.py``'s parser,
the window and the device ``scopes.py`` uses) for the one question it
has: which operations ran under ``veles.recompute``.
"""

import gzip
import re

from benchmark import harness
from benchmark.reduce import scopes, trace

RECOMPUTE = re.compile(r"(?:^|/)veles\.recompute(?=[/:]|$)")


def recomputed(tf_op):
    """True for an operation of the loop's repeated forward: the scope
    comes BEFORE the unit's. (A unit's own ``jax.checkpoint`` names a
    forward path, this scope with it, INSIDE its gradient unit's: that
    is the unit's backward.)"""
    again = RECOMPUTE.search(tf_op or "")
    unit = scopes.UNIT.search(tf_op or "")
    return bool(again and unit and again.start() < unit.start())


def load(path, device_name, window):
    """[(start, end)] of the recomputed operations of the plane
    ``device_name`` inside ``window``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        planes = trace.read_planes(f.read())
    start, end = window
    found = []
    for plane in planes:
        if plane["name"] != device_name:
            continue
        for key, s, e in plane["lines"].get("XLA Ops", ()):
            name, stats = plane["events"].get(key, ("", {}))
            if e <= s or s < start or e > end or trace.is_control_flow(
                    stats.get("hlo_category", ""), name):
                continue
            if recomputed(stats.get("tf_op", "")):
                found.append((s, e))
    return found


def seconds(ctx):
    """Device 0's seconds under ``veles.recompute`` in the traced
    window; None where nothing was traced on a device or the program
    has no such scope (a stack run once, a build before PR 32, or an
    executable compiled by one)."""
    if ctx.trace is None:
        return None
    device = ctx.trace.devices[0]
    found = load(trace.find_xplane(harness.trace_dir(
        ctx.cell["bench_dir"], ctx.cell["name"])),
        device.name, device.window()[:2])
    return trace.union_seconds(found) if found else None
