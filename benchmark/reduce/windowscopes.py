"""Device time under the sub-scope of PR 38: ``veles.window``, the
windowed attention proper, inside ``veles.core`` inside ``GQAttention``
/ ``GDGQAttention``.

``reduce/scopes.py`` finds ``veles.core`` (the first sub-scope after
the unit) and ``reduce/deltascopes.py`` has a pattern fixed to its two
names; neither may be edited. This module is their twin for the new
name, with the same rule: the sub-scope's name is looked for after the
first unit of the path, as a whole word, bare (the forward) or inside
the wrappers jax puts around a transposed operation
(``transpose(jvp(veles.window))``). It keeps each operation's kind
beside it (``trace.classify``: a Mosaic kernel is a ``custom_call``),
reads the same xplane file with ``reduce/trace.py``'s parser, the
window and the device ``scopes.py`` uses, and keeps the answer on
``ctx``. A program without the scope (a build before PR 38, or a cell
with no windowed layer) gives no operation, and every reader over it
leaves its metric out.
"""

import collections
import gzip
import re

from benchmark import harness
from benchmark.reduce import scopes, trace

SUB = re.compile(r"[/(]veles\.(window)(?=[/:)]|$)")

SubOp = collections.namedtuple("SubOp", "start end kind cls sub")


def sub_of(tf_op):
    """``tf_op`` -> (class of the first unit of the path, this module's
    sub-scope after it); None where there is none."""
    unit = scopes.UNIT.search(tf_op or "")
    if not unit:
        return None, None
    found = SUB.search(tf_op, unit.end(3))
    return unit.group(2), found.group(1) if found else None


def load(path, device_name, window):
    """[SubOp] of the plane ``device_name`` inside ``window``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        planes = trace.read_planes(f.read())
    start, end = window
    ops = []
    for plane in planes:
        if plane["name"] != device_name:
            continue
        for key, s, e in plane["lines"].get("XLA Ops", ()):
            name, stats = plane["events"].get(key, ("", {}))
            category = stats.get("hlo_category", "")
            if e <= s or s < start or e > end \
                    or trace.is_control_flow(category, name):
                continue
            tf_op = stats.get("tf_op", "")
            ops.append(SubOp(s, e, trace.classify(category, tf_op, name),
                             *sub_of(tf_op)))
    return ops


def of(ctx):
    """The traced window's operations on device 0 with their sub-scope,
    read once a run; None where nothing was traced on a device."""
    if not hasattr(ctx, "windowscopes"):
        ctx.windowscopes = None
        if ctx.trace is not None:
            device = ctx.trace.devices[0]
            ctx.windowscopes = load(
                trace.find_xplane(harness.trace_dir(
                    ctx.cell["bench_dir"], ctx.cell["name"])),
                device.name, device.window()[:2])
    return ctx.windowscopes


def seconds(ctx, pred):
    """Union of the device's seconds in the operations ``pred`` holds
    for; None where it holds for none."""
    mine = [(op.start, op.end) for op in of(ctx) or () if pred(op)]
    return trace.union_seconds(mine) if mine else None


def share_percent(ctx, pred):
    """The same as percent of the device's busy time."""
    took = seconds(ctx, pred)
    if took is None:
        return None
    return 100.0 * took / ctx.trace.per_device[0]["busy_s"]
