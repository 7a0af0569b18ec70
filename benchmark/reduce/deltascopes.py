"""Device time under the sub-scopes of PR 34: ``veles.delta`` (the
delta-rule recurrence proper, inside ``DeltaAttention`` /
``GDDeltaAttention``) and ``veles.shared`` (the shared expert, inside
``ExpertFFN`` / ``GDExpertFFN``).

``reduce/subscopes.py`` does the same for ``veles.experts`` and
``veles.route`` with a pattern fixed to those two names, and may not be
edited; this module is its twin for the two new names, with the same
rule: the sub-scope's name is looked for after the first unit of the
path, as a whole word, bare (the forward, and the recurrence the
backward runs again) or inside the wrappers jax puts around a
transposed operation (``transpose(jvp(veles.delta))``). It reads the
same xplane file with ``reduce/trace.py``'s parser, the window and the
device ``scopes.py`` uses, and keeps the answer on ``ctx``. A program
without the scopes (a build before PR 34, or an executable compiled by
one) gives None, and every reader over it leaves its metric out.
"""

import collections
import gzip
import re

from benchmark import harness
from benchmark.reduce import scopes, trace

SUB = re.compile(r"[/(]veles\.(delta|shared)(?=[/:)]|$)")

SubOp = collections.namedtuple("SubOp", "start end cls sub")


def sub_of(tf_op):
    """``tf_op`` -> (class of the first unit of the path, the first of
    this module's sub-scopes after it); None where there is none."""
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
            if e <= s or s < start or e > end or trace.is_control_flow(
                    stats.get("hlo_category", ""), name):
                continue
            ops.append(SubOp(s, e, *sub_of(stats.get("tf_op", ""))))
    return ops


def of(ctx):
    """The traced window's operations on device 0 with their sub-scope,
    read once a run; None where nothing was traced on a device."""
    if not hasattr(ctx, "deltascopes"):
        ctx.deltascopes = None
        if ctx.trace is not None:
            device = ctx.trace.devices[0]
            ctx.deltascopes = load(
                trace.find_xplane(harness.trace_dir(
                    ctx.cell["bench_dir"], ctx.cell["name"])),
                device.name, device.window()[:2])
    return ctx.deltascopes


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
