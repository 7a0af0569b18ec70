"""Device time under a sub-scope that ``reduce/scopes.py`` does not
know: ``veles.experts`` (the expert layer's grouped products) and
``veles.route`` (its router, top-k, sort, gather, weighting and
combine), PR 28.

``scopes.py`` finds ``veles.update`` and ``veles.core`` as whole path
components after a unit. The expert layer's backward is ``jax.vjp`` of
its traced forward, and jax names a transposed operation after the
forward's scope, wrapped: the backward of a product under
``veles.experts`` runs as

    .../veles.bwd.GDExpertFFN.GDExpertFFN_2/
        transpose(veles.fwd.ExpertFFN.ExpertFFN_2)/jvp(veles.experts)/...

and that of an elementwise operation as ``transpose(jvp(veles.route))``.
So this parser looks for the sub-scope's name after the first unit of
the path, as a whole word, bare or inside such wrappers.

The grouped products themselves carry NO path on this compiler: the
TPU compiler rewrites ``jax.lax.ragged_dot`` into Mosaic kernels of its
own, named ``ragged-dot-*`` (with a small ``ragged-dot-metadata`` kernel
before each), and gives them that name as their only metadata (read off
PR 28's first traced run: 2.69 s of custom calls under no scope,
``device_ops`` ``ragged-dot-none``). An operation without a path whose
instruction is so named counts under ``experts``, with no unit; the
accepted ``unscoped_share`` keeps reading it as unscoped, which it is.

It reads the
same xplane file with ``reduce/trace.py``'s parser, the window and the
device ``scopes.py`` uses, and keeps the answer on ``ctx``.
"""

import collections
import gzip
import re

from benchmark import harness
from benchmark.reduce import scopes, trace

SUB = re.compile(r"[/(]veles\.(experts|route)(?=[/:)]|$)")

#: XLA's own grouped-matmul kernels, by instruction name
GROUPED = re.compile(r"^ragged-dot")

SubOp = collections.namedtuple("SubOp", "start end kind role cls sub")


def sub_of(tf_op, instruction=""):
    """``tf_op`` -> (role, class, sub-scope) of the first unit of the
    path and the first of this module's sub-scopes after it; sub-scope
    None where there is none, all None without a unit — but for the
    compiler's own grouped kernels, which are ``experts`` by name."""
    unit = scopes.UNIT.search(tf_op or "")
    if not unit:
        grouped = GROUPED.match(trace.short_name(instruction))
        return None, None, "experts" if grouped else None
    found = SUB.search(tf_op, unit.end(3))
    return unit.group(1), unit.group(2), found.group(1) if found else None


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
                             *sub_of(tf_op, name)))
    return ops


def of(ctx):
    """The traced window's operations on device 0 with their sub-scope,
    read once a run; None where nothing was traced on a device."""
    if not hasattr(ctx, "subscopes"):
        ctx.subscopes = None
        if ctx.trace is not None:
            device = ctx.trace.devices[0]
            ctx.subscopes = load(
                trace.find_xplane(harness.trace_dir(
                    ctx.cell["bench_dir"], ctx.cell["name"])),
                device.name, device.window()[:2])
    return ctx.subscopes


def share_percent(ctx, pred):
    """Percent of the device's busy time in the operations ``pred``
    holds for; None where nothing was traced or no operation of the
    window carries a unit scope (``scopes.py`` says so aloud)."""
    ops = of(ctx)
    if not ops or not any(op.cls for op in ops):
        return None
    took = trace.union_seconds(
        [(op.start, op.end) for op in ops if pred(op)])
    return 100.0 * took / ctx.trace.per_device[0]["busy_s"]


def seconds(ctx, sub):
    """Union of the device's seconds under the sub-scope ``sub``; None
    where no operation of the window carries it (a program without the
    scope, or an executable compiled by one)."""
    ops = of(ctx)
    mine = [(op.start, op.end) for op in ops or () if op.sub == sub]
    return trace.union_seconds(mine) if mine else None
