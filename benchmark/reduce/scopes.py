"""Device time by the program's own names: the unit scopes of a trace.

    python3 -m benchmark.reduce.scopes <trace dir or .xplane.pb[.gz]>

(from the checkout's root) prints the table of a trace by hand.

``StepCompiler.trace_step`` runs every unit of the compiled step under
``jax.named_scope("veles.<role>.<Class>.<name>")`` — role ``fwd``,
``bwd`` or ``loss`` — the solver inside a gradient unit under
``veles.update`` and the attention proper inside an attention unit
under ``veles.core``. XLA keeps the scope path of the jax primitive an
instruction was lowered from, and the profiler shows it as the
operation's ``tf_op``, e.g.

    jit(veles_epoch_scan)/while/body/while/body/
        veles.bwd.GDTransformerFFN.GDTransformerFFN_7/veles.update/mul:

``reduce/trace.py`` drops ``tf_op`` from its ``Op``, so this module
reads the same xplane file again, with that module's parser, and keeps
for every operation of device 0's ``XLA Ops`` line inside the traced
window its interval, its kind (``trace.classify``; control flow left
out, as there) and its place in the program. Nothing here knows a unit,
a class or one of jax's own path components (``jit(...)``, ``while``,
``body``, ``closed_call``, primitive names): one regular expression
finds the first ``veles.<role>.<Class>.<name>`` of the path and a
``veles.update`` or ``veles.core`` after it.

What an operation is attributed to is what XLA says it came from. A
fusion is one operation with one path: a loop fusion carries that of
its ROOT instruction, so a producer fused into a consumer of the next
unit counts for that unit; an output fusion (a product with its
epilogue fused in) carries the PRODUCT's. XLA fuses the momentum update
of a weight matrix into the weight-gradient product that feeds it, so
that update counts as the gradient unit's ``backward``, not as its
``update`` (read off the first scoped traces, PR 25: all 49 weight
matrices of the LM; what stays under ``veles.update`` is the update of
biases, layer norms and the embedding, and the layer statistics). What
XLA made itself — layout copies, prefetches, the packing of a boolean
mask — carries no path and is counted under no scope; the combined
gradient all-reduce of a data-parallel step carries the path of one of
the products it reduces (the HLO compiled for four chips, PR 25) and
counts as that unit's backward, kind ``collective``. ``unscoped`` is
therefore the guard on the names: a refactor that loses the scope shows
there.

The names in a trace are those of the build that COMPILED the
executable: jax's persistent cache key leaves metadata out, so an
executable found in the cache keeps the scopes (or the lack of them) it
was compiled with. Where no operation of the window carries a unit
scope, :func:`of` says so on standard error and returns None, and every
reader over it leaves its metric out: never 0, never 100.
"""

import collections
import gzip
import json
import re
import sys

from benchmark import harness
from benchmark.reduce import trace

#: role, class, unit name and sub-scope of a ``tf_op`` path
UNIT = re.compile(r"(?:^|/)veles\.(fwd|bwd|loss)\.([^./]+)\.([^/:]+)"
                  r"(?:/(?:[^/]+/)*?veles\.(update|core)(?=[/:]|$))?")
#: the table's column of an operation
PARTS = {"fwd": "forward", "bwd": "backward", "loss": "loss"}
NO_SCOPE = "(no scope)"

ScopedOp = collections.namedtuple(
    "ScopedOp", "start end kind role cls name sub")


def unit_of(tf_op):
    """``tf_op`` -> (role, class, name, sub-scope), each None where the
    path holds no unit scope; sub-scope is ``"update"``, ``"core"`` or
    None."""
    found = UNIT.search(tf_op or "")
    return found.groups() if found else (None, None, None, None)


class Scopes:
    """The operations of one device's traced window, each with the
    unit scope it ran under."""

    def __init__(self, ops, busy_s):
        self.ops = ops
        #: the device's busy seconds (``reduce/trace.py``'s figure)
        self.busy_s = busy_s

    def seconds(self, pred):
        """Union of the intervals of the operations ``pred`` holds for."""
        return trace.union_seconds(
            [(op.start, op.end) for op in self.ops if pred(op)])

    def share(self, pred):
        """The same over the device's busy time."""
        return self.seconds(pred) / self.busy_s

    @property
    def scoped(self):
        return any(op.cls for op in self.ops)

    def table(self):
        """[[class, part, seconds]], longest first: part is
        ``forward``, ``backward`` or ``loss`` by the unit's role (``
        core`` appended inside ``veles.core``) and ``update`` inside
        ``veles.update``; operations under no unit scope are rows of
        class ``(no scope)`` with their kind as the part."""
        rows = collections.defaultdict(list)
        for op in self.ops:
            if op.cls is None:
                key = (NO_SCOPE, op.kind)
            elif op.sub == "update":
                key = (op.cls, "update")
            else:
                key = (op.cls, PARTS[op.role]
                       + (" core" if op.sub == "core" else ""))
            rows[key].append((op.start, op.end))
        table = [[cls, part, trace.union_seconds(ivs)]
                 for (cls, part), ivs in rows.items()]
        return sorted(table, key=lambda row: -row[2])


def load(path, device_name, window, busy_s):
    """:class:`Scopes` of the plane ``device_name`` of an xplane file,
    operations inside ``window`` = (start ns, end ns)."""
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
            ops.append(ScopedOp(s, e, trace.classify(category, tf_op, name),
                                *unit_of(tf_op)))
    return Scopes(ops, busy_s)


def of(ctx):
    """The :class:`Scopes` of the run's traced window on device 0, read
    once a run and kept on ``ctx``; None where nothing was traced on a
    device, and None, said aloud, where no operation carries a unit
    scope. The first call prints the ``device scopes:`` line."""
    if not hasattr(ctx, "scopes"):
        ctx.scopes = _read(ctx)
    return ctx.scopes


def _read(ctx):
    if ctx.trace is None:
        return None
    device = ctx.trace.devices[0]
    path = trace.find_xplane(harness.trace_dir(ctx.cell["bench_dir"],
                                               ctx.cell["name"]))
    scopes = load(path, device.name, device.window()[:2],
                  ctx.trace.per_device[0]["busy_s"])
    if not scopes.scoped:
        print("device scopes: NONE — no operation of the traced window "
              "carries a veles.<role>.<Class>.<name> scope: the "
              "executable was compiled by a build without them (the "
              "compile cache's key leaves metadata out); the metrics "
              "that read scopes are left out", file=sys.stderr, flush=True)
        return None
    print("device scopes: %s" % json.dumps(
        {"device": device.name, "busy_s": scopes.busy_s,
         "seconds_by_class_and_part": scopes.table()}),
        file=sys.stderr, flush=True)
    return scopes


def share_percent(ctx, pred):
    """A reader's whole body: percent of device busy time in the
    operations ``pred`` holds for, None without scopes."""
    scopes = of(ctx)
    return None if scopes is None else 100.0 * scopes.share(pred)


if __name__ == "__main__":
    reduction = trace.reduce_dir(sys.argv[1], chips=1)
    first = reduction.devices[0]
    found = load(trace.find_xplane(sys.argv[1]), first.name,
                 first.window()[:2], reduction.per_device[0]["busy_s"])
    print(json.dumps({"busy_s": found.busy_s, "scoped": found.scoped,
                      "seconds_by_class_and_part": found.table()},
                     indent=1))
