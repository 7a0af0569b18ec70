"""The device's idle time between dispatches, by the program's own
phases (PR 36).

``XLAStep._dispatch_epoch`` tiles the host's time from one dispatch to
the next into four phases and records each where the work happens:

* ``build`` — the arguments and the compiled program's look-up,
  ``launch`` — the jit call up to its return, ``fetch`` — the wait for
  the device, the one packed transfer and the unpacking, each a
  ``jax.profiler.TraceAnnotation("veles.dispatch.<phase>")``: an event
  of the profiler's ``/host:CPU`` plane, ON THE DEVICE TRACE'S CLOCK;
* ``replay`` — the end of one ``fetch`` to the start of the next
  ``build`` (the workflow's loop serving the chunk's minibatches to the
  decision): no annotation, it is what lies between two;
* all four as spans ``xla.dispatch.epoch.<phase>`` of the program's
  tracer, with the ``dispatch`` ordinal their parent
  ``xla.dispatch.epoch`` carries (``launch`` + ``fetch`` are the
  parent; it also carries ``compiles``).

For every gap ``between_dispatches`` of device 0 (``reduce/trace.py``'s
``Reduction.per_device[0]["gaps"]``, in memory already) this module
takes the gap's overlap with each phase: under ``fetch`` the device had
finished and the host had not yet got its metrics (wake-up, transfer,
unpacking); under ``launch`` the jit call had begun and no operation
ran yet. What of a gap no phase covers is ``unnamed``, and is never
spread over the four. The metrics are milliseconds a dispatch: the
mean over the window's boundaries between two runs of the step program
(the metric fetch's own small program cuts a boundary's idle time into
two gaps, which its row puts together again). A trace without the
annotations — a program before PR 36 — gives None, and every reader
leaves its metric out.

The annotations are NOT taken from ``Reduction.host``:
``trace.read_planes`` keeps a plane's lines in a dict by NAME, every
Python thread's line is named after the process (``python3``), and the
last one wins — the thread that dispatches is there in some runs and
lost in others (first chip call of PR 36: lost in both traced cells;
the same reason the ledger's ``idle_gaps`` host rows hold
``PjitFunction(veles_epoch_scan)`` in some cells and never in others).
So :func:`annotations` walks the xplane file's ``/host:CPU`` plane
itself, with that module's wire-format primitives over a memory map:
no plane but the host's is parsed, nothing but the three names' events
is kept, and no copy of the file is made (PERF.md section 7(20): the
traced run of ``solar_open2_250b_s4k_train`` stands at the machine's
host-memory limit).

The first call prints the ``dispatch phases:`` line on standard error:
``traced``, every boundary's parts; ``recorded``, from the flight recorder,
the four phases in ms and ``compiles`` of each of the window's
dispatches it still finds there (matched to ``ctx.dispatches`` by start
time; ``found`` of ``of``: the ring of 16,384 spans is shared with
every unit's ``.run`` span, hundreds a dispatch in ``alexnet_train``).
"""

import bisect
import gzip
import json
import mmap
import sys
import time

from benchmark import harness
from benchmark.reduce import trace

ANNOTATION = "veles.dispatch."
SPAN = "xla.dispatch.epoch"
#: in the order they follow a dispatch's last operation on the device
PHASES = ("fetch", "replay", "build", "launch")
#: how much of a boundary's idle time the phases may leave unnamed
#: before the boundary is left out of the mean: the larger of the two
UNNAMED_SHARE, UNNAMED_MS = 0.05, 0.5


def annotations(path):
    """[(start ns, end ns, name)] of the program's annotations in the
    ``/host:CPU`` plane of an xplane file, from EVERY line of the plane
    (fields as ``trace.read_planes`` documents them)."""
    with open(path, "rb") as f:
        if path.endswith(".gz"):
            data = gzip.decompress(f.read())
            return _annotations(memoryview(data))
        with mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as mapped, \
                memoryview(mapped) as view:
            return _annotations(view)


def _annotations(space):
    found = []
    for number, raw in trace.fields(space):
        if number != 1 or next(
                (trace.text([value]) for key, value in trace.fields(raw)
                 if key == 2), "") != "/host:CPU":
            continue
        plane = trace.message(raw)
        names = {}
        for entry in plane[4]:
            entry = trace.message(entry)
            name = trace.text(trace.message(entry[2][0])[2])
            if name.startswith(ANNOTATION):
                names[entry[1][0]] = name
        for raw_line in plane[3]:
            line = trace.message(raw_line)
            origin = (line[3][0] if line[3] else 0) * 1000      # ps
            for raw_event in line[4]:
                # metadata_id is an event's first field (tag 0x08):
                # most events are the runtime's, told by it alone
                if len(raw_event) and raw_event[0] == 8 and \
                        trace.varint(raw_event, 1)[0] not in names:
                    continue
                event = trace.message(raw_event)
                name = names.get(event[1][0] if event[1] else 0)
                if name is not None:
                    start = origin + (event[2][0] if event[2] else 0)
                    end = start + (event[3][0] if event[3] else 0)
                    found.append((start // 1000, end // 1000, name))
    return found


def phase_intervals(host):
    """{phase: sorted [(start ns, end ns)]} of ``(start, end, name)``
    host events; None where none is one of the program's annotations.
    ``replay`` runs from a ``fetch``'s end to the first ``build`` that
    starts at or after it; a last ``fetch`` with no ``build`` after it
    leaves none."""
    found = {"build": [], "launch": [], "fetch": []}
    for start, end, name in host:
        if name.startswith(ANNOTATION) \
                and name[len(ANNOTATION):] in found:
            found[name[len(ANNOTATION):]].append((start, end))
    if not any(found.values()):
        return None
    for intervals in found.values():
        intervals.sort()
    found["replay"] = []
    for _, fetched in found["fetch"]:
        builds = [start for start, _ in found["build"] if start >= fetched]
        if builds:
            found["replay"].append((fetched, builds[0]))
    return found


def overlap_ns(intervals, start, end):
    return sum(max(0, min(e, end) - max(s, start)) for s, e in intervals)


def split(host, gaps, run_ends):
    """The idle time ``between_dispatches`` of ``gaps`` (``(ns, where,
    start, end)``, as ``Reduction.per_device[n]["gaps"]``) by phase,
    one row for each boundary between two runs of the step program
    (``run_ends``: when each run ended, ns); None without annotations,
    or where no boundary is named. A boundary is ``named`` where the
    phases leave no more of it unnamed than ``UNNAMED_SHARE`` or
    ``UNNAMED_MS``, whichever is larger; ``ms_a_dispatch``, what the
    readers report, is the mean over the named ones alone: a boundary
    the annotations do not reach — the profiler session began after
    its ``fetch`` was entered — would halve every figure."""
    phases = phase_intervals(host)
    if phases is None:
        return None
    run_ends = sorted(run_ends)
    rows = [dict.fromkeys(PHASES + ("gap", "pieces"), 0)
            for _ in run_ends[1:]]
    for ns, where, start, end in gaps:
        # a gap belongs to the boundary after the last run that ended
        # before the gap did
        after = bisect.bisect_right(run_ends, end) - 1
        if where != "between_dispatches" or not 0 <= after < len(rows):
            continue
        row = rows[after]
        for phase in PHASES:
            row[phase] += overlap_ns(phases[phase], start, end)
        row["gap"] += ns
        row["pieces"] += 1
    for row in rows:
        row["unnamed"] = row["gap"] - sum(row[phase] for phase in PHASES)
        row["named"] = row["unnamed"] <= max(UNNAMED_SHARE * row["gap"],
                                             UNNAMED_MS * 1e6)
    named = [row for row in rows if row["named"]]
    if not named:
        return None
    times = PHASES + ("unnamed", "gap")
    return {
        "boundaries": [dict({key + "_ms": row[key] / 1e6 for key in times},
                            pieces=row["pieces"], named=row["named"])
                       for row in rows],
        "annotations": {phase: len(phases[phase])
                        for phase in ("build", "launch", "fetch")},
        "between_dispatches_ms": sum(row["gap"] for row in rows) / 1e6,
        "unnamed_ms": sum(row["unnamed"] for row in rows) / 1e6,
        "ms_a_dispatch": {
            phase: sum(row[phase] for row in named) / 1e6 / len(named)
            for phase in PHASES},
    }


def recorded(ctx):
    """The window's dispatches as the program's flight recorder still
    holds them: ``found`` of ``of``, and for each found its ordinal,
    ``compiles`` and the phases in ms (a phase the ring has lost, or a
    last ``replay`` never closed, is left out of its row). A program
    without the phase spans gives ``found`` 0."""
    from veles import telemetry
    if not ctx.dispatches:
        return {"found": 0, "of": 0, "dispatches": []}
    rows = {}
    back = time.time() - ctx.dispatches[0]["start"] + 1.0
    for wall, ev in telemetry.tracer.flight_spans(window=back):
        name, args = ev["name"], ev.get("args", {})
        if not name.startswith(SPAN) or "dispatch" not in args:
            continue
        row = rows.setdefault(args["dispatch"],
                              {"dispatch": args["dispatch"]})
        if name == SPAN:
            row["start"] = wall
            row["compiles"] = args.get("compiles")
        else:
            row[name[len(SPAN) + 1:] + "_ms"] = ev["dur"] / 1e3
    by_start = {row.pop("start"): row for row in rows.values()
                if "start" in row}
    found = [by_start[d["start"]] for d in ctx.dispatches
             if d["start"] in by_start]
    return {"found": len(found), "of": len(ctx.dispatches),
            "dispatches": found}


def of(ctx):
    """:func:`split` of the run's traced window on device 0, made once
    a run and kept on ``ctx``; None where nothing was traced on a
    device or the trace holds no annotation. The first call prints the
    ``dispatch phases:`` line."""
    if not hasattr(ctx, "phases"):
        ctx.phases = None
        if ctx.trace is not None:
            device = ctx.trace.devices[0]
            step = device.step_module()
            ctx.phases = split(
                annotations(trace.find_xplane(harness.trace_dir(
                    ctx.cell["bench_dir"], ctx.cell["name"]))),
                ctx.trace.per_device[0]["gaps"],
                [m.end for m in device.modules if m.name == step])
        print("dispatch phases: %s" % json.dumps(
            {"traced": ctx.phases, "recorded": recorded(ctx)}),
            file=sys.stderr, flush=True)
    return ctx.phases


def idle_ms(ctx, phase):
    """A reader's whole body: milliseconds a dispatch that device 0
    waited under ``phase``; None where :func:`of` is."""
    found = of(ctx)
    return None if found is None else found["ms_a_dispatch"][phase]
