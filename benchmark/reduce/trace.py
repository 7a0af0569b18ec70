"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device metrics.

    python3 benchmark/reduce/trace.py <trace dir or .xplane.pb[.gz]> [chips]

prints the planes and lines of the trace and the reduction, which is how
to look at a trace by hand before trusting a number made from it.

What a v5e trace of this repo's training step holds (read off the first
traces of PR 22): one plane per chip, ``/device:TPU:<n>``, with the lines

* ``XLA Modules`` — one event per run of a compiled program, named
  ``jit_<function>(<fingerprint>)`` (``jit_chunk_fn`` is the epoch
  program, ``jit_pack`` the metric fetch);
* ``XLA Ops`` — one event per HLO instruction that ran, one after
  another (the core runs one at a time; only control flow — ``while``,
  ``conditional``, ``call`` — spans the instructions inside it). The
  event's NAME is the whole instruction text; what it is sits in the
  event METADATA's stats: ``hlo_category`` (``convolution fusion``,
  ``loop fusion``, ``custom-call``, ``all-reduce``, ``data formatting``
  ...), ``tf_op`` (the jax primitive it came from, e.g.
  ``.../dot_general:``), ``source`` (file:line of the program that made
  it). On a TPU a matrix product is lowered to a convolution, so
  ``tf_op`` is what tells ``dot_general`` from ``conv_general_dilated``;
* ``Async XLA Ops`` — one event per asynchronous operation from its
  ``-start`` to its ``-done`` (copies, slices, and collectives in
  flight).

``jax.profiler.ProfileData`` does not show metadata stats, so the file is
read here with a few lines of protobuf wire format (tensorflow's
``xplane.proto``: only the fields named below).

The reduction, per device:

* the **window** runs from the start of the first to the end of the last
  run of the step program (the module that took most time): whole
  dispatches, with the host's gaps between them;
* **busy** is the union of the intervals of the operations inside the
  window (control flow left out); **idle share** is 1 - busy / window;
* every operation has a **kind** — ``custom_call`` (Mosaic kernels
  only), ``convolution``, ``matmul``, ``collective``, ``copy``,
  ``other`` — from ``hlo_category``, ``tf_op`` and the custom call's
  target;
* **exposed collective time** is the part of the union of collective
  intervals (the waiting ``-done`` operations and the asynchronous
  operations in flight) that no compute operation's interval covers.
"""

import collections
import glob
import gzip
import os
import sys

KINDS = ("custom_call", "convolution", "matmul", "collective", "copy",
         "other")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CONTROL_FLOW = ("while", "conditional", "call")

Op = collections.namedtuple("Op", "start end name kind source")
Module = collections.namedtuple("Module", "start end name")


# -- protobuf wire format ----------------------------------------------


def varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: ints for varints, bytes
    for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 2:
            size, i = varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError("wire type %d in an XSpace" % wire)
        yield key >> 3, value


def message(buf):
    """{field: [values]} of one message."""
    out = collections.defaultdict(list)
    for number, value in fields(buf):
        out[number].append(value)
    return out


def text(values):
    return bytes(values[0]).decode("utf-8", "replace") if values else ""


def read_planes(data):
    """XSpace bytes -> [{"name", "lines": {line name: [(metadata id,
    start ns, end ns)]}, "events": {metadata id: (name, {stat: str})}}].

    XSpace.planes = 1; XPlane: name 2, lines 3, event_metadata 4 (map:
    key 1, value 2), stat_metadata 5; XLine: name 2, timestamp_ns 3,
    events 4; XEvent: metadata_id 1, offset_ps 2, duration_ps 3;
    XEventMetadata: name 2, stats 5; XStatMetadata: name 2; XStat:
    metadata_id 1, str_value 5, ref_value 7 (a stat_metadata id whose
    name is the string)."""
    planes = []
    for number, raw in fields(data):
        if number != 1:
            continue
        plane = message(raw)
        stat_names = {}
        for entry in plane[5]:
            entry = message(entry)
            stat_names[entry[1][0]] = text(message(entry[2][0])[2])
        events = {}
        for entry in plane[4]:
            entry = message(entry)
            meta = message(entry[2][0])
            stats = {}
            for stat in meta[5]:
                stat = message(stat)
                key = stat_names.get(stat[1][0] if stat[1] else 0, "")
                if stat[5]:
                    stats[key] = text(stat[5])
                elif stat[7]:
                    stats[key] = stat_names.get(stat[7][0], "")
            events[entry[1][0]] = (text(meta[2]), stats)
        lines = {}
        for raw_line in plane[3]:
            line = message(raw_line)
            origin = (line[3][0] if line[3] else 0) * 1000      # ps
            spans = []
            for raw_event in line[4]:
                ev = message(raw_event)
                start = origin + (ev[2][0] if ev[2] else 0)
                end = start + (ev[3][0] if ev[3] else 0)
                spans.append((ev[1][0] if ev[1] else 0,
                              start // 1000, end // 1000))
            lines[text(line[2])] = spans
        planes.append({"name": text(plane[2]), "lines": lines,
                       "events": events})
    return planes


# -- classification ------------------------------------------------------


def short_name(instruction):
    """``%fusion.123 = f32[...] fusion(...)`` -> ``fusion.123``."""
    return instruction.split(" = ", 1)[0].lstrip("%")


def classify(category, tf_op="", instruction=""):
    """Kind of one device operation from its ``hlo_category``, the jax
    primitive it was lowered from and, for custom calls, its target:
    only a Mosaic kernel (``tpu_custom_call``) is a ``custom_call``;
    XLA's own (``AllocateBuffer``, ``ConcatBitcast``) are ``other``."""
    category = category.lower()
    if any(c in category for c in COLLECTIVES):
        return "collective"
    if "custom-call" in category or "custom call" in category:
        return "custom_call" if "tpu_custom_call" in instruction \
            else "other"
    if "convolution" in category:
        return "convolution" if "conv_general_dilated" in tf_op \
            else "matmul"
    if "data formatting" in category or "copy" in category:
        return "copy"
    return "other"


def is_control_flow(category, instruction):
    if category:
        return category.lower() in CONTROL_FLOW
    return short_name(instruction).split(".")[0] in CONTROL_FLOW


def source_file(source):
    """``/root/repo/veles/znicz_tpu/nn_units.py:346`` ->
    ``znicz_tpu/nn_units.py``."""
    path = source.rsplit(":", 1)[0]
    return "/".join(path.split("/")[-2:]) if path else "(no source)"


def union_seconds(intervals):
    """Total length of the union of [(start, end)] in ns, as seconds."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total / 1e9


def subtract_seconds(intervals, cover):
    """Length of union(intervals) not covered by union(cover)."""
    cover = list(cover)
    return union_seconds(list(intervals) + cover) - union_seconds(cover)


# -- the reduction -------------------------------------------------------


class NoDeviceTrace(ValueError):
    """The trace holds no run of a program on a TPU device."""


class DeviceTrace:
    """One chip's operations, asynchronous collectives in flight and
    program runs, times in ns."""

    def __init__(self, name, ops, in_flight, modules):
        self.name = name
        self.ops = sorted(ops)
        self.in_flight = sorted(in_flight)
        self.modules = sorted(modules)

    def step_module(self):
        """Name of the program that took most of the device's time."""
        total = collections.Counter()
        for m in self.modules:
            total[m.name] += m.end - m.start
        return total.most_common(1)[0][0] if total else None

    def window(self):
        """(start, end, runs) of the step program's runs."""
        name = self.step_module()
        runs = [m for m in self.modules if m.name == name]
        if not runs:
            return None
        return runs[0].start, runs[-1].end, len(runs)


def load_xplane(path):
    """-> ([DeviceTrace] of the ``/device:TPU:<n>`` planes in device
    order, [(start, end, name)] of the ``/host:CPU`` plane's events)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        planes = read_planes(f.read())
    devices, host = [], []
    for plane in planes:
        if plane["name"] == "/host:CPU":
            host = [(start, end, plane["events"].get(key, ("", {}))[0])
                    for spans in plane["lines"].values()
                    for key, start, end in spans if end > start]
        if not plane["name"].startswith("/device:TPU:"):
            continue
        meta = plane["events"]
        ops, in_flight, modules = [], [], []
        for key, start, end in plane["lines"].get("XLA Ops", ()):
            name, stats = meta.get(key, ("", {}))
            category = stats.get("hlo_category", "")
            if end > start and not is_control_flow(category, name):
                ops.append(Op(start, end, name,
                              classify(category, stats.get("tf_op", ""),
                                       name),
                              stats.get("source", "")))
        for key, start, end in plane["lines"].get("Async XLA Ops", ()):
            name, stats = meta.get(key, ("", {}))
            if classify(stats.get("hlo_category", "")
                        or short_name(name)) == "collective":
                in_flight.append((start, end))
        for key, start, end in plane["lines"].get("XLA Modules", ()):
            modules.append(Module(start, end,
                                  meta.get(key, ("", {}))[0].split("(")[0]))
        devices.append(DeviceTrace(plane["name"], ops, in_flight, modules))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return devices, host


class Reduction:
    """The metrics of one traced window over the chips a cell used."""

    def __init__(self, devices, chips, host=()):
        self.host = host
        self.devices = [d for d in devices if d.window()][:chips]
        if not self.devices:
            raise NoDeviceTrace("the trace holds no run of a program "
                                "on any TPU device")
        self.per_device = [self._reduce(d) for d in self.devices]
        first = self.per_device[0]
        #: seconds, averaged over the chips used
        self.window_s = self._mean("window_s")
        self.busy_s = self._mean("busy_s")
        #: the device that idled most
        self.idle_share = max(1.0 - r["busy_s"] / r["window_s"]
                              for r in self.per_device)
        self.runs = first["runs"]
        self.collective_exposed_s = first["collective_exposed_s"]

    def _mean(self, key):
        return sum(r[key] for r in self.per_device) / len(self.per_device)

    @staticmethod
    def _reduce(device):
        start, end, runs = device.window()
        ops = [op for op in device.ops
               if op.start >= start and op.end <= end]
        by_kind = collections.defaultdict(list)
        by_name = collections.Counter()
        by_source = collections.Counter()
        for op in ops:
            by_kind[op.kind].append((op.start, op.end))
            label = "%s (%s, %s)" % (short_name(op.name).split(".")[0],
                                     op.kind, source_file(op.source))
            by_name[label] += op.end - op.start
            by_source[source_file(op.source)] += op.end - op.start
        compute = [iv for kind, ivs in by_kind.items()
                   if kind != "collective" for iv in ivs]
        collective = by_kind.get("collective", []) + [
            (max(s, start), min(e, end)) for s, e in device.in_flight
            if e > start and s < end]
        busy = [(op.start, op.end) for op in ops]
        gaps, reach = [], start
        module_ends = sorted(m.end for m in device.modules)
        for s, e in sorted(busy):
            if s > reach:
                between = any(reach <= m <= s for m in module_ends)
                gaps.append((s - reach, "between_dispatches" if between
                             else "inside_step_program", reach, s))
            reach = max(reach, e)
        return {
            "window_s": (end - start) / 1e9, "runs": runs,
            "busy_s": union_seconds(busy),
            "kind_s": {k: union_seconds(by_kind.get(k, ()))
                       for k in KINDS},
            "collective_s": union_seconds(collective),
            "collective_exposed_s": subtract_seconds(collective, compute),
            "top_ops": [[name, ns / 1e9]
                        for name, ns in by_name.most_common(10)],
            "by_source": [[name, ns / 1e9]
                          for name, ns in by_source.most_common(8)],
            "gaps": gaps,
        }

    # -- what the readers ask -------------------------------------------

    def kind_seconds(self, kind):
        """Device seconds of one kind, averaged over the chips."""
        return sum(r["kind_s"][kind] for r in self.per_device) \
            / len(self.per_device)

    def kind_share(self, kind):
        """Share of busy time, averaged over the chips."""
        return self.kind_seconds(kind) / self.busy_s

    @property
    def collective_s(self):
        """Seconds with a collective waiting or in flight, device 0."""
        return self.per_device[0]["collective_s"]

    def steps(self, ctx):
        """Optimizer steps inside the traced window."""
        if not ctx.dispatches:
            return 0
        return self.runs * ctx.dispatches[-1]["epochs"] \
            * ctx.steps_per_epoch

    def breakdown(self):
        """The result line's ``breakdown``, device 0, seconds inside the
        traced window: the ten operations that took most time (by HLO
        opcode, kind and the program file they come from); the idle
        time by where it fell, the longest gap of each place, and what
        the host's threads were doing during the gaps between
        dispatches (events overlap and nest, so these name the work and
        do not add up)."""
        first = self.per_device[0]
        idle = collections.Counter()
        longest = collections.Counter()
        host = collections.Counter()
        for ns, where, start, end in first["gaps"]:
            idle[where] += ns
            longest[where] = max(longest[where], ns)
            if where == "between_dispatches":
                for s, e, name in self.host:
                    overlap = min(e, end) - max(s, start)
                    if overlap > 0:
                        host["host: " + name[:60]] += overlap
        gaps = [[where, ns / 1e9] for where, ns in idle.most_common()]
        gaps += [["longest_" + where, ns / 1e9]
                 for where, ns in longest.most_common()]
        gaps += [[name, ns / 1e9] for name, ns in host.most_common(6)]
        return {"device_ops": first["top_ops"], "idle_gaps": gaps[:10]}

    def summary(self):
        return {
            "devices": [d.name for d in self.devices],
            "step_program": self.devices[0].step_module(),
            "runs": self.runs, "window_s": self.window_s,
            "busy_s": self.busy_s, "idle_share": self.idle_share,
            "kind_share_of_busy": {k: self.kind_share(k) for k in KINDS},
            "collective_s": self.collective_s,
            "collective_exposed_s": self.collective_exposed_s,
            "seconds_by_program_file": self.per_device[0]["by_source"],
        }


def find_xplane(path):
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb*"),
                      recursive=True)
    if not found:
        raise FileNotFoundError("no *.xplane.pb under %s" % path)
    return max(found, key=os.path.getmtime)


def reduce_dir(path, chips):
    devices, host = load_xplane(find_xplane(path))
    return Reduction(devices, chips, host)


def describe(path, out=sys.stdout):
    """Planes, lines, event counts and a few events with their stats."""
    path = find_xplane(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        planes = read_planes(f.read())
    print("%s (%d bytes)" % (path, os.path.getsize(path)), file=out)
    for plane in planes:
        print("plane %r" % plane["name"], file=out)
        for name, spans in plane["lines"].items():
            print("  line %r: %d events" % (name, len(spans)), file=out)
            for key, start, end in spans[:2]:
                event, stats = plane["events"].get(key, ("", {}))
                print("    %d..%d %s %s" % (start, end, event[:100],
                                            {k: v[:60] for k, v in
                                             stats.items()}), file=out)


if __name__ == "__main__":
    import json
    describe(sys.argv[1])
    reduction = reduce_dir(sys.argv[1],
                           int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    print(json.dumps(reduction.summary(), indent=1))
    print(json.dumps(reduction.breakdown(), indent=1))
