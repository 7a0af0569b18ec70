"""``reduce/phases.py`` on hand-made host events and gaps, every figure
computed by hand below; the same events read back from an xplane file
whose host plane has three lines of one name; the eight readers over
it; and the line's ``recorded`` part against a flight recorder filled
by hand."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import harness           # noqa: E402
from benchmark.reduce import phases     # noqa: E402

MS = 1_000_000      # ns
#: the host thread over three dispatches, times in ms: fetch k ends,
#: the chunk is replayed, build k+1, launch k+1 (returns before the
#: device has finished), fetch k+1 waits for the device
HOST_MS = [
    ("veles.dispatch.build", 0, 4), ("veles.dispatch.launch", 4, 10),
    ("veles.dispatch.fetch", 10, 103),
    ("veles.dispatch.build", 120, 126), ("veles.dispatch.launch", 126, 135),
    ("veles.dispatch.fetch", 135, 232),
    ("veles.dispatch.build", 240, 243), ("veles.dispatch.launch", 243, 250),
    ("veles.dispatch.fetch", 250, 330),
    # the runtime's own frames nest inside ours and are no phase
    ("CommonPjRtLoadedExecutable::Execute", 127, 134),
    ("veles.dispatch.other", 0, 400),
]
HOST = [(start * MS, end * MS, name) for name, start, end in HOST_MS]
#: device 0: the step program runs 8..100, 130..230 and 247..328 ms.
#: After the first run the packing program cuts the idle time in two
#: (100..101 and 101.5..130); the second boundary is one gap that
#: begins BEFORE its fetch annotation is over (230..247); one gap lies
#: inside a step
GAPS_MS = [(100, 101, "between_dispatches"),
           (101.5, 130, "between_dispatches"),
           (180, 180.5, "inside_step_program"),
           (230, 247, "between_dispatches")]
GAPS = [(int((end - start) * MS), where, int(start * MS), int(end * MS))
        for start, end, where in GAPS_MS]
RUN_ENDS = [100 * MS, 230 * MS, 328 * MS]


def text_proto(host, origin=0):
    """An XSpace whose ``/host:CPU`` plane is what a run of the program
    leaves: the thread that dispatches and two more of Python's, every
    one's line named after the process; the others hold the runtime's
    events, one of them without a single field."""
    names = sorted({name for _, _, name in host} | {"CollectGarbage"})
    ids = {name: i + 1 for i, name in enumerate(names)}
    # offsets are relative to the line's own start, ``origin`` ns
    out = ['planes { id: 1 name: "/device:TPU:0" }',
           'planes { id: 2 name: "/host:CPU"',
           '  lines { id: 11 name: "python3" timestamp_ns: %d' % origin]
    out += ['    events { metadata_id: %d offset_ps: %d duration_ps: %d }'
            % (ids[name], (start - origin) * 1000, (end - start) * 1000)
            for start, end, name in host]
    for line in (12, 13):
        out += ['  }', '  lines { id: %d name: "python3" timestamp_ns: 0'
                % line, '    events { metadata_id: %d offset_ps: 5000 '
                'duration_ps: 1000 }' % ids["CollectGarbage"],
                '    events { }']
    out += ['  }']
    out += ['  event_metadata { key: %d value { id: %d name: "%s" } }'
            % (key, key, name) for name, key in ids.items()]
    return "\n".join(out + ['}'])


def context(tmp_path, host=HOST, gaps=GAPS, run_ends=RUN_ENDS,
            dispatches=(), origin=0):
    """A ``Context`` as a traced run's: the reduction's gaps in memory,
    the annotations in the trace file of the cell."""
    from jax.profiler import ProfileData
    cell = {"bench_dir": str(tmp_path / "benchmark"), "name": "toy_cell"}
    where = harness.trace_dir(cell["bench_dir"], cell["name"])
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "t.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            text_proto(host, origin)))
    from benchmark.reduce import trace
    modules = [trace.Module(end - 90 * MS, end, "jit_veles_epoch_scan")
               for end in run_ends]
    modules += [trace.Module(end + MS, end + 3 * MS // 2, "jit_pack")
                for end in run_ends]
    reduction = types.SimpleNamespace(
        per_device=[{"gaps": gaps}],
        devices=[trace.DeviceTrace("/device:TPU:0", [], [], modules)])
    return harness.Context(cell=cell, trace=reduction,
                           dispatches=list(dispatches))


def test_annotations_are_read_from_every_line_of_a_name(tmp_path):
    """``trace.read_planes`` keeps one line a NAME, the last: the
    thread that dispatches is lost behind another ``python3``. This
    reader keeps every line."""
    import gzip
    from benchmark.reduce import trace
    late = [(start + MS, end + MS, name) for start, end, name in HOST]
    ctx = context(tmp_path, host=late, origin=1000)
    path = trace.find_xplane(harness.trace_dir(ctx.cell["bench_dir"],
                                               ctx.cell["name"]))
    want = sorted(event for event in late
                  if event[2].startswith("veles.dispatch."))
    assert sorted(phases.annotations(path)) == want
    with open(path, "rb") as f, gzip.open(path + ".gz", "wb") as g:
        g.write(f.read())
    assert sorted(phases.annotations(path + ".gz")) == want
    # (when this fails ``read_planes`` keeps every line, and
    # ``phases.of`` can take its events from ``Reduction.host``)
    _, host = trace.load_xplane(path)
    assert [name for _, _, name in host] == ["CollectGarbage"]


def test_the_four_parts_of_every_boundary():
    found = phases.split(HOST, GAPS, RUN_ENDS)
    assert found["annotations"] == {"build": 3, "launch": 3, "fetch": 3}
    first, second = found["boundaries"]
    # 100..101 is all under the first fetch (10..103); 101.5..130 is
    # fetch to 103, replay 103..120, build 120..126, launch 126..130
    # (the device's first operation)
    assert first == {"fetch_ms": 2.5, "replay_ms": 17.0, "build_ms": 6.0,
                     "launch_ms": 4.0, "unnamed_ms": 0.0, "gap_ms": 29.5,
                     "pieces": 2, "named": True}
    # 230..247: fetch to 232, replay 232..240, build 240..243, launch
    # 243..247; the gap inside the step (180..180.5) is no boundary's
    assert second == {"fetch_ms": 2.0, "replay_ms": 8.0, "build_ms": 3.0,
                      "launch_ms": 4.0, "unnamed_ms": 0.0, "gap_ms": 17.0,
                      "pieces": 1, "named": True}
    assert found["between_dispatches_ms"] == 46.5
    assert found["unnamed_ms"] == 0.0
    # a dispatch: the mean over the window's two boundaries
    assert found["ms_a_dispatch"] == {
        "fetch": 2.25, "replay": 12.5, "build": 4.5, "launch": 4.0}
    assert sum(found["ms_a_dispatch"].values()) == 46.5 / 2


def test_what_no_phase_covers_is_unnamed_and_not_spread():
    """A profiler session that began after the first dispatch's
    annotations were entered holds none of them (first chip call of
    PR 36, ``lm110m_s512_train``): the first boundary's idle time up to
    the second build has no name, the boundary is not ``named``, and
    the mean is the second boundary's alone, not half of it."""
    found = phases.split(HOST[3:], GAPS, RUN_ENDS)
    first, second = found["boundaries"]
    assert first == {"fetch_ms": 0.0, "replay_ms": 0.0, "build_ms": 6.0,
                     "launch_ms": 4.0, "unnamed_ms": 19.5, "gap_ms": 29.5,
                     "pieces": 2, "named": False}
    assert second["named"] and second["unnamed_ms"] == 0.0
    assert found["unnamed_ms"] == 19.5
    assert found["ms_a_dispatch"] == {
        "fetch": 2.0, "replay": 8.0, "build": 3.0, "launch": 4.0}
    # one dispatch's annotations alone: no replay can be told, no
    # boundary is named, and no number is made of the rest
    assert phases.split(HOST[:3], GAPS, RUN_ENDS) is None


def test_a_sliver_between_two_annotations_leaves_the_boundary_named():
    """The phases tile the host's time but for the instant between one
    annotation's exit and the next one's entry: up to 5% of a boundary
    or 0.5 ms, whichever is larger, may go unnamed."""
    host = [(start, end - (MS // 5 if name.endswith("build") else 0), name)
            for start, end, name in HOST]
    found = phases.split(host, GAPS, RUN_ENDS)
    first, second = found["boundaries"]
    assert (first["unnamed_ms"], second["unnamed_ms"]) == (0.2, 0.2)
    assert first["named"] and second["named"]
    assert found["ms_a_dispatch"]["build"] == 4.3


@pytest.mark.parametrize("host, run_ends", [
    ([event for event in HOST if "veles.dispatch" not in event[2]],
     RUN_ENDS),
    ([], RUN_ENDS),
    (HOST, RUN_ENDS[:1]),
])
def test_no_annotations_or_no_boundary_gives_none(host, run_ends, capsys,
                                                  tmp_path):
    ctx = context(tmp_path, host=host, run_ends=run_ends)
    assert phases.of(ctx) is None
    for phase in phases.PHASES:
        assert phases.idle_ms(ctx, phase) is None
    line = capsys.readouterr().err
    assert line.count("dispatch phases: ") == 1     # once a run
    assert json.loads(line.split("dispatch phases: ")[1])["traced"] is None


def test_an_untraced_run_reads_none_and_still_prints_the_line(capsys):
    ctx = harness.Context(trace=None, dispatches=[])
    assert phases.idle_ms(ctx, "fetch") is None
    doc = json.loads(capsys.readouterr().err.split("dispatch phases: ")[1])
    assert doc == {"traced": None,
                   "recorded": {"found": 0, "of": 0, "dispatches": []}}


NAMES = ["idle_fetch_ms", "idle_replay_ms", "idle_build_ms",
         "idle_launch_ms"]
WANT = {"idle_fetch_ms": 2.25, "idle_replay_ms": 12.5,
        "idle_build_ms": 4.5, "idle_launch_ms": 4.0}


@pytest.mark.parametrize("prefix", ["", "img_"])
@pytest.mark.parametrize("name", NAMES)
def test_each_reader_reports_its_phase(prefix, name, capsys, tmp_path):
    reader = harness.load_module(BENCH_DIR, "layer_metrics", prefix + name)
    assert reader.read(context(tmp_path)) == WANT[name]
    line = capsys.readouterr().err
    assert json.loads(line.split("dispatch phases: ")[1])["traced"][
        "ms_a_dispatch"][name[len("idle_"):-len("_ms")]] == WANT[name]
    assert reader.read(context(tmp_path, host=[])) is None


def test_the_manifest_lists_the_eight_under_the_layer():
    manifest = harness.load_json(os.path.dirname(BENCH_DIR),
                                 "BENCHMARK.json")
    entries = {m["name"]: m for m in manifest["per_layer"]}
    tokens = entries["dispatch_gap_share"]["workloads"]
    for name in NAMES:
        for entry, moves, cells in (
                (entries[name], "train_tokens_per_s", tokens),
                (entries["img_" + name], "train_images_per_s",
                 ["alexnet_train"])):
            assert entry["layer"] == "host_step_dispatch"
            assert (entry["unit"], entry["better"], entry["source"]) == \
                ("ms", "lower", "device_trace")
            assert entry["moves"] == moves
            assert entry["workloads"] == cells


# -- the flight recorder's part of the line ---------------------------------

def record_dispatch(tracer, ordinal, start, with_children=True):
    """The spans one dispatch of the program leaves, times in seconds
    on the tracer's clock: build 10 ms, launch 20, fetch 70, replay 5."""
    ids = {"dispatch": ordinal, "epochs": 1}
    tracer.add_complete("xla.dispatch.epoch", start, 0.09, warm=True,
                        compiles=ordinal % 2, **ids)
    if with_children:
        for phase, at, dur in (("build", -0.01, 0.01), ("launch", 0, 0.02),
                               ("fetch", 0.02, 0.07),
                               ("replay", 0.09, 0.005)):
            tracer.add_complete("xla.dispatch.epoch." + phase, start + at,
                                dur, **ids)
    tracer.add_complete("loader.run", start, 0.001, unit="Loader")


@pytest.fixture
def tracer():
    from veles import telemetry
    telemetry.tracer.clear()
    yield telemetry.tracer
    telemetry.tracer.clear()


def window_of(tracer, ordinals):
    """``ctx.dispatches`` as the driver makes them: the parent spans'
    wall start."""
    return [{"start": wall, "dur": ev["dur"] / 1e6, "epochs": 1,
             "warm": True}
            for wall, ev in tracer.flight_spans()
            if ev["name"] == "xla.dispatch.epoch"
            and ev["args"]["dispatch"] in ordinals]


def test_recorded_finds_the_windows_dispatches_by_start_time(tracer):
    import time
    now = time.perf_counter()
    for ordinal in range(5):
        record_dispatch(tracer, ordinal, now - 5 + ordinal)
    ctx = harness.Context(trace=None,
                          dispatches=window_of(tracer, {1, 2, 3}))
    found = phases.recorded(ctx)
    assert (found["found"], found["of"]) == (3, 3)
    assert [row["dispatch"] for row in found["dispatches"]] == [1, 2, 3]
    for row in found["dispatches"]:
        assert row["compiles"] == row["dispatch"] % 2
        got = [row[phase + "_ms"] for phase in ("build", "launch", "fetch",
                                                "replay")]
        assert got == pytest.approx([10.0, 20.0, 70.0, 5.0])


def test_recorded_says_how_many_the_ring_has_lost(tracer, capsys):
    """The ring is shared with every unit's ``.run`` span: what it no
    longer holds is counted, not guessed; a program without the phase
    spans (its parent span has no ordinal) gives none."""
    import time
    now = time.perf_counter()
    for ordinal in range(4):
        record_dispatch(tracer, ordinal, now - 4 + ordinal)
    window = window_of(tracer, {0, 1, 2, 3})
    # the two oldest dispatches' spans fall out of the ring
    kept = [(wall, ev) for wall, ev in tracer._ring
            if ev.get("args", {}).get("dispatch") not in (0, 1)]
    tracer._ring.clear()
    tracer._ring.extend(kept)
    ctx = harness.Context(trace=None, dispatches=window)
    assert phases.of(ctx) is None
    doc = json.loads(capsys.readouterr().err.split("dispatch phases: ")[1])
    assert (doc["recorded"]["found"], doc["recorded"]["of"]) == (2, 4)
    assert [row["dispatch"] for row in doc["recorded"]["dispatches"]] \
        == [2, 3]

    tracer.clear()
    tracer.add_complete("xla.dispatch.epoch", now - 1, 0.09, warm=True,
                        epochs=1)
    parent_only = [{"start": wall, "dur": 0.09, "epochs": 1, "warm": True}
                   for wall, _ in tracer.flight_spans()]
    old = phases.recorded(harness.Context(trace=None,
                                          dispatches=parent_only))
    assert old == {"found": 0, "of": 1, "dispatches": []}
