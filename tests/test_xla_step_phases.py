"""A scan-mode dispatch records its host time in four phases.

``XLAStep._dispatch_epoch`` tiles the host's time from one dispatch to
the next into ``build`` (arguments and program look-up), ``launch`` (the
jit call), ``fetch`` (the wait for the device and the one packed
transfer) and ``replay`` (serving the chunk's minibatches, up to the
next dispatch or the stop). ``launch`` + ``fetch`` are the
``xla.dispatch.epoch`` span the benchmark has always read, which must
stay what it was; ``build`` + ``replay`` are what lies outside it.
Checked here: the child spans and their ordinal, the tiling, the
histogram, the three profiler annotations (through a stand-in and in a
real ``jax.profiler`` trace read back with the benchmark's reader), the
compile counter behind ``compiles``, and the line the run's end prints.
Every dispatch path (epoch scan, stream windows, per step) also sets
the samples/s and tokens/s gauges, and traces each of its programs
once.
"""

import collections
import io
import re
import sys
import threading

import jax
import numpy
import pytest

from veles import telemetry
from veles.accelerated_units import StepCompiler
from veles.znicz_tpu import xla_step

from test_xla_step_hyper import (  # noqa: F401 (the fixture applies here)
    _restore_config, dispatches, tiny_lm, tiny_mlp)

PARENT = "xla.dispatch.epoch"
PHASE_FAMILY = "veles_xla_dispatch_phase_seconds"
ANNOTATIONS = ["veles.dispatch.build", "veles.dispatch.launch",
               "veles.dispatch.fetch"]


def spans_by_dispatch():
    """{ordinal: {"parent" | phase: (start s, duration s, args)}} of
    the flight recorder, and how often each (ordinal, name) came."""
    rows, seen = {}, {}
    for _, ev in telemetry.tracer.flight_spans():
        if not ev["name"].startswith(PARENT):
            continue
        args = ev["args"]
        what = ev["name"][len(PARENT) + 1:] or "parent"
        key = (args["dispatch"], what)
        seen[key] = seen.get(key, 0) + 1
        rows.setdefault(args["dispatch"], {})[what] = (
            ev["ts"] / 1e6, ev["dur"] / 1e6, args)
    return rows, seen


def phase_counts():
    return {dict(items)["phase"]: child.count
            for items, child in telemetry.histogram(
                PHASE_FAMILY, "", ("kind", "phase")).children()}


@pytest.fixture
def run_of_three():
    """Three dispatches of a toy LM, run to its end and stopped."""
    wf = tiny_lm("Phases", epochs=3)
    wf.run()
    wf.stop()
    rows, seen = spans_by_dispatch()
    assert len(rows) == 3
    return wf, [rows[k] for k in sorted(rows)], seen


def test_four_child_spans_once_a_dispatch(run_of_three):
    _, rows, seen = run_of_three
    assert set(seen.values()) == {1}
    for row in rows:
        assert set(row) == {"parent"} | set(xla_step.PHASES)
        ordinal = row["parent"][2]["dispatch"]
        for phase in xla_step.PHASES:
            assert row[phase][2] == {"dispatch": ordinal, "epochs": 1}
    ordinals = [row["parent"][2]["dispatch"] for row in rows]
    assert ordinals == list(range(ordinals[0], ordinals[0] + 3))


def test_launch_and_fetch_are_the_parent_span(run_of_three):
    _, rows, _ = run_of_three
    for row in rows:
        start, dur, _ = row["parent"]
        assert row["launch"][0] == start
        assert abs(row["launch"][1] + row["fetch"][1] - dur) < 1e-6
        assert abs(row["launch"][0] + row["launch"][1]
                   - row["fetch"][0]) < 1e-6


def test_the_phases_tile_the_time_between_dispatches(run_of_three):
    _, rows, _ = run_of_three
    for row, after in zip(rows, rows[1:]):
        build_end = row["build"][0] + row["build"][1]
        assert abs(build_end - row["parent"][0]) < 1e-6
        parent_end = row["parent"][0] + row["parent"][1]
        assert abs(row["replay"][0] - parent_end) < 1e-6
        replay_end = row["replay"][0] + row["replay"][1]
        assert abs(replay_end - after["build"][0]) < 1e-6


def test_parent_span_keeps_epochs_and_warm(run_of_three):
    _, rows, _ = run_of_three
    assert [row["parent"][2]["warm"] for row in rows] == \
        [False, True, True]
    for row in rows:
        args = row["parent"][2]
        assert args["epochs"] == 1
        assert set(args) == {"warm", "epochs", "dispatch", "compiles"}


def test_histogram_counts_every_phase_of_every_dispatch(run_of_three):
    assert dispatches("epoch") == 3
    assert phase_counts() == {phase: 3 for phase in xla_step.PHASES}
    text = telemetry.get_registry().render_prometheus()
    assert '%s_count{kind="epoch",phase="replay"} 3' % PHASE_FAMILY in text


def test_replay_closes_at_the_next_dispatch_without_a_stop():
    wf = tiny_lm("PhasesNoStop", epochs=3)
    wf.run()
    assert phase_counts() == {"build": 3, "launch": 3, "fetch": 3,
                              "replay": 2}
    wf.stop()
    wf.stop()       # a second stop finds nothing open
    assert phase_counts()["replay"] == 3


def test_stop_before_any_dispatch_records_nothing():
    wf = tiny_lm("PhasesIdle")
    wf.stop()
    assert phase_counts() == {}


def test_stops_from_many_threads_close_one_replay():
    """``stop()`` comes from another thread than the one that
    dispatches (the benchmark's watcher, a signal handler's caller):
    however many race, a dispatch has one ``replay``."""
    wf = tiny_lm("PhasesRace", epochs=8)
    step = wf.xla_step
    previous = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(4):
            step._dispatch_epoch()
            start = threading.Barrier(16)

            def stop():
                start.wait(timeout=30)
                step.stop()

            threads = [threading.Thread(target=stop) for _ in range(16)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=30)
                assert not thread.is_alive()
    finally:
        sys.setswitchinterval(previous)
    assert phase_counts() == {phase: 4 for phase in xla_step.PHASES}
    assert set(spans_by_dispatch()[1].values()) == {1}


# -- the annotations ------------------------------------------------------

def test_each_annotation_is_entered_once_a_dispatch(monkeypatch):
    entered = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("end " + self.name)
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    wf = tiny_lm("PhasesAnnotated", epochs=3)
    wf.run()
    one = [step for name in ANNOTATIONS for step in (name, "end " + name)]
    assert entered == one * 3


def test_annotations_stand_in_the_profilers_host_plane(tmp_path):
    """A real trace, device and runtime events only as the benchmark
    takes it, read back with the benchmark's reader of the host plane:
    the three names once a traced dispatch, one after the other."""
    from benchmark.reduce import phases, trace
    wf = tiny_lm("PhasesTraced", epochs=8)
    step = wf.xla_step
    step._dispatch_epoch()          # compiles outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            step._dispatch_epoch()
    finally:
        jax.profiler.stop_trace()
    found = sorted(phases.annotations(trace.find_xplane(str(tmp_path))))
    assert [name for _, _, name in found] == ANNOTATIONS * 3
    for (_, end, _), (start, _, _) in zip(found, found[1:]):
        assert end <= start


# -- compilations, counted --------------------------------------------------

def test_first_dispatch_compiles_and_the_third_does_not(run_of_three):
    _, rows, _ = run_of_three
    compiles = [row["parent"][2]["compiles"] for row in rows]
    assert compiles[0] >= 1
    assert compiles[1:] == [0, 0]
    registry = telemetry.get_registry()
    assert registry.counter_total("veles_xla_compilations_total") \
        >= compiles[0]
    assert registry.counter_total("veles_xla_compile_seconds_total") > 0


def test_the_compile_listener_is_registered_once():
    from jax._src import monitoring
    tiny_lm("PhasesListenerA")
    tiny_lm("PhasesListenerB")
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(xla_step._on_compile) == 1


def test_only_the_backend_compile_event_is_counted():
    xla_step._on_compile("/jax/core/compile/jaxpr_trace_duration", 1.0)
    assert xla_step._compilations() == 0
    xla_step._on_compile(xla_step._COMPILE_EVENT, 0.25, fun_name="f")
    assert xla_step._compilations() == 1
    assert telemetry.get_registry().counter_total(
        "veles_xla_compile_seconds_total") == 0.25


# -- the line of the run's end ----------------------------------------------

def test_the_runs_end_prints_where_each_dispatch_spent_its_time(
        run_of_three):
    wf, rows, _ = run_of_three
    out = io.StringIO()
    wf.xla_step.print_dispatch_phases(out)
    line = out.getvalue()
    assert line.startswith("dispatch phases: build/launch/fetch/replay ms")
    assert line.endswith("\n") and line.count("\n") == 1
    assert "3 dispatches counted, 3 in the flight recorder" in line
    for row in rows:
        entry = "#%d %s c%d" % (
            row["parent"][2]["dispatch"],
            "/".join("%.1f" % (1e3 * row[phase][1])
                     for phase in xla_step.PHASES),
            row["parent"][2]["compiles"])
        assert entry in line
    out = io.StringIO()
    wf.xla_step.print_dispatch_phases(out, newest=1)
    assert "the newest 1: #%d " % rows[-1]["parent"][2]["dispatch"] \
        in out.getvalue()


def test_an_open_replay_and_an_empty_recorder_read_plainly():
    wf = tiny_lm("PhasesOpen", epochs=2)
    wf.run()
    out = io.StringIO()
    wf.xla_step.print_dispatch_phases(out)
    assert out.getvalue().rstrip().endswith("/- c0")
    telemetry.tracer.clear()
    out = io.StringIO()
    wf.xla_step.print_dispatch_phases(out)
    assert out.getvalue() == ""


def test_the_launcher_prints_the_line_after_the_unit_table(capsys):
    from veles.launcher import Launcher
    wf = tiny_lm("PhasesLauncher", epochs=2)
    launcher = Launcher(stats=True)
    launcher.workflow = wf
    launcher.run()
    err = capsys.readouterr().err
    assert err.index("xla_step") < err.index("dispatch phases: ")


# -- the rates of a dispatch, and the traces behind it ------------------------

#: the perceptron's path for each dispatch kind
MLP_MODES = {"epoch": "scan", "stream": "stream", "step": "step"}
SAMPLES = "veles_step_samples_per_second"
TOKENS = "veles_step_tokens_per_second"


def build(case, name):
    """(workflow, dispatch kind) of a test case, two epochs long: the
    perceptron on one of the three paths, or the toy LM on a
    data-parallel mesh of four."""
    if case == "lm_dp4":
        return tiny_lm(name, epochs=2, data_parallel=4), "epoch"
    return tiny_mlp(name, MLP_MODES[case], epochs=2), case


def step_families():
    """The ``veles_step_*`` and ``veles_perf_*`` families a scrape
    renders."""
    text = telemetry.get_registry().render_prometheus()
    return set(re.findall(r"^(veles_(?:step|perf)_\w+?)[{ ]", text, re.M))


@pytest.mark.parametrize("case", ("epoch", "stream", "step", "lm_dp4"))
def test_step_metrics_on_real_run(case):
    """After a CPU training run, a scrape has the samples/s gauge of
    the path that ran (tokens/s too for an LM loader) and no other
    step or cost-model family, the memory gauges registered."""
    from veles import profiling
    wf, kind = build(case, "StepRates_" + case)
    wf.run()
    profiling.register_memory_gauges()
    lm = case == "lm_dp4"
    assert step_families() == ({SAMPLES, TOKENS} if lm else {SAMPLES})
    rates = {name: telemetry.gauge(name, "", ("kind",)).labels(kind).value
             for name in step_families()}
    assert rates[SAMPLES] > 0
    if lm:
        # 16 tokens a sample
        assert rates[TOKENS] == pytest.approx(16 * rates[SAMPLES])


def test_tokens_per_second_for_lm_loaders():
    """An LM loader's (mb, S) integer minibatch yields a tokens/s
    gauge; float image batches must not."""

    class FakeMem:
        def __init__(self, arr):
            self.mem = arr

    from veles.znicz_tpu.xla_step import XLAStep
    step = XLAStep.__new__(XLAStep)
    step.loader = type("L", (), {})()
    step.loader.minibatch_data = FakeMem(
        numpy.zeros((4, 32), numpy.int32))
    assert XLAStep._tokens_per_sample(step) == 32
    step.loader.minibatch_data = FakeMem(
        numpy.zeros((4, 784), numpy.float32))
    assert XLAStep._tokens_per_sample(step) is None


@pytest.mark.parametrize("case", ("epoch", "stream", "step", "lm_dp4"))
def test_first_dispatch_traces_the_step_once(case, monkeypatch):
    """Over two epochs of dispatches, the step body of each program
    (its class segment: train or evaluation units) is traced once:
    nothing but the jit call traces a program, and the second epoch
    reuses what the first one traced."""
    traced = collections.Counter()
    trace_step = StepCompiler.trace_step

    def counting(self, params, state, hyper, key, train, units, bind):
        traced[train, tuple(unit.name for unit in units)] += 1
        return trace_step(self, params, state, hyper, key, train, units,
                          bind)

    monkeypatch.setattr(StepCompiler, "trace_step", counting)
    wf, kind = build(case, "Traces_" + case)
    wf.run()
    if kind == "step":
        # two epochs of 2 evaluation and 6 training minibatches
        assert wf.xla_step.step_index == 16
    else:
        assert dispatches(kind) == 2
    assert sorted(train for train, _ in traced) == [False, True]
    assert set(traced.values()) == {1}, traced
