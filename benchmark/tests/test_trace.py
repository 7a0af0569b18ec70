"""``reduce/trace.py`` on a small trace whose every figure is computed
by hand below. The trace is an XSpace written as text in the layout a
v5e trace of this repo's training step has — a ``/device:TPU:0`` plane
with ``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops`` lines, what an
operation is in its event METADATA's stats, and a ``/host:CPU`` plane —
and serialized by jax, so the reader's protobuf wire parsing is under
test too."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.reduce import trace      # noqa: E402

#: (name, start ns, end ns) on the line's clock
MODULES = [("jit_chunk_fn(1)", 1000, 5000), ("jit_pack(2)", 5200, 5300),
           ("jit_chunk_fn(1)", 6000, 10000)]
#: (instruction, hlo_category, tf_op, start ns, end ns)
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),  # spans the step
    ("%fusion.1 = f32[8] fusion()", "convolution fusion",
     "jit(f)/conv_general_dilated:", 1000, 2000),
    ("%closed_call.2 = f32[8] custom-call(), custom_call_target='tpu_custom_call'",
     "custom-call", "jit(f)/pallas_call:", 2000, 3000),
    ("%all-reduce.3 = f32[8] all-reduce()", "all-reduce", "", 2500, 3500),
    ("%copy.4 = f32[8] copy()", "data formatting", "", 3400, 3600),
    ("%fusion.5 = f32[8] fusion()", "loop fusion", "", 4000, 4800),
    ("%fusion.9 = f32[8] fusion()", "loop fusion", "", 5200, 5300),
    ("%fusion.6 = f32[8] fusion()", "convolution fusion",
     "jit(f)/dot_general:", 6000, 8000),
    ("%all-reduce-done.7 = f32[8] all-reduce-done()", "all-reduce", "",
     8500, 9000),
    ("%alloc.10 = f32[8] custom-call(), custom_call_target='AllocateBuffer'",
     "custom-call", "", 9000, 9000),
    ("%cat.12 = f32[8] custom-call(), custom_call_target='ConcatBitcast'",
     "custom-call", "", 9000, 9100),                # XLA's own: "other"
    ("%fusion.8 = f32[8] fusion()", "loop fusion", "", 9100, 10000),
]
#: all-reduce.7 in flight from its start to its done
ASYNC = [("%all-reduce-start.7 = f32[8] all-reduce-start()", "all-reduce",
          "", 7500, 9000),
         ("%copy-start.11 = f32[8] copy-start()", "data formatting", "",
          1000, 1500)]
HOST = [("PjitFunction(chunk_fn)", 4900, 6100), ("DevicePut", 5350, 5950),
        ("np.asarray", 100, 200)]


def text_proto():
    names = sorted({row[0] for row in MODULES + OPS + ASYNC})
    ids = {name: i + 1 for i, name in enumerate(names)}

    def event(key, start, end):
        return ("    events { metadata_id: %d offset_ps: %d "
                "duration_ps: %d }" % (key, start * 1000,
                                       (end - start) * 1000))

    def metadata(name, category="", tf_op=""):
        stats = "".join(' stats { metadata_id: %d str_value: "%s" }'
                        % (key, value)
                        for key, value in ((1, category), (2, tf_op))
                        if value)
        return ('  event_metadata { key: %d value { id: %d name: "%s"%s } }'
                % (ids[name], ids[name], name, stats))

    out = ['planes {', '  id: 1', '  name: "/device:TPU:0"',
           '  lines { id: 1 name: "XLA Modules" timestamp_ns: 0']
    out += [event(ids[n], s, e) for n, s, e in MODULES] + ['  }']
    # the ops' line starts 500 ns late: offsets are relative to it
    out += ['  lines { id: 2 name: "XLA Ops" timestamp_ns: 500']
    out += [event(ids[n], s - 500, e - 500) for n, _, _, s, e in OPS]
    out += ['  }', '  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 0']
    out += [event(ids[n], s, e) for n, _, _, s, e in ASYNC] + ['  }']
    out += [metadata(n) for n, _, _ in MODULES[:2]]
    out += [metadata(n, c, t) for n, c, t, _, _ in OPS + ASYNC]
    out += ['  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }',
            '  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }', '}',
            'planes { id: 2 name: "/host:CPU"',
            '  lines { id: 1 name: "python3" timestamp_ns: 0']
    out += [event(i + 1, s, e) for i, (_, s, e) in enumerate(HOST)] + ['  }']
    out += ['  event_metadata { key: %d value { id: %d name: "%s" } }'
            % (i + 1, i + 1, n) for i, (n, _, _) in enumerate(HOST)]
    return "\n".join(out + ['}'])


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        text_proto()))
    return trace.reduce_dir(str(path.parent), chips=1)


def test_window_busy_and_idle(reduction):
    # window: first start to last end of the step program, 1000-10000
    assert reduction.runs == 2
    assert reduction.window_s == pytest.approx(9000e-9)
    # busy (the while and the zero-length allocation left out):
    # 1000-3600 (2600) + 4000-4800 (800) + 5200-5300 (100)
    # + 6000-8000 (2000) + 8500-10000 (1500) = 7000
    assert reduction.busy_s == pytest.approx(7000e-9)
    assert reduction.idle_share == pytest.approx(2000 / 9000)


def test_time_by_kind(reduction):
    # a convolution fusion is a convolution or a matmul by its tf_op;
    # "other": fusion.5 (800), fusion.9 (100), cat.12 (100), fusion.8 (900)
    want = {"convolution": 1000, "custom_call": 1000, "matmul": 2000,
            "collective": 1000 + 500, "copy": 200, "other": 1900}
    for kind, ns in want.items():
        assert reduction.kind_seconds(kind) == pytest.approx(ns * 1e-9), kind
    assert reduction.kind_share("custom_call") == pytest.approx(1000 / 7000)


def test_exposed_collective(reduction):
    # all-reduce.3 runs 2500-3500: closed_call.2 covers it to 3000 and
    # copy.4 from 3400, so 400 is exposed. all-reduce.7 is in flight
    # 7500-9000 (the async line) and waited for 8500-9000: fusion.6
    # hides it to 8000, so 1000 is exposed. The asynchronous copy is
    # not a collective.
    assert reduction.collective_s == pytest.approx(2500e-9)
    assert reduction.collective_exposed_s == pytest.approx(1400e-9)


def test_breakdown_names_the_gaps(reduction):
    breakdown = reduction.breakdown()
    gaps = dict(breakdown["idle_gaps"])
    # 3600-4000 and 8000-8500 lie inside a run of the step program;
    # 4800-5200 and 5300-6000 each hold the end of a program
    assert gaps["inside_step_program"] == pytest.approx(900e-9)
    assert gaps["between_dispatches"] == pytest.approx(1100e-9)
    assert gaps["longest_between_dispatches"] == pytest.approx(700e-9)
    # the host during 4800-5200 and 5300-6000
    assert gaps["host: PjitFunction(chunk_fn)"] == pytest.approx(
        (300 + 700) * 1e-9)
    assert gaps["host: DevicePut"] == pytest.approx(600e-9)
    assert "host: np.asarray" not in gaps
    assert len(breakdown["idle_gaps"]) <= 10
    assert len(breakdown["device_ops"]) <= 10
    top = dict((name, s) for name, s in breakdown["device_ops"])
    assert top["fusion (matmul, (no source))"] == pytest.approx(2000e-9)


def test_classify():
    assert trace.classify("convolution fusion",
                          "jit(f)/conv_general_dilated:") == "convolution"
    assert trace.classify("convolution fusion",
                          "jit(f)/dot_general:") == "matmul"
    assert trace.classify("all-reduce") == "collective"
    assert trace.classify("custom-call", "", "custom_call_target="
                          '"tpu_custom_call"') == "custom_call"
    assert trace.classify("custom-call", "", "custom_call_target="
                          '"AllocateBuffer"') == "other"
    assert trace.classify("loop fusion") == "other"
    assert trace.classify("data formatting") == "copy"
    assert trace.short_name("%fusion.3 = f32[2] fusion()") == "fusion.3"
    assert trace.source_file("/root/repo/veles/znicz_tpu/nn_units.py:346") \
        == "znicz_tpu/nn_units.py"


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }'))
    with pytest.raises(trace.NoDeviceTrace):
        trace.reduce_dir(str(tmp_path), chips=1)
