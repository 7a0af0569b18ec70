"""``reduce/scopes.py`` and the readers over it on small traces whose
every figure is computed by hand below. The traces are XSpaces written
as text the way ``test_trace.py`` writes its own (the helper is copied
from there), with the ``tf_op`` paths a v5e trace of this repo's scoped
step holds."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import harness                   # noqa: E402
from benchmark.costs import lm as lm_costs      # noqa: E402
from benchmark.reduce import scopes, trace      # noqa: E402

#: (name, start ns, end ns): two runs of the epoch program, one fetch
MODULES = [("jit_veles_epoch_scan(1)", 1000, 5000),
           ("jit_pack(2)", 5200, 5300),
           ("jit_veles_epoch_scan(1)", 6000, 10000)]
P = "jit(veles_epoch_scan)/while/body/closed_call/while/body/closed_call/"
MOSAIC = "f32[8] custom-call(), custom_call_target='tpu_custom_call'"
#: (instruction, hlo_category, tf_op, start ns, end ns)
LM_OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),
    ("%fusion.1 = f32[8] fusion()", "convolution fusion",               # 400
     P + "veles.fwd.MultiHeadAttention.MultiHeadAttention_3/dot_general:",
     1000, 1400),
    ("%closed_call.2 = " + MOSAIC, "custom-call",                       # 600
     P + "veles.fwd.MultiHeadAttention.MultiHeadAttention_3/veles.core/"
     "closed_call/pallas_call:", 1400, 2000),
    ("%fusion.3 = f32[8] fusion()", "loop fusion",                      # 200
     P + "veles.fwd.TokenDense.TokenDense/add:", 2000, 2200),
    ("%fusion.4 = f32[8] fusion()", "loop fusion",                      # 300
     P + "veles.loss.EvaluatorLM.evaluator/reduce_sum:", 2200, 2500),
    ("%fusion.5 = f32[8] fusion()", "convolution fusion",               # 400
     P + "veles.bwd.GDTokenDense.GDTokenDense/dot_general:", 2500, 2900),
    ("%fusion.6 = f32[8] fusion()", "loop fusion",                      # 100
     P + "veles.bwd.GDTokenDense.GDTokenDense/veles.update/cond/"
     "branch_1_fun/mul:", 2900, 3000),
    ("%closed_call.7 = " + MOSAIC, "custom-call",                       # 1200
     P + "veles.bwd.GDMultiHeadAttention.GDMultiHeadAttention_3/"
     "veles.core/pallas_call:", 3000, 4200),
    ("%fusion.8 = f32[8] fusion()", "loop fusion",                      # 100
     P + "veles.bwd.GDMultiHeadAttention.GDMultiHeadAttention_3/"
     "veles.core/while/body/mul:", 4200, 4300),
    ("%copy.9 = f32[8] copy()", "data formatting", "", 4300, 4500),     # 200
    ("%fusion.10 = f32[8] fusion()", "loop fusion", P + "gather:",      # 300
     4500, 4800),
    ("%fusion.11 = f32[8] fusion()", "loop fusion",                     # 100
     "jit(pack)/concatenate:", 5200, 5300),
    # the second run: a validation forward, then a fusion whose event
    # says "fusion" and whose tf_op is its ROOT's, inside an update
    ("%closed_call.12 = " + MOSAIC, "custom-call",                      # 600
     P + "veles.fwd.MultiHeadAttention.MultiHeadAttention_3/veles.core/"
     "pallas_call:", 6000, 6600),
    ("%fusion.13 = f32[8] fusion()", "loop fusion",                     # 400
     P + "veles.bwd.GDTransformerFFN.GDTransformerFFN_7/veles.update/add:",
     6600, 7000),
    ("%all-reduce.14 = f32[8] all-reduce()", "all-reduce", "",          # 500
     7000, 7500),
    # after the window: not counted
    ("%fusion.15 = f32[8] fusion()", "loop fusion",
     P + "veles.fwd.TokenDense.TokenDense/add:", 10500, 10700),
]
LM_BUSY = 3800 + 100 + 1500     # 1000-4800, 5200-5300, 6000-7500

IMG_MODULES = [("jit_veles_epoch_scan(1)", 1000, 4000)]
IMG_OPS = [
    ("%fusion.1 = f32[8] fusion()", "convolution fusion",               # 1000
     P + "veles.fwd.ConvRELU.ConvRELU_2/conv_general_dilated:", 1000, 2000),
    ("%fusion.2 = f32[8] fusion()", "convolution fusion",               # 300
     P + "veles.fwd.All2AllRELU.All2AllRELU/dot_general:", 2000, 2300),
    ("%fusion.3 = f32[8] fusion()", "convolution fusion",               # 400
     P + "veles.bwd.GDRELU.GDRELU/dot_general:", 2300, 2700),
    ("%fusion.4 = f32[8] fusion()", "loop fusion",                      # 300
     P + "veles.bwd.GDRELU.GDRELU/veles.update/add:", 2700, 3000),
    ("%fusion.5 = f32[8] fusion()", "convolution fusion",               # 500
     P + "veles.bwd.GDRELUConv.GDRELUConv_2/conv_general_dilated:",
     3000, 3500),
    ("%fusion.6 = f32[8] fusion()", "loop fusion",                      # 100
     P + "veles.fwd.All2AllSoftmax.All2AllSoftmax/exp:", 3500, 3600),
    ("%fusion.7 = f32[8] fusion()", "convolution fusion",               # 100
     P + "veles.bwd.GDSoftmax.GDSoftmax/dot_general:", 3600, 3700),
    ("%copy.8 = f32[8] copy()", "data formatting", "", 3700, 4000),     # 300
]
IMG_BUSY = 3000


def text_proto(modules, ops):
    names = sorted({row[0] for row in modules + ops})
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
    out += [event(ids[n], s, e) for n, s, e in modules] + ['  }']
    out += ['  lines { id: 2 name: "XLA Ops" timestamp_ns: 500']
    out += [event(ids[n], s - 500, e - 500) for n, _, _, s, e in ops]
    out += ['  }']
    out += [metadata(n) for n in sorted({n for n, _, _ in modules})]
    out += [metadata(n, c, t) for n, c, t, _, _ in ops]
    out += ['  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }',
            '  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }', '}']
    return "\n".join(out)


def context(tmp_path, modules, ops, **fields):
    """A reader's context over a trace written where the harness keeps
    a cell's profile (``harness.trace_dir``)."""
    from jax.profiler import ProfileData
    bench_dir = str(tmp_path / "benchmark")
    where = harness.trace_dir(bench_dir, "cell")
    os.makedirs(where)
    with open(os.path.join(where, "t.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            text_proto(modules, ops)))
    cell = {"name": "cell", "bench_dir": bench_dir}
    cell.update(fields.pop("cell", {}))
    return harness.Context(cell=cell, chips=1,
                           trace=trace.reduce_dir(where, chips=1), **fields)


def lm_context(tmp_path, ops=LM_OPS, flops=1e10, nbytes=1e10):
    # 2 runs of 1 epoch of 2 steps = 4 steps; one validation minibatch
    # an epoch; minibatches of 2 sequences of 4 tokens
    return context(
        tmp_path, MODULES, ops,
        cell={"config": {"model": {"layers": 2, "dim": 8, "heads": 2}},
              "traffic": {"seq_len": 4, "minibatch": 2, "n_valid": 2}},
        dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1, "warm": True}],
        steps_per_epoch=2, costs=lm_costs,
        peaks={"bf16_flops_per_s": flops, "hbm_bytes_per_s": nbytes})


def read(name, ctx):
    return harness.load_module(BENCH_DIR, "layer_metrics", name).read(ctx)


def test_unit_of():
    assert scopes.unit_of(
        P + "veles.bwd.GDTransformerFFN.GDTransformerFFN_7/veles.update/"
        "mul:") == ("bwd", "GDTransformerFFN", "GDTransformerFFN_7",
                    "update")
    assert scopes.unit_of(
        "jit(veles_step)/veles.fwd.MultiHeadAttention.MultiHeadAttention/"
        "veles.core/closed_call/pallas_call:") == (
            "fwd", "MultiHeadAttention", "MultiHeadAttention", "core")
    # the class is in the scope because the name need not hold it
    assert scopes.unit_of("veles.loss.EvaluatorLM.evaluator/exp:") == (
        "loss", "EvaluatorLM", "evaluator", None)
    # a sub-scope counts only after a unit, and only as a whole component
    assert scopes.unit_of(P + "veles.update/mul:") == (None,) * 4
    assert scopes.unit_of(
        P + "veles.bwd.GDConv.c1/veles.updates/mul:")[3] is None
    assert scopes.unit_of(P + "gather:") == (None,) * 4
    assert scopes.unit_of("") == (None,) * 4


def test_split_by_unit_role_and_sub_scope(tmp_path, capsys):
    found = scopes.of(lm_context(tmp_path))
    assert found.busy_s == pytest.approx(LM_BUSY * 1e-9)
    table = {(cls, part): s for cls, part, s in found.table()}
    want = {
        ("MultiHeadAttention", "forward"): 400,
        ("MultiHeadAttention", "forward core"): 600 + 600,
        ("TokenDense", "forward"): 200,
        ("EvaluatorLM", "loss"): 300,
        ("GDTokenDense", "backward"): 400,
        ("GDTokenDense", "update"): 100,
        ("GDMultiHeadAttention", "backward core"): 1200 + 100,
        # a fusion is attributed to the scope of its root instruction
        ("GDTransformerFFN", "update"): 400,
        ("(no scope)", "copy"): 200,
        ("(no scope)", "other"): 300 + 100,
        ("(no scope)", "collective"): 500,
    }
    assert set(table) == set(want)
    for key, ns in want.items():
        assert table[key] == pytest.approx(ns * 1e-9), key
    # the rows, unscoped time included, are the device's busy time
    assert sum(table.values()) == pytest.approx(found.busy_s)
    assert [row[2] for row in found.table()] == sorted(
        table.values(), reverse=True)
    err = capsys.readouterr().err
    assert err.count("device scopes: {") == 1


def test_the_table_is_printed_once_a_run(tmp_path, capsys):
    ctx = lm_context(tmp_path)
    for name in ("flash_attn_share", "solver_update_share",
                 "unscoped_share"):
        read(name, ctx)
    assert capsys.readouterr().err.count("device scopes:") == 1


def test_share_readers(tmp_path):
    ctx = lm_context(tmp_path)
    assert read("flash_attn_share", ctx) == pytest.approx(
        100 * (1200 + 1300) / LM_BUSY)
    # head forward + loss + head backward; the head's update is the solver's
    assert read("vocab_head_share", ctx) == pytest.approx(
        100 * (200 + 300 + 400) / LM_BUSY)
    assert read("solver_update_share", ctx) == pytest.approx(
        100 * (100 + 400) / LM_BUSY)
    assert read("unscoped_share", ctx) == pytest.approx(
        100 * (200 + 400 + 500) / LM_BUSY)


def test_attention_rooflines_count_the_validation_forwards(tmp_path):
    ctx = lm_context(tmp_path)
    # forward, a sequence: 2 x 2 layers x 4 x 5 x 8 = 640 FLOP and
    # 2 x (4 x 4 x 8 x 2 + 2 x 4 x 4) = 576 B: 64 ns at 1e10 a second.
    # 4 train steps + 2 validation minibatches, 2 sequences each = 12
    # sequences over the 1200 ns of forward kernels
    assert read("attn_fwd_roofline", ctx) == pytest.approx(
        100 * 64 * 12 / 1200)
    # backward: 1280 FLOP, 2 x (8 x 64 + 32) = 1088 B, train steps only
    # (8 sequences), the 1200 ns of Mosaic kernel (not the 100 ns of
    # fusion.8 beside it under the same scope)
    assert read("attn_bwd_roofline", ctx) == pytest.approx(
        100 * 128 * 8 / 1200)


def test_attention_rooflines_take_the_larger_bound(tmp_path):
    ctx = lm_context(tmp_path, nbytes=1e9)      # bytes bound now
    assert read("attn_fwd_roofline", ctx) == pytest.approx(
        100 * 576 * 12 / 1200)
    assert read("attn_bwd_roofline", ctx) == pytest.approx(
        100 * 1088 * 8 / 1200)


def test_image_cell_readers(tmp_path):
    ctx = context(tmp_path, IMG_MODULES, IMG_OPS)
    # All2All* forward and the ops/gd.py units, update left out; the
    # convolution's gradient unit (GDRELUConv) is no FC unit
    assert read("fc_share", ctx) == pytest.approx(
        100 * (300 + 400 + 100 + 100) / IMG_BUSY)
    assert read("img_solver_update_share", ctx) == pytest.approx(
        100 * 300 / IMG_BUSY)
    assert read("img_unscoped_share", ctx) == pytest.approx(
        100 * 300 / IMG_BUSY)


NEW_READERS = ("attn_fwd_roofline", "attn_bwd_roofline", "flash_attn_share",
               "vocab_head_share", "solver_update_share",
               "img_solver_update_share", "fc_share", "unscoped_share",
               "img_unscoped_share")


def test_a_trace_without_scopes_gives_none_and_says_so(tmp_path, capsys):
    """An executable compiled before the scopes existed: the same
    operations, their paths without a unit."""
    bare = [(name, category,
             "jit(chunk_fn)/while/body/" + tf_op.rsplit("/", 1)[-1]
             if tf_op else "", start, end)
            for name, category, tf_op, start, end in LM_OPS]
    ctx = lm_context(tmp_path, ops=bare)
    assert ctx.trace.busy_s == pytest.approx(LM_BUSY * 1e-9)
    for name in NEW_READERS:
        assert read(name, ctx) is None, name
    err = capsys.readouterr().err
    assert err.count("device scopes: NONE") == 1


def test_no_trace_no_number():
    """The CPU rehearsal has no device plane: ``ctx.trace`` is None."""
    ctx = harness.Context(cell={"name": "cell", "bench_dir": BENCH_DIR},
                          trace=None, peaks=None, dispatches=[])
    for name in NEW_READERS:
        assert read(name, ctx) is None, name
