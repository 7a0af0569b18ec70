"""What the EVA byte LM cell adds: the configuration file against the
catalog's statement, key by key; ``costs/evabyte.py`` against counts
made by hand (the configuration's table, to the parameter; the
16,785,408 local and 7,340,032 remote pairs a head);
``reduce/evascopes.py`` and the two
new readers on a hand-made trace with ``veles.eva`` inside
``veles.core`` (and the accepted readers the cell joins); and the cell
end to end on the CPU at a tiny preset (``cpu_cell_evabyte.py``)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from benchmark import harness, run                      # noqa: E402
from benchmark.costs import evabyte as costs            # noqa: E402
from benchmark.reduce import evascopes                  # noqa: E402
from benchmark.tests.test_scopes import (               # noqa: E402
    MODULES, MOSAIC, P, context, read)

CONFIG = harness.load_json(BENCH_DIR, "configs", "evabyte.json")
TRAFFIC = harness.load_json(BENCH_DIR, "traffic", "evabyte_s16k_train.json")
MODEL = CONFIG["model"]
CELL = "evabyte_s16k_train"


# -- the configuration file ---------------------------------------------------


def test_config_matches_the_catalog_row_key_by_key():
    """``evabyte_catalog_row.json``: the catalog's entry, copied beside
    this test as this configuration found it. Every key of its
    ``config`` is in the file under the same name with the same value;
    ``published`` holds the row's config verbatim; the one cut is the
    depth, under a key of its own."""
    row = harness.load_json(HERE, "evabyte_catalog_row.json")
    assert row["name"] == "EvaByte"
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["published"] == row["config"]
    differing = [key for key, value in row["config"].items()
                 if CONFIG[key] != value]
    assert differing == []
    assert CONFIG["reduced"] == ["layers"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert CONFIG["layers"] == len(MODEL["layers"]) == 4
    assert CONFIG["num_hidden_layers"] == 32


def test_what_is_run_has_every_published_width():
    pub = CONFIG["published"]
    assert (MODEL["dim"], MODEL["heads"], MODEL["kv_heads"],
            MODEL["ffn_hidden"], MODEL["vocab"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["intermediate_size"],
        pub["vocab_size"]) == (4096, 32, 32, 11008, 320)
    assert MODEL["head_dim"] == pub["hidden_size"] \
        // pub["num_attention_heads"] == 128
    assert pub["attention_class"] == "eva"
    assert (MODEL["eva_window"], MODEL["eva_chunk"]) \
        == (pub["window_size"], pub["chunk_size"]) == (2048, 16)
    assert MODEL["pred_heads"] == pub["num_pred_heads"] == 8
    assert MODEL["rope_theta"] == pub["rope_theta"] == 100000
    assert pub["rope_scaling"] is None
    assert MODEL["norm_eps"] == pub["rms_norm_eps"] == 1e-5
    assert pub["fp32_skip_add"] is pub["fp32_logits"] is True
    assert MODEL["residual_dtype"] == MODEL["logits_dtype"] == "float32"
    assert pub["attention_bias"] is False \
        and pub["tie_word_embeddings"] is False
    assert pub["hidden_act"] == "silu"
    # every layer is EVA over the dense SwiGLU: published layers 0-3
    assert MODEL["layers"] == ["eva_attention"] * 4
    assert MODEL["dense_layers"] == 4
    # every reading the config does not state is written down
    assert {"pooling", "pooling_draw", "rotated_summaries", "windows",
            "heads", "loss", "norm_add_unit_offset", "untied_head",
            "initialisation", "optimizer", "corpus"} \
        <= set(CONFIG["assumed"])
    # every override names a key the file holds
    for value in CONFIG["program"]["overrides"].values():
        if isinstance(value, str) and value.startswith("$model."):
            assert value[7:] in MODEL, value
        if isinstance(value, str) and value.startswith("$traffic."):
            assert value[9:] in TRAFFIC, value
    assert (TRAFFIC["seq_len"], TRAFFIC["minibatch"], TRAFFIC["n_train"],
            TRAFFIC["n_valid"], TRAFFIC["max_period"],
            TRAFFIC["data_parallel"]) == (16384, 1, 4, 1, 8192, 1)
    assert TRAFFIC["learning_rate"] in (0.01, 0.002)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "evabyte.py")) as f:
        text = f.read()
    assert "import veles" not in text and "from veles" not in text


# -- the costs, by hand ---------------------------------------------------------


def test_parameters_by_hand_are_the_configurations_table():
    attention = 4 * 4096 * 4096
    ffn = 3 * 4096 * 11008
    assert (attention, ffn) == (67_108_864, 135_266_304)
    assert costs.attention_params(MODEL) == attention
    assert costs.ffn_params(MODEL) == ffn
    # two gains, mu and phi
    layer = attention + ffn + 2 * 4096 + 2 * 32 * 128
    assert costs.layer_params(MODEL) == layer == 202_391_552
    assert 4 * layer == 809_566_208
    assert 320 * 4096 == 1_310_720
    assert costs.head_params(MODEL) == 4096 * 8 * 320 == 10_485_760
    total = costs.parameters(MODEL)
    assert total == 4 * layer + 1_310_720 + 10_485_760 + 4096 \
        == 821_366_784
    assert round(8 * total / 1e9, 2) == 6.57
    # the reference counts the same from ITS shapes
    ref = harness.load_module(BENCH_DIR, "reference", CONFIG["reference"])
    assert ref.count_parameters(MODEL) == total
    # and the uncut model is the catalog's 6.5B
    assert costs.parameters(MODEL, 32) == 32 * layer + 11_800_576 \
        == 6_488_330_240


def test_the_pairs_by_hand():
    assert costs.local_pairs(MODEL, 16384) == 8 * 2048 * 2049 // 2 \
        == 16_785_408
    assert costs.remote_pairs(MODEL, 16384) \
        == 2048 * 128 * (0 + 1 + 2 + 3 + 4 + 5 + 6 + 7) == 7_340_032
    assert round(7_340_032 / 16_785_408, 2) == 0.44
    assert (16_785_408 + 7_340_032) / 16384 == 1472.5
    # at the config's 32,768 the two come near
    assert round(costs.remote_pairs(MODEL, 32768)
                 / costs.local_pairs(MODEL, 32768), 2) == 0.94
    # a window past S: the causal triangle, no remote part
    assert costs.local_pairs(dict(MODEL, eva_window=4096), 2048) \
        == 2048 * 2049 // 2
    assert costs.remote_pairs(dict(MODEL, eva_window=4096), 2048) == 0
    # the program counts the same pairs, and its remote tiles visit
    # exactly them
    from veles.znicz_tpu.ops import eva_attention as E
    from veles.znicz_tpu.parallel import pallas_attention as PA
    assert E.local_pairs(16384, 2048) == 16_785_408
    assert PA.prefix_pairs(16384, (2048, 128)) == 7_340_032


def test_train_flops_by_hand():
    seq = 16384
    parts = costs.train_flops_by_part(MODEL, seq)
    assert parts["projections"] == 6 * seq * 4 * 67_108_864
    assert parts["ffn"] == 6 * seq * 4 * 135_266_304
    assert parts["heads"] == 6 * seq * 10_485_760
    # forward 4 dh a pair and head, backward 8 more
    assert parts["local"] == 4 * 12 * 128 * 32 * 16_785_408
    assert parts["remote"] == 4 * 12 * 128 * 32 * 7_340_032
    assert parts["summaries"] == 4 * 3 * 8 * 128 * seq * 32
    # the cell's figures: 24.1 MFLOP of EVA core a token and layer
    # forward against 404.8 of projections and FFN, 5.6%
    core = 4 * 128 * 32 * (16_785_408 + 7_340_032) / seq
    dense = 2 * (67_108_864 + 135_266_304)
    assert (round(core / 1e6, 1), round(dense / 1e6, 1)) == (24.1, 404.8)
    assert round(100 * core / (core + dense), 1) == 5.6
    total = costs.train_flops_per_sample(MODEL, TRAFFIC)
    assert total == sum(parts.values())
    assert round(total / seq / 1e9, 3) == 5.210
    assert round(100 * (parts["local"] + parts["remote"]) / total, 1) \
        == 5.6
    assert round(100 * parts["ffn"] / total, 1) == 62.3
    assert round(100 * parts["heads"] / total, 1) == 1.2


def test_core_costs_by_hand():
    tiny = {"heads": 2, "head_dim": 4, "eva_window": 4, "eva_chunk": 2,
            "layers": ["eva_attention"] * 3}
    traffic = {"seq_len": 8, "minibatch": 2}
    # a head and sequence: 2 windows of 4 x 5 / 2 local pairs, window 1
    # sees 2 summaries with its 4 queries
    assert costs.local_pairs(tiny, 8) == 20
    assert costs.remote_pairs(tiny, 8) == 8
    flops, nbytes = costs.core_cost(tiny, traffic)
    assert flops == 3 * 2 * 12 * 4 * 2 * 28
    row = 16 * 2 * 4 * 2
    assert nbytes == 3 * (8 * row + 4 * (row // 2) + 2 * 16 * 2 * 4)
    assert costs.core_cost(tiny, traffic, backward=False) == (
        3 * 2 * 4 * 4 * 2 * 28,
        3 * (4 * row + 2 * (row // 2) + 2 * 16 * 2 * 4))
    # at the timed sizes the operations bound the core, not the bytes
    flops, nbytes = costs.core_cost(MODEL, TRAFFIC)
    assert flops / 197e12 > nbytes / 819e9


# -- the new scopes on a hand-made trace ---------------------------------------

A1 = "veles.fwd.EvaAttention.EvaAttention/"
GA1 = "veles.bwd.GDEvaAttention.GDEvaAttention/"
EVA = "veles.core/veles.eva/"
F1 = "veles.fwd.SwiGLUFFN.SwiGLUFFN/"


def fusion(n, path, start, end):
    return ("%%fusion.%d = f32[8] fusion()" % n, "loop fusion", path,
            start, end)


def kernel(n, path, start, end):
    return ("%%closed_call.%d = %s" % (n, MOSAIC), "custom-call", path,
            start, end)


#: one step + one validation forward of one EVA layer and its SwiGLU
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),
    fusion(1, P + A1 + "dot_general:", 1000, 1100),
    fusion(2, P + A1 + EVA + "veles.eva.summary/jvp()/reduce_sum:",
           1100, 1150),
    kernel(3, P + A1 + EVA + "veles.eva.local/closed_call/pallas_call:",
           1150, 1350),
    fusion(4, P + A1 + EVA + "veles.eva.remote/transpose:", 1350, 1370),
    kernel(5, P + A1 + EVA + "veles.eva.remote/pallas_call:", 1370, 1450),
    fusion(6, P + A1 + EVA + "log1p:", 1450, 1480),
    fusion(7, P + F1 + "dot_general:", 1480, 1780),
    # the backward
    fusion(8, P + GA1 + EVA + "reduce_sum:", 1780, 1800),
    kernel(9, P + GA1 + EVA + "veles.eva.local/pallas_call:", 1800, 2200),
    kernel(10, P + GA1 + EVA + "veles.eva.remote/pallas_call:",
           2200, 2350),
    fusion(11, P + GA1 + EVA + "veles.eva.summary/transpose(jvp())/mul:",
           2350, 2400),
    fusion(12, P + GA1 + "transpose(veles.fwd.EvaAttention."
           "EvaAttention)/jvp()/dot_general:", 2400, 2600),
    fusion(13, P + GA1 + "veles.update/add:", 2600, 2700),
    # the validation forward
    kernel(14, P + A1 + EVA + "veles.eva.local/pallas_call:", 2700, 2900),
    kernel(15, P + A1 + EVA + "veles.eva.remote/pallas_call:",
           2900, 3000),
    ("%copy.16 = f32[8] copy()", "data formatting", "", 3000, 3100),
]
BUSY = 2100
TINY = {"heads": 2, "head_dim": 4, "eva_window": 4, "eva_chunk": 2,
        "layers": ["eva_attention"]}


def traced(tmp_path, ops=OPS):
    return context(
        tmp_path, MODULES[:1], ops,
        cell={"config": {"model": TINY},
              "traffic": {"seq_len": 8, "minibatch": 2, "n_valid": 2}},
        dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1, "warm": True}],
        steps_per_epoch=1, costs=costs,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e15})


def test_sub_of():
    assert evascopes.sub_of(P + A1 + EVA + "veles.eva.local/pallas_call:") \
        == ("EvaAttention", "eva.local")
    assert evascopes.sub_of(P + GA1 + EVA + "veles.eva.remote/x:") \
        == ("GDEvaAttention", "eva.remote")
    assert evascopes.sub_of(
        P + GA1 + EVA + "veles.eva.summary/transpose(jvp())/mul:")[1] \
        == "eva.summary"
    assert evascopes.sub_of(
        P + GA1 + "transpose(jvp(veles.eva.summary))/mul:")[1] \
        == "eva.summary"
    assert evascopes.sub_of(P + A1 + EVA + "log1p:")[1] == "eva"
    assert evascopes.sub_of(P + A1 + "veles.core/veles.eva")[1] == "eva"
    # whole words only, after a unit, and not the other scopes
    assert evascopes.sub_of(P + A1 + "veles.core/veles.evade/x:")[1] \
        is None
    assert evascopes.sub_of(P + A1 + EVA + "veles.eva.other/x:")[1] \
        == "eva"
    assert evascopes.sub_of(P + "veles.eva/mul:") == (None, None)
    assert evascopes.sub_of(P + A1 + "dot_general:")[1] is None
    assert evascopes.sub_of("") == (None, None)
    # reduce/scopes.py keeps finding veles.core first
    from benchmark.reduce import scopes
    assert scopes.unit_of(P + A1 + EVA + "veles.eva.local/pallas_call:") \
        == ("fwd", "EvaAttention", "EvaAttention", "core")


def test_readers_by_hand(tmp_path):
    ctx = traced(tmp_path)
    eva_all = 50 + 200 + 20 + 80 + 30 + 20 + 400 + 150 + 50 + 200 + 100
    assert read("eva_attn_share", ctx) == pytest.approx(
        100.0 * eva_all / BUSY)
    # one step forward + backward and one validation forward of the 28
    # pairs a head and sequence, 2 heads, 2 sequences: (12 + 4) x 4 FLOP
    # a pair; over the time of what lies under .local and .remote
    parts = 200 + 20 + 80 + 400 + 150 + 200 + 100
    assert read("eva_attn_roofline", ctx) == pytest.approx(
        100.0 * ((12 + 4) * 4 * 2 * 2 * 28 / 1e9) / (parts * 1e-9))
    # the accepted readers the cell joins
    assert read("solver_update_share", ctx) == pytest.approx(
        100.0 * 100 / BUSY)
    assert read("unscoped_share", ctx) == pytest.approx(100.0 * 100 / BUSY)


def test_bytes_bound_the_core_where_the_chip_is_slow_to_read(tmp_path):
    ctx = traced(tmp_path)
    ctx.peaks = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}
    row = 16 * 2 * 4 * 2
    step = 8 * row + 4 * (row // 2) + 2 * 16 * 2 * 4
    valid = 4 * row + 2 * (row // 2) + 2 * 16 * 2 * 4
    assert read("eva_attn_roofline", ctx) == pytest.approx(
        100.0 * ((step + valid) / 1e9) / 1150e-9)


def test_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    """A build before EVA attention has no such scope: the readers
    return nothing and do not raise."""
    plain = [op[:2] + (op[2].replace("veles.eva", "veles.x"),) + op[3:]
             for op in OPS]
    ctx = traced(tmp_path, ops=plain)
    for name in ("eva_attn_share", "eva_attn_roofline"):
        assert read(name, ctx) is None, name
    untraced = harness.Context(cell=ctx.cell, trace=None, peaks=None,
                               costs=costs, dispatches=[])
    for name in ("eva_attn_share", "eva_attn_roofline"):
        assert read(name, untraced) is None, name


def test_another_configuration_reads_no_roofline(tmp_path):
    """The roofline laid over another configuration's cell (the traced
    runs of the accepted cells): no ``core_cost``, no metric."""
    from benchmark.costs import laguna
    ctx = traced(tmp_path)
    ctx.costs = laguna
    assert read("eva_attn_roofline", ctx) is None


# -- the cell on the CPU --------------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu(trace, seconds=4):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell_evabyte.py"),
         "--workload", CELL, "--trace", str(trace),
         "--seconds", str(seconds)],
        cwd=CHECKOUT, env=ENV, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_cell_runs_end_to_end_on_the_cpu():
    result, out = run_cpu(trace=0)
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # forward and the reference's own epoch of training agree to
    # float32 rounding: the equations are the program's
    diffs = [float(line.split("|diff| ")[1].split()[0])
             for line in out.splitlines() if line.startswith("check ")]
    assert len(diffs) == 3 and max(diffs) < 1e-4, out[-3000:]


def test_traced_cpu_run_reports_what_the_cpu_can():
    """No device trace on the CPU: the span metrics appear, the
    device-trace readers leave theirs out and do not raise."""
    result, _ = run_cpu(trace=1)
    listed = {m["name"] for m in run.resolve(BENCH_DIR, CELL)["per_layer"]}
    assert {"eva_attn_share", "eva_attn_roofline", "step_ms", "train_mfu",
            "vocab_head_share", "peak_hbm_gb"} <= listed
    assert {"step_ms", "dispatch_gap_share"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed


def test_the_parent_refuses_the_cells_command_at_once():
    """A build before EVA attention does not know the operator:
    ``pre_norm_body`` raises before anything is built. Here the same
    refusal is shown for an operator this build does not know either."""
    from benchmark.drivers import train
    from veles.config import root
    cell = run.resolve(BENCH_DIR, CELL)
    argv = train.build_argv(cell, 1, "cpu")
    assert "root.lm.model.layers=['eva_attention', 'eva_attention', " \
        "'eva_attention', 'eva_attention']" in argv
    from veles.znicz_tpu.models import transformer_lm as T
    saved = root.lm.model.to_dict()
    try:
        root.lm.model.update({"block": "pre_norm",
                              "layers": ["eva_attention", "lsh_attention"]})
        with pytest.raises(ValueError, match="has the operators"):
            T.build_layers()
    finally:
        root.lm.model.update(saved)
