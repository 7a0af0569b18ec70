"""What PR 28 adds for ``lfm2_24b_a2b``: ``costs/lfm2_moe.py`` against
counts made by hand, the new readers and ``reduce/subscopes.py`` on a
hand-made trace, and the cell end to end on the CPU at a tiny preset
(``cpu_cell_lfm2.py``)."""

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
from benchmark.costs import lfm2_moe as costs           # noqa: E402
from benchmark.reduce import subscopes                  # noqa: E402
from benchmark.tests.test_scopes import (               # noqa: E402
    MODULES, MOSAIC, P, context, read)

CONFIG = harness.load_json(BENCH_DIR, "configs", "lfm2_24b_a2b.json")
MODEL = CONFIG["model"]
CELL = "lfm2_24b_a2b_s8k_train"


# -- the configuration file ---------------------------------------------------


def test_config_states_the_published_widths_and_the_cut():
    catalog = {"conv_L_cache": 3, "hidden_size": 2048,
               "intermediate_size": 11776, "moe_intermediate_size": 1536,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "num_dense_layers": 2, "num_experts_per_tok": 4,
               "num_hidden_layers": 40, "vocab_size": 65536,
               "norm_eps": 1e-05, "routed_scaling_factor": 1}
    for key, value in catalog.items():
        assert CONFIG[key] == value, key
    assert len(CONFIG["layer_types"]) == 40
    assert CONFIG["rope_parameters"]["rope_theta"] == 1000000
    assert CONFIG["reduced"] == ["layers", "num_experts", "vocab"]
    assert (CONFIG["layers"], CONFIG["num_experts"], CONFIG["vocab"]) \
        == (5, 16, 16384)
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    # no width is cut: the model the program runs has the published ones
    assert (MODEL["dim"], MODEL["heads"], MODEL["kv_heads"],
            MODEL["head_dim"], MODEL["ffn_hidden"], MODEL["moe_hidden"],
            MODEL["moe_experts"], MODEL["moe_top_k"],
            MODEL["conv_kernel"]) == (2048, 32, 8, 64, 11776, 1536, 64,
                                      4, 3)
    # the five layers are published layer 0 and the period 2..5
    assert MODEL["layers"] == [CONFIG["layer_types"][0]] \
        + CONFIG["layer_types"][2:6]
    assert len(MODEL["layers"]) == CONFIG["layers"] == 5
    assert MODEL["experts_held"] == [0, CONFIG["num_experts"]]
    # every override names a key the files hold
    cell = run.resolve(BENCH_DIR, CELL)
    from benchmark.drivers import train
    for path, value in CONFIG["program"]["overrides"].items():
        if isinstance(value, str) and value.startswith("$") \
                and value != "$traffic.data_parallel":
            assert train.resolve_value(value, cell) is not None, path


# -- costs by hand ------------------------------------------------------------


def test_parameters_a_token_meets_by_hand():
    conv = 2048 * 6144 + 2048 * 2048            # W_in, W_out
    attn = 2048 * (32 + 2 * 8) * 64 + 32 * 64 * 2048
    assert (conv, attn) == (16_777_216, 10_485_760)
    assert costs.operator_params(MODEL, "conv") == conv
    assert costs.operator_params(MODEL, "full_attention") == attn
    dense = 3 * 2048 * 11776
    expert = 3 * 2048 * 1536
    assert (dense, expert) == (72_351_744, 9_437_184)
    assert costs.expert_params(MODEL) == expert
    assert costs.held_experts(MODEL) == 16
    router, head = 2048 * 64, 2048 * 16384
    # 4 experts a token, 16 of 64 here: one expert's parameters a token
    total = head + (conv + dense) + (attn + router + expert) \
        + 3 * (conv + router + expert)
    assert total == 221_773_824
    assert costs.matmul_params(MODEL) == total
    # all 64 held: four experts' parameters a token
    whole = dict(MODEL, experts_held=None)
    assert costs.matmul_params(whole) == total + 4 * 3 * expert


def test_train_flops_by_hand():
    # the one attention layer: 32 query heads of 64, 8192 * 8193 / 2
    # causal pairs, 2 matmuls of 2 FLOP forward, three passes in all
    assert costs.attention_layers(MODEL) == 1
    forward = 32 * (8192 * 8193 // 2) * 2 * 64 * 2
    assert costs.attention_flops_per_sequence(MODEL, 8192, passes=1) \
        == forward == 274_911_461_376
    assert costs.train_flops_per_token(MODEL, 8192) \
        == 6 * 221_773_824 + 3 * forward / 8192 == 1_431_318_528
    traffic = {"seq_len": 8192, "minibatch": 2}
    # ISSUE 28's 2.35e13 FLOP a step of two sequences
    assert 2 * costs.train_flops_per_sample(MODEL, traffic) \
        == 1_431_318_528 * 16384 == 23_450_722_762_752
    # a step's minibatch: q, k, v, out forward; q, k, v, out, dout, dq,
    # dk, dv backward; the row statistics written and read
    flops, nbytes = costs.attention_kernel_cost(MODEL, traffic)
    assert flops == 2 * 3 * forward
    assert nbytes == 12 * 2 * 8192 * 2048 * 2 + 2 * 2 * 32 * 8192 * 4
    # a validation minibatch: the forward alone
    flops, nbytes = costs.attention_kernel_cost(MODEL, traffic,
                                                backward=False)
    assert flops == 2 * forward
    assert nbytes == 4 * 2 * 8192 * 2048 * 2 + 2 * 32 * 8192 * 4


def test_expert_matmul_cost_by_hand():
    pairs = 16384               # a step at uniform routing: 1024 each
    flops, nbytes = costs.expert_matmul_cost(MODEL, pairs)
    assert flops == 6 * 9_437_184 * pairs == 927_712_935_936
    weights = 16 * 9_437_184
    rows = pairs * (2048 + 3072 + 1536 + 2048) * 2
    assert nbytes == weights * (2 + 2 + 4) + 3 * rows == 2_063_597_568
    forward, fbytes = costs.expert_matmul_cost(MODEL, pairs,
                                               backward=False)
    assert (forward, fbytes) == (flops // 3, weights * 2 + rows)
    assert flops / nbytes > 240     # over the ridge: compute-bound


# -- the readers on a hand-made trace -------------------------------------------

E = "veles.fwd.ExpertFFN.ExpertFFN_2/"
GE = "veles.bwd.GDExpertFFN.GDExpertFFN_2/"
#: (instruction, hlo_category, tf_op, start ns, end ns)
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4300),
    ("%fusion.1 = f32[8] fusion()", "loop fusion",                      # 300
     P + E + "veles.route/sort:", 1000, 1300),
    # the compiler's own grouped kernel: no path, known by its name
    ("%ragged-dot-none.2 = " + MOSAIC, "custom-call", "", 1300, 1900),  # 600
    ("%fusion.3 = f32[8] fusion()", "convolution fusion",               # 400
     P + "veles.fwd.ShortConv.ShortConv/dot_general:", 1900, 2300),
    # the backward of a vjp'd unit: jax wraps the forward's scope
    ("%ragged-dot.4 = " + MOSAIC, "custom-call",                        # 1200
     P + GE + "transpose(veles.fwd.ExpertFFN.ExpertFFN_2)/"
     "jvp(veles.experts)/ragged_dot_general:", 2300, 3500),
    ("%fusion.5 = f32[8] fusion()", "loop fusion",                      # 200
     P + GE + "transpose(jvp(veles.route))/scatter-add:", 3500, 3700),
    ("%fusion.6 = f32[8] fusion()", "loop fusion",                      # 100
     P + GE + "veles.update/add:", 3700, 3800),
    ("%fusion.7 = f32[8] fusion()", "loop fusion",                      # 300
     P + "veles.bwd.GDShortConv.GDShortConv/transpose(jvp())/mul:",
     3800, 4100),
    ("%copy.8 = f32[8] copy()", "data formatting", "", 4100, 4300),     # 200
    # the second run: a validation forward
    ("%ragged-dot.9 = " + MOSAIC, "custom-call",                        # 600
     P + E + "veles.experts/ragged_dot_general:", 6000, 6600),
]
BUSY = 3300 + 600
TINY = {"dim": 8, "moe_hidden": 4, "moe_experts": 8, "moe_top_k": 2,
        "experts_held": [0, 2]}


def traced(tmp_path, peak=1e12):
    # 2 runs of 1 epoch of 2 steps = 4 steps; one validation minibatch
    # an epoch
    return context(
        tmp_path, MODULES, OPS,
        cell={"config": {"model": TINY},
              "traffic": {"seq_len": 4, "minibatch": 2, "n_valid": 2}},
        dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1, "warm": True}],
        steps_per_epoch=2, costs=costs,
        peaks={"bf16_flops_per_s": peak, "hbm_bytes_per_s": peak})


def test_sub_of():
    assert subscopes.sub_of(P + E + "veles.experts/ragged_dot_general:") \
        == ("fwd", "ExpertFFN", "experts")
    assert subscopes.sub_of(
        P + GE + "transpose(veles.fwd.ExpertFFN.ExpertFFN_2)/"
        "jvp(veles.experts)/dot_general:") == ("bwd", "GDExpertFFN",
                                               "experts")
    assert subscopes.sub_of(
        P + GE + "transpose(jvp(veles.route))/jit(silu)/mul:") \
        == ("bwd", "GDExpertFFN", "route")
    # only as a whole word, only after a unit
    assert subscopes.sub_of(P + E + "veles.routes/mul:")[2] is None
    assert subscopes.sub_of(P + GE + "veles.update/add:")[2] is None
    assert subscopes.sub_of(P + "veles.route/mul:") == (None,) * 3
    assert subscopes.sub_of("") == (None,) * 3
    # the compiler's grouped kernels have a name and no path
    assert subscopes.sub_of("", "%ragged-dot-none.7 = " + MOSAIC) \
        == (None, None, "experts")
    assert subscopes.sub_of("", "%ragged-dot-metadata = " + MOSAIC)[2] \
        == "experts"
    assert subscopes.sub_of("", "%closed_call.2 = " + MOSAIC)[2] is None


def test_shares_of_the_new_units(tmp_path, capsys):
    ctx = traced(tmp_path)
    assert read("moe_share", ctx) == pytest.approx(
        100.0 * (300 + 600 + 1200 + 200 + 100 + 600) / BUSY)
    assert read("short_conv_share", ctx) == pytest.approx(
        100.0 * (400 + 300) / BUSY)
    assert read("expert_route_share", ctx) == pytest.approx(
        100.0 * (300 + 200) / BUSY)
    assert subscopes.seconds(ctx, "experts") == pytest.approx(2400e-9)
    # the accepted readers see the new units as they see the old: the
    # backward's wrapped scopes count for the gradient unit
    assert read("solver_update_share", ctx) == pytest.approx(
        100.0 * 100 / BUSY)
    # ... and the pathless kernel as unscoped, which it is
    assert read("unscoped_share", ctx) == pytest.approx(
        100.0 * (200 + 600) / BUSY)
    assert read("flash_attn_share", ctx) == 0.0


def test_expert_matmul_roofline_by_hand(tmp_path):
    from veles import telemetry
    ctx = traced(tmp_path)
    with telemetry.scoped():
        assert read("expert_matmul_roofline", ctx) is None  # no counters
        assert read("expert_load_max_over_mean", ctx) is None
        labels = ("layer",)
        telemetry.counter("veles_moe_pairs_total", "", labels) \
            .labels("ExpertFFN_2").inc(400)
        telemetry.counter("veles_moe_steps_total", "", labels) \
            .labels("ExpertFFN_2").inc(4)
        telemetry.gauge("veles_moe_load_max_over_mean", "", labels) \
            .labels("ExpertFFN_2").set(1.5)
        telemetry.gauge("veles_moe_load_max_over_mean", "", labels) \
            .labels("ExpertFFN_4").set(2.25)
        # 100 pairs a step; an expert is 3 * 8 * 4 = 96 parameters, two
        # held. A step: 6 * 96 * 100 FLOP against 2 * 96 * 8 + 3 * (100
        # * (16 + 12) * 2) bytes: the FLOPs bound it at equal peaks; a
        # validation forward a third of the FLOPs
        step, valid = 57_600 / 1e12, 19_200 / 1e12
        assert read("expert_matmul_roofline", ctx) == pytest.approx(
            100.0 * (4 * step + 2 * valid) / 2400e-9)
        assert read("expert_load_max_over_mean", ctx) == 2.25
        # and where the bytes bound it
        slow = traced(tmp_path / "b", peak=1e12)
        slow.peaks["hbm_bytes_per_s"] = 1e9
        step, valid = 18_336 / 1e9, 5_984 / 1e9
        assert read("expert_matmul_roofline", slow) == pytest.approx(
            100.0 * (4 * step + 2 * valid) / 2400e-9)


def test_gqa_attn_roofline_by_hand(tmp_path):
    """Only the Mosaic kernels under ``veles.core`` are timed: the
    grouped products' kernels and the projections are not attention."""
    A = "veles.fwd.GQAttention.GQAttention/"
    GA = "veles.bwd.GDGQAttention.GDGQAttention/"
    ops = [
        ("%while.1 = () while()", "while", "", 1000, 4300),
        ("%fusion.1 = f32[8] fusion()", "convolution fusion",
         P + A + "dot_general:", 1000, 1200),
        ("%closed_call.2 = " + MOSAIC, "custom-call",               # 500
         P + A + "veles.core/closed_call/pallas_call:", 1200, 1700),
        ("%ragged-dot-none.3 = " + MOSAIC, "custom-call", "", 1700, 2300),
        ("%closed_call.4 = " + MOSAIC, "custom-call",               # 1000
         P + GA + "veles.core/closed_call/pallas_call:", 2300, 3300),
        ("%fusion.5 = f32[8] fusion()", "loop fusion",
         P + GA + "veles.core/convert:", 3300, 3400),
        # the second run: a validation forward
        ("%closed_call.6 = " + MOSAIC, "custom-call",               # 500
         P + A + "veles.core/closed_call/pallas_call:", 6000, 6500),
    ]
    model = {"dim": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
             "layers": ["conv", "full_attention"]}

    def ctx_at(where, flops, nbytes):
        return context(
            where, MODULES, ops,
            cell={"config": {"model": model},
                  "traffic": {"seq_len": 4, "minibatch": 2, "n_valid": 2}},
            dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1,
                         "warm": True}],
            steps_per_epoch=2, costs=costs,
            peaks={"bf16_flops_per_s": flops, "hbm_bytes_per_s": nbytes})

    # 2 runs of 2 steps and 1 validation minibatch each. A forward of
    # one sequence: 2 heads * 4 * 5 / 2 pairs * 2 matmuls * 2 * 4 = 320
    # FLOP; a minibatch of 2: 640 forward, 1920 a step. A tensor: 2 * 4
    # * 8 * 2 = 128 bytes, the statistics 2 * 2 * 4 * 4 = 64
    fast = ctx_at(tmp_path / "f", 1e9, 1e15)
    assert read("gqa_attn_roofline", fast) == pytest.approx(
        100.0 * (4 * 1920 + 2 * 640) / 1e9 / 2000e-9)
    slow = ctx_at(tmp_path / "b", 1e15, 1e9)
    step, valid = 12 * 128 + 2 * 64, 4 * 128 + 64
    assert read("gqa_attn_roofline", slow) == pytest.approx(
        100.0 * (4 * step + 2 * valid) / 1e9 / 2000e-9)
    # a cost module without the function (another configuration's)
    # gives nothing to read
    fast.costs = object()
    assert read("gqa_attn_roofline", fast) is None


def test_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    plain = [op for op in OPS
             if "Expert" not in op[2] and "ragged-dot" not in op[0]]
    ctx = context(tmp_path, MODULES, plain, cell={},
                  dispatches=[], steps_per_epoch=2, costs=costs,
                  peaks=None)
    assert read("expert_route_share", ctx) == 0.0
    assert read("expert_matmul_roofline", ctx) is None
    assert read("gqa_attn_roofline", ctx) is None
    assert read("moe_share", ctx) == 0.0
    unscoped = [op[:2] + ("",) + op[3:] for op in plain]
    bare = context(tmp_path / "bare", MODULES, unscoped, cell={},
                   dispatches=[], steps_per_epoch=2, costs=costs,
                   peaks=None)
    assert read("moe_share", bare) is None
    assert read("expert_route_share", bare) is None


# -- the cell on the CPU --------------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu(trace, seconds=2):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell_lfm2.py"),
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


def test_traced_cpu_run_reports_the_counter_metric():
    result, _ = run_cpu(trace=1, seconds=3)
    listed = {m["name"] for m in run.resolve(BENCH_DIR, CELL)["per_layer"]}
    assert {"step_ms", "dispatch_gap_share",
            "expert_load_max_over_mean"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
