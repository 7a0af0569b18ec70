"""What PR 34 adds for ``solar_open2_250b``: the configuration file
against the catalog's statement, key by key; ``costs/solar_open2.py``
against counts made by hand (the issue's table, to the parameter);
``reduce/deltascopes.py`` and the four new readers on a hand-made trace
(and the accepted readers the cell joins); and the cell end to end on
the CPU at a tiny preset (``cpu_cell_solar.py``)."""

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
from benchmark.costs import solar_open2 as costs        # noqa: E402
from benchmark.reduce import deltascopes                # noqa: E402
from benchmark.tests.test_scopes import (               # noqa: E402
    MODULES, MOSAIC, P, context, read)

CONFIG = harness.load_json(BENCH_DIR, "configs", "solar_open2_250b.json")
TRAFFIC = harness.load_json(BENCH_DIR, "traffic", "solar_s4k_train.json")
MODEL = CONFIG["model"]
CELL = "solar_open2_250b_s4k_train"


# -- the configuration file ---------------------------------------------------


def test_config_matches_the_catalog_row_key_by_key():
    """``solar_catalog_row.json``: the catalog's entry, copied beside
    this test as PR 34 found it. Every key of its ``config`` is in the
    file under the same name with the same value, but for the one
    ``reduced`` names; ``published`` holds the row's config verbatim."""
    row = harness.load_json(HERE, "solar_catalog_row.json")
    assert row["name"] == "Solar-Open2-250B"
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["published"] == row["config"]
    differing = [key for key, value in row["config"].items()
                 if CONFIG[key] != value]
    assert differing == ["n_routed_experts"]
    assert CONFIG["reduced"] == ["layers", "n_routed_experts", "vocab"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["layers"], CONFIG["n_routed_experts"], CONFIG["vocab"]) \
        == (4, 8, 24576)


def test_what_is_run_has_every_published_width():
    pub = CONFIG["published"]
    linear = pub["linear_attn_config"]
    assert (MODEL["dim"], MODEL["heads"], MODEL["kv_heads"],
            MODEL["head_dim"]) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"]) == (4096, 64, 8, 128)
    assert (MODEL["delta_heads"], MODEL["delta_head_dim"],
            MODEL["delta_conv_kernel"], MODEL["delta_gate_rank"]) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"], 128) == (64, 128, 4, 128)
    assert (MODEL["moe_hidden"], MODEL["moe_experts"], MODEL["moe_top_k"],
            MODEL["moe_shared_hidden"], MODEL["routed_scaling"]) == (
        pub["moe_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"],
        pub["n_shared_experts"] * pub["moe_intermediate_size"],
        pub["routed_scaling_factor"]) == (1280, 320, 8, 1280, 1)
    assert MODEL["norm_eps"] == pub["rms_norm_eps"]
    # the cut: one whole period in the published order (gqa_layers 0,
    # 4, ...: layer 0 is the attention), 8 of 320 experts, 1/8 of the
    # vocabulary
    assert pub["gqa_layers"][:2] == [0, 4] and pub["use_rope"] is False
    assert MODEL["layers"] == ["gated_nope_attention"] \
        + ["delta_attention"] * 3
    assert len(MODEL["layers"]) == CONFIG["layers"]
    assert MODEL["dense_layers"] == pub["first_k_dense_replace"] == 0
    lo, hi = MODEL["experts_held"]
    assert hi - lo == CONFIG["n_routed_experts"]
    assert MODEL["vocab"] == CONFIG["vocab"] == pub["vocab_size"] // 8
    # every reading the config does not state is written down
    assert {"router", "low_rank_gates", "gate_bias",
            "decay_initialisation", "beta", "qk_norm", "gqa_gate",
            "untied_head", "no_positions", "initialisation", "optimizer",
            "corpus"} <= set(CONFIG["assumed"])
    # every override names a key the file holds
    for value in CONFIG["program"]["overrides"].values():
        if isinstance(value, str) and value.startswith("$model."):
            assert value[7:] in MODEL, value
        if isinstance(value, str) and value.startswith("$traffic."):
            assert value[9:] in TRAFFIC, value
    assert (TRAFFIC["seq_len"], TRAFFIC["minibatch"], TRAFFIC["n_train"],
            TRAFFIC["n_valid"], TRAFFIC["max_period"]) \
        == (4096, 1, 8, 1, 2048)


# -- the costs, by hand ---------------------------------------------------------


def test_parameters_by_hand_are_the_issues_table():
    # delta-rule operator: q, k, v, o of 4096 x 8192; two low-rank gates
    # 4096 -> 128 -> 8192; beta 4096 x 64
    matrices = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert costs.operator_params(MODEL, "delta_attention") == matrices \
        == 137_625_600
    # three 4-tap convolutions, A_log, dt_bias, gate bias, two gains
    vectors = 3 * 8192 * 4 + 64 + 8192 + 8192 + 4096 + 128
    assert costs.vector_params(MODEL, "delta_attention") == vectors
    assert round((matrices + vectors) / 1e6, 1) == 137.7
    # gated GQA operator: q and gate 4096 x 8192, k and v 4096 x 1024,
    # o 8192 x 4096
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert costs.operator_params(MODEL, "gated_nope_attention") == gqa
    assert round((gqa + 4096) / 1e6, 1) == 109.1
    # one expert 3 x 4096 x 1280; shared + router + norm 17.0M; 8 held
    assert costs.expert_params(MODEL) == 15_728_640
    assert round((15_728_640 + 4096 * 320 + 4096) / 1e6, 1) == 17.0
    assert round(8 * 15_728_640 / 1e6, 1) == 125.8
    ffn = costs.ffn_params(MODEL)
    assert ffn == 9 * 15_728_640 + 4096 * 320 + 4096
    assert round((matrices + vectors + ffn) / 1e6, 1) == 280.6
    assert round((gqa + 4096 + ffn) / 1e6, 1) == 251.9
    assert round(2 * 4096 * 24576 / 1e6, 1) == 201.3
    total = costs.parameters(MODEL)
    assert total == 3 * (matrices + vectors + ffn) + gqa + 4096 + ffn \
        + 2 * 4096 * 24576 + 4096 == 1_295_110_720
    assert round(total / 1e6, 1) == 1295.1
    assert round(8 * total / 1e9, 2) == 10.36
    # the reference counts the same from ITS shapes
    ref = harness.load_module(BENCH_DIR, "reference", CONFIG["reference"])
    assert ref.count_parameters(MODEL) == total
    # and the uncut model is the published 250B
    whole = dict(MODEL, experts_held=[0, 320], vocab=196608,
                 layers=MODEL["layers"] * 12)
    assert round(costs.parameters(whole) / 1e9, 1) == 250.3


def test_train_flops_by_hand():
    seq = 4096
    delta = 2 * 137_625_600 / 1e6           # 275 MFLOP of projections
    assert delta == pytest.approx(275.3, abs=0.05)
    core = 7 * 64 * 128 * 128 / 1e6         # the recurrence as written
    assert costs.delta_flops_per_token(MODEL) / 1e6 == core \
        == pytest.approx(7.34, abs=0.005)
    gqa = 2 * (3 * 4096 * 8192 + 2 * 4096 * 1024) / 1e6
    attention = 2.0 * (seq + 1) * 8192 / 1e6
    assert (gqa, attention) == (pytest.approx(218.1, abs=0.05),
                                pytest.approx(67.1, abs=0.05))
    head = 2 * 4096 * 24576 / 1e6
    shared = 4 * 2 * 15_728_640 / 1e6
    routed = 4 * 2 * (4096 * 320 + 8 * 8 / 320 * 15_728_640) / 1e6
    forward = 3 * (delta + core) + gqa + attention + head + shared \
        + routed
    assert costs.train_flops_per_token(MODEL, seq) / 1e6 \
        == pytest.approx(3 * forward)
    assert 3 * forward / 1e3 == pytest.approx(4.487, abs=0.001)
    assert costs.train_flops_per_sample(MODEL, TRAFFIC) \
        == pytest.approx(costs.train_flops_per_token(MODEL, seq) * seq)
    # the shares the cell's `why` states
    assert round(100 * 3 * (delta + core) / forward) == 57
    assert round(100 * (gqa + attention) / forward) == 19
    assert round(100 * head / forward) == 13
    assert round(100 * shared / forward) == 8
    assert round(100 * routed / forward) == 2
    # a held expert's load at uniform routing, against the deployment's
    assert seq * 8 * 8 / 320 / 8 == 102.4


def test_kernel_costs_by_hand():
    tiny = {"dim": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
            "delta_heads": 3, "delta_head_dim": 2, "moe_hidden": 6,
            "moe_experts": 10, "experts_held": [2, 5],
            "layers": ["gated_nope_attention", "delta_attention",
                       "delta_attention"]}
    traffic = {"seq_len": 4, "minibatch": 2}
    # ONE attention layer: 2 heads x 10 pairs x 2 matmuls x 2 x 4
    flops, nbytes = costs.attention_kernel_cost(tiny, traffic)
    assert flops == 2 * 3 * 320
    tensor, rows = 2 * 4 * 8 * 2, 2 * 2 * 4 * 4
    assert nbytes == 12 * tensor + 2 * rows
    assert costs.attention_kernel_cost(tiny, traffic, backward=False) \
        == (2 * 320, 4 * tensor + rows)
    # the routed pairs only: 3 x 8 x 6 parameters an expert, 3 held
    flops, nbytes = costs.expert_matmul_cost(tiny, 5)
    assert flops == 6 * 144 * 5
    assert nbytes == 3 * 144 * 8 + 3 * 5 * (2 * 8 + 3 * 6) * 2
    # the recurrence of ONE layer: 7 x 3 heads x 2 x 2 a token forward
    assert costs.delta_core_cost(tiny, 10, backward=False) \
        == (7 * 3 * 4 * 10, 10 * (3 * 9 + 3 * 2) * 2)
    assert costs.delta_core_cost(tiny, 10) \
        == (3 * 7 * 3 * 4 * 10, 10 * (3 * 3 * 9 + 2 * 3 * 2) * 2)
    # at the timed sizes the bytes bound the recurrence, not the
    # operations: 7 FLOP a state element, and the state stays on chip
    flops, nbytes = costs.delta_core_cost(MODEL, 4096)
    assert nbytes / 819e9 > flops / 197e12


# -- the new scopes on a hand-made trace ----------------------------------------

D1 = "veles.fwd.DeltaAttention.DeltaAttention/"
GD1 = "veles.bwd.GDDeltaAttention.GDDeltaAttention/"
E1 = "veles.fwd.ExpertFFN.ExpertFFN/"
GE1 = "veles.bwd.GDExpertFFN.GDExpertFFN/"
A1 = "veles.fwd.GQAttention.GQAttention/"
GA1 = "veles.bwd.GDGQAttention.GDGQAttention/"
BACK = "transpose(veles.fwd.DeltaAttention.DeltaAttention)/"


def fusion(n, path, start, end):
    return ("%%fusion.%d = f32[8] fusion()" % n, "loop fusion", path,
            start, end)


#: one step + one validation forward of: attention, a delta-rule layer,
#: an expert layer with a shared expert
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),
    ("%closed_call.1 = " + MOSAIC, "custom-call",
     P + A1 + "veles.core/closed_call/pallas_call:", 1000, 1200),
    fusion(2, P + D1 + "dot_general:", 1200, 1400),
    fusion(3, P + D1 + "veles.delta/while/body/checkpoint/exp:",
           1400, 1700),
    fusion(4, P + D1 + "veles.delta/triangular_solve:", 1700, 1800),
    fusion(5, P + E1 + "veles.route/top_k:", 1800, 1900),
    fusion(6, P + E1 + "veles.shared/dot_general:", 1900, 2100),
    ("%ragged-dot.7 = " + MOSAIC, "custom-call", "", 2100, 2200),
    fusion(8, P + GE1 + "transpose(veles.fwd.ExpertFFN.ExpertFFN)/"
           "jvp(veles.shared)/dot_general:", 2200, 2350),
    fusion(9, P + GE1 + "transpose(jvp(veles.route))/mul:", 2350, 2400),
    # the backward: the recurrence again (bare), then its transpose
    fusion(10, P + GD1 + "veles.delta/while/body/checkpoint/exp:",
           2400, 2700),
    fusion(11, P + GD1 + BACK + "jvp(veles.delta)/while/body/mul:",
           2700, 3200),
    fusion(12, P + GD1 + BACK + "jvp()/dot_general:", 3200, 3400),
    fusion(13, P + GD1 + "veles.update/add:", 3400, 3500),
    ("%closed_call.14 = " + MOSAIC, "custom-call",
     P + GA1 + "veles.core/closed_call/pallas_call:", 3500, 3800),
    # the validation forward
    fusion(15, P + D1 + "veles.delta/while/body/dot_general:",
           3800, 4000),
]
BUSY = 3000


def traced(tmp_path, ops=OPS, tokens=(8, 1)):
    from veles import telemetry
    model = {"heads": 2, "head_dim": 4, "delta_heads": 3,
             "delta_head_dim": 2,
             "layers": ["gated_nope_attention", "delta_attention"]}
    total, steps = tokens
    telemetry.counter("veles_delta_tokens_total", "t", ("layer",)) \
        .labels("DeltaAttention").inc(total)
    telemetry.counter("veles_delta_steps_total", "t", ("layer",)) \
        .labels("DeltaAttention").inc(steps)
    return context(
        tmp_path, MODULES[:1], ops,
        cell={"config": {"model": model},
              "traffic": {"seq_len": 4, "minibatch": 2, "n_valid": 2}},
        dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1, "warm": True}],
        steps_per_epoch=1, costs=costs,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e15})


@pytest.fixture
def fresh_registry():
    """A registry of this test's own: the readers find the counters
    ``traced`` sets and no other run's."""
    from veles import telemetry
    with telemetry.scoped():
        yield


def test_sub_of():
    assert deltascopes.sub_of(P + D1 + "veles.delta/while/body/exp:") \
        == ("DeltaAttention", "delta")
    assert deltascopes.sub_of(
        P + GD1 + BACK + "jvp(veles.delta)/mul:") \
        == ("GDDeltaAttention", "delta")
    assert deltascopes.sub_of(
        P + GD1 + "transpose(jvp(veles.delta))/mul:")[1] == "delta"
    assert deltascopes.sub_of(P + E1 + "veles.shared/dot_general:") \
        == ("ExpertFFN", "shared")
    # whole words only, after a unit, and not the other module's scopes
    assert deltascopes.sub_of(P + D1 + "veles.delta_rule/mul:")[1] is None
    assert deltascopes.sub_of(P + "veles.delta/mul:") == (None, None)
    assert deltascopes.sub_of(P + E1 + "veles.route/top_k:")[1] is None
    assert deltascopes.sub_of("") == (None, None)


def test_readers_by_hand(tmp_path, fresh_registry):
    ctx = traced(tmp_path)
    delta_all = 200 + 300 + 100 + 300 + 500 + 200 + 100 + 200
    core = 300 + 100 + 300 + 500 + 200
    assert read("kda_share", ctx) == pytest.approx(100.0 * delta_all / BUSY)
    assert read("kda_core_share", ctx) == pytest.approx(100.0 * core / BUSY)
    assert read("shared_expert_share", ctx) == pytest.approx(
        100.0 * (200 + 150) / BUSY)
    # one step forward + backward of 8 tokens, one validation minibatch
    # forward of 8: 3 heads x 7 x 2 x 2 FLOP a token
    flops = (3 + 1) * 7 * 3 * 4 * 8
    assert read("kda_core_roofline", ctx) == pytest.approx(
        100.0 * (flops / 1e9) / (core * 1e-9))
    # the accepted readers the cell joins see their scopes as before:
    # the shared expert is no routed product and no routing
    assert read("expert_route_share", ctx) == pytest.approx(
        100.0 * (100 + 50) / BUSY)
    assert read("moe_share", ctx) == pytest.approx(
        100.0 * (100 + 200 + 100 + 150 + 50) / BUSY)
    assert read("flash_attn_share", ctx) == pytest.approx(
        100.0 * (200 + 300) / BUSY)
    assert read("gqa_attn_roofline", ctx) == pytest.approx(
        100.0 * ((3 + 1) * 2 * 320 / 1e9) / 500e-9)
    assert read("solver_update_share", ctx) == pytest.approx(
        100.0 * 100 / BUSY)
    assert read("unscoped_share", ctx) == pytest.approx(100.0 * 100 / BUSY)


def test_readers_find_nothing_in_a_program_without_the_scopes(
        tmp_path, fresh_registry):
    """A build before PR 34 has neither scope nor counter: the readers
    return nothing and do not raise."""
    plain = [op[:2] + (op[2].replace("veles.delta/", "")
                       .replace("jvp(veles.delta)", "jvp()")
                       .replace("veles.shared/", "")
                       .replace("jvp(veles.shared)", "jvp()")
                       .replace("DeltaAttention", "ShortConv"),) + op[3:]
             for op in OPS]
    ctx = traced(tmp_path, ops=plain, tokens=(0, 0))
    for name in ("kda_share", "kda_core_share", "kda_core_roofline",
                 "shared_expert_share"):
        assert read(name, ctx) is None, name
    untraced = harness.Context(cell=traced(tmp_path / "u").cell,
                               trace=None, peaks=None, costs=costs,
                               dispatches=[])
    for name in ("kda_share", "kda_core_share", "kda_core_roofline",
                 "shared_expert_share"):
        assert read(name, untraced) is None, name


# -- the cell on the CPU --------------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu(trace, seconds=6):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell_solar.py"),
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
    """No device trace on the CPU: the span and counter metrics appear,
    the device-trace readers leave theirs out and do not raise."""
    result, _ = run_cpu(trace=1)
    listed = {m["name"] for m in run.resolve(BENCH_DIR, CELL)["per_layer"]}
    assert {"kda_share", "kda_core_share", "kda_core_roofline",
            "shared_expert_share", "gqa_attn_roofline",
            "expert_matmul_roofline"} <= listed
    assert {"step_ms", "dispatch_gap_share",
            "expert_load_max_over_mean"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed
