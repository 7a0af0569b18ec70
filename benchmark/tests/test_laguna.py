"""What PR 38 adds for ``laguna_s_2_1``: the configuration file against
the catalog's statement, key by key; ``costs/laguna.py`` against counts
made by hand (the issue's table, to the parameter; the band's 4,063,488
pairs a head); ``reduce/windowscopes.py`` and the two new readers on a
hand-made trace with ``veles.window`` inside ``veles.core`` (and the
accepted readers the cell joins); and the cell end to end on the CPU at
a tiny preset (``cpu_cell_laguna.py``)."""

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
from benchmark.costs import laguna as costs             # noqa: E402
from benchmark.reduce import windowscopes               # noqa: E402
from benchmark.tests.test_scopes import (               # noqa: E402
    MODULES, MOSAIC, P, context, read)

CONFIG = harness.load_json(BENCH_DIR, "configs", "laguna_s_2_1.json")
TRAFFIC = harness.load_json(BENCH_DIR, "traffic", "laguna_s8k_train.json")
MODEL = CONFIG["model"]
CELL = "laguna_s_2_1_s8k_train"
FULL, SLIDING = (MODEL["operators"][k]
                 for k in ("full_attention", "sliding_attention"))


# -- the configuration file ---------------------------------------------------


def test_config_matches_the_catalog_row_key_by_key():
    """``laguna_catalog_row.json``: the catalog's entry, copied beside
    this test as PR 38 found it. Every key of its ``config`` is in the
    file under the same name with the same value, but for the one
    ``reduced`` names; ``published`` holds the row's config verbatim."""
    row = harness.load_json(HERE, "laguna_catalog_row.json")
    assert row["name"] == "Laguna-S-2.1"
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["published"] == row["config"]
    differing = [key for key, value in row["config"].items()
                 if CONFIG[key] != value]
    assert differing == ["num_experts"]
    assert CONFIG["reduced"] == ["layers", "num_experts", "vocab"]
    assert set(CONFIG["reduced_from"]) == set(CONFIG["reduced"])
    assert (CONFIG["layers"], CONFIG["num_experts"], CONFIG["vocab"]) \
        == (5, 8, 12544)


def test_what_is_run_has_every_published_width():
    pub = CONFIG["published"]
    assert (MODEL["dim"], MODEL["kv_heads"], MODEL["head_dim"],
            MODEL["ffn_hidden"]) == (
        pub["hidden_size"], pub["num_key_value_heads"], pub["head_dim"],
        pub["intermediate_size"]) == (3072, 8, 128, 12288)
    # the two operators, each with the published shapes of ITS layer
    # type: published layers 0-4
    types = pub["layer_types"][:5]
    assert MODEL["layers"] == types == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert [MODEL["operators"][k]["heads"] for k in types] \
        == pub["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert pub["gating"] == "per-head" \
        and set(pub["gating_types"]) == {"per_head"}
    assert FULL["gate"] == SLIDING["gate"] == "head"
    rope = pub["rope_parameters"]
    assert SLIDING["window"] == pub["sliding_window"] == 512
    assert "window" not in FULL
    assert (SLIDING["rope_theta"], rope["sliding_attention"]["rope_type"],
            rope["sliding_attention"]["partial_rotary_factor"]) \
        == (rope["sliding_attention"]["rope_theta"], "default", 1)
    assert "rotary_dim" not in SLIDING and "rope_scaling" not in SLIDING
    yarn = rope["full_attention"]
    assert FULL["rope_theta"] == yarn["rope_theta"] == 500000
    assert FULL["rotary_dim"] == yarn["partial_rotary_factor"] \
        * pub["head_dim"] == 64
    assert FULL["rope_scaling"] == {
        k: yarn[k] for k in ("rope_type", "factor",
                             "original_max_position_embeddings",
                             "beta_slow", "beta_fast", "attention_factor")}
    assert FULL["qk_norm"] is SLIDING["qk_norm"] is False
    # the feed-forwards
    assert MODEL["dense_layers"] == len(pub["mlp_only_layers"]) == 1
    assert pub["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert (MODEL["moe_hidden"], MODEL["moe_shared_hidden"],
            MODEL["moe_experts"], MODEL["moe_top_k"],
            MODEL["routed_scaling"]) == (
        pub["moe_intermediate_size"],
        pub["shared_expert_intermediate_size"], pub["num_experts"],
        pub["num_experts_per_tok"], pub["moe_routed_scaling_factor"]) \
        == (1024, 1024, 256, 10, 2.5)
    assert MODEL["norm_eps"] == pub["rms_norm_eps"] == 1e-6
    assert pub["moe_router_logit_softcapping"] == 0
    assert pub["moe_apply_router_weight_on_input"] is False
    # the cut: layers 0-4, 8 of 256 experts, 1/8 of the vocabulary
    assert len(MODEL["layers"]) == CONFIG["layers"]
    lo, hi = MODEL["experts_held"]
    assert hi - lo == CONFIG["num_experts"]
    assert MODEL["vocab"] == CONFIG["vocab"] == pub["vocab_size"] // 8
    # every reading the config does not state is written down
    assert {"router", "gate", "qk_norm", "shared_expert",
            "attention_factor", "yarn", "untied_head", "initialisation",
            "optimizer", "corpus"} <= set(CONFIG["assumed"])
    # every override names a key the file holds
    for value in CONFIG["program"]["overrides"].values():
        if isinstance(value, str) and value.startswith("$model."):
            assert value[7:] in MODEL, value
        if isinstance(value, str) and value.startswith("$traffic."):
            assert value[9:] in TRAFFIC, value
    assert (TRAFFIC["seq_len"], TRAFFIC["minibatch"], TRAFFIC["n_train"],
            TRAFFIC["n_valid"], TRAFFIC["max_period"]) \
        == (8192, 1, 4, 1, 4096)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH_DIR, "reference", "laguna.py")) as f:
        text = f.read()
    assert "import veles" not in text and "from veles" not in text


# -- the costs, by hand ---------------------------------------------------------


def test_parameters_by_hand_are_the_issues_table():
    # full operator: W_q 3072 x 6144, W_k and W_v 3072 x 1024, the gate
    # 3072 x 48, W_o 6144 x 3072, and its gain
    full = 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48
    assert costs.operator_params(MODEL, "full_attention") == full
    assert full + 3072 == 44_190_720
    # sliding operator: W_q 3072 x 9216, ..., the gate 3072 x 72
    sliding = 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    assert costs.operator_params(MODEL, "sliding_attention") == sliding
    assert sliding + 3072 == 63_138_816
    # the dense SwiGLU of layer 0; one expert; the shared expert, the
    # router and the norm; 8 held
    assert costs.ffn_params(MODEL, 0) - 3072 == 3 * 3072 * 12288 \
        == 113_246_208
    assert costs.expert_params(MODEL) == 9_437_184
    assert round((9_437_184 + 3072 * 256 + 3072) / 1e6, 2) == 10.23
    assert round(8 * 9_437_184 / 1e6, 2) == 75.50
    ffn = costs.ffn_params(MODEL, 1)
    assert ffn == 9 * 9_437_184 + 3072 * 256 + 3072
    layer0 = full + 3072 + 3 * 3072 * 12288 + 3072
    assert round(layer0 / 1e6, 2) == 157.44
    assert round((sliding + 3072 + ffn) / 1e6, 2) == 148.86
    # (the issue's 129.92 adds the rounded 44.19 + 10.23 + 75.50)
    assert round((full + 3072 + ffn) / 1e6, 2) == 129.91
    assert round(2 * 3072 * 12544 / 1e6, 2) == 77.07
    total = costs.parameters(MODEL)
    assert total == layer0 + 3 * (sliding + 3072 + ffn) \
        + full + 3072 + ffn + 2 * 3072 * 12544 + 3072 == 811_017_216
    assert round(total / 1e6, 1) == 811.0
    assert round(8 * total / 1e9, 2) == 6.49
    # the reference counts the same from ITS shapes
    ref = harness.load_module(BENCH_DIR, "reference", CONFIG["reference"])
    assert ref.count_parameters(MODEL) == total
    # and the uncut model is the catalog's 118B
    whole = dict(MODEL, experts_held=[0, 256], vocab=100352,
                 layers=CONFIG["published"]["layer_types"])
    assert round(costs.parameters(whole) / 1e9, 1) == 117.6


def test_the_band_by_hand():
    assert costs.visible_pairs(8192, 512) \
        == 512 * 513 // 2 + (8192 - 512) * 512 == 4_063_488
    assert costs.visible_pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    assert costs.visible_pairs(8192, 9000) == 33_558_528
    assert round(33_558_528 / 4_063_488, 1) == 8.3
    # the program counts the same band (and the tiles it visits for it)
    from veles.znicz_tpu.parallel import pallas_attention as PA
    assert PA.band_pairs(8192, 512) == 4_063_488
    assert PA.visited_pairs(8192, 512, 512, 512) == 2 * 4_063_488 - 512


def test_train_flops_by_hand():
    seq = 8192
    sliding = 2 * (2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72) / 1e6
    full = 2 * (2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48) / 1e6
    assert (sliding, full) == (pytest.approx(126.3, abs=0.05),
                               pytest.approx(88.4, abs=0.05))
    band = 4.0 * 128 * 72 * 4_063_488 / seq / 1e6
    triangle72 = 4.0 * 128 * 72 * 33_558_528 / seq / 1e6
    triangle = 4.0 * 128 * 48 * 33_558_528 / seq / 1e6
    assert (band, triangle72, triangle) == (
        pytest.approx(18.3, abs=0.05), pytest.approx(151.0, abs=0.05),
        pytest.approx(100.7, abs=0.05))
    dense = 2 * 3 * 3072 * 12288 / 1e6
    shared = 4 * 2 * 9_437_184 / 1e6
    routed = 4 * 2 * (3072 * 256 + 10 * 8 / 256 * 9_437_184) / 1e6
    head = 2 * 3072 * 12544 / 1e6
    forward = 3 * (sliding + band) + 2 * (full + triangle) + dense \
        + shared + routed + head
    assert forward == pytest.approx(1220.7, abs=0.05)
    assert costs.train_flops_per_token(MODEL, seq) / 1e6 \
        == pytest.approx(3 * forward)
    assert 3 * forward / 1e3 == pytest.approx(3.662, abs=0.001)
    assert costs.train_flops_per_sample(MODEL, TRAFFIC) \
        == pytest.approx(costs.train_flops_per_token(MODEL, seq) * seq)
    # the shares the cell's `why` states
    assert round(100 * 3 * (sliding + band) / forward) == 36
    assert round(100 * 3 * band / forward, 1) == 4.5
    assert round(100 * 2 * (full + triangle) / forward) == 31
    assert round(100 * dense / forward, 1) == 18.6
    assert round(100 * shared / forward) == 6
    assert round(100 * head / forward) == 6
    assert round(100 * routed / forward, 1) == 2.4
    # as a masked triangle the three layers would be a third more work
    masked = forward + 3 * (triangle72 - band)
    assert round(masked / forward, 2) == 1.33
    # a held expert's load at uniform routing, against the deployment's
    assert seq * 10 * 8 / 256 / 8 == 320
    assert 32 * seq * 10 * 8 / 256 / 8 == 10240


def test_kernel_costs_by_hand():
    tiny = {"dim": 8, "kv_heads": 1, "head_dim": 4, "moe_hidden": 6,
            "moe_experts": 10, "experts_held": [2, 5],
            "layers": ["full_attention", "sliding_attention",
                       "sliding_attention"],
            "operators": {"full_attention": {"heads": 2},
                          "sliding_attention": {"heads": 3, "window": 2}}}
    traffic = {"seq_len": 4, "minibatch": 2}
    # the full layer: 2 heads x 10 pairs; a sliding layer: 3 heads x
    # (1 + 2 + 2 + 2) pairs; 2 matmuls x 2 FLOP x 4 a pair forward
    pairs = 2 * 10 + 2 * 3 * 7
    flops, nbytes = costs.attention_kernel_cost(tiny, traffic)
    assert flops == 2 * 3 * 16 * pairs
    # bytes: K and V as the kernels see them, at the query heads
    full = 12 * (8 * 2 * 4 * 2) + 2 * (8 * 2 * 4)
    sliding = 12 * (8 * 3 * 4 * 2) + 2 * (8 * 3 * 4)
    assert nbytes == full + 2 * sliding
    forward = costs.attention_kernel_cost(tiny, traffic, backward=False)
    assert forward == (2 * 16 * pairs,
                       4 * (8 * 2 * 4 * 2) + 8 * 2 * 4
                       + 2 * (4 * (8 * 3 * 4 * 2) + 8 * 3 * 4))
    # the band of ONE sliding layer from the counter's pairs: the same
    # operations a pair; K and V at the model's ONE K/V head
    flops, nbytes = costs.window_kernel_cost(tiny, 2 * 3 * 7, 8, 3)
    assert flops == 3 * 16 * 2 * 3 * 7
    assert nbytes == 6 * (8 * 3 * 4 * 2) + 6 * (8 * 1 * 4 * 2) \
        + 2 * (8 * 3 * 4)
    assert costs.window_kernel_cost(tiny, 42, 8, 3, backward=False) == (
        16 * 42, 2 * (8 * 3 * 4 * 2) + 2 * (8 * 1 * 4 * 2) + 8 * 3 * 4)
    # the two counts agree on the sliding layers' operations
    assert 2 * costs.window_kernel_cost(tiny, 42, 8, 3)[0] \
        == costs.attention_flops_per_sequence(tiny, 4, 3, True) * 2
    # the routed pairs only: 3 x 8 x 6 parameters an expert, 3 held
    flops, nbytes = costs.expert_matmul_cost(tiny, 5)
    assert flops == 6 * 144 * 5
    assert nbytes == 3 * 144 * 8 + 3 * 5 * (2 * 8 + 3 * 6) * 2
    # at the timed sizes the operations bound the band, not the bytes
    flops, nbytes = costs.window_kernel_cost(MODEL, 72 * 4_063_488,
                                             8192, 72)
    assert flops / 197e12 > nbytes / 819e9


# -- the new scope on a hand-made trace -----------------------------------------

A1 = "veles.fwd.GQAttention.GQAttention/"
A2 = "veles.fwd.GQAttention.GQAttention_2/"
GA1 = "veles.bwd.GDGQAttention.GDGQAttention/"
GA2 = "veles.bwd.GDGQAttention.GDGQAttention_2/"
E1 = "veles.fwd.ExpertFFN.ExpertFFN/"
WIN = "veles.core/veles.window/"


def fusion(n, path, start, end):
    return ("%%fusion.%d = f32[8] fusion()" % n, "loop fusion", path,
            start, end)


def kernel(n, path, start, end):
    return ("%%closed_call.%d = %s" % (n, MOSAIC), "custom-call", path,
            start, end)


#: one step + one validation forward of: a full layer (A1), a sliding
#: layer (A2) and an expert layer with a shared expert
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),
    fusion(1, P + A1 + "dot_general:", 1000, 1100),
    kernel(2, P + A1 + "veles.core/closed_call/pallas_call:", 1100, 1400),
    fusion(3, P + A2 + "dot_general:", 1400, 1500),
    fusion(4, P + A2 + WIN + "transpose:", 1500, 1550),
    kernel(5, P + A2 + WIN + "closed_call/pallas_call:", 1550, 1650),
    fusion(6, P + E1 + "veles.route/top_k:", 1650, 1750),
    fusion(7, P + E1 + "veles.shared/dot_general:", 1750, 1950),
    ("%ragged-dot.8 = " + MOSAIC, "custom-call", "", 1950, 2050),
    # the backward
    fusion(9, P + GA2 + WIN + "reduce_sum:", 2050, 2100),
    kernel(10, P + GA2 + WIN + "pallas_call:", 2100, 2350),
    fusion(11, P + GA2 + WIN + "convert_element_type:", 2350, 2400),
    fusion(12, P + GA2 + "transpose(veles.fwd.GQAttention."
           "GQAttention_2)/jvp()/dot_general:", 2400, 2600),
    fusion(13, P + GA2 + "veles.update/add:", 2600, 2700),
    kernel(14, P + GA1 + "veles.core/pallas_call:", 2700, 3300),
    fusion(15, P + GA1 + "veles.core/convert_element_type:", 3300, 3400),
    # the validation forward
    kernel(16, P + A1 + "veles.core/closed_call/pallas_call:",
           3400, 3700),
    kernel(17, P + A2 + WIN + "closed_call/pallas_call:", 3700, 3800),
]
BUSY = 2800
TINY = {"dim": 8, "kv_heads": 1, "head_dim": 4,
        "layers": ["full_attention", "sliding_attention"],
        "operators": {"full_attention": {"heads": 2},
                      "sliding_attention": {"heads": 3, "window": 2}}}


def traced(tmp_path, ops=OPS, counted=(42, 1)):
    from veles import telemetry
    pairs, steps = counted
    telemetry.counter("veles_window_pairs_total", "t", ("layer",)) \
        .labels("GQAttention_2").inc(pairs)
    telemetry.counter("veles_window_steps_total", "t", ("layer",)) \
        .labels("GQAttention_2").inc(steps)
    return context(
        tmp_path, MODULES[:1], ops,
        cell={"config": {"model": TINY},
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
    assert windowscopes.sub_of(P + A2 + WIN + "pallas_call:") \
        == ("GQAttention", "window")
    assert windowscopes.sub_of(P + GA2 + WIN + "reduce_sum:") \
        == ("GDGQAttention", "window")
    assert windowscopes.sub_of(
        P + GA2 + "transpose(jvp(veles.window))/mul:")[1] == "window"
    assert windowscopes.sub_of(P + A2 + "veles.core/veles.window")[1] \
        == "window"
    # whole words only, after a unit, and not the other scopes
    assert windowscopes.sub_of(P + A2 + "veles.windowed/mul:")[1] is None
    assert windowscopes.sub_of(P + "veles.window/mul:") == (None, None)
    assert windowscopes.sub_of(P + A1 + "veles.core/pallas_call:")[1] \
        is None
    assert windowscopes.sub_of("") == (None, None)
    # reduce/scopes.py keeps finding veles.core first
    from benchmark.reduce import scopes
    assert scopes.unit_of(P + A2 + WIN + "pallas_call:") \
        == ("fwd", "GQAttention", "GQAttention_2", "core")


def test_readers_by_hand(tmp_path, fresh_registry):
    ctx = traced(tmp_path)
    window_all = 50 + 100 + 50 + 250 + 50 + 100
    window_kernels = 100 + 250 + 100
    assert read("window_attn_share", ctx) == pytest.approx(
        100.0 * window_all / BUSY)
    # one step forward + backward and one validation forward of the 42
    # pairs: (3 + 1) x 4 x 4 FLOP a pair
    assert read("window_attn_roofline", ctx) == pytest.approx(
        100.0 * ((3 + 1) * 16 * 42 / 1e9) / (window_kernels * 1e-9))
    # the accepted readers the cell joins: veles.core holds all of the
    # attention proper, the windowed part too
    core_all = 300 + 600 + 100 + 300 + window_all
    assert read("flash_attn_share", ctx) == pytest.approx(
        100.0 * core_all / BUSY)
    full_and_band = 2 * 10 + 3 * 7      # visible pairs, all heads
    assert read("gqa_attn_roofline", ctx) == pytest.approx(
        100.0 * ((3 + 1) * 2 * 16 * full_and_band / 1e9)
        / ((300 + 600 + 300 + window_kernels) * 1e-9))
    assert read("shared_expert_share", ctx) == pytest.approx(
        100.0 * 200 / BUSY)
    # (with the grouped product XLA names itself, which has no path)
    assert read("moe_share", ctx) == pytest.approx(
        100.0 * (100 + 200 + 100) / BUSY)
    assert read("solver_update_share", ctx) == pytest.approx(
        100.0 * 100 / BUSY)
    assert read("unscoped_share", ctx) == pytest.approx(100.0 * 100 / BUSY)


def test_bytes_bound_the_band_where_the_chip_is_slow_to_read(
        tmp_path, fresh_registry):
    ctx = traced(tmp_path)
    ctx.peaks = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e9}
    step = 6 * (8 * 3 * 4 * 2) + 6 * (8 * 1 * 4 * 2) + 2 * (8 * 3 * 4)
    valid = 2 * (8 * 3 * 4 * 2) + 2 * (8 * 1 * 4 * 2) + 8 * 3 * 4
    assert read("window_attn_roofline", ctx) == pytest.approx(
        100.0 * ((step + valid) / 1e9) / 450e-9)


def test_readers_find_nothing_in_a_program_without_the_scope(
        tmp_path, fresh_registry):
    """A build before PR 38 has neither scope nor counter: the readers
    return nothing and do not raise."""
    plain = [op[:2] + (op[2].replace("veles.window/", ""),) + op[3:]
             for op in OPS]
    ctx = traced(tmp_path, ops=plain, counted=(0, 0))
    for name in ("window_attn_share", "window_attn_roofline"):
        assert read(name, ctx) is None, name
    # the counters without the scope (another build's executable from
    # the compile cache), and the scope without the counters
    with_counters = traced(tmp_path / "c", ops=plain)
    assert read("window_attn_roofline", with_counters) is None
    untraced = harness.Context(cell=ctx.cell, trace=None, peaks=None,
                               costs=costs, dispatches=[])
    for name in ("window_attn_share", "window_attn_roofline"):
        assert read(name, untraced) is None, name


def test_a_configuration_without_operators_reads_nothing(tmp_path,
                                                         fresh_registry):
    """The readers laid over another configuration's cell (the driver's
    traced runs of the accepted cells): no counter, no metric."""
    from benchmark.costs import lfm2_moe
    ctx = traced(tmp_path, counted=(0, 0))
    ctx.costs = lfm2_moe
    assert read("window_attn_roofline", ctx) is None


# -- the cell on the CPU --------------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu(trace, seconds=10):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell_laguna.py"),
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
    assert {"window_attn_share", "window_attn_roofline",
            "gqa_attn_roofline", "flash_attn_share", "moe_share",
            "shared_expert_share", "expert_matmul_roofline"} <= listed
    assert {"step_ms", "dispatch_gap_share",
            "expert_load_max_over_mean"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed


def test_the_parent_refuses_the_cells_command_at_once():
    """Without ``root.lm.model.operators`` - what a build before PR 38
    makes of the cell's command line - ``sliding_attention`` is no
    operator and ``pre_norm_body`` raises before anything is built."""
    from benchmark.drivers import train
    from veles.config import root
    cell = run.resolve(BENCH_DIR, CELL)
    argv = train.build_argv(cell, 1, "cpu")
    assert any(a.startswith("root.lm.model.operators={") for a in argv)
    assert "root.lm.model.layers=['full_attention', 'sliding_attention'," \
        " 'sliding_attention', 'sliding_attention', 'full_attention']" \
        in argv
    from veles.znicz_tpu.models import transformer_lm as T
    saved = root.lm.model.to_dict()
    try:
        root.lm.model.update({"block": "pre_norm",
                              "layers": MODEL["layers"]})
        with pytest.raises(ValueError, match="has the operators"):
            T.build_layers()
    finally:
        root.lm.model.update(saved)
