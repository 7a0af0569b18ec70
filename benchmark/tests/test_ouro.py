"""What PR 32 adds for ``ouro_2_6b``: the configuration file against
the catalog's statement, ``costs/ouro.py`` against counts made by hand,
``reduce/loopscopes.py`` and the new reader on a hand-made trace (and
the accepted readers the cell joins), and the cell end to end on the
CPU at a tiny preset
(``cpu_cell_ouro.py``)."""

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
from benchmark.costs import ouro as costs               # noqa: E402
from benchmark.reduce import loopscopes                 # noqa: E402
from benchmark.tests.test_scopes import (               # noqa: E402
    MODULES, MOSAIC, P, context, read)

CONFIG = harness.load_json(BENCH_DIR, "configs", "ouro_2_6b.json")
TRAFFIC = harness.load_json(BENCH_DIR, "traffic", "ouro_s8k_train.json")
MODEL = CONFIG["model"]
CELL = "ouro_2_6b_s8k_train"


# -- the configuration file ---------------------------------------------------


def test_config_states_the_published_widths_and_the_cut():
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert CONFIG["reduced"] == ["layers"] and CONFIG["layers"] == 8
    # what is run: every width as published, the depth alone cut
    assert (MODEL["dim"], MODEL["heads"], MODEL["kv_heads"],
            MODEL["head_dim"], MODEL["ffn_hidden"], MODEL["vocab"]) \
        == (2048, 16, 16, 128, 5632, 49152)
    assert MODEL["ut_steps"] == CONFIG["total_ut_steps"]
    assert MODEL["norm_eps"] == CONFIG["rms_norm_eps"]
    assert MODEL["rope_theta"] == CONFIG["rope_theta"]
    assert MODEL["layers"] == ["plain_attention"] * CONFIG["layers"]
    assert MODEL["dense_layers"] == len(MODEL["layers"]) >= 4
    assert MODEL["norm"] == "sandwich"
    # every reading the config does not state is written down
    assert {"no_bias", "sandwich_norm", "final_norm_every_pass", "gate",
            "objective", "early_exit_threshold", "initialisation",
            "optimizer", "corpus"} <= set(CONFIG["assumed"])
    # every override names a key the file holds
    for value in CONFIG["program"]["overrides"].values():
        if isinstance(value, str) and value.startswith("$model."):
            assert value[7:] in MODEL, value
        if isinstance(value, str) and value.startswith("$traffic."):
            assert value[9:] in TRAFFIC, value
    assert (TRAFFIC["seq_len"], TRAFFIC["minibatch"], TRAFFIC["n_train"],
            TRAFFIC["n_valid"]) == (8192, 1, 2, 1)


def test_config_matches_the_catalog_row():
    """``ouro_catalog_row.json``: the catalog's entry, copied beside
    this test as PR 32 found it."""
    row = harness.load_json(HERE, "ouro_catalog_row.json")
    assert row["name"] == "Ouro-2.6B"
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key


# -- the costs, by hand ---------------------------------------------------------


def test_parameters_by_hand():
    # W_qkv 2048 x 6144 + W_o 2048 x 2048 + three 2048 x 5632
    assert costs.layer_params(MODEL) == 12_582_912 + 4_194_304 \
        + 34_603_008 == 51_380_224
    assert costs.applications(MODEL) == 32
    assert costs.exit_params(MODEL) == 2048 * 49_153
    assert costs.matmul_params_met(MODEL) \
        == 32 * 51_380_224 + 4 * 2048 * 49_153 == 2_046_828_544
    # the arithmetic the configuration file states
    body = 8 * (51_380_224 + 4 * 2048)
    assert round(body / 1e6, 1) == 411.1
    total = body + 2 * 49_152 * 2048 + 2048 + 2049
    assert round(total / 1e6, 1) == 612.4


def test_train_flops_by_hand():
    seq = 8192
    # attention: 32 applications x 3 passes x 2 x S (S + 1) x 2048,
    # a token's share
    attention = 3 * 2.0 * 32 * (seq + 1) * 2048
    want = 6.0 * 2_046_828_544 + attention
    assert costs.train_flops_per_token(MODEL, seq) == pytest.approx(want)
    assert want / 1e9 == pytest.approx(15.50, abs=0.01)
    assert costs.train_flops_per_sample(MODEL, TRAFFIC) \
        == pytest.approx(want * seq)
    # a layer application forward: 102.8 MFLOP of products and 33.6 of
    # causal attention a token; an exit 201 MFLOP
    assert 2 * costs.layer_params(MODEL) / 1e6 == pytest.approx(102.8,
                                                                abs=0.05)
    assert 2.0 * (seq + 1) * 2048 / 1e6 == pytest.approx(33.6, abs=0.05)
    assert 2 * costs.exit_params(MODEL) / 1e6 == pytest.approx(201.3,
                                                               abs=0.05)


def test_attention_kernel_cost_by_hand():
    tiny = {"heads": 2, "head_dim": 4, "layers": ["plain_attention"] * 3,
            "ut_steps": 2}
    traffic = {"seq_len": 4, "minibatch": 2}
    # forward of one sequence, one application: 2 heads x 10 pairs x 2
    # matmuls x 2 x 4 = 320 FLOP = 2 x 4 x 5 x 8; 6 applications
    forward = 6 * 320
    assert costs.attention_flops_per_sequence(tiny, 4, passes=1) \
        == forward
    flops, nbytes = costs.attention_kernel_cost(tiny, traffic)
    assert flops == 2 * 3 * forward
    tensor, rows = 2 * 4 * 8 * 2, 2 * 2 * 4 * 4
    assert nbytes == 6 * (12 * tensor + 2 * rows)
    flops, nbytes = costs.attention_kernel_cost(tiny, traffic,
                                                backward=False)
    assert (flops, nbytes) == (2 * forward, 6 * (4 * tensor + rows))
    # at the timed sizes the operations bound it
    flops, nbytes = costs.attention_kernel_cost(MODEL, TRAFFIC)
    assert flops / 197e12 > 5 * nbytes / 819e9


# -- the loop's scopes on a hand-made trace ------------------------------------

LOOP = P + "while/body/veles.pass/"
A1 = "veles.fwd.GQAttention.GQAttention/"
F1 = "veles.fwd.SwiGLUFFN.SwiGLUFFN/"
A2 = "veles.fwd.GQAttention.GQAttention_2/"
GA1 = "veles.bwd.GDGQAttention.GDGQAttention/"
GF1 = "veles.bwd.GDSwiGLUFFN.GDSwiGLUFFN/"
GA2 = "veles.bwd.GDGQAttention.GDGQAttention_2/"
R = "veles.recompute/"


def fusion(n, path, start, end):
    return ("%%fusion.%d = f32[8] fusion()" % n, "loop fusion", path,
            start, end)


def kernel(n, path, start, end):
    return ("%%closed_call.%d = %s" % (n, MOSAIC), "custom-call",
            path + "veles.core/closed_call/pallas_call:", start, end)


#: one run of one step at ut_steps 2 over two attention layers and one
#: feed-forward; pass 2's backward holds 350 ns of recomputation, pass
#: 1's 300
OPS = [
    ("%while.1 = () while()", "while", "", 1000, 4800),
    fusion(1, P + "veles.fwd.EmbeddingForward.EmbeddingForward/gather:",
           1000, 1100),
    # forward, pass 1
    kernel(2, LOOP + A1, 1100, 1200), fusion(3, LOOP + F1 + "mul:",
                                             1200, 1300),
    kernel(4, LOOP + A2, 1300, 1500),
    # forward, pass 2
    kernel(5, LOOP + A1, 1500, 1700), fusion(6, LOOP + F1 + "mul:",
                                             1700, 1800),
    kernel(7, LOOP + A2, 1800, 2100),
    # an exit
    fusion(8, P + "while/body/veles.fwd.TokenDense.TokenDense/dot:",
           2100, 2400),
    # backward, pass 2: segment 2 (A2 alone), then segment 1 (A1, F1)
    kernel(9, LOOP + R + A2, 2400, 2550),
    kernel(10, LOOP + GA2, 2550, 2800),
    kernel(11, LOOP + R + A1, 2800, 2900),
    fusion(12, LOOP + R + F1 + "mul:", 2900, 3000),
    # the unit's own checkpoint inside its gradient unit: not the
    # loop's recomputation
    fusion(13, LOOP + GF1 + "transpose(jvp(veles.pass))/" + R + F1
           + "jvp()/checkpoint/mul:", 3000, 3100),
    kernel(14, LOOP + GA1, 3100, 3300),
    # backward, pass 1
    kernel(15, LOOP + R + A2, 3300, 3400),
    kernel(16, LOOP + GA2, 3400, 3600),
    kernel(17, LOOP + R + A1, 3600, 3700),
    fusion(18, LOOP + R + F1 + "mul:", 3700, 3800),
    fusion(19, LOOP + GF1 + "mul:", 3800, 3850),
    kernel(20, LOOP + GA1, 3850, 4000),
    # the one update, outside the passes
    fusion(21, P + GA1 + "veles.update/add:", 4000, 4200),
]
BUSY = 3200


def traced(tmp_path, ops=OPS, peak=1e9, ut_steps=2):
    model = {"heads": 2, "head_dim": 4, "ut_steps": ut_steps,
             "layers": ["plain_attention"] * 2}
    return context(
        tmp_path, MODULES[:1], ops,
        cell={"config": {"model": model},
              "traffic": {"seq_len": 4, "minibatch": 2, "n_valid": 0}},
        dispatches=[{"start": 0.0, "dur": 1.0, "epochs": 1, "warm": True}],
        steps_per_epoch=1, costs=costs,
        peaks={"bf16_flops_per_s": peak, "hbm_bytes_per_s": 1e15})


def test_recomputed():
    assert not loopscopes.recomputed(LOOP + A1 + "veles.core/pallas_call:")
    assert loopscopes.recomputed(LOOP + R + A2 + "dot_general:")
    assert not loopscopes.recomputed(LOOP + GF1 + "mul:")
    # a forward path named INSIDE a gradient unit is that unit's own
    # checkpoint, whatever scopes the forward ran under
    assert not loopscopes.recomputed(
        LOOP + GF1 + "transpose(jvp(veles.pass))/" + R + F1 + "mul:")
    # whole words only, and a unit after it
    assert not loopscopes.recomputed(P + "veles.recomputed/" + A1 + "mul:")
    assert not loopscopes.recomputed(P + R + "mul:")
    assert not loopscopes.recomputed("")


def test_readers_by_hand(tmp_path):
    ctx = traced(tmp_path)
    assert read("recompute_share", ctx) == pytest.approx(
        100.0 * (350 + 300) / BUSY)
    # the accepted reader the cell joins: the kernels under
    # veles.core, recomputed forwards in the time — 800 ns forward +
    # 700 + 550 backward for one step's forward + backward of 4
    # applications, 3 x 4 x 2 sequences x 320 FLOP
    assert read("gqa_attn_roofline", ctx) == pytest.approx(
        100.0 * (3 * 4 * 2 * 320 / 1e9) / 2050e-9)
    # the accepted readers see the looped units as they see the others
    assert read("flash_attn_share", ctx) == pytest.approx(
        100.0 * 2050 / BUSY)
    assert read("vocab_head_share", ctx) == pytest.approx(
        100.0 * 300 / BUSY)
    assert read("solver_update_share", ctx) == pytest.approx(
        100.0 * 200 / BUSY)
    assert read("unscoped_share", ctx) == 0.0


def test_reader_finds_nothing_in_a_program_without_the_scope(tmp_path):
    """The parent's program has no ``veles.recompute``: the reader
    returns nothing and does not raise."""
    plain = [op[:2] + (op[2].replace("veles.pass/", "")
                       .replace("veles.recompute/", ""),) + op[3:]
             for op in OPS]
    assert read("recompute_share", traced(tmp_path, ops=plain)) is None
    untraced = harness.Context(cell=traced(tmp_path / "u").cell,
                               trace=None, peaks=None, costs=costs,
                               dispatches=[])
    assert read("recompute_share", untraced) is None


# -- the cell on the CPU --------------------------------------------------------

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cpu(trace, seconds=2):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "cpu_cell_ouro.py"),
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
    # forward and the reference's own epoch of training (64 steps at
    # this preset) agree to float32 rounding: the equations are the
    # program's
    diffs = [float(line.split("|diff| ")[1].split()[0])
             for line in out.splitlines() if line.startswith("check ")]
    assert len(diffs) == 3 and max(diffs) < 1e-4, out[-3000:]


def test_traced_cpu_run_reports_what_the_cpu_can():
    """No device trace on the CPU: the span metrics appear, the
    device-trace readers leave theirs out and do not raise."""
    result, _ = run_cpu(trace=1, seconds=3)
    listed = {m["name"] for m in run.resolve(BENCH_DIR, CELL)["per_layer"]}
    assert {"recompute_share", "gqa_attn_roofline"} <= listed
    assert {"step_ms", "dispatch_gap_share"} <= set(result["metrics"])
    assert set(result["metrics"]) <= listed
