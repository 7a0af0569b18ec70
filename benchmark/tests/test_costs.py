"""``costs/`` against counts made by hand."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import harness          # noqa: E402
from benchmark.costs import alexnet, lm     # noqa: E402

BENCH_DIR = os.path.dirname(HERE)
LM = harness.load_json(BENCH_DIR, "configs", "lm110m.json")["model"]
ALEXNET = harness.load_json(BENCH_DIR, "configs", "alexnet.json")["model"]


def test_one_lm_layer_by_hand():
    # one layer: qkv 768 x 2304, out 768 x 768, FFN 768 x 3072 twice
    per_layer = 768 * 2304 + 768 * 768 + 2 * 768 * 3072
    assert per_layer == 7_077_888
    one = dict(LM, layers=1)
    assert lm.matmul_params(one) == per_layer + 768 * 16384
    assert lm.matmul_params(LM) == 12 * per_layer + 768 * 16384 \
        == 97_517_568
    # attention of one layer, one sequence of 8 tokens, forward: each of
    # 12 heads multiplies over the 8 * 9 / 2 = 36 causal pairs twice
    # (scores, context), 64 multiply-accumulates of 2 FLOP each
    assert lm.attention_flops_per_sequence(one, 8, passes=1) \
        == 12 * 36 * 2 * 64 * 2
    # and the backward is four such matmuls: three passes in all
    assert lm.attention_flops_per_sequence(one, 8) \
        == 3 * lm.attention_flops_per_sequence(one, 8, passes=1)


def test_lm_train_flops_per_token():
    # the figures ISSUE 22 quotes: 613 and 1,038 MFLOP a token, of
    # which attention 28 and 453
    assert lm.train_flops_per_token(LM, 512) == 613_472_256
    assert lm.train_flops_per_token(LM, 8192) == 1_038_145_536
    assert lm.attention_flops_per_sequence(LM, 8192) / 8192 \
        == 453_040_128
    traffic = {"seq_len": 512, "minibatch": 32}
    assert lm.train_flops_per_sample(LM, traffic) == 613_472_256 * 512


def test_attention_kernel_cost_is_compute_bound_at_8k():
    flops, nbytes = lm.attention_kernel_cost(
        LM, {"seq_len": 8192, "minibatch": 4})
    assert flops == 4 * 8192 * 453_040_128
    # twelve bf16 (4, 8192, 768) tensors and two f32 (4, 12, 8192) rows
    assert nbytes == 12 * (12 * 4 * 8192 * 768 * 2 + 2 * 4 * 12 * 8192 * 4)
    assert flops / nbytes > 1500      # the chip's ridge is 240 FLOP/B


def test_alexnet_conv2_by_hand():
    macs = dict(alexnet.layer_macs(ALEXNET))
    # conv1: (227 - 11) / 4 + 1 = 55; pool 3/2: 27; conv2 pads 2
    assert macs["conv1"] == 55 * 55 * 96 * 11 * 11 * 3
    assert macs["conv2"] == 27 * 27 * 256 * 5 * 5 * 96 == 447_897_600
    assert macs["fc6"] == 6 * 6 * 256 * 4096
    assert macs["fc8"] == 4096 * 1000
    total = sum(macs.values())
    assert total == 1_135_256_096
    # 3 x forward, less conv1's input gradient
    assert alexnet.train_flops_per_sample(ALEXNET) \
        == 6 * total - 2 * macs["conv1"]
