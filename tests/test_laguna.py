"""The pre-norm block at Laguna's layer pattern — a leading dense layer,
three sliding-window layers to one full-attention layer with ANOTHER
head count, per-head output gates, two rotary regimes (half of a head
under YaRN; the whole head, plain), over an expert layer with a shared
expert — against its plain float32 reference,
``benchmark/reference/laguna.py``: each operator alone in every mode of
the attention proper, the banded kernels (interpreted) against the dense
masked core for windows below, at and above the tile and S, the whole LM
through ``StandardWorkflow`` (loss, every parameter's gradient, one
momentum step), YaRN's tables against a hand count, the expert layer's
32 shares adding up to the uncut layer with the shared expert counted
once, the counters and scopes, and ``window=None`` left as the parent
traced it."""

import hashlib
import os
import re
import sys

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.config import root
from veles.znicz_tpu.ops import gqa_attention
from veles.znicz_tpu.ops.attention import (
    dense_attention_core_bwd, dense_attention_core_fwd)
from veles.znicz_tpu.ops.expert_ffn import ExpertFFN
from veles.znicz_tpu.ops.gqa_attention import GQAttention
from veles.znicz_tpu.parallel import flash
from veles.znicz_tpu.parallel import pallas_attention as PA

from tests.test_conv_stack import xla_backward, xla_forward
from tests.test_lfm2_moe import B, D, S, build

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmark.reference import laguna as ref   # noqa: E402

YARN = {"rope_type": "yarn", "factor": 8.0,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.2079441541679836}
#: the tiny preset: d 64; 2 K/V heads of 16 under 4 query heads (groups
#: of 2), half of each head rotated under YaRN, in the full layers and
#: 6 (groups of 3), the whole head rotated, window 24 of S 64, in the
#: sliding ones; a dense SwiGLU of 96, then 8 experts top-3 of width 32
#: and a shared one of 48; dense + S, S, S, F
OPERATORS = {
    "full_attention": {"heads": 4, "rope_theta": 500000.0,
                       "rotary_dim": 8, "rope_scaling": YARN,
                       "gate": "head", "qk_norm": False},
    "sliding_attention": {"heads": 6, "rope_theta": 10000.0, "window": 24,
                          "gate": "head", "qk_norm": False}}
MODEL = {"dim": 64, "kv_heads": 2, "head_dim": 16,
         "layers": ["full_attention"] + ["sliding_attention"] * 3
         + ["full_attention"],
         "operators": OPERATORS, "dense_layers": 1, "ffn_hidden": 96,
         "moe_hidden": 32, "moe_shared_hidden": 48, "moe_experts": 8,
         "moe_top_k": 3, "experts_held": [0, 8], "routed_scaling": 2.5,
         "norm_eps": 1e-6, "vocab": 32, "gradient_moment": 0.9}
EXPERT = dict(experts=8, top_k=3, hidden=32, shared_hidden=48,
              scaling=2.5, eps=1e-6)


def unit_keywords(kind, **more):
    return dict(OPERATORS[kind], kv_heads=2, head_dim=16, eps=1e-6,
                **more)


def reference_block(kind, model):
    """x (S, d), params -> the unit's output by the reference."""
    import jax

    def block(p, x):
        with jax.default_matmul_precision("highest"):
            if kind == "expert_ffn":
                return ref.feed_forward(p, x, kind, model)
            return ref.operator(p, x, kind, model, 16)

    return block


def check_unit(cls, kwargs, kind, model=MODEL, tol=2e-5):
    """Output, input gradient and every parameter's gradient of one
    unit against ``jax.grad`` of the reference's function."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(cls, **kwargs)
    params0 = comp.gather_params()
    state0 = comp.gather_state()
    y = numpy.asarray(xla_forward(comp, feed, fwd, params0, x))
    dx, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                               x, err)
    block = reference_block(kind, model)
    p = {k: jnp.asarray(v) for k, v in params0[fwd.name].items()}

    def total(p, x):
        out = jax.vmap(lambda row: block(p, row))(x)
        return (out * err).sum(), out

    (_, want), (gp, gx) = jax.value_and_grad(
        total, argnums=(0, 1), has_aux=True)(p, jnp.asarray(x))
    assert numpy.abs(y - numpy.asarray(want)).max() < tol
    assert numpy.abs(numpy.asarray(dx) - numpy.asarray(gx)).max() \
        < 10 * tol
    for name, g in gp.items():
        moved = numpy.asarray(params0[fwd.name][name]) \
            - numpy.asarray(params1[fwd.name][name])
        if name == "expert_bias":       # a buffer: never moves
            assert not moved.any()
            continue
        assert numpy.abs(numpy.asarray(g)).max() > 0, name
        scale = max(1.0, float(numpy.abs(numpy.asarray(g)).max()))
        assert numpy.abs(moved - numpy.asarray(g)).max() \
            < 10 * tol * scale, name
    return fwd, y


# -- each operator alone, in every mode of the attention proper ---------------

MODES = {"dense": {},
         "scan": {"attn_block_size": 16},
         "kernels": {"attn_block_size": 16, "attn_impl": "pallas",
                     "pallas_tile": 16},
         "one_tile": {"attn_block_size": 16, "attn_impl": "pallas"}}
#: windows below, at and above the tile (16) and S (64); 24 and 40 are
#: no multiple of the tile
WINDOWS = (1, 7, 16, 24, 40, 64, 100)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_full_attention_unit_against_reference(mode):
    """48-for-72's twin at 4 query heads: groups of 2, half of each
    head rotated, YaRN's tables, the per-head gate."""
    fwd, _ = check_unit(GQAttention,
                        unit_keywords("full_attention", **MODES[mode]),
                        "full_attention")
    assert fwd.weights.shape == (D, (4 + 2 * 2) * 16 + 4)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("mode", sorted(MODES))
def test_sliding_attention_unit_against_reference(mode, window):
    """6 query heads over 2 K/V heads (groups of 3), the whole head
    rotated at another base, a window: the unit's three cores against
    the reference's mask on dense scores."""
    model = dict(MODEL, operators=dict(OPERATORS, sliding_attention=dict(
        OPERATORS["sliding_attention"], window=window)))
    fwd, _ = check_unit(
        GQAttention, dict(unit_keywords("sliding_attention",
                                        **MODES[mode]), window=window),
        "sliding_attention", model)
    assert fwd.weights.shape == (D, (6 + 2 * 2) * 16 + 6)
    assert fwd.weights_out.shape == (6 * 16, D)


def test_expert_layer_top_3_of_8_against_reference():
    check_unit(ExpertFFN, EXPERT, "expert_ffn")


def test_a_window_changes_the_output_and_a_window_past_s_does_not():
    def out(window):
        feed, fwd, _, x, _, comp = build(GQAttention, **dict(
            unit_keywords("sliding_attention"), window=window))
        return numpy.asarray(xla_forward(
            comp, feed, fwd, comp.gather_params(), x, train=False))

    whole = out(None)
    assert numpy.abs(out(S) - whole).max() == 0
    assert numpy.abs(out(24) - whole).max() > 1e-3


# -- the banded kernels against the dense masked core -------------------------


def heads_of(s, dh=16, h=3, seed=0):
    import jax.numpy as jnp
    gen = numpy.random.RandomState(seed)
    return [jnp.asarray(gen.randn(2, h, s, dh).astype(numpy.float32))
            for _ in range(4)]


def dense(q, k, v, do, window):
    import jax.numpy as jnp
    scale = numpy.float32(1.0 / numpy.sqrt(q.shape[-1]))
    probs, out = dense_attention_core_fwd(jnp, q, k, v, True, scale,
                                          window=window)
    return (out,) + tuple(
        dense_attention_core_bwd(jnp, q, k, v, probs, do, scale))


BANDS = [(64, 16, 16, w) for w in WINDOWS] + [
    (128, 32, 32, 33), (128, 32, 16, 40), (128, 16, 32, 40),
    (128, 16, 64, 5), (64, 64, 64, 24), (64, 64, 64, 1),
    (32, 32, 32, 31)]


@pytest.mark.parametrize("s,block_q,block_k,window", BANDS)
def test_banded_kernels_against_the_dense_masked_core(s, block_q, block_k,
                                                      window):
    """Forward and fused backward, interpreted: the K-loop pair's band
    (its loop bounds and both edges' masks) and, where the sequence is
    one tile, the one-tile pair's second mask term."""
    q, k, v, do = heads_of(s)
    want = dense(q, k, v, do, window)
    out, lse = PA.flash_attention_fwd(
        q, k, v, block_q=block_q, block_k=block_k, interpret=True,
        window=window)
    got = (out,) + tuple(PA.flash_attention_bwd(
        q, k, v, out, lse, do, block_q=block_q, block_k=block_k,
        interpret=True, window=window))
    for mine, theirs in zip(got, want):
        assert numpy.abs(numpy.asarray(mine - theirs)).max() < 2e-5


@pytest.mark.parametrize("window", WINDOWS)
def test_scan_flash_window_against_the_dense_masked_core(window):
    q, k, v, do = heads_of(64)
    want = dense(q, k, v, do, window)
    out, lse = flash.blocked_attention_fwd(q, k, v, block=16,
                                           window=window)
    got = (out,) + tuple(flash.blocked_attention_bwd(
        q, k, v, out, lse, do, block=16, window=window))
    for mine, theirs in zip(got, want):
        assert numpy.abs(numpy.asarray(mine - theirs)).max() < 2e-5


@pytest.mark.parametrize("s,block_q,block_k,window", [
    (64, 16, 16, 20), (128, 32, 16, 40), (128, 16, 32, 5),
    (8192, 512, 512, 512), (128, 16, 16, 1), (4096, 512, 512, 700)])
def test_forward_and_backward_bounds_visit_the_tiles_the_band_cuts(
        s, block_q, block_k, window):
    """The forward's K-tile bounds and the backward's Q-tile bounds
    name the same (Q tile, K tile) pairs: exactly those that hold a
    visible pair; and a span marked plain holds no hidden pair."""
    def visible(t, key):
        return key <= t < key + window

    fwd, bwd = set(), set()
    for qi in range(s // block_q):
        spans = PA._band_spans_fwd(qi, block_q, block_k, window)
        assert all(lo <= hi for lo, hi, _ in spans)
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        for lo, hi, masked in spans:
            for j in range(lo, hi):
                fwd.add((qi, j))
                corners = [visible(t, key) for t in (
                    qi * block_q, (qi + 1) * block_q - 1)
                    for key in (j * block_k, (j + 1) * block_k - 1)]
                assert masked or all(corners)
    for ki in range(s // block_k):
        spans = PA._band_spans_bwd(ki, block_q, block_k, window,
                                   s // block_q)
        assert all(lo <= hi for lo, hi, _ in spans)
        assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        for lo, hi, masked in spans:
            for j in range(lo, hi):
                bwd.add((j, ki))
                corners = [visible(t, key) for t in (
                    j * block_q, (j + 1) * block_q - 1)
                    for key in (ki * block_k, (ki + 1) * block_k - 1)]
                assert masked or all(corners)
    want = {(qi, j) for qi in range(s // block_q)
            for j in range(s // block_k)
            if qi * block_q + block_q - 1 >= j * block_k
            and qi * block_q - (j * block_k + block_k - 1) < window}
    assert fwd == bwd == want


def test_the_band_of_the_cell_by_hand():
    """S = 8192, window 512: 4,063,488 pairs a head where the triangle
    holds 33,558,528; at tile 512 the loop bounds visit 31 tiles, 2.0
    times the band, and 136 tiles of the triangle."""
    assert PA.band_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512 \
        == 4063488
    assert PA.band_pairs(8192) == 33558528
    assert PA.visited_pairs(8192, 512, 512, 512) == 31 * 512 * 512
    assert PA.visited_pairs(8192, 512, 512, None) == 136 * 512 * 512
    assert round(PA.visited_pairs(8192, 512, 512, 512)
                 / PA.band_pairs(8192, 512), 2) == 2.0
    assert PA.band_pairs(64, 100) == PA.band_pairs(64) == 64 * 65 // 2


@pytest.mark.parametrize("call", ["fwd", "bwd", "scan", "dense"])
def test_a_window_of_a_row_that_is_not_causal_is_refused(call):
    q, k, v, do = heads_of(32)
    with pytest.raises(ValueError, match="causal"):
        if call == "fwd":
            PA.flash_attention_fwd(q, k, v, causal=False, block_q=16,
                                   block_k=16, interpret=True, window=8)
        elif call == "bwd":
            PA.flash_attention_bwd(
                q, k, v, q, q[..., 0], do, causal=False, block_q=16,
                block_k=16, interpret=True, window=8)
        elif call == "scan":
            flash.blocked_attention_fwd(q, k, v, causal=False, block=16,
                                        window=8)
        else:
            import jax.numpy as jnp
            dense_attention_core_fwd(jnp, q, k, v, False, 0.25,
                                     window=8)


# -- window=None is today's program -------------------------------------------

#: sha256 of the jaxpr of each core at a fixed shape, REAL kernels
#: (``interpret=False``: the Mosaic kernels' bodies as the TPU compiler
#: gets them), traced by the PARENT of PR 38 (commit e9b8d9a) in this
#: container's jax. ``window=None``, left out or said, and a window
#: that hides nothing give the same text: shape and window alone
#: choose a kernel.
PARENT_CORES = {
    "kloop_fwd": "0b3afd6d1992094bb4730383f0b1a14eb6eee2c1dd9ef1498fbc2e65dd7e61e0",
    "kloop_bwd": "c70321591233a0be4267a456cf091d8620c7bb3536498b45cd8b9f8f720720eb",
    "tile_fwd": "bc9583fb58eb67876747e1387e509d59d17c31301e0760f6cb996dc3aabfdfe6",
    "tile_bwd": "a14bc29b0e50d371b5cef6a4a0b7f739561b4515a1a4ac3dde2f0c6cafd2d645",
    "scan_fwd": "96e4346a2ebf9f2b529ae7e127db347d09421d1f42937ec4489440d2e85a12da",
    "scan_bwd": "9c11a704196708744beeaffde5d461542857c128365eae58a99faa58f6c4b9ad",
    "dense_fwd": "4b9ed59d15677fce3c10d301083ece81bba79a51079d09d478bf35cb05577d49",
}


def core_text(case, **window):
    import jax
    import jax.numpy as jnp

    def heads(s, dh=128, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, 2, s, dh), dtype)

    def rows(s):
        return jax.ShapeDtypeStruct((1, 2, s), jnp.float32)

    name, part = case.split("_")
    if name in ("kloop", "tile"):
        s = 1024 if name == "kloop" else 512
        q = heads(s)
        if part == "fwd":
            return str(jax.make_jaxpr(
                lambda q, k, v: PA.flash_attention_fwd(
                    q, k, v, block_q=512, block_k=512, interpret=False,
                    **window))(q, q, q))
        return str(jax.make_jaxpr(
            lambda q, k, v, o, l, d: PA.flash_attention_bwd(
                q, k, v, o, l, d, block_q=512, block_k=512,
                interpret=False, **window))(q, q, q, q, rows(s), q))
    q = heads(64, 16, jnp.float32)
    if case == "scan_fwd":
        return str(jax.make_jaxpr(
            lambda q, k, v: flash.blocked_attention_fwd(
                q, k, v, block=16, **window))(q, q, q))
    if case == "scan_bwd":
        return str(jax.make_jaxpr(
            lambda q, k, v, o, l, d: flash.blocked_attention_bwd(
                q, k, v, o, l, d, block=16, **window))(
                    q, q, q, q, rows(64), q))
    return str(jax.make_jaxpr(
        lambda q, k, v: dense_attention_core_fwd(
            jnp, q, k, v, True, numpy.float32(0.25), **window))(q, q, q))


@pytest.mark.parametrize("case", sorted(PARENT_CORES))
def test_window_none_traces_the_cores_the_parent_traced(case,
                                                        monkeypatch):
    monkeypatch.setattr(PA, "_device_vmem_bytes", lambda: 128 << 20)
    default = core_text(case)
    assert hashlib.sha256(default.encode()).hexdigest() \
        == PARENT_CORES[case]
    assert core_text(case, window=None) == default
    if not case.startswith(("scan", "dense")):
        # ... and a window that hides nothing runs the same kernels
        assert core_text(case, window=4096) == default
    if case != "dense_fwd":     # (its mask is a constant of the trace)
        assert core_text(case, window=24) != default


# -- YaRN ----------------------------------------------------------------------


def test_yarn_tables_of_the_published_keys_by_hand():
    """r = 64, theta 5e5, factor 128 over 8192 positions, beta 32 | 1:
    c(32) = 64 ln(8192 / 64 pi) / (2 ln 5e5) = 9.04 and c(1) = 17.49,
    so frequencies 0..9 are kept, 18..31 divided by 128, and j = 12 is
    a third of the way; both tables times 0.1 ln 128 + 1."""
    scaling = {"rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618}
    lo = 64 * numpy.log(8192 / (2 * numpy.pi * 32)) \
        / (2 * numpy.log(5e5))
    hi = 64 * numpy.log(8192 / (2 * numpy.pi)) / (2 * numpy.log(5e5))
    assert (int(numpy.floor(lo)), int(numpy.ceil(hi))) == (9, 18)
    assert abs(0.1 * numpy.log(128) + 1 - 1.4852030263919618) < 1e-12
    cos, sin = gqa_attention.rope_tables(300, 64, 5e5, scaling)
    assert cos.shape == sin.shape == (300, 32)
    assert cos.dtype == numpy.float32
    position = 257.0
    for j, share in ((0, 0.0), (9, 0.0), (12, 3 / 9), (17, 8 / 9),
                     (18, 1.0), (31, 1.0)):
        plain = 5e5 ** (-2.0 * j / 64)
        inv = plain * (1 - share) + plain / 128 * share
        assert abs(cos[257, j] - 1.4852030263919618
                   * numpy.cos(position * inv)) < 1e-6, j
        assert abs(sin[257, j] - 1.4852030263919618
                   * numpy.sin(position * inv)) < 1e-6, j
    # no attention_factor stated: 0.1 ln(factor) + 1
    del scaling["attention_factor"]
    again, _ = gqa_attention.rope_tables(300, 64, 5e5, scaling)
    assert numpy.abs(again - cos).max() < 1e-6
    # the plain tables are what they were
    plain_cos, _ = gqa_attention.rope_tables(300, 64, 1e4)
    assert abs(plain_cos[257, 5] - numpy.cos(257 * 1e4 ** (-10 / 64))) \
        < 1e-6
    # the reference computes its own, from the same formulas
    own = {"rope_theta": 5e5, "rotary_dim": 64,
           "rope_scaling": dict(scaling, attention_factor=1.4852030263919618)}
    theirs, _ = ref.rotary_tables(300, own, 128)
    assert numpy.abs(theirs - cos).max() < 1e-6


def test_rope_turns_the_first_values_of_a_head_and_passes_the_rest():
    import jax.numpy as jnp
    gen = numpy.random.RandomState(3)
    t = jnp.asarray(gen.randn(1, 2, 5, 16).astype(numpy.float32))
    cos, sin = gqa_attention.rope_tables(5, 8, 1e4)
    out = numpy.asarray(gqa_attention.rope(t, cos, sin))
    assert numpy.abs(out[..., 8:] - numpy.asarray(t)[..., 8:]).max() == 0
    a, b = numpy.asarray(t)[..., :4], numpy.asarray(t)[..., 4:8]
    assert numpy.abs(out[..., :4] - (a * cos - b * sin)).max() < 1e-6
    assert numpy.abs(out[..., 4:8] - (b * cos + a * sin)).max() < 1e-6
    assert numpy.abs(out[:, :, 0] - numpy.asarray(t)[:, :, 0]).max() \
        < 1e-6                      # position 0 is not turned


# -- the share sums to the model ----------------------------------------------


def test_32_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """32 experts, 10 a token, as 32 shares ``experts_held [i, i + 1)``
    (one of 32 chips each): the shares' routed parts, with the shared
    expert counted once, add up to what the reference gives for the
    layer that holds all 32."""
    import jax
    wide = dict(EXPERT, experts=32, top_k=10)
    model = dict(MODEL, moe_experts=32, moe_top_k=10,
                 experts_held=[0, 32])
    feed, fwd, _, x, _, comp = build(ExpertFFN, **wide)
    params = comp.gather_params()[fwd.name]
    whole = numpy.asarray(jax.vmap(lambda row: reference_block(
        "expert_ffn", model)(params, row))(x)) - x

    def part(lo, hi, shared):
        feed, fwd, _, _, _, comp = build(
            ExpertFFN, **dict(wide, experts_held=(lo, hi),
                              shared_hidden=48 if shared else 0))
        mine = dict(params, weights13=params["weights13"][lo:hi],
                    weights2=params["weights2"][lo:hi])
        if not shared:
            del mine["shared13"], mine["shared2"]
        y = xla_forward(comp, feed, fwd, {fwd.name: mine}, x,
                        train=False)
        return numpy.asarray(y) - x

    routed = [part(lo, lo + 1, False) for lo in range(32)]
    assert sum(numpy.abs(p).max() > 1e-3 for p in routed) == 32
    shared = part(0, 1, True) - routed[0]
    assert numpy.abs(shared).max() > 1e-3
    assert numpy.abs(sum(routed) + shared - whole).max() < 5e-5
    # every chip adding its own copy of the shared expert is NOT the
    # layer
    assert numpy.abs(sum(routed) + 32 * shared - whole).max() > 1e-3


# -- the whole LM -------------------------------------------------------------


@pytest.fixture
def tiny_lm():
    from veles.znicz_tpu.models import transformer_lm as T
    saved = {k: getattr(root.lm, k).to_dict()
             for k in ("loader", "model", "train", "decision")}
    root.lm.loader.update({"minibatch_size": 1, "n_train": 1,
                           "n_valid": 1, "seq_len": S, "vocab": 32,
                           "max_period": 40})
    root.lm.model.update(dict(
        {k: v for k, v in MODEL.items()
         if k not in ("vocab", "gradient_moment", "routed_scaling")},
        block="pre_norm", attn_block=16, moe_scaling=2.5))
    root.lm.train.update({"learning_rate": 0.5, "gradient_moment": 0.9})
    root.lm.decision.update({"max_epochs": 1})
    prng.seed_all(5)
    try:
        yield T
    finally:
        del root.lm.model.operators
        for k, v in saved.items():
            getattr(root.lm, k).update(v)


def exported(wf):
    return [(type(u).MAPPING, u.export_params()) for u in wf.forwards]


def test_lm_loss_gradients_and_one_step_against_reference(tiny_lm):
    """The program trained through StandardWorkflow / xla_step for one
    step: first validation loss, train loss, and every parameter after
    the step, against the reference's walk over the sub-layers +
    momentum SGD; and that walk, every parameter's gradient, against
    ``jax.grad`` of the reference's whole loss."""
    import jax
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    loader = wf.loader
    data, labels = loader.original_data.mem, loader.original_labels.mem
    valid = (data[:1].copy(), labels[:1].copy())
    train = (data[1:2].copy(), labels[1:2].copy())
    tree = ref.from_program(exported(wf), MODEL)
    heads = [layer["op"]["weights_out"].shape[0] // 16
             for layer in tree["layers"]]
    assert heads == [4, 6, 6, 6, 4]
    assert ref.count_parameters(MODEL) == sum(
        a.size for a in jax.tree_util.tree_leaves(tree)) - 4 * 8

    def whole(tree, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return ref.sequence_loss(tree, tokens, labels, MODEL, 16)

    value, want_grads = jax.value_and_grad(whole)(
        tree, train[0][0], train[1][0])
    walked, grads = ref.gradients(tree, train, MODEL)
    assert abs(walked - float(value) / S) < 1e-5
    for (path, mine), theirs in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        theirs = numpy.asarray(theirs) / S
        assert numpy.abs(mine - theirs).max() \
            < 1e-5 * max(1.0, numpy.abs(theirs).max()), path

    wf.run()
    history = wf.decision.history
    assert abs(history[0]["validation"]["loss"]
               - ref.loss(tree, valid, MODEL)) < 1e-5
    after, losses = ref.train(tree, [train], MODEL, 0.5, 0.9)
    assert abs(history[0]["train"]["loss"] - losses[0]) < 1e-5
    stepped = ref.from_program(exported(wf), MODEL)
    flat = jax.tree_util.tree_leaves_with_path
    moved = 0
    for (path, new), (_, want), (_, old) in zip(
            flat(stepped), flat(after), flat(tree)):
        change = numpy.abs(want - old).max()
        assert numpy.abs(new - want).max() < 1e-5 + 1e-3 * change, path
        moved += change > 0
    # everything but the four (zero) selection biases took a step
    assert moved == len(flat(tree)) - 4


@pytest.mark.parametrize("fault,planted", [
    ("window_dropped", {"window": "none"}),
    ("window_25", {"window": 25}),
    ("plain_rope", {"plain_rope": True}),
    ("whole_head", {"whole_head": True}),
    ("group_of_2_for_3", {"group": 2})])
def test_a_planted_fault_moves_the_reference(fault, planted, tiny_lm):
    """The seam ``chip_grads_laguna.py`` plants its faults through:
    each changes the loss, and empty it is the model again."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    data = wf.loader.original_data.mem
    labels = wf.loader.original_labels.mem
    batch = (data[:1].copy(), labels[:1].copy())
    tree = ref.from_program(exported(wf), MODEL)
    sound = ref.loss(tree, batch, MODEL)
    ref.experiment = dict(planted)
    try:
        faulty = ref.loss(tree, batch, MODEL)
    finally:
        ref.experiment = {}
    assert abs(faulty - sound) > 1e-6
    assert ref.loss(tree, batch, MODEL) == sound


def test_counters_and_gauges_ride_the_metric_fetch(tiny_lm):
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    wf.run()
    registry = telemetry.get_registry()
    units = [u for u in wf.forwards if isinstance(u, GQAttention)]
    sliding = [u.name for u in units if u.window is not None]
    assert [u.heads for u in units] == [4, 6, 6, 6, 4]
    assert len(sliding) == 3
    band = 24 * 25 // 2 + (S - 24) * 24
    for layer in sliding:
        assert registry.counter_total("veles_window_steps_total",
                                      layer=layer) == 1
        assert registry.counter_total("veles_window_pairs_total",
                                      layer=layer) == 6 * band
        # on the CPU the scan masks and skips nothing: the whole square
        assert registry.counter_total("veles_window_tile_pairs_total",
                                      layer=layer) == 6 * S * S
    text = registry.render_prometheus()
    for unit in units:
        if unit.window is None:
            assert 'veles_window_steps_total{layer="%s"}' % unit.name \
                not in text
    gates = [float(line.split()[-1]) for line in text.splitlines()
             if line.startswith("veles_attn_gate_mean{")]
    assert len(gates) == 5 and all(0.2 < g < 0.8 for g in gates)
    for unit in wf.forwards:
        if isinstance(unit, ExpertFFN):
            assert registry.counter_total(
                "veles_moe_pairs_total", layer=unit.name) == S * 3
    assert registry.counter_total("veles_moe_dropped_pairs_total") == 0


def test_kernels_count_the_tiles_their_bounds_visit(tiny_lm):
    """The kernels forced (interpreted) at tile 16: the counter holds
    the tiles the loop bounds visit, and the step trains to the scan's
    loss."""
    with telemetry.scoped():
        wf = tiny_lm.create_workflow()
        wf.initialize(device="cpu")
        wf.run()
    want = wf.decision.history[0]
    prng.seed_all(5)
    root.lm.model.update({"attn_impl": "pallas", "pallas_tile": 16})
    try:
        wf = tiny_lm.create_workflow()
        wf.initialize(device="cpu")
        wf.run()
    finally:
        root.lm.model.update({"attn_impl": None, "pallas_tile": None})
    registry = telemetry.get_registry()
    visited = PA.visited_pairs(S, 16, 16, 24)
    assert visited == (1 + 2 + 3 + 3) * 16 * 16
    for unit in wf.forwards:
        if isinstance(unit, GQAttention) and unit.window is not None:
            assert registry.counter_total(
                "veles_window_tile_pairs_total", layer=unit.name) \
                == 6 * visited
    got = wf.decision.history[0]
    for phase in ("validation", "train"):
        assert abs(got[phase]["loss"] - want[phase]["loss"]) < 1e-5


def test_step_program_names_the_window_inside_the_core(tiny_lm):
    """``veles.window`` inside ``veles.core`` in both directions of the
    sliding layers and in no other unit: ``reduce/scopes.py`` finds
    ``veles.core`` first and keeps reading all of the attention proper."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    text = fn.lower(*args).as_text(debug_info=True)
    units = [u for u in wf.forwards if isinstance(u, GQAttention)]
    for unit in units:
        for path in ("veles.fwd.GQAttention." + unit.name,
                     "veles.bwd.GDGQAttention.GD" + unit.name):
            pattern = re.escape(path) + r"/veles\.core/veles\.window[/\"]"
            assert bool(re.search(pattern, text)) \
                == (unit.window is not None), path
            assert path + "/veles.core" in text
    assert not re.search(r"veles\.window[^\"]*veles\.core", text)
    assert not re.search(r"ExpertFFN[^\"]*veles\.window", text)


# -- the builder ---------------------------------------------------------------


def test_builder_gives_each_operator_its_own_shapes(tiny_lm):
    units = [layer["->"] for layer in tiny_lm.build_layers()
             if layer["type"] == "gqa_attention"]
    assert [u["heads"] for u in units] == [4, 6, 6, 6, 4]
    assert [u.get("window") for u in units] == [None, 24, 24, 24, None]
    assert [u["rope_theta"] for u in units] \
        == [5e5, 1e4, 1e4, 1e4, 5e5]
    assert [u.get("rotary_dim") for u in units] == [8, None, None, None, 8]
    assert units[0]["rope_scaling"] == YARN \
        and "rope_scaling" not in units[1]
    assert all(u["gate"] == "head" and u["qk_norm"] is False
               and u["kv_heads"] == 2 and u["eps"] == 1e-6
               for u in units)
    kinds = [layer["type"] for layer in tiny_lm.build_layers()]
    assert kinds[1:5] == ["gqa_attention", "swiglu_ffn", "gqa_attention",
                          "expert_ffn"]


def test_an_operator_of_the_table_keeps_its_switches(tiny_lm):
    """The model's own keywords go OVER ``ATTENTION_OPERATORS``' entry
    of the same name: a gated NoPE layer with its own head count."""
    root.lm.model.update({
        "layers": ["gated_nope_attention"], "dense_layers": 0,
        "heads": 4, "operators": {"gated_nope_attention": {"heads": 2}}})
    unit = tiny_lm.build_layers()[1]["->"]
    assert (unit["heads"], unit["rope"], unit["gate"], unit["qk_norm"]) \
        == (2, False, True, False)


def test_an_unknown_operator_is_refused_with_the_models_own_named(
        tiny_lm):
    root.lm.model.update({"layers": ["sliding_attention", "windowed"]})
    with pytest.raises(ValueError, match="has the operators 'conv', "
                       "'delta_attention', 'eva_attention', "
                       "'full_attention', 'plain_attention', "
                       "'gated_nope_attention', 'sliding_attention', "
                       "got"):
        tiny_lm.build_layers()


def test_without_its_operators_the_pattern_is_refused(tiny_lm):
    """What the parent does with the new cell's command: the operator
    is no name it knows."""
    del root.lm.model.operators
    with pytest.raises(ValueError, match="has the operators 'conv', "
                       "'delta_attention', 'eva_attention', "
                       "'full_attention', 'plain_attention', "
                       "'gated_nope_attention', got"):
        tiny_lm.build_layers()
    root.lm.model.operators = {}


@pytest.mark.parametrize("keywords,message", [
    ({"rotary_dim": 7}, "rotary_dim is even"),
    ({"rotary_dim": 0}, "rotary_dim is even"),
    ({"rotary_dim": 32, "head_dim": 16}, "at most the head's 16"),
    ({"window": 0}, "a window holds the query itself"),
    ({"window": -3}, "a window holds the query itself"),
    ({"gate": "per_head"}, "gate is False, 'elementwise'"),
    ({"gate": 2}, "gate is False, 'elementwise'"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 2}},
     "rope_type 'yarn' alone"),
])
def test_gqattention_refuses_at_construction_by_name(keywords, message):
    from veles.workflow import Workflow
    wf = Workflow(None, name="wf")
    with pytest.raises(ValueError, match="GQAttention.*" + message):
        GQAttention(wf, heads=4, kv_heads=2, **keywords)


def test_a_rotary_dim_past_the_derived_head_is_refused_at_initialize():
    with pytest.raises(ValueError, match="rotary_dim 32 exceeds the "
                       "head's 16"):
        build(GQAttention, heads=4, kv_heads=2, rotary_dim=32)


@pytest.mark.parametrize("gate,width", [
    (False, 0), (True, 4 * 16), ("elementwise", 4 * 16), ("head", 4)])
def test_the_gate_is_the_last_columns_of_the_projection(gate, width):
    _, fwd, _, _, _, _ = build(GQAttention, heads=4, kv_heads=2,
                               gate=gate)
    assert fwd.weights.shape == (D, (4 + 2 * 2) * 16 + width)
    assert B * S * D == fwd.input.size
