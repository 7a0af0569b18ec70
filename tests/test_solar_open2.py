"""The pre-norm block at Solar-Open2's layer pattern — gated delta-rule
linear attention, gated NoPE grouped-query attention, an expert layer
with a shared expert — against its plain float32 reference,
``benchmark/reference/solar_open2.py``: each new piece alone, the whole
LM through ``StandardWorkflow`` (loss, logits, every parameter after
one step), the chunked recurrence against the token-by-token one where
``exp(-cumsum(a))`` overflows, the expert layer's shares adding up to
the uncut layer with the shared expert counted once, the counters and
scopes, and the accepted units' programs left as the parent traced
them."""

import hashlib
import os
import re
import sys

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.accelerated_units import FlowContext
from veles.config import root
from veles.znicz_tpu.ops import delta_attention as delta
from veles.znicz_tpu.ops.delta_attention import DeltaAttention
from veles.znicz_tpu.ops.expert_ffn import ExpertFFN
from veles.znicz_tpu.ops.gqa_attention import GQAttention

from tests.test_conv_stack import xla_backward, xla_forward
from tests.test_lfm2_moe import B, D, S, build, rigged, step_aux
from tests import test_lfm2_moe as lfm2
from tests.test_lfm2_moe import chunk_of_16, poisoned_rows  # noqa: F401

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmark.reference import solar_open2 as ref   # noqa: E402

#: the tiny preset: d 64; 4 query / 2 K/V heads of 16; 4 delta-rule
#: heads of 16, gates of rank 8, 4 taps; 8 experts top-2 of width 32
#: and a shared one of 48; one period; S 64 as four chunks of 16
MODEL = {"dim": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "layers": ["gated_nope_attention"] + ["delta_attention"] * 3,
         "dense_layers": 0, "delta_heads": 4, "delta_head_dim": 16,
         "delta_conv_kernel": 4, "delta_gate_rank": 8,
         "moe_hidden": 32, "moe_shared_hidden": 48, "moe_experts": 8,
         "moe_top_k": 2, "experts_held": [0, 8], "routed_scaling": 1.0,
         "norm_eps": 1e-5, "vocab": 32, "gradient_moment": 0.9}
DELTA = dict(heads=4, head_dim=16, kernel=4, gate_rank=8)
GATED = dict(heads=4, kv_heads=2, rope=False, gate=True, qk_norm=False)
EXPERT = dict(experts=8, top_k=2, hidden=32, shared_hidden=48)


@pytest.fixture(autouse=True)
def chunks_of_16(monkeypatch):
    """S = 64 is four chunks: the state crosses chunk boundaries; and
    4 heads are two groups of 2 run one after the other."""
    monkeypatch.setattr(delta, "CHUNK", 16)
    monkeypatch.setattr(delta, "HEADS_AT_ONCE", 2)


def reference_block(kind, model):
    """x (S, d), params -> the unit's output by the reference."""
    import jax

    def block(p, x):
        with jax.default_matmul_precision("highest"):
            if kind == "expert_ffn":
                return ref.expert_layer(p, x, model)
            return ref.operator(p, x, kind, model, 16)

    return block


def check_unit(cls, kwargs, kind, model=MODEL, tol=2e-5, prepare=None):
    """Output, input gradient and every parameter's gradient of one
    unit against ``jax.grad`` of the reference's function."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(cls, **kwargs)
    params0 = comp.gather_params()
    if prepare:
        prepare(params0[fwd.name])
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


def strong_decay(p):
    """``A = 16`` and ``dt = 1`` on every channel: a chunk's whole
    decay is exp(-16 x 16 x 1.3), far past float32's range."""
    p["a_log"][...] = numpy.log(16.0)
    p["dt_bias"][...] = numpy.log(numpy.expm1(1.0))


def steps_near_2(p):
    """``b = 2 sigmoid(.)`` pushed to 1.9 and beyond: the negative
    eigenvalues ``kda_allow_neg_eigval`` allows."""
    p["weights_beta"][...] *= 0.1
    p["weights_beta"][...] += 3.0 / numpy.sqrt(D) \
        * numpy.sign(p["norm"])[:, None]


UNIT_CASES = {
    "delta_attention": (DeltaAttention, DELTA, "delta_attention", None),
    "delta_strong_decay": (DeltaAttention, DELTA, "delta_attention",
                           strong_decay),
    "delta_one_chunk": (DeltaAttention, dict(DELTA, heads=2, head_dim=8),
                        "delta_attention", None),
    "gated_nope_dense_core": (GQAttention, GATED,
                              "gated_nope_attention", None),
    "gated_nope_scan_core": (GQAttention, dict(GATED, attn_block_size=16),
                             "gated_nope_attention", None),
    "gated_nope_pallas_core": (GQAttention, dict(
        GATED, attn_block_size=16, attn_impl="pallas"),
        "gated_nope_attention", None),
    "experts_with_shared": (ExpertFFN, EXPERT, "expert_ffn", None),
    "experts_share_with_shared": (ExpertFFN, dict(
        EXPERT, experts_held=(2, 6)), "expert_ffn", None),
    # 2 of 24 held: under an eighth, the backward runs the routed part
    # again instead of keeping its buffers
    "experts_thin_share": (ExpertFFN, dict(
        EXPERT, experts=24, top_k=4, experts_held=(3, 5)), "expert_ffn",
        None),
}
PATCHES = {"delta_one_chunk": {"delta_heads": 2, "delta_head_dim": 8},
           "experts_share_with_shared": {"experts_held": [2, 6]},
           "experts_thin_share": {"moe_experts": 24, "moe_top_k": 4,
                                  "experts_held": [3, 5]}}


@pytest.mark.parametrize("case", sorted(UNIT_CASES))
def test_unit_against_reference(case, monkeypatch):
    cls, kwargs, kind, prepare = UNIT_CASES[case]
    if case == "delta_one_chunk":       # and one group of heads
        monkeypatch.setattr(delta, "CHUNK", 64)
        monkeypatch.setattr(delta, "HEADS_AT_ONCE", 16)
    tol = 2e-3 if "pallas" in case else 2e-5    # interpreted kernels
    check_unit(cls, kwargs, kind, dict(MODEL, **PATCHES.get(case, {})),
               tol=tol, prepare=prepare)


# -- the thin share's combine: one-hot products over the real pairs ---------

#: 2 of 24 experts held, 4 a token: ``sparse``, so ``combine`` runs over
#: the chunks that hold a real pair (no shared expert: it adds outside)
THIN = dict(lfm2.EXPERT, experts=24, top_k=4, experts_held=(3, 5))
THIN_MODEL = dict(lfm2.MODEL, moe_experts=24, moe_top_k=4,
                  experts_held=[3, 5])
#: name: (the experts every token is made to select or None for the
#: router's own choice, real pairs or None, CHUNK or None for 48: rows
#: of 16)
THIN_CASES = {
    "no_held_pair_selected": ((6, 7, 8, 9), 0, None),
    "rows_inside_a_chunk": (None, None, None),
    # CHUNK 128: the T = 128 pairs of expert 3 are exactly one chunk
    "one_full_chunk": ((3, 6, 7, 8), B * S, 128),
    "every_held_pair_real": ((3, 4, 6, 7), B * S * 2, None),
}


def combine_forms_agree(rows, tokens, k, d, seed=0):
    """``combine`` by products and by gathers on one buffer whose rows
    past ``rows`` are NaN, against a float64 sum of the real rows: in
    float32 (the cast a no-op) to float32 rounding of a sum of <= k
    rows, in bf16 to the cast's rounding; and against each other."""
    import jax.numpy as jnp
    from veles.znicz_tpu.ops.expert_ffn import pair_moves
    gen = numpy.random.RandomState(seed)
    order = gen.permutation(tokens * k).astype(numpy.int32)
    inv = numpy.argsort(order).astype(numpy.int32)
    raw = gen.standard_normal((tokens * k, d)).astype(numpy.float32)
    raw[rows:] = numpy.nan
    sorting = (jnp.asarray(order), jnp.asarray(inv),
               jnp.asarray(rows, jnp.int32))
    for dtype, tol in ((jnp.float32, 4e-7 * k), (jnp.bfloat16, 2 ** -8)):
        buffer = jnp.asarray(raw, dtype)
        rounded = numpy.asarray(buffer.astype(jnp.float32), numpy.float64)
        want = numpy.where((inv < rows)[:, None], rounded[inv], 0.0) \
            .reshape(tokens, k, d).sum(1)
        bound = tol * numpy.maximum(1.0, numpy.abs(want))
        products, gathers = (numpy.asarray(
            pair_moves(k, sparse)[1](buffer, *sorting).astype(jnp.float32))
            for sparse in (True, False))
        for form in (products, gathers):
            assert (numpy.abs(form - want) <= bound).all()
        assert (numpy.abs(products - gathers) <= bound).all()
    assert rows or not products.any()


@pytest.mark.parametrize("case", sorted(THIN_CASES))
def test_thin_share_combine_reads_the_real_pairs(
        case, poisoned_rows, chunk_of_16, monkeypatch):  # noqa: F811
    """A thin held share at ``rows`` = 0, off a chunk's edge, one full
    chunk and every held pair, the rows past them poisoned: output,
    input gradient and every parameter's gradient against the
    reference; the loop's trips, ``ceil(rows / chunk)``; and
    ``combine``'s product form against its gather form on one buffer."""
    from veles.znicz_tpu.ops import expert_ffn
    chosen, pairs, chunk = THIN_CASES[case]
    if chunk:
        monkeypatch.setattr(expert_ffn, "CHUNK", chunk)
    chunk = chunk or chunk_of_16
    fwd, _ = lfm2.check_unit(ExpertFFN, THIN, THIN_MODEL, chosen=chosen)
    assert fwd.sparse
    feed, fwd, _, x, _, comp = build(ExpertFFN, **THIN)
    _, outs = step_aux(fwd, comp, feed,
                       rigged(fwd, comp.gather_params(), chosen), x)
    rows = int(outs["moe_pairs_" + fwd.name])
    if pairs is None:       # the router's own choice: off a chunk's edge
        assert 0 < rows < B * S * 4 and rows % chunk
    else:
        assert rows == pairs
    assert int(outs["moe_touched_" + fwd.name]) == -(-rows // chunk) * chunk
    combine_forms_agree(rows, B * S, 4, D)


@pytest.mark.parametrize("experts,top_k,held,sparse", [
    (8, 2, (0, 8), False),      # LFM2's layer: every expert held
    (8, 2, (2, 6), False),      # half held: the gathers still
    (24, 4, (3, 5), True),      # 2 of 24: under an eighth
    (320, 8, (0, 8), True),     # Solar-Open2's share, 8 of 320
])
def test_the_held_share_picks_the_combine(experts, top_k, held, sparse,
                                          monkeypatch):
    """``ExpertFFN.sparse`` (under an eighth of the experts held) picks
    the product form, which holds a loop of one-hot products and no
    gather; every other share traces the gathers, ``k`` of them and no
    loop."""
    import jax
    import jax.numpy as jnp
    from veles.znicz_tpu.ops import expert_ffn
    asked = []
    moves = expert_ffn.pair_moves
    monkeypatch.setattr(expert_ffn, "pair_moves",
                        lambda k, thin: asked.append((k, thin))
                        or moves(k, thin))
    feed, fwd, _, x, _, comp = build(
        ExpertFFN, experts=experts, top_k=top_k, hidden=32,
        experts_held=held)
    assert fwd.sparse == sparse
    step_aux(fwd, comp, feed, comp.gather_params(), x)
    assert asked == [(top_k, sparse)]
    n = B * S * top_k
    jaxpr = str(jax.make_jaxpr(moves(top_k, sparse)[1])(
        jnp.zeros((n, D), jnp.bfloat16), jnp.arange(n), jnp.arange(n),
        jnp.int32(n)))
    assert ("while[" in jaxpr, "dot_general[" in jaxpr) == (sparse,) * 2
    assert jaxpr.count("gather[") == (0 if sparse else top_k)


# -- the chunked recurrence against the token-by-token one -------------------

RECURRENCE_CASES = {
    # (A, dt, b): log-decay -A softplus(. + softplus^-1(dt)), step b
    "mild": (1.0, 0.05, 1.0),
    "strong_decay": (16.0, 1.0, 1.0),
    "steps_near_2": (1.0, 0.01, 1.99),
    "no_decay_steps_near_2": (0.01, 0.01, 1.99),
    "strong_decay_steps_near_2": (16.0, 1.0, 1.99),
}


#: (S, CHUNK, BLOCK, heads, dk, how the state pass runs): chunk 16 as
#: one block (the pairwise form over the whole chunk), chunk 16 in
#: blocks of 4, the cell's 64 / 16, and that at the cell's head width
#: with the pass over chunks as the Pallas kernel pair (interpreted)
RECURRENCE_SHAPES = {"one_block": (64, 16, 16, 3, 8, None),
                     "blocks_of_4": (64, 16, 4, 3, 8, None),
                     "cell_64_16": (128, 64, 16, 3, 8, None),
                     "cell_kernels": (128, 64, 16, 2, 128, "interpret")}


def decays(gen, shape, scale, dt):
    """Log-decays ``-A softplus(. + softplus^-1(dt))``."""
    return -scale * numpy.logaddexp(
        0, gen.randn(*shape) * 0.5 + numpy.log(numpy.expm1(dt)))


def unit_rows(t):
    return t / numpy.sqrt((t * t).sum(-1, keepdims=True))


@pytest.mark.parametrize("shape", sorted(RECURRENCE_SHAPES))
@pytest.mark.parametrize("case", sorted(RECURRENCE_CASES))
def test_chunks_against_tokens(case, shape, monkeypatch):
    """``delta_rule`` (chunks cut into blocks: pairwise decays inside a
    block, products of two factors <= 1 below it) against the
    reference's scan over tokens: output, final state and the gradient
    of every input. At ``strong_decay`` the product form
    ``(exp(G) k)(exp(-G) k)^T`` holds exp(+300) inside one chunk."""
    import jax
    import jax.numpy as jnp
    scale, dt, step = RECURRENCE_CASES[case]
    s, chunk, block, h, dk, kernels = RECURRENCE_SHAPES[shape]
    monkeypatch.setattr(delta, "CHUNK", chunk)
    monkeypatch.setattr(delta, "BLOCK", block)
    gen = numpy.random.RandomState(3)
    q = unit_rows(gen.randn(s, h, dk)) * dk ** -0.5
    k, v = unit_rows(gen.randn(s, h, dk)), gen.randn(s, h, dk)
    a = decays(gen, (s, h, dk), scale, dt)
    b = numpy.full((s, h), step)
    args = [jnp.asarray(t, jnp.float32) for t in (q, k, v, a, b)]
    weights = jnp.asarray(gen.randn(s, h, dk), jnp.float32)
    if scale == 16.0:
        assert -float(a.reshape(-1, 16, h, dk).sum(1).min()) > 88.0

    def chunked(*args):
        o, state = delta.delta_rule(*(t[None] for t in args), kernels)
        return (o[0] * weights).sum() + state.sum(), (o[0], state[0])

    def by_token(*args):
        o, state = ref.delta_recurrence(*args)
        return (o * weights).sum() + state.sum(), (o, state)

    with jax.default_matmul_precision("highest"):
        (_, got), g_got = jax.value_and_grad(
            chunked, argnums=range(5), has_aux=True)(*args)
        (_, want), g_want = jax.value_and_grad(
            by_token, argnums=range(5), has_aux=True)(*args)
    for mine, theirs in zip(got + g_got, want + g_want):
        assert numpy.isfinite(numpy.asarray(mine)).all()
        bound = 2e-5 * max(1.0, float(jnp.abs(theirs).max()))
        assert float(jnp.abs(mine - theirs).max()) < bound


# -- the pass over chunks: the kernel pair against the scan -------------------


def pass_terms(n, heads, c=64, dk=128, dv=128):
    """The six terms of :func:`delta.state_pass` for ``n`` chunks of
    ``c`` tokens, (N, 1, heads, ., .), at the sizes the cell's have: a
    solved system, keys and queries of unit length, decays in (0, 1]."""
    import jax.numpy as jnp
    gen = numpy.random.RandomState(17)
    lead = (n, 1, heads)
    last = decays(gen, lead + (1, dk), 4.0, 0.05)
    terms = (gen.randn(*lead, c, dv), unit_rows(gen.randn(*lead, c, dk)),
             unit_rows(gen.randn(*lead, c, dk)) * dk ** -0.5,
             numpy.tril(gen.randn(*lead, c, c)) * dk ** -0.5,
             unit_rows(gen.randn(*lead, c, dk)), last)
    return [jnp.asarray(t, jnp.float32) for t in terms]


@pytest.mark.parametrize("n,heads", [(3, 2), (1, 1)])
def test_state_pass_kernels_against_scan(n, heads):
    """The Pallas pair (interpreted) against the ``lax.scan`` at the
    cell's widths: the output, the final state, the entry states the
    backward reads, and all six cotangents, with a cotangent on the
    final state too."""
    import jax
    import jax.numpy as jnp
    from veles.znicz_tpu.parallel import pallas_delta
    terms = pass_terms(n, heads)
    gen = numpy.random.RandomState(19)
    w_o = jnp.asarray(gen.randn(n, 1, heads, 64, 128), jnp.float32)
    w_s = jnp.asarray(gen.randn(1, heads, 128, 128), jnp.float32)

    def close(mine, theirs):
        assert mine.shape == theirs.shape
        assert numpy.isfinite(numpy.asarray(mine)).all()
        bound = 2e-5 * max(1.0, float(jnp.abs(theirs).max()))
        assert float(jnp.abs(mine - theirs).max()) < bound

    def total(run):
        def loss(*terms):
            o, state = run(*terms)
            return (o * w_o).sum() + (state * w_s).sum(), (o, state)
        return jax.value_and_grad(loss, argnums=range(6), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, want), g_want = total(delta.scan_pass)(*terms)
        (_, got), g_got = total(
            lambda *t: delta.state_pass(*t, "interpret"))(*terms)
        plain = delta.state_pass(*terms, "interpret")
        entry = [jnp.zeros_like(w_s)] + [
            delta.scan_pass(*(t[:i] for t in terms))[1]
            for i in range(1, n)]
        kept = pallas_delta._forward(
            *(t[:, 0] for t in terms), interpret=True, rows=heads,
            keep=True)[2]
    assert float(jnp.abs(want[1]).max()) > 0.1
    for mine, theirs in zip(got + plain + g_got, want + want + g_want):
        close(mine, theirs)
    # W_k, Q_in and ``last`` meet the ENTRY state, zero before the
    # first chunk
    for i, grad in enumerate(g_want):
        assert (float(jnp.abs(grad).max()) > 0) == (n > 1 or i in (0, 3, 4))
    close(kept.swapaxes(-1, -2), jnp.stack(entry)[:, 0])


@pytest.mark.parametrize("c,dk,dv,on_a_tpu", [
    (64, 128, 128, "mosaic"),   # the cell
    (64, 256, 128, "mosaic"),
    (64, 8, 8, None),           # the tiny presets' heads
    (64, 16, 16, None),
    (64, 128, 64, None),        # half a lane tile
    (37, 128, 128, None),       # a sequence that is one odd chunk
    (16, 128, 128, None),
])
def test_rule_that_picks_the_state_pass(c, dk, dv, on_a_tpu, monkeypatch):
    """The kernels where the step compiles for a TPU and the widths are
    whole lane tiles under chunks of ``CHUNK``; the scan on any other
    shape whatever the platform, and on the CPU whatever the shape."""
    monkeypatch.setattr(delta, "CHUNK", 64)
    assert delta.state_pass_kernels("tpu", c, dk, dv) == on_a_tpu
    assert delta.state_pass_kernels("cpu", c, dk, dv) is None


def test_cpu_step_at_the_cells_widths_holds_no_kernel(monkeypatch):
    """A delta-rule layer of 2 heads of 128 over one chunk of 64,
    forward + backward lowered for the CPU: the scan, no Pallas call in
    the program's text; the same layer with the kernels forced holds
    them (so the text would show one)."""
    monkeypatch.setattr(delta, "CHUNK", 64)
    wide = dict(DELTA, heads=2, head_dim=128)
    text = program_text(DeltaAttention, wide, debug_info=True)
    assert (text.count("pallas"), text.count("tpu_custom_call")) == (0, 0)
    assert text.count("stablehlo.while") > 0
    monkeypatch.setattr(delta, "state_pass_kernels",
                        lambda *shape: "interpret")
    assert program_text(DeltaAttention, wide,
                        debug_info=True).count("pallas_call") > 0


def cell_chunk(mild_channels, c=64, dk=128):
    """q, k, G of one chunk at the cell's widths under ``strong_decay``
    (float64): a block's whole decay is past float32's range. The last
    ``mild_channels`` channels decay mildly instead: scores far below
    the diagonal that are not zero."""
    gen = numpy.random.RandomState(11)
    q = unit_rows(gen.randn(c, dk)) * dk ** -0.5
    k = unit_rows(gen.randn(c, dk))
    a = decays(gen, (c, dk), 16.0, 1.0)
    if mild_channels:
        a[:, -mild_channels:] = decays(gen, (c, mild_channels), 1.0, 0.01)
    g = numpy.cumsum(a, 0)
    assert -g[15, :dk - mild_channels].min() > 88.0
    return q, k, g


@pytest.mark.parametrize("mild_channels", [0, 64])
def test_chunk_scores_at_the_cells_widths_against_float64(mild_channels):
    """C 64, dk 128 in blocks of 16: finite, exactly zero above the
    diagonal, within 1e-6 of the pairwise sum in float64, and the
    gradients of ``q, k, g`` finite."""
    import jax
    import jax.numpy as jnp
    assert (delta.BLOCK, delta.block_of(64)) == (16, 16)
    q, k, g = cell_chunk(mild_channels)
    later = numpy.tril(numpy.ones((64, 64), bool))
    decay = numpy.exp(numpy.where(
        later[:, :, None], g[:, None, :] - g[None, :, :], -numpy.inf))
    want_a = (decay * k[None, :, :] * k[:, None, :]).sum(-1)
    want_b = (decay * k[None, :, :] * q[:, None, :]).sum(-1)
    args = [jnp.asarray(t, jnp.float32) for t in (q, k, g)]
    for got, want in zip(delta.chunk_scores(*args), (want_a, want_b)):
        got = numpy.asarray(got)
        assert numpy.isfinite(got).all()
        assert not numpy.triu(got, 1).any()
        assert numpy.abs(got - want).max() <= 1e-6
        # below the diagonal blocks: the products' part
        assert (numpy.abs(numpy.tril(got, -16)).max() > 1e-3) \
            == bool(mild_channels)

    def total(*args):
        a, b = delta.chunk_scores(*args)
        return (a * a).sum() + (b * numpy.arange(64.0)).sum()

    for grad in jax.grad(total, argnums=(0, 1, 2))(*args):
        assert numpy.isfinite(numpy.asarray(grad)).all()
        assert numpy.abs(numpy.asarray(grad)).max() > 0


def intermediate_sizes(jaxpr):
    """The element count of every value a jaxpr makes, inner jaxprs
    included."""
    import jax
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield int(numpy.prod(var.aval.shape, dtype=numpy.int64))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from intermediate_sizes(inner)


@pytest.mark.parametrize("c,block", [
    (64, 16),       # the cell's chunk: four blocks
    (72, 72),       # 16 does not divide it: one block
    (16, 16),       # a chunk of one block
])
def test_pairwise_tensor_is_a_diagonal_block(c, block):
    """The mechanism itself: where the chunk is cut into blocks no
    value of ``chunk_scores`` has ``C x C x dk`` elements — the largest
    is one block's pairwise tensor, and there is one a block — and
    where it falls back to one block the old tensor is there."""
    import jax
    import jax.numpy as jnp
    assert delta.BLOCK == 16 and delta.block_of(c) == block

    def sizes(c):
        spec = jax.ShapeDtypeStruct((2, c, 128), jnp.float32)
        out = jax.eval_shape(delta.chunk_scores, spec, spec, spec)
        assert [t.shape for t in out] == [(2, c, c)] * 2
        closed = jax.make_jaxpr(delta.chunk_scores)(spec, spec, spec)
        return list(intermediate_sizes(closed.jaxpr))

    pairwise = 2 * block * block * 128
    made = sizes(c)
    assert max(made) == pairwise
    # as many such tensors a block as a chunk of one block makes
    assert made.count(pairwise) \
        == c // block * sizes(block).count(pairwise)


# -- the chunks' triangular system: products against triangular_solve ------

#: ``product_solve``'s error against float64 over ``triangular_solve``'s
#: own, for the solution and both cotangents, at most (read 0.17-2.35
#: on the cases below: aligned keys are the worst, where the block
#: products cancel to the alternating inverse of ``I + 1.99 N``)
SOLVE_FACTOR = 4.0
#: ... over an error no smaller than this (float32's epsilon), so a
#: solve that is exact by chance does not set the bar at zero
SOLVE_FLOOR = 2.0 ** -23

SOLVE_CASES = dict(
    {case: RECURRENCE_CASES[case] + (64, False)
     for case in RECURRENCE_CASES},
    # every k_t the same unit vector and no decay: |L_ts| = b = 1.99
    aligned_keys=(0.0, 0.01, 1.99, 64, True),
    # 16 does not divide 72: one block, a sequence that is one chunk
    chunk_72=(1.0, 0.05, 1.0, 72, False))


def chunk_system(case, n=512, dk=128, dv=128):
    """``n`` chunks' systems as ``chunked_delta_rule`` makes them, in
    float64: ``L = diag(b) A`` (diagonal and all), ``R = diag(b) [V |
    exp(G) K]``; and a cotangent for the solution."""
    scale, dt, step, c, aligned = SOLVE_CASES[case]
    gen = numpy.random.RandomState(23)
    k = unit_rows(gen.randn(n, 1 if aligned else c, dk))
    k = numpy.broadcast_to(k, (n, c, dk))
    a = decays(gen, (n, c, dk), scale, dt) if scale else numpy.zeros(
        (n, c, dk))
    g = numpy.cumsum(a, 1)
    l = numpy.zeros((n, c, c))
    for t in range(c):      # decays masked before the exponential
        l[:, t, :t + 1] = (k[:, t, None] * k[:, :t + 1] * numpy.exp(
            g[:, t, None] - g[:, :t + 1])).sum(-1)
    l *= step
    r = step * numpy.concatenate(
        [gen.randn(n, c, dv), numpy.exp(g) * k], -1)
    return l, r, gen.randn(n, c, dv + dk)


def substitute(l, r):
    """``(I + strict_lower(l))^-1 r`` in float64, row by row: numpy's
    elementwise loops and no BLAS or LAPACK, whose threads stall when
    several test processes share the cores."""
    w = numpy.zeros_like(r)
    for t in range(r.shape[-2]):
        w[:, t] = r[:, t] - (l[:, t, :t, None] * w[:, :t]).sum(-2)
    return w


@pytest.mark.parametrize("case", sorted(SOLVE_CASES))
def test_product_solve_against_triangular_solve_and_float64(case):
    """At the cell's widths (512 chunks' systems of 64 in blocks of 16,
    right-hand sides 256 wide): the solution and both cotangents of
    ``product_solve`` and of ``triangular_solve`` against float64 on
    the same float32 inputs; the products' error is within
    ``SOLVE_FACTOR`` of the solve's; in chunks of 64, and in one chunk
    of 72 that no block divides."""
    import jax
    import jax.numpy as jnp
    l, r, dw = (numpy.asarray(t, numpy.float32).astype(numpy.float64)
                for t in chunk_system(case))
    w = substitute(l, r)
    dr = substitute(l.swapaxes(-1, -2)[..., ::-1, ::-1],
                    dw[..., ::-1, :])[..., ::-1, :]
    want = (w, dr, -numpy.tril(numpy.einsum("nik,njk->nij", dr, w), -1))
    if case == "aligned_keys":
        assert numpy.abs(numpy.tril(l, -1)).max() > 1.98

    def solve_by(solver):
        def total(l, r):
            w = solver(l, r)
            return (w * jnp.asarray(dw, jnp.float32)).sum(), w
        (_, w), (dl, dr) = jax.jit(jax.value_and_grad(
            total, argnums=(0, 1), has_aux=True))(
            *(jnp.asarray(t, jnp.float32) for t in (l, r)))
        return [float(numpy.abs(numpy.asarray(got, numpy.float64)
                                - theirs).max() / numpy.abs(theirs).max())
                for got, theirs in zip((w, dr, dl), want)]

    mine = solve_by(delta.product_solve)
    theirs = solve_by(lambda l, r: jax.lax.linalg.triangular_solve(
        l, r, left_side=True, lower=True, unit_diagonal=True))
    for got, bar in zip(mine, theirs):
        assert got <= SOLVE_FACTOR * max(bar, SOLVE_FLOOR), (mine, theirs)


@pytest.mark.parametrize("block", [
    16,     # the cell's chunk of 64 in blocks of 16
    64,     # the chunk is one block
])
def test_cpu_layer_at_the_cells_widths_holds_no_solve(block, monkeypatch):
    """A delta-rule layer of 2 heads of 128 over one chunk of 64,
    forward + backward lowered for the CPU: no ``triangular_solve`` in
    the program (the op's name, or the LAPACK call it lowers to on the
    CPU), whether blocks cut the chunk or not."""
    monkeypatch.setattr(delta, "CHUNK", 64)
    monkeypatch.setattr(delta, "BLOCK", block)
    text = program_text(DeltaAttention, dict(DELTA, heads=2, head_dim=128),
                        debug_info=True)
    assert not re.search(r"custom_call @lapack_\w*trsm", text)
    assert "triangular_solve" not in text


# -- the share sums to the model ---------------------------------------------


def test_five_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """40 experts as five shares ``experts_held [8i, 8i + 8)``: the
    shares' routed parts, with the shared expert counted once, add up
    to what the reference gives for the layer that holds all 40."""
    import jax
    wide = dict(EXPERT, experts=40, top_k=8)
    model = dict(MODEL, moe_experts=40, moe_top_k=8, experts_held=[0, 40])
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

    routed = [part(lo, lo + 8, False) for lo in range(0, 40, 8)]
    assert all(numpy.abs(p).max() > 1e-3 for p in routed)
    with_shared = part(0, 8, True)
    shared = with_shared - routed[0]
    assert numpy.abs(shared).max() > 1e-3
    assert numpy.abs(sum(routed) + shared - whole).max() < 3e-5
    # every chip adding its own copy of the shared expert is NOT the
    # layer
    assert numpy.abs(sum(routed) + 5 * shared - whole).max() > 1e-3


# -- the whole LM ------------------------------------------------------------


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
        block="pre_norm", attn_block=16, moe_scaling=1.0))
    root.lm.train.update({"learning_rate": 0.5, "gradient_moment": 0.9})
    root.lm.decision.update({"max_epochs": 1})
    prng.seed_all(5)
    try:
        yield T
    finally:
        for k, v in saved.items():
            getattr(root.lm, k).update(v)


def exported(wf):
    return [(type(u).MAPPING, u.export_params()) for u in wf.forwards]


def test_lm_loss_logits_and_one_step_against_reference(tiny_lm):
    """The program trained through StandardWorkflow / xla_step for one
    step: first validation loss, train loss, the logits, and every
    parameter after the step, against the reference's walk over the
    sub-layers + momentum SGD; and that walk against ``jax.grad`` of
    the reference's whole loss."""
    import jax
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    loader = wf.loader
    data, labels = loader.original_data.mem, loader.original_labels.mem
    valid = (data[:1].copy(), labels[:1].copy())
    train = (data[1:2].copy(), labels[1:2].copy())
    tree = ref.from_program(exported(wf), MODEL)
    assert ref.count_parameters(MODEL) == sum(
        a.size for a in jax.tree_util.tree_leaves(tree)) - 4 * 8
    comp = wf.xla_step.compiler

    def logits(p, tokens):
        ctx = FlowContext(comp, dict(p), {}, {}, jax.random.PRNGKey(0),
                          False)
        ctx.set(wf.loader, "minibatch_data", tokens)
        for unit in wf.forwards:
            unit.xla_run(ctx)
        return ctx.get(wf.forwards[-1], "output")

    got = numpy.asarray(jax.jit(logits)(comp.gather_params(), valid[0]))

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
    assert numpy.isfinite(got).all() and got.shape == (1, S, 32)
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


def test_counters_and_gauges_ride_the_metric_fetch(tiny_lm):
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    wf.run()
    registry = telemetry.get_registry()
    layers = [u.name for u in wf.forwards
              if isinstance(u, DeltaAttention)]
    assert len(layers) == 3
    for layer in layers:
        assert registry.counter_total("veles_delta_steps_total",
                                      layer=layer) == 1
        assert registry.counter_total("veles_delta_tokens_total",
                                      layer=layer) == S
        # S = 64 in chunks of 16, which are one block of 16 each
        assert registry.counter_total(
            "veles_delta_pairwise_pairs_total", layer=layer) == S * 16
        # on the CPU the pass over chunks is the scan
        assert registry.counter_total(
            "veles_delta_kernel_chunks_total", layer=layer) == 0
    text = registry.render_prometheus()
    assert 'veles_delta_kernel_chunks_total{layer="%s"} 0' % layers[0] \
        in text

    def gauges(name):
        return [float(line.split()[-1]) for line in text.splitlines()
                if line.startswith(name + "{")]

    assert all(0.0 < g < 1.0 for g in gauges("veles_delta_decay_mean"))
    assert all(0.5 < g < 1.5 for g in gauges("veles_delta_beta_mean"))
    rms = gauges("veles_delta_state_rms")
    assert len(rms) == 3 and all(0.0 < g < 10.0 for g in rms)
    # the expert counters keep their names: 8 of 8 held, every pair
    for unit in wf.forwards:
        if isinstance(unit, ExpertFFN):
            assert registry.counter_total(
                "veles_moe_pairs_total", layer=unit.name) == S * 2
    assert registry.counter_total("veles_moe_dropped_pairs_total") == 0


@pytest.mark.parametrize("experts,top_k,held", [
    (8, 2, [2, 6]),         # half held: the gathers read every row
    (24, 4, [3, 5]),        # 2 of 24, sparse: the real pairs' chunks
])
def test_combine_rows_are_counted_by_the_form_that_ran(
        tiny_lm, chunk_of_16, experts, top_k, held):  # noqa: F811
    """After one step ``veles_moe_combine_rows_total`` reads the row
    stages' count where the share is sparse, and ``T x k`` elsewhere
    (where the row stages touched fewer)."""
    root.lm.model.update({"moe_experts": experts, "moe_top_k": top_k,
                          "experts_held": held})
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    wf.run()
    registry = telemetry.get_registry()
    units = [u for u in wf.forwards if isinstance(u, ExpertFFN)]
    assert len(units) == 4
    for unit in units:
        combined, touched = (registry.counter_total(
            name, layer=unit.name) for name in (
            "veles_moe_combine_rows_total", "veles_moe_rows_touched_total"))
        assert 0 < touched < S * top_k and touched % chunk_of_16 == 0
        assert combined == (touched if unit.sparse else S * top_k)
    assert [u.sparse for u in units] == [experts > 8] * 4


def test_kernel_chunks_are_counted_where_the_kernels_run(tiny_lm,
                                                         monkeypatch):
    """The kernels forced (interpreted) in the whole LM: every chunk of
    the step is counted, and the step trains to the scan's loss."""
    with telemetry.scoped():
        wf = tiny_lm.create_workflow()
        wf.initialize(device="cpu")
        wf.run()
    want = wf.decision.history[0]
    prng.seed_all(5)
    monkeypatch.setattr(delta, "state_pass_kernels",
                        lambda *shape: "interpret")
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    wf.run()
    registry = telemetry.get_registry()
    layers = [u.name for u in wf.forwards
              if isinstance(u, DeltaAttention)]
    assert len(layers) == 3
    for layer in layers:
        assert registry.counter_total("veles_delta_tokens_total",
                                      layer=layer) == S
        assert registry.counter_total(
            "veles_delta_kernel_chunks_total", layer=layer) == S // 16
    got = wf.decision.history[0]
    for phase in ("validation", "train"):
        assert abs(got[phase]["loss"] - want[phase]["loss"]) < 1e-5


def test_tiny_lm_trains_to_the_triangular_solves_loss(tiny_lm,
                                                     monkeypatch):
    """The tiny LM's step with the chunks' systems by products (chunks
    of 16 in blocks of 4) trains to the losses of the same step with
    ``triangular_solve`` in their place, to 1e-5."""
    import jax

    def run():
        prng.seed_all(5)
        wf = tiny_lm.create_workflow()
        wf.initialize(device="cpu")
        wf.run()
        return wf.decision.history[0]

    monkeypatch.setattr(delta, "BLOCK", 4)
    with telemetry.scoped():
        got = run()
        monkeypatch.setattr(delta, "product_solve", lambda l, r: (
            jax.lax.linalg.triangular_solve(
                l, r, left_side=True, lower=True, unit_diagonal=True)))
        want = run()
    for phase in ("validation", "train"):
        assert abs(got[phase]["loss"] - want[phase]["loss"]) < 1e-5


def test_step_program_names_the_new_scopes(tiny_lm):
    """``veles.delta`` inside both delta-rule units and nowhere else,
    ``veles.shared`` inside the expert units beside ``veles.experts``
    and ``veles.route``, ``veles.core`` in the softmax attention
    alone."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    text = fn.lower(*args).as_text(debug_info=True)
    for unit in (r"veles\.fwd\.DeltaAttention\.DeltaAttention",
                 r"veles\.bwd\.GDDeltaAttention\.GDDeltaAttention"):
        assert re.search(unit + r'[^"]*?[/(]veles\.delta[/)]', text), unit
        assert not re.search(unit + r'[^"/]*/[^"]*veles\.core', text)
    for unit in (r"veles\.fwd\.ExpertFFN\.ExpertFFN",
                 r"veles\.bwd\.GDExpertFFN\.GDExpertFFN"):
        found = set(re.findall(
            unit + r'[^"/]*/[^"]*?[/(]veles\.(experts|route|shared)[/)]',
            text))
        assert found == {"experts", "route", "shared"}, unit
    assert "veles.fwd.GQAttention.GQAttention/veles.core" in text
    assert not re.search(r"GQAttention[^\"]*veles\.delta", text)
    # the shared expert lies outside the routed scopes
    assert not re.search(r"veles\.(experts|route)[^\"]*veles\.shared",
                         text)


def test_numpy_device_refuses_the_new_units(tiny_lm):
    wf = tiny_lm.create_workflow()
    with pytest.raises(NotImplementedError, match="no numpy oracle"):
        wf.initialize(device="numpy")
        wf.run()


@pytest.mark.parametrize("patch,message", [
    # (the id the case had before the operator list gained
    # 'eva_attention')
    pytest.param(
        {"layers": ["delta_attention", "window"]},
        "has the operators 'conv', 'delta_attention', 'eva_attention', "
        "'full_attention', 'plain_attention', 'gated_nope_attention', got",
        id="patch0-has the operators 'conv', 'delta_attention', "
        "'full_attention', 'plain_attention', 'gated_nope_attention', got"),
    ({"layers": ["delta_attention"], "dense_layers": 1, "ut_steps": 2,
      "ffn_hidden": 32}, "no delta_attention"),
    ({"layers": ["delta_attention"], "dense_layers": 1,
      "norm": "sandwich", "ffn_hidden": 32}, "norm='sandwich'"),
])
def test_build_layers_refuses(tiny_lm, patch, message):
    root.lm.model.update(patch)
    with pytest.raises(ValueError, match=message):
        tiny_lm.build_layers()


def test_builder_hands_every_stated_constant_to_the_new_units(tiny_lm):
    root.lm.model.update({"delta_conv_kernel": 3, "norm_eps": 1e-4})
    by_type = {}
    for layer in tiny_lm.build_layers():
        by_type.setdefault(layer["type"], layer["->"])
    assert by_type["delta_attention"] == {
        "heads": 4, "head_dim": 16, "kernel": 3, "gate_rank": 8,
        "eps": 1e-4}
    gated = by_type["gqa_attention"]
    assert (gated["rope"], gated["gate"], gated["qk_norm"]) \
        == (False, True, False)
    assert by_type["expert_ffn"]["shared_hidden"] == 48


# -- the accepted units trace the programs the parent traced -----------------

#: sha256 of the StableHLO text of one unit pair's forward + backward +
#: update on the fixed inputs of ``build``, traced by the PARENT of PR 34
#: (commit 0497976) in this container's jax: the same program gives the
#: same bits on any backend. A PR that changes what these units compute
#: by default changes these on purpose.
PARENT_PROGRAMS = {
    "gqa_attention": "7e75d6438b243c03560289867b6b2a064bcbdf762742c54f04084430e48e9b39",
    "gqa_plain_sandwich": "2c2dffde543f1542746c4d5467470cd54aa406b5faba87d0ed59bfe68ba81bcd",
    "expert_ffn": "66579489f03160d6833a59a68d41d1076aa2b01fc83cb4de6546a44880cb0e53",
}
ACCEPTED = {
    "gqa_attention": (GQAttention, dict(heads=4, kv_heads=2),
                      dict(gate=False, rope=True)),
    "gqa_plain_sandwich": (GQAttention, dict(
        heads=4, kv_heads=4, qk_norm=False, sandwich=True),
        dict(gate=False, rope=True)),
    "expert_ffn": (ExpertFFN, dict(experts=8, top_k=2, hidden=32,
                                   bias_stddev=0.05),
                   dict(shared_hidden=0)),
}


def program_text(cls, kwargs, debug_info=False):
    import jax
    feed, fwd, gd, x, err, comp = build(cls, **kwargs)

    def fn(p, s, xv, ev):
        ctx = FlowContext(comp, dict(p), dict(s),
                          {gd.name: gd.hyperparams()},
                          jax.random.PRNGKey(7), True)
        ctx.set(feed, "minibatch_data", xv)
        fwd.xla_run(ctx)
        ctx.set(gd, "err_output", ev)
        gd.xla_run(ctx)
        return (ctx.get(fwd, "output"),
                ctx.values.get((gd.name, "err_input")), ctx.params)

    return jax.jit(fn).lower(comp.gather_params(), comp.gather_state(),
                             x, err).as_text(debug_info=debug_info)


@pytest.mark.parametrize("case", sorted(ACCEPTED))
def test_defaults_leave_the_accepted_units_as_the_parent_traced_them(
        case):
    """``gate=False, rope=True`` and ``shared_hidden=0``, said aloud or
    left to the defaults, trace the program the parent traced: the
    outputs are bit-equal on every input."""
    cls, kwargs, said = ACCEPTED[case]
    default = program_text(cls, kwargs)
    assert program_text(cls, dict(kwargs, **said)) == default
    assert hashlib.sha256(default.encode()).hexdigest() \
        == PARENT_PROGRAMS[case]
