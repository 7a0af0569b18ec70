"""EVA attention (``ops/eva_attention.py``, operator ``eva_attention`` of
``root.lm.model.layers``) and what carries it: the prefix kernels of
``parallel/pallas_attention.py`` (interpreted) against a dense masked
softmax, the unit against its plain float32 reference
(``benchmark/reference/evabyte.py``), the two identities (a chunk of 1,
a window past S: exact causal softmax), the whole LM with its eight
prediction heads and float32 residual stream through
``StandardWorkflow`` (loss, every parameter's gradient and one step),
the counters by hand, the scopes and the refusals."""

import os
import re
import sys

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.accelerated_units import StepCompiler
from veles.backends import XLADevice
from veles.config import root
from veles.memory import Array
from veles.workflow import Workflow
from veles.znicz_tpu.nn_units import gradient_unit_for
from veles.znicz_tpu.ops.attention import dense_attention_core_fwd
from veles.znicz_tpu.ops.eva_attention import EvaAttention, summarize
from veles.znicz_tpu.ops.evaluator import EvaluatorHeadsLM, EvaluatorLM
from veles.znicz_tpu.parallel import flash
from veles.znicz_tpu.parallel import pallas_attention as PA

from tests.test_conv_stack import FeedUnit, xla_backward, xla_forward

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmark.reference import evabyte as ref   # noqa: E402

#: the tiny preset: d 64, 4 heads of 16, window 32 and chunks of 4 over
#: S 128 (4 windows, 32 summaries, prefixes of 0 to 24), a SwiGLU of
#: 96, 3 prediction heads over 32 ids, two layers
MODEL = {"dim": 64, "heads": 4, "kv_heads": 4, "head_dim": 16,
         "layers": ["eva_attention"] * 2, "dense_layers": 2,
         "ffn_hidden": 96, "eva_window": 32, "eva_chunk": 4,
         "rope_theta": 1e5, "norm_eps": 1e-5, "vocab": 32,
         "pred_heads": 3, "residual_dtype": "float32",
         "logits_dtype": "float32", "gradient_moment": 0.9}
B, S, D = 2, 128, 64
#: tiles of the tiny kernels: a window's queries in two, the summaries
#: of a window (8) in two
UNIT = dict(heads=4, head_dim=16, rope_theta=1e5, eps=1e-5,
            local_window=32, chunk=4, attn_block_size=16,
            attn_impl="pallas", pallas_tile=16)


def build(**kwargs):
    """An EVA unit pair on a (B, S, D) input, lr 1 and no momentum (so a
    step moves a parameter by minus its gradient)."""
    prng.seed_all(31)
    wf = Workflow(None, name="wf")
    gen = prng.get("eva")
    x = gen.normal(0, 1.0, (B, S, D)).astype(numpy.float32)
    feed = FeedUnit(wf, x)
    fwd = EvaAttention(wf, **kwargs)
    fwd.link_attrs(feed, ("input", "minibatch_data"))
    fwd.initialize(device=None)
    fwd.norm.mem[...] = gen.normal(1.0, 0.2, fwd.norm.shape)
    err = gen.normal(0, 1.0, (B, S, D)).astype(numpy.float32)
    gd = gradient_unit_for(EvaAttention)(wf, learning_rate=1.0)
    gd.setup_forward(fwd)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    comp = StepCompiler([fwd, gd], XLADevice(platform="cpu"))
    return feed, fwd, gd, x, err, comp


def reference_unit(model):
    import jax

    def block(p, x):
        with jax.default_matmul_precision("highest"):
            return ref.operator(p, x, model)

    return block


def check_unit(model, tol=2e-5, still=(), **kwargs):
    """Output, input gradient and every parameter's gradient of one unit
    against ``jax.grad`` of the reference's layer -> (unit, output).
    ``still``: the parameters no gradient reaches by construction."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(**kwargs)
    params0 = comp.gather_params()
    state0 = comp.gather_state()
    y = numpy.asarray(xla_forward(comp, feed, fwd, params0, x))
    dx, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                               x, err)
    block = reference_unit(model)
    p = {k: jnp.asarray(v) for k, v in params0[fwd.name].items()}

    def total(p, x):
        out = jax.vmap(lambda row: block(p, row))(x)
        return (out * err).sum(), out

    (_, want), (gp, gx) = jax.value_and_grad(
        total, argnums=(0, 1), has_aux=True)(p, jnp.asarray(x))
    scale = numpy.abs(numpy.asarray(want)).max()
    assert numpy.abs(y - numpy.asarray(want)).max() < tol * scale
    gx = numpy.asarray(gx)
    assert numpy.abs(numpy.asarray(dx) - gx).max() \
        < tol * numpy.abs(gx).max()
    for name, g in gp.items():
        g = numpy.asarray(g)
        moved = numpy.asarray(params0[fwd.name][name]) \
            - numpy.asarray(params1[fwd.name][name])
        if name in still:
            assert not g.any() and not moved.any(), name
            continue
        assert numpy.abs(g).max() > 0, name
        assert numpy.abs(moved - g).max() < tol * numpy.abs(g).max(), name
    return fwd, y


# -- the prefix kernels ---------------------------------------------------------


def dense_prefix(q, k, v, span_q, span_k):
    """Softmax over the first ``(t // span_q) span_k`` keys of query t,
    dense, -> (out, lse); a query that sees none: out 0, lse -inf."""
    import jax.numpy as jnp
    s, sk = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / numpy.sqrt(q.shape[-1])
    seen = jnp.arange(sk)[None, :] \
        < (jnp.arange(s) // span_q * span_k)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    top = scores.max(-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    e = jnp.exp(scores - top)
    total = e.sum(-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd",
                     e / jnp.where(total > 0, total, 1.0), v)
    return out, (top + jnp.log(total))[..., 0]


@pytest.mark.parametrize("block_q,block_k", [(8, 2), (16, 4), (32, 8)])
def test_prefix_kernels_against_the_dense_masked_softmax(block_q, block_k):
    """Forward (out, lse, and the -inf of the first span) and the three
    gradients of the remote pair, interpreted, at S 128, spans (32, 8)."""
    import jax
    q, do = (jax.random.normal(key, (1, 2, S, 16)) for key in
             jax.random.split(jax.random.PRNGKey(1), 2))
    k, v = (jax.random.normal(key, (1, 2, S // 4, 16)) for key in
            jax.random.split(jax.random.PRNGKey(2), 2))
    keys = dict(causal=False, block_q=block_q, block_k=block_k,
                interpret=True, prefix=(32, 8))
    with jax.default_matmul_precision("highest"):
        out, lse = PA.flash_attention_fwd(q, k, v, **keys)
        want, want_lse = dense_prefix(q, k, v, 32, 8)
        grads = PA.flash_attention_bwd(q, k, v, out, lse, do, **keys)
        wanted = jax.vjp(lambda *a: dense_prefix(*a, 32, 8)[0],
                         q, k, v)[1](do)
    lse, want_lse = numpy.asarray(lse), numpy.asarray(want_lse)
    first = numpy.isneginf(want_lse)
    assert first.sum() == 2 * 32                # the first window's rows
    assert (numpy.isneginf(lse) == first).all()
    assert numpy.abs(lse[~first] - want_lse[~first]).max() < 1e-5
    assert numpy.abs(numpy.asarray(out) - want).max() < 1e-5
    assert not numpy.asarray(out)[:, :, :32].any()
    for got, wanted_grad in zip(grads, wanted):
        assert numpy.abs(numpy.asarray(got) - wanted_grad).max() < 2e-5


def test_a_prefix_row_is_checked_against_its_spans():
    import jax.numpy as jnp
    q = jnp.zeros((1, 1, 64, 16))
    k = jnp.zeros((1, 1, 16, 16))
    with pytest.raises(ValueError, match="need a prefix"):
        PA.flash_attention_fwd(q, k, k, block_q=16, block_k=16,
                               interpret=True)
    with pytest.raises(ValueError, match="not causal"):
        PA.flash_attention_fwd(q, k, k, causal=True, block_q=16,
                               block_k=4, interpret=True,
                               prefix=(16, 4))
    with pytest.raises(ValueError, match="divide the spans"):
        PA.flash_attention_fwd(q, k, k, causal=False, block_q=32,
                               block_k=4, interpret=True,
                               prefix=(16, 4))


def test_the_prefix_of_the_cell_by_hand():
    """The counts of the cell: per head and sequence at S = 16384,
    W = 2048, C = 16, 16,785,408 local and 7,340,032 remote pairs; the
    remote tiles visit exactly the remote pairs."""
    from veles.znicz_tpu.ops import eva_attention as E
    assert E.local_pairs(16384, 2048) == 8 * 2048 * 2049 // 2 == 16785408
    assert PA.prefix_pairs(16384, (2048, 128)) \
        == 2048 * 128 * sum(range(8)) == 7340032
    tiles = sum(PA._prefix_tiles(qi, 512, 128, (2048, 128))
                for qi in range(16384 // 512))
    assert tiles * 512 * 128 == 7340032
    # each K tile of window w's summaries is seen by the queries of the
    # windows after w: the backward starts at the first of them
    assert [PA._prefix_first_q(kj, 512, 128, (2048, 128))
            for kj in (0, 1, 7)] == [4, 8, 32]


def test_merge_partial_weighs_an_empty_part_zero():
    """``flash.merge_partial``: a part that saw no key (lse -inf, out 0)
    leaves the other as it was, and two empty parts give 0, not nan."""
    import jax.numpy as jnp
    out = jnp.ones((1, 1, 2, 4))
    lse = jnp.array([[[0.5, -jnp.inf]]])
    merged, new_lse = flash.merge_partial(
        out * jnp.array([1.0, 0.0])[:, None], lse,
        jnp.zeros((1, 1, 2, 4)), jnp.full((1, 1, 2), -jnp.inf))
    assert numpy.asarray(merged)[0, 0, 0].tolist() == [1.0] * 4
    assert not numpy.isnan(numpy.asarray(merged)).any()
    assert numpy.asarray(new_lse)[0, 0].tolist() == [0.5, -numpy.inf]


# -- the unit against its reference ----------------------------------------------


def test_eva_unit_against_reference():
    """Both parts, the summaries and the merge: output, input gradient
    and every parameter's (mu and phi included) at float32 rounding."""
    fwd, _ = check_unit(MODEL, **UNIT)
    assert fwd.weights.shape == (D, 3 * 4 * 16)
    assert fwd.pool_key.shape == fwd.pool_value.shape == (4, 16)


@pytest.mark.parametrize("chunk,window", [(1, 32), (4, 128), (4, 512)])
def test_a_chunk_of_one_or_a_window_past_s_is_causal_softmax(chunk, window):
    """The two identities: with C = 1 a summary is its key, with W >= S
    there is no earlier window; either way the unit is exact causal
    softmax attention (the dense core on the same q, k, v)."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(**dict(UNIT, chunk=chunk,
                                               local_window=window))
    params = comp.gather_params()
    y = numpy.asarray(xla_forward(comp, feed, fwd, params, x))
    p = {k: jnp.asarray(v) for k, v in params[fwd.name].items()}

    def causal(p, x):
        n = ref.rms(x, p["norm"], 1e-5)
        w_q, w_k, w_v = jnp.split(p["weights"], 3, axis=1)
        cos, sin = ref.rotary_tables(S, 16, 1e5)

        def heads(t):
            return t.reshape(S, 4, 16).transpose(1, 0, 2)

        q, k = (ref.rotate(heads(n @ w), cos, sin) for w in (w_q, w_k))
        _, out = dense_attention_core_fwd(
            jnp, q[None], k[None], heads(n @ w_v)[None], True,
            numpy.float32(0.25))
        return x + out[0].transpose(1, 0, 2).reshape(S, 64) \
            @ p["weights_out"]

    with jax.default_matmul_precision("highest"):
        want = numpy.asarray(jax.vmap(lambda row: causal(p, row))(x))
    assert numpy.abs(y - want).max() < 2e-5 * numpy.abs(want).max()
    if chunk == 1:
        # ... and the reference's definition agrees; a softmax over one
        # key is 1 whatever mu and phi are
        check_unit(dict(MODEL, eva_chunk=1), still=EvaAttention.POOL,
                   **dict(UNIT, chunk=1))


def test_summaries_pool_each_chunk_by_its_own_softmax():
    """k^ and v^ by hand: the softmax of the chunk's keys' scores
    against mu (for k^) and phi (for v^)."""
    import jax.numpy as jnp
    gen = numpy.random.RandomState(3)
    k, v = (gen.randn(1, 2, 8, 3).astype(numpy.float32) for _ in "kv")
    pool = {"pool_key": gen.randn(2, 3).astype(numpy.float32),
            "pool_value": gen.randn(2, 3).astype(numpy.float32)}
    kh, vh = summarize({n: jnp.asarray(a) for n, a in pool.items()},
                       jnp.asarray(k), jnp.asarray(v), 4)
    for h in range(2):
        for c in range(2):
            rows = slice(4 * c, 4 * c + 4)
            for vector, values, got in (("pool_key", k, kh),
                                        ("pool_value", v, vh)):
                e = numpy.exp(k[0, h, rows] @ pool[vector][h])
                want = (e / e.sum()) @ values[0, h, rows]
                assert numpy.abs(numpy.asarray(got)[0, h, c] - want).max() \
                    < 1e-5


def test_the_remote_part_changes_what_a_later_window_sees():
    """Dropped from the reference, the summaries change the output of
    every window but the first, and nothing of the first."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(**UNIT)
    p = {k: jnp.asarray(v)
         for k, v in comp.gather_params()[fwd.name].items()}
    block = reference_unit(MODEL)
    sound = numpy.asarray(block(p, jnp.asarray(x[0])))
    ref.experiment = {"no_remote": True}
    try:
        local = numpy.asarray(jax.jit(block)(p, jnp.asarray(x[0])))
    finally:
        ref.experiment = {}
    diff = numpy.abs(sound - local).max(-1)
    assert diff[:32].max() < 1e-6
    assert diff[32:].min() > 1e-6


def test_the_bf16_stream_control_rounds_the_stream_alone():
    """Under ``stream_bf16`` the reference's layer reads and writes a
    stream that bf16 holds exactly, within bf16's rounding of the
    float32 layer; without it the stream is float32."""
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(**UNIT)
    p = {k: jnp.asarray(v)
         for k, v in comp.gather_params()[fwd.name].items()}
    block = reference_unit(MODEL)
    sound = numpy.asarray(block(p, jnp.asarray(x[0])))
    ref.experiment = {"stream_bf16": True}
    try:
        rounded = numpy.asarray(jax.jit(block)(p, jnp.asarray(x[0])))
    finally:
        ref.experiment = {}
    held = numpy.asarray(jnp.asarray(rounded).astype(jnp.bfloat16),
                         numpy.float32)
    assert (held == rounded).all()
    assert not (numpy.asarray(jnp.asarray(sound).astype(jnp.bfloat16),
                              numpy.float32) == sound).all()
    assert numpy.abs(rounded - sound).max() \
        < 2 ** -6 * numpy.abs(sound).max()


@pytest.mark.parametrize("keywords,message", [
    ({"window": 8}, "no window"),
    ({"gate": "head"}, "no gate"),
    ({"kv_heads": 2}, "as many K/V heads"),
    ({"qk_norm": True}, "no q/k norm"),
    ({"chunk": 5}, "tile a window")])
def test_eva_refuses_what_it_has_not(keywords, message):
    wf = Workflow(None, name="wf")
    with pytest.raises(ValueError, match=message):
        EvaAttention(wf, **dict(UNIT, **keywords))


def test_a_window_that_does_not_tile_s_is_refused_at_initialize():
    with pytest.raises(ValueError, match="tile a sequence"):
        build(**dict(UNIT, local_window=48, chunk=4))


# -- the evaluator of several heads --------------------------------------------


def test_heads_evaluator_of_one_head_is_the_lm_evaluator():
    gen = numpy.random.RandomState(0)
    logits = gen.randn(2, 5, 7).astype(numpy.float32)
    labels = gen.randint(0, 7, (2, 5))
    one = EvaluatorHeadsLM.__new__(EvaluatorHeadsLM)
    one.heads = 1
    got = one._compute(numpy, logits, labels, numpy.int32(1))
    want = EvaluatorLM._compute(one, numpy, logits, labels, numpy.int32(1))
    for a, b in zip(got, want):
        assert numpy.abs(numpy.asarray(a) - numpy.asarray(b)).max() < 1e-6


def test_heads_evaluator_by_hand():
    """Head j's target at t is the label j ahead; the positions past the
    row have none; the loss is the heads' mean of their means; the
    gradient is the loss's."""
    import jax
    import jax.numpy as jnp
    gen = numpy.random.RandomState(1)
    logits = gen.randn(2, 6, 3 * 5).astype(numpy.float32)
    labels = gen.randint(0, 5, (2, 6))
    unit = EvaluatorHeadsLM.__new__(EvaluatorHeadsLM)
    unit.heads = 3
    err, loss, wrong = unit._compute(numpy, logits, labels, numpy.int32(2))

    def by_hand(logits):
        total = 0.0
        for j in range(3):
            lp = jax.nn.log_softmax(logits.reshape(2, 6, 3, 5)[:, :6 - j, j])
            total += -jnp.take_along_axis(
                lp, jnp.asarray(labels[:, j:])[..., None], -1).mean()
        return total / 3

    want, grad = jax.value_and_grad(by_hand)(jnp.asarray(logits))
    assert abs(float(loss) - float(want)) < 1e-5
    assert numpy.abs(err - numpy.asarray(grad)).max() < 1e-6
    assert wrong == ((logits.reshape(2, 6, 3, 5)[:, :, 0].argmax(-1)
                      != labels).sum())


# -- the whole LM -------------------------------------------------------------


@pytest.fixture
def tiny_lm():
    from veles.znicz_tpu.models import transformer_lm as T
    saved = {k: getattr(root.lm, k).to_dict()
             for k in ("loader", "model", "train", "decision")}
    root.lm.loader.update({"minibatch_size": 1, "n_train": 1,
                           "n_valid": 1, "seq_len": S, "vocab": 32,
                           "max_period": 80})
    root.lm.model.update(dict(
        {k: v for k, v in MODEL.items()
         if k not in ("vocab", "gradient_moment")},
        block="pre_norm", attn_block=16, attn_impl="pallas",
        pallas_tile=16))
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


@pytest.mark.parametrize("remat", [False, True])
def test_lm_loss_gradients_and_one_step_against_reference(tiny_lm, remat):
    """The program trained through StandardWorkflow / xla_step for one
    step: first validation loss, train loss, and every parameter after
    the step, against the reference's walk over the sub-layers +
    momentum SGD; and that walk, every parameter's gradient, against
    ``jax.grad`` of the reference's whole loss. ``remat``: the SwiGLU
    feed-forwards run their forward again in the backward."""
    import jax
    root.lm.model.remat = remat
    wf = tiny_lm.create_workflow()
    assert [u.recompute for u in wf.forwards
            if type(u).__name__ == "SwiGLUFFN"] == [remat] * 2
    wf.initialize(device="cpu")
    loader = wf.loader
    data, labels = loader.original_data.mem, loader.original_labels.mem
    valid = (data[:1].copy(), labels[:1].copy())
    train = (data[1:2].copy(), labels[1:2].copy())
    tree = ref.from_program(exported(wf), MODEL)
    assert ref.count_parameters(MODEL) == sum(
        a.size for a in jax.tree_util.tree_leaves(tree))
    assert tree["head"].shape == (64, 3 * 32)

    def whole(tree, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return ref.sequence_loss(tree, tokens, labels, MODEL)

    value, want_grads = jax.value_and_grad(whole)(
        tree, train[0][0], train[1][0])
    walked, grads = ref.gradients(tree, train, MODEL)
    assert abs(walked - float(value)) < 1e-5
    for (path, mine), theirs in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        theirs = numpy.asarray(theirs)
        assert numpy.abs(mine - theirs).max() \
            < 1e-5 * max(1.0, numpy.abs(theirs).max()), path

    wf.run()
    history = wf.decision.history
    assert abs(history[0]["validation"]["loss"]
               - ref.loss(tree, valid, MODEL)) < 1e-4
    after, losses = ref.train(tree, [train], MODEL, 0.5, 0.9)
    assert abs(history[0]["train"]["loss"] - losses[0]) < 1e-4
    stepped = ref.from_program(exported(wf), MODEL)
    flat = jax.tree_util.tree_leaves_with_path
    moved = 0
    for (path, new), (_, want), (_, old) in zip(
            flat(stepped), flat(after), flat(tree)):
        change = numpy.abs(want - old).max()
        assert numpy.abs(new - want).max() < 1e-5 + 1e-3 * change, path
        moved += change > 0
    assert moved == len(flat(tree))


def test_the_stream_between_units_and_the_logits_are_float32(tiny_lm):
    """``residual_dtype`` and ``logits_dtype``: the embedding's, the
    layers' and the final norm's outputs, and the head's logits, leave
    their units in float32 where the activation type is bf16; without
    the keys they are bf16."""
    import jax.numpy as jnp

    def dtypes(**keys):
        import jax
        from veles.accelerated_units import FlowContext
        root.lm.model.update(keys)
        wf = tiny_lm.create_workflow()
        amp = root.common.engine.get("amp")
        root.common.engine.amp = "bfloat16"
        try:
            wf.initialize(device="cpu")
        finally:
            root.common.engine.amp = amp
        assert wf.device.act_dtype == jnp.bfloat16
        comp = StepCompiler(wf.forwards, wf.device)
        params = comp.gather_params()
        seen = {}

        def run(p, ids):
            ctx = FlowContext(comp, dict(p), {}, {},
                              jax.random.PRNGKey(0), False)
            ctx.set(wf.loader, "minibatch_data", ids)
            for u in wf.forwards:
                u.xla_run(ctx)
                seen[u.name] = ctx.get(u, "output").dtype
            return 0

        jax.eval_shape(run, params, jnp.zeros((1, S), jnp.int32))
        return [seen[u.name] for u in wf.forwards]

    wide = dtypes()
    assert set(wide) == {numpy.dtype("float32")}
    narrow = dtypes(residual_dtype=None, logits_dtype=None)
    assert set(narrow) == {numpy.dtype(jnp.bfloat16)}


def test_counters_ride_the_metric_fetch(tiny_lm):
    """Per layer and training step, by hand at S 128, W 32, C 4, tile
    16, 4 heads: 32 summaries a row; 4 windows of 32 x 33 / 2 local
    pairs; 8 x 32 x (0 + 1 + 2 + 3) remote pairs; local tiles 4 x (1 +
    2) tiles of 16 x 16 a window, the remote tiles their pairs."""
    with telemetry.scoped():
        wf = tiny_lm.create_workflow()
        wf.initialize(device="cpu")
        wf.run()
        registry = telemetry.get_registry()
        units = [u for u in wf.forwards if isinstance(u, EvaAttention)]
        assert len(units) == 2
        local = 4 * 32 * 33 // 2
        remote = 8 * 32 * 6
        tiles = 4 * 3 * 16 * 16 + remote
        for unit in units:
            for name, value in (("summaries", 32), ("local_pairs", local),
                                ("remote_pairs", remote),
                                ("tile_pairs", tiles)):
                assert registry.counter_total(
                    "veles_eva_%s_total" % name, layer=unit.name) \
                    == 4 * value, name


def test_step_program_names_the_three_parts_inside_the_core(tiny_lm):
    """The scopes the readers of ``benchmark/reduce/evascopes.py`` find:
    ``veles.eva.summary``, ``.local`` and ``.remote`` under
    ``veles.eva`` inside ``veles.core``, in the forward and the
    backward unit of every EVA layer."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    text = fn.lower(*args).as_text(debug_info=True)
    units = [u for u in wf.forwards if isinstance(u, EvaAttention)]
    assert len(units) == 2
    for unit in units:
        for path in ("veles.fwd.EvaAttention." + unit.name,
                     "veles.bwd.GDEvaAttention.GD" + unit.name):
            for part in ("summary", "local", "remote"):
                # (the backward's summaries are its own forward's again
                # and their transpose: the core scope comes wrapped)
                assert re.search(
                    re.escape(path) + r"/(?:\w+\()*veles\.core\)*"
                    r"/veles\.eva/veles\.eva\." + part, text), (path, part)


def test_the_builder_refuses_what_it_cannot_run(tiny_lm):
    root.lm.model.update({"ut_steps": 2, "norm": "pre"})
    try:
        with pytest.raises(ValueError, match="eva_attention"):
            tiny_lm.build_layers()
    finally:
        root.lm.model.ut_steps = 1
    root.lm.model.update({"pred_heads": 0})
    with pytest.raises(ValueError, match="pred_heads"):
        tiny_lm.build_layers()
    root.lm.model.update({"pred_heads": 3, "block": "post_ln",
                          "layers": 2})
    with pytest.raises(ValueError, match="residual_dtype"):
        tiny_lm.build_layers()
