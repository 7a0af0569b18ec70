"""The pre-norm block (``root.lm.model.block="pre_norm"``, at LFM2-MoE's
layer pattern) against its plain
float32 reference, ``benchmark/reference/lfm2_moe.py``: each new unit
alone, the whole LM through ``StandardWorkflow`` (loss, logits, every
parameter after one step), the expert layer's shares adding up to the
whole layer, no dropped pair under a rigged router, the counters, the
scopes, and the refusals (numpy device, serving, layouts)."""

import json
import os
import sys

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.accelerated_units import FlowContext, StepCompiler
from veles.backends import XLADevice
from veles.config import root
from veles.memory import Array
from veles.workflow import Workflow
from veles.znicz_tpu.nn_units import gradient_unit_for
from veles.znicz_tpu.ops.expert_ffn import ExpertFFN
from veles.znicz_tpu.ops.gqa_attention import GQAttention
from veles.znicz_tpu.ops.rmsnorm import RMSNorm
from veles.znicz_tpu.ops.short_conv import ShortConv
from veles.znicz_tpu.ops.swiglu import SwiGLUFFN

from tests.test_conv_stack import FeedUnit, xla_backward, xla_forward

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmark.reference import lfm2_moe as ref   # noqa: E402

#: the tiny preset: d 64, 4 query / 2 K/V heads of 16, 8 experts top-2
#: of width 32, dense FFN 96, 1 dense + 4 pattern layers, S 64
MODEL = {"dim": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
         "layers": ["conv", "full_attention", "conv", "conv", "conv"],
         "dense_layers": 1, "ffn_hidden": 96,
         "moe_hidden": 32, "moe_experts": 8, "moe_top_k": 2,
         "experts_held": [0, 8], "conv_kernel": 3, "rope_theta": 1e6,
         "norm_eps": 1e-5, "vocab": 32, "gradient_moment": 0.9}
#: the whole-LM tests run the router's scaling off 1, so that a value
#: the builder did not hand to the unit would show against the reference
LM_MODEL = dict(MODEL, routed_scaling=1.5)
B, S, D = 2, 64, 64


def build(cls, **kwargs):
    """One unit pair on a (B, S, D) input, lr 1 and no momentum (so a
    step moves a parameter by minus its gradient)."""
    prng.seed_all(31)
    wf = Workflow(None, name="wf")
    gen = prng.get("lfm2")
    x = gen.normal(0, 1.0, (B, S, D)).astype(numpy.float32)
    feed = FeedUnit(wf, x)
    fwd = cls(wf, **kwargs)
    fwd.link_attrs(feed, ("input", "minibatch_data"))
    fwd.initialize(device=None)
    for name in fwd.PARAMS:     # gains off 1, so their gradients count
        arr = getattr(fwd, name)
        if arr.mem.ndim == 1 and name != "expert_bias":
            arr.mem[...] = gen.normal(1.0, 0.2, arr.shape)
    err = gen.normal(0, 1.0, (B, S, D)).astype(numpy.float32)
    gd = gradient_unit_for(cls)(wf, learning_rate=1.0)
    gd.setup_forward(fwd)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    comp = StepCompiler([fwd, gd], XLADevice(platform="cpu"))
    return feed, fwd, gd, x, err, comp


def reference_block(kind, model):
    """x (S, d), params -> the unit's output by the reference."""
    import jax
    tables = ref.rope_tables(S, model["head_dim"], model["rope_theta"])

    def block(p, x):
        with jax.default_matmul_precision("highest"):
            if kind == "rms_norm":
                return ref.rms(x, p["weights"], model["norm_eps"])
            n = ref.rms(x, p["norm"], model["norm_eps"])
            if kind == "short_conv":
                return x + ref.short_conv(n, p, model)
            if kind == "gqa_attention":
                return x + ref.gqa_attention(n, p, model, tables, 16)
            if kind == "swiglu_ffn":
                return x + ref.swiglu_ffn(n, p)
            return x + ref.expert_ffn(n, p, model)

    return block


def rigged(fwd, params, chosen):
    """``params`` with a selection bias that makes every token select
    the experts ``chosen`` (k of them; None: as they are)."""
    if not chosen:
        return params
    bias = numpy.full(fwd.experts, -10.0, numpy.float32)
    bias[list(chosen)] = 10.0
    return {fwd.name: dict(params[fwd.name], expert_bias=bias)}


def check_unit(cls, kwargs, model=MODEL, tol=2e-5, chosen=None):
    import jax
    import jax.numpy as jnp
    feed, fwd, gd, x, err, comp = build(cls, **kwargs)
    params0 = rigged(fwd, comp.gather_params(), chosen)
    state0 = comp.gather_state()
    y = numpy.asarray(xla_forward(comp, feed, fwd, params0, x))
    dx, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                               x, err)
    block = reference_block(cls.MAPPING, model)
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
        scale = max(1.0, float(numpy.abs(numpy.asarray(g)).max()))
        assert numpy.abs(moved - numpy.asarray(g)).max() \
            < 10 * tol * scale, name
    return fwd, y


#: a selection bias large enough that the top-k of ``s + b`` is not the
#: top-k of ``s``: selection and weighting differ
EXPERT = dict(experts=8, top_k=2, hidden=32, bias_stddev=0.05)
UNIT_CASES = {
    "rms_norm": (RMSNorm, {}, {}),
    "short_conv": (ShortConv, {"kernel": 3}, {}),
    "short_conv_4_taps": (ShortConv, {"kernel": 4}, {"conv_kernel": 4}),
    "gqa_dense_core": (GQAttention, dict(heads=4, kv_heads=2), {}),
    "gqa_scan_core": (GQAttention, dict(
        heads=4, kv_heads=2, attn_block_size=16), {}),
    "gqa_pallas_core": (GQAttention, dict(
        heads=4, kv_heads=2, attn_block_size=16, attn_impl="pallas"),
        {}),
    "gqa_equal_heads": (GQAttention, dict(heads=4, kv_heads=4),
                        {"kv_heads": 4}),
    "swiglu_ffn": (SwiGLUFFN, {"hidden": 96}, {}),
    "experts_all_held": (ExpertFFN, EXPERT, {}),
    "experts_share_2_to_6": (ExpertFFN, dict(
        EXPERT, experts_held=(2, 6)), {"experts_held": [2, 6]}),
    "experts_top_1": (ExpertFFN, dict(EXPERT, top_k=1),
                      {"moe_top_k": 1}),
    "experts_top_4_of_8": (ExpertFFN, dict(EXPERT, top_k=4),
                           {"moe_top_k": 4}),
    "experts_scaled": (ExpertFFN, dict(EXPERT, scaling=2.5),
                       {"routed_scaling": 2.5}),
}


@pytest.mark.parametrize("case", sorted(UNIT_CASES))
def test_unit_against_reference(case):
    """Output, input gradient and every parameter's gradient of one
    unit against ``jax.grad`` of the reference's function."""
    cls, kwargs, patch = UNIT_CASES[case]
    tol = 2e-3 if "pallas" in case else 2e-5    # interpreted kernels
    check_unit(cls, kwargs, dict(MODEL, **patch), tol=tol)


def expert_output(held, params, x):
    """The expert layer's part of the sum (no residual) for the share
    ``held``, on the weights ``params`` of a layer that holds all."""
    feed, fwd, _, _, _, comp = build(ExpertFFN, experts_held=held,
                                     **EXPERT)
    lo, hi = held
    mine = dict(params, weights13=params["weights13"][lo:hi],
                weights2=params["weights2"][lo:hi])
    y = xla_forward(comp, feed, fwd, {fwd.name: mine}, x, train=False)
    return numpy.asarray(y) - x


def test_shares_add_up_to_the_whole_layer():
    """Guide section 4's share test: 8 experts held as 4 shares of 2 —
    the shares' parts of the sum add up to what the uncut reference
    gives for the whole layer."""
    import jax
    feed, fwd, _, x, _, comp = build(ExpertFFN, **EXPERT)
    params = comp.gather_params()[fwd.name]
    whole = jax.vmap(lambda row: reference_block("expert_ffn", MODEL)(
        params, row))(x) - x
    parts = [expert_output((lo, lo + 2), params, x)
             for lo in range(0, 8, 2)]
    assert all(numpy.abs(part).max() > 1e-3 for part in parts)
    assert numpy.abs(sum(parts) - numpy.asarray(whole)).max() < 2e-5


def step_aux(fwd, comp, feed, params, x):
    import jax

    def fn(p, xv):
        ctx = FlowContext(comp, dict(p), {}, {}, jax.random.PRNGKey(7),
                          True)
        ctx.set(feed, "minibatch_data", xv)
        fwd.xla_run(ctx)
        return ctx.get(fwd, "output"), ctx.outputs

    return jax.jit(fn)(params, x)


def test_no_pair_is_dropped_when_every_token_takes_one_expert():
    """A router rigged so that all tokens select experts 0 and 1: all
    T x k pairs land on the two held experts, every one is computed
    (the buffer holds the worst case), and the result is the
    reference's."""
    import jax
    feed, fwd, _, x, _, comp = build(ExpertFFN, experts_held=(0, 2),
                                     **EXPERT)
    params = rigged(fwd, comp.gather_params(), (0, 1))
    y, outs = step_aux(fwd, comp, feed, params, x)
    name = fwd.name
    assert int(outs["moe_pairs_" + name]) == B * S * 2
    assert int(outs["moe_max_load_" + name]) == B * S
    assert int(outs["moe_dropped_" + name]) == 0
    model = dict(MODEL, experts_held=[0, 2])
    want = jax.vmap(lambda row: reference_block("expert_ffn", model)(
        params[name], row))(x)
    assert numpy.abs(numpy.asarray(y) - numpy.asarray(want)).max() < 2e-5


@pytest.fixture
def poisoned_rows(monkeypatch):
    """What a TPU leaves undefined reads as NaN: the rows past the last
    group of a grouped product's result (the CPU's writes zeros there)
    and the rows of a buffer that no chunk of ``over_prefix`` wrote
    (on a CPU ``jax.lax.empty`` is a zero fill, which would hide a
    missing mask)."""
    import jax
    import jax.numpy as jnp
    from veles.znicz_tpu.ops import expert_ffn, vjp_units
    plain = jax.lax.ragged_dot

    def poisoned(x, w, sizes, **kwargs):
        out = plain(x, w, sizes, **kwargs)
        real = jnp.arange(out.shape[0])[:, None] < sizes.sum()
        return jnp.where(real, out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    monkeypatch.setattr(
        expert_ffn, "unwritten",
        lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    vjp_units._products.cache_clear()
    yield
    vjp_units._products.cache_clear()


@pytest.fixture
def chunk_of_16(monkeypatch):
    """The row stages at ``CHUNK`` 48: the (B x S x k = 256)-row
    buffer of these tests goes in chunks of 16 rows, the largest
    divisor it shares with that."""
    from veles.znicz_tpu.ops import expert_ffn
    monkeypatch.setattr(expert_ffn, "CHUNK", 48)
    return 16


def test_rows_the_grouped_product_skips_may_hold_anything(
        poisoned_rows, chunk_of_16):
    """The TPU's grouped kernel stops at the last group, and the row
    stages at the last chunk that holds a real pair; both leave what
    lies past undefined. With those rows poisoned, output and every
    gradient are still the reference's: no 0 * NaN reaches a token or
    a weight."""
    check_unit(ExpertFFN, dict(EXPERT, experts_held=(2, 6)),
               dict(MODEL, experts_held=[2, 6]))


#: name: (experts held, the experts every token is made to select or
#: None for the router's own choice, real pairs or None)
PREFIX_CASES = {
    "no_held_pair_selected": ((0, 2), (6, 7), 0),
    "rows_inside_a_chunk": ((2, 6), None, None),
    "one_expert_of_two_held": ((0, 2), (0, 7), B * S),
    "every_row_real": ((0, 2), (0, 1), B * S * 2),
}


@pytest.mark.parametrize("case", sorted(PREFIX_CASES))
def test_row_stages_follow_the_real_pairs(case, poisoned_rows,
                                          chunk_of_16):
    """The row stages at ``rows`` = 0, off a chunk's edge and = T x k,
    the rows they leave alone poisoned: output, input gradient and
    every parameter's gradient against the reference, and the loop's
    trips, read from the counter's source, ``ceil(rows / chunk)``."""
    held, chosen, pairs = PREFIX_CASES[case]
    check_unit(ExpertFFN, dict(EXPERT, experts_held=held),
               dict(MODEL, experts_held=list(held)), chosen=chosen)
    feed, fwd, _, x, _, comp = build(ExpertFFN, experts_held=held,
                                     **EXPERT)
    _, outs = step_aux(fwd, comp, feed,
                       rigged(fwd, comp.gather_params(), chosen), x)
    rows = int(outs["moe_pairs_" + fwd.name])
    if pairs is None:       # the router's own choice: off a chunk's edge
        assert rows % chunk_of_16
    else:
        assert rows == pairs
    assert int(outs["moe_touched_" + fwd.name]) \
        == -(-rows // chunk_of_16) * chunk_of_16


# -- the whole LM ------------------------------------------------------------


@pytest.fixture
def tiny_lm():
    from veles.znicz_tpu.models import transformer_lm as T
    saved = {k: getattr(root.lm, k).to_dict()
             for k in ("loader", "model", "train", "decision")}
    root.lm.loader.update({"minibatch_size": 2, "n_train": 2,
                           "n_valid": 2, "seq_len": S, "vocab": 32,
                           "max_period": 40})
    root.lm.model.update(dict(
        {k: v for k, v in MODEL.items()
         if k not in ("vocab", "gradient_moment")},
        block="pre_norm", attn_block=16, moe_scaling=1.5,
        moe_bias_stddev=0.05))
    root.lm.train.update({"learning_rate": 0.5, "gradient_moment": 0.9})
    root.lm.decision.update({"max_epochs": 1})
    prng.seed_all(5)
    try:
        yield T
    finally:
        for k, v in saved.items():
            getattr(root.lm, k).update(v)


def initial(wf):
    loader = wf.loader
    units = [(type(u).MAPPING, u.export_params()) for u in wf.forwards]
    data, labels = loader.original_data.mem, loader.original_labels.mem
    return units, (data[:2].copy(), labels[:2].copy()), \
        (data[2:4].copy(), labels[2:4].copy())


def test_lm_loss_logits_and_one_step_against_reference(tiny_lm):
    """The program trained through StandardWorkflow / xla_step for one
    step: first validation loss, train loss, the logits, and every
    parameter after the step, against the reference's jax.grad +
    momentum SGD."""
    import jax
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    units, valid, train = initial(wf)
    comp = wf.xla_step.compiler

    def logits(p, tokens):
        ctx = FlowContext(comp, dict(p), {}, {}, jax.random.PRNGKey(0),
                          False)
        ctx.set(wf.loader, "minibatch_data", tokens)
        for unit in wf.forwards:
            unit.xla_run(ctx)
        return ctx.get(wf.forwards[-1], "output")

    got = numpy.asarray(jax.jit(logits)(comp.gather_params(), valid[0]))
    tree = ref.from_program(units, LM_MODEL)
    tables = ref.rope_tables(S, LM_MODEL["head_dim"],
                             LM_MODEL["rope_theta"])
    with jax.default_matmul_precision("highest"):
        want = numpy.stack([numpy.asarray(ref.sequence_logits(
            tree, row, tables, LM_MODEL, 16)) for row in valid[0]])
    assert numpy.abs(got - want).max() < 5e-5

    wf.run()
    history = wf.decision.history
    assert abs(history[0]["validation"]["loss"]
               - ref.loss(tree, valid, LM_MODEL)) < 1e-5
    after, losses = ref.train(tree, [train], LM_MODEL, 0.5, 0.9)
    assert abs(history[0]["train"]["loss"] - losses[0]) < 1e-5
    stepped = ref.from_program(
        [(type(u).MAPPING, u.export_params()) for u in wf.forwards],
        LM_MODEL)
    flat = jax.tree_util.tree_leaves_with_path
    moved = 0
    for (path, new), (_, want), (_, old) in zip(
            flat(stepped), flat(after), flat(tree)):
        delta = numpy.abs(want - old).max()
        assert numpy.abs(new - want).max() < 1e-5 + 1e-3 * delta, path
        moved += delta > 0
    # everything but the four expert biases took a step
    assert moved == len(flat(tree)) - 4


def test_counters_ride_the_metric_fetch(tiny_lm):
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    wf.run()
    registry = telemetry.get_registry()
    layers = [u.name for u in wf.forwards if isinstance(u, ExpertFFN)]
    assert len(layers) == 4
    for layer in layers:
        assert registry.counter_total("veles_moe_steps_total",
                                      layer=layer) == 1
        # all 8 experts held: every one of the T x k pairs is computed
        assert registry.counter_total("veles_moe_pairs_total",
                                      layer=layer) == 2 * S * 2
        # ... so the row stages ran over the whole buffer, one chunk
        assert registry.counter_total("veles_moe_rows_touched_total",
                                      layer=layer) == 2 * S * 2
    assert registry.counter_total("veles_moe_dropped_pairs_total") == 0
    text = registry.render_prometheus()

    def gauges(name):
        return [float(line.split()[-1]) for line in text.splitlines()
                if line.startswith(name + "{")]

    loads = gauges("veles_moe_load_max_over_mean")
    assert len(loads) == 4 and all(1.0 <= g <= 8.0 for g in loads)
    assert gauges("veles_moe_buffer_rows") == [2 * S * 2] * 4


def test_step_program_names_the_new_scopes(tiny_lm):
    """``veles.experts`` and ``veles.route`` inside the expert units,
    ``veles.core`` inside both attention units and nowhere in the
    short conv, in the program the workflow compiles."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    text = fn.lower(*args).as_text(debug_info=True)
    import re
    for scope in ("veles.fwd.GQAttention.GQAttention/veles.core",
                  "veles.bwd.GDGQAttention.GDGQAttention/veles.core",
                  "veles.bwd.GDShortConv.GDShortConv/",
                  "veles.bwd.GDExpertFFN.GDExpertFFN/veles.update"):
        assert scope in text, scope
    # forward and backward (jax wraps the forward's scopes in
    # transpose(jvp(...)) and checkpoint components) both name them
    for unit in (r"veles\.fwd\.ExpertFFN\.ExpertFFN",
                 r"veles\.bwd\.GDExpertFFN\.GDExpertFFN"):
        found = set(re.findall(
            unit + r'/[^"]*?[/(]veles\.(experts|route)[/)]', text))
        assert found == {"experts", "route"}, unit
    assert not re.search(r"ShortConv[^\"]*veles\.core", text)


def test_defaults_build_the_post_ln_graph():
    from veles.znicz_tpu.models import transformer_lm as T
    kinds = [layer["type"] for layer in T.build_layers()]
    block = ["attention", "layernorm", "transformer_ffn", "layernorm"]
    assert kinds == ["embedding"] + block * root.lm.model.layers \
        + ["token_dense"]


@pytest.mark.parametrize("patch,message", [
    ({"layers": []}, "a count or the list"),
    ({"layers": "conv"}, "a count or the list"),
    ({"layers": ["conv"] * 4 + ["window"]}, "has the operators"),
    ({"block": "post_ln", "layers": ["conv"], "moe_experts": 0},
     "'full_attention' alone"),
    ({"stacked": True}, "per-unit path"),
    ({"block": "parallel_residual"}, "unknown block"),
])
def test_build_layers_refuses(tiny_lm, patch, message):
    root.lm.model.update(patch)
    with pytest.raises(ValueError, match=message):
        tiny_lm.build_layers()


def test_builder_hands_every_stated_constant_to_the_expert_layer(tiny_lm):
    """``moe_scaling`` and ``moe_bias_stddev`` reach the unit: a key
    only the reference read would let the two disagree in silence."""
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    experts = [u for u in wf.forwards if isinstance(u, ExpertFFN)]
    assert len(experts) == 4
    for unit in experts:
        assert unit.scaling == 1.5 and unit.top_k == 2
        assert unit.expert_bias.mem.std() > 0.01
    root.lm.model.update({"moe_bias_stddev": 0.0})
    wf = tiny_lm.create_workflow()
    wf.initialize(device="cpu")
    assert all(not u.expert_bias.mem.any() for u in wf.forwards
               if isinstance(u, ExpertFFN))


@pytest.mark.parametrize("layers", [3, ["full_attention", "conv"]])
def test_pre_norm_with_dense_layers_alone_is_a_gated_lm(tiny_lm, layers):
    """``layers`` as a count is that many attention layers, in this
    block as in the post-LN one; with ``dense_layers`` covering them
    every feed-forward is the dense SwiGLU. The LM trains."""
    n = layers if isinstance(layers, int) else len(layers)
    root.lm.model.update({"layers": layers, "dense_layers": n})
    root.lm.train.update({"learning_rate": 0.05})
    root.lm.decision.update({"max_epochs": 3})
    wf = tiny_lm.create_workflow()
    kinds = [type(u).MAPPING for u in wf.forwards]
    assert kinds.count("swiglu_ffn") == n and "expert_ffn" not in kinds
    assert kinds.count("gqa_attention") == (n if isinstance(layers, int)
                                            else 1)
    wf.initialize(device="cpu")
    wf.run()
    losses = [h["train"]["loss"] for h in wf.decision.history]
    assert numpy.isfinite(losses).all() and losses[-1] < losses[0]


def test_the_post_ln_block_takes_a_list_of_attention_layers():
    from veles.znicz_tpu.models import transformer_lm as T
    saved = root.lm.model.layers
    counted = T.build_layers()
    root.lm.model.update({"layers": ["full_attention"] * saved})
    try:
        assert T.build_layers() == counted
    finally:
        root.lm.model.update({"layers": saved})


def test_the_drop_counter_counts_pairs_outside_their_group():
    """``misplaced_pairs`` reads the pairs' own rows: it is 0 for the
    layer's sort, and counts what a capacity or a misfiled pair would
    leave uncomputed."""
    import jax.numpy as jnp
    from veles.znicz_tpu.ops.expert_ffn import misplaced_pairs
    local = jnp.array([1, 0, 3, 1, 1, 0, 2, 1])    # expert 3: not held
    held = local < 3
    order = jnp.argsort(jnp.where(held, local, 3), stable=True)
    inv = jnp.argsort(order)
    sizes = jnp.array([2, 4, 1], jnp.int32)
    assert int(misplaced_pairs(local, held, inv, sizes)) == 0
    # a capacity of 2 rows an expert: two of expert 1's four pairs, and
    # expert 2's pair now lies outside its (moved) group
    capped = jnp.minimum(sizes, 2)
    assert int(misplaced_pairs(local, held, inv, capped)) == 3
    # two pairs filed under each other's expert
    swapped = inv.at[jnp.array([0, 1])].set(inv[jnp.array([1, 0])])
    assert int(misplaced_pairs(local, held, swapped, sizes)) == 2


def test_tensor_parallel_layout_is_refused(tiny_lm):
    saved = root.lm.parallel.to_dict()
    root.lm.parallel.update({"model": 2})
    try:
        with pytest.raises(ValueError, match="per-unit path"):
            tiny_lm.build_layers()
    finally:
        root.lm.parallel.update(saved)


def test_numpy_device_is_refused():
    feed, fwd, gd, *_ = build(SwiGLUFFN, hidden=96)
    with pytest.raises(NotImplementedError, match="no numpy oracle"):
        fwd.numpy_run()
    with pytest.raises(NotImplementedError, match="no numpy oracle"):
        gd.numpy_run()


@pytest.mark.parametrize("kind", ["rms_norm", "short_conv",
                                  "gqa_attention", "swiglu_ffn",
                                  "expert_ffn"])
def test_serving_refuses_an_archive_with_the_new_units(tmp_path, kind):
    """No serving forward exists for the new units: loading an archive
    that holds one must reach ``serving/model.py``'s "cannot serve
    unit" error, not a KeyError at the first request."""
    from veles.serving.model import ArchiveModel
    numpy.save(tmp_path / "u_weights.npy", numpy.ones(4, numpy.float32))
    doc = {"format": 1, "workflow": "lm", "input_sample_shape": [8],
           "units": [{"type": kind, "name": "u", "config": {},
                      "weights": "u_weights.npy"}]}
    (tmp_path / "contents.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="cannot serve unit u"):
        ArchiveModel.from_dir(str(tmp_path))
