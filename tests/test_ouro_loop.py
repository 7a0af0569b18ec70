"""A layer stack run several times over ONE set of weights
(``root.lm.model.ut_steps``, ``znicz_tpu.loop.Loop``) against its
plain float32 reference, ``benchmark/reference/ouro.py``: the whole LM
through ``StandardWorkflow`` (loss and every parameter after one step,
per unit), a tied weight's gradient against the sum over untied copies,
ONE momentum update on the summed gradient, the recomputed backward
against jax's stored one, the exit distribution and the gate's two
gradient paths, the counters, the scopes, the sandwich norm and the
q/k-norm switch alone, the unchanged ``ut_steps=1`` graph, the
refusals, and the planted faults of ``chip_grads_ouro.py``."""

import os
import sys

import numpy
import pytest

import veles.prng as prng
from veles import telemetry
from veles.accelerated_units import FlowContext
from veles.config import root
from veles.znicz_tpu.loop import Loop
from veles.znicz_tpu.ops.evaluator import EvaluatorLM, EvaluatorLoopLM
from veles.znicz_tpu.ops.exit_gate import ExitGate
from veles.znicz_tpu.ops.gqa_attention import GQAttention
from veles.znicz_tpu.ops.swiglu import SwiGLUFFN

from tests.test_lfm2_moe import MODEL as LFM2_MODEL
from tests.test_lfm2_moe import build, xla_backward, xla_forward

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "benchmark", "tests")]
from benchmark.reference import ouro as ref     # noqa: E402

S, T = 32, 3
#: the tiny preset: d 32, 2 heads of 16, FFN 48, two layers run three
#: times; the configuration's keys as the reference reads them
MODEL = {"dim": 32, "heads": 2, "head_dim": 16,
         "layers": ["plain_attention"] * 2, "ffn_hidden": 48,
         "vocab": 32, "ut_steps": T, "exit_entropy_weight": 0.1,
         "rope_theta": 1e6, "norm_eps": 1e-6, "gradient_moment": 0.9}
PROGRAM = {"dim": 32, "heads": 2, "kv_heads": 2, "head_dim": 16,
           "layers": ["plain_attention"] * 2, "dense_layers": 2,
           "ffn_hidden": 48, "block": "pre_norm", "norm": "sandwich",
           "ut_steps": T, "exit_entropy_weight": 0.1, "attn_block": None,
           "norm_eps": 1e-6, "rope_theta": 1e6}


@pytest.fixture
def lm_config():
    """``root.lm`` at the tiny preset, restored afterwards; -> the
    model module and a function that builds the initialized workflow
    with every gain, the gate included, off its initial value."""
    from veles.znicz_tpu.models import transformer_lm as module
    saved = {k: getattr(root.lm, k).to_dict()
             for k in ("loader", "model", "train", "decision")}
    root.lm.loader.update({"minibatch_size": 2, "n_train": 2,
                           "n_valid": 2, "seq_len": S, "vocab": 32,
                           "max_period": 20})
    root.lm.model.update(PROGRAM)
    root.lm.train.update({"learning_rate": 0.5, "gradient_moment": 0.9})
    root.lm.decision.update({"max_epochs": 1})

    def make(**model):
        root.lm.model.update(model)
        prng.seed_all(5)
        wf = module.create_workflow()
        wf.initialize(device="cpu")
        gen = prng.get("ouro_test")
        for unit in wf.forwards:
            for name in unit.PARAMS:
                arr = getattr(unit, name)
                if arr and arr.mem.ndim == 1:
                    arr.map_write()
                    gate = isinstance(unit, ExitGate)
                    arr.mem[...] = gen.normal(0.0 if gate else 1.0, 0.3,
                                              arr.shape)
        step = wf.xla_step
        step.params = step._place_tree(step.compiler.gather_params())
        return wf

    try:
        yield module, make
    finally:
        for k, v in saved.items():
            getattr(root.lm, k).update(v)


def exported(wf):
    return [(type(u).MAPPING, u.export_params()) for u in wf.forwards]


def batches(wf):
    data = wf.loader.original_data.mem
    labels = wf.loader.original_labels.mem
    return (data[:2].copy(), labels[:2].copy()), \
        (data[2:4].copy(), labels[2:4].copy())


def flat(tree):
    import jax
    return {jax.tree_util.keystr(path): numpy.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def trained(wf, model=MODEL):
    """Run the workflow's one epoch (one step); -> (reference tree
    before, reference tree of the program's parameters after, history,
    the two batches)."""
    tree = ref.from_program(exported(wf), model)
    valid, train = batches(wf)
    wf.run()
    return tree, ref.from_program(exported(wf), model), \
        wf.decision.history, valid, train


# -- the whole LM ------------------------------------------------------------


@pytest.mark.parametrize("core", ["dense", "scan", "pallas"])
def test_loss_and_one_step_against_reference(lm_config, core):
    """First validation loss, train loss and every parameter after one
    step (per unit: each leaf of the tree) against the reference's
    jax.grad + momentum SGD, on each attention core."""
    _, make = lm_config
    wf = make(**{"dense": {}, "scan": {"attn_block": 16},
                 "pallas": {"attn_block": 16,
                            "attn_impl": "pallas"}}[core])
    assert isinstance(wf.loop, Loop) and wf.loop.steps == T
    tree, stepped, history, valid, train = trained(wf)
    tol = 2e-3 if core == "pallas" else 1e-5    # interpreted kernels
    assert abs(history[0]["validation"]["loss"]
               - ref.loss(tree, valid, MODEL)) < tol
    after, losses = ref.train(tree, [train], MODEL, 0.5, 0.9)
    assert abs(history[0]["train"]["loss"] - losses[0]) < tol
    old, want, got = flat(tree), flat(after), flat(stepped)
    assert len(want) == 1 + 2 * 8 + 1 + 2 + 1
    for path in want:
        delta = numpy.abs(want[path] - old[path]).max()
        assert delta > 1e-4, path           # every parameter is trained
        assert numpy.abs(got[path] - want[path]).max() \
            < tol * (1 + 100 * delta), path
    root.lm.model.update({"attn_impl": None})


def untied_gradients(tree, batch):
    """[gradient tree of pass t] with the shared parameters' use in
    every other pass held constant: the gradients of T untied copies."""
    import chip_grads_ouro as twin
    return [twin.twin_gradients(ref, tree, batch, MODEL, live=t)
            for t in range(1, T + 1)]


def test_a_tied_weight_takes_the_sum_over_its_visits(lm_config):
    """lr 1, no momentum: a parameter moves by minus its gradient, and
    that is the SUM of the gradients of the T untied copies — for the
    layers, the final norm, the head and the gate alike."""
    _, make = lm_config
    root.lm.train.update({"learning_rate": 1.0, "gradient_moment": 0.0})
    wf = make()
    tree, stepped, _, _, train = trained(wf)
    parts = [flat(g) for g in untied_gradients(tree, train)]
    old, got = flat(tree), flat(stepped)
    for path in old:
        if path == "['embedding']":
            continue
        total = sum(part[path] for part in parts)
        moved = old[path] - got[path]
        scale = max(1.0, numpy.abs(total).max())
        assert numpy.abs(moved - total).max() < 2e-5 * scale, path
        # ... and no single visit's gradient is the whole of it (the
        # last pass's gate is read by nothing: p_T is what is left)
        used = sum(numpy.abs(part[path]).max() > 1e-6 for part in parts)
        assert used >= (T - 1 if "gate" in path else T), path


def test_one_momentum_update_a_step_on_the_summed_gradient(lm_config):
    """After one step from zero velocity the momentum state is
    ``-lr x (summed gradient)``: one update. Four updates, one a visit,
    would leave ``-lr x sum_t m^(T-t) g_t``, which it is not."""
    _, make = lm_config
    wf = make()
    tree = ref.from_program(exported(wf), MODEL)
    _, train = batches(wf)
    wf.run()
    parts = untied_gradients(tree, train)
    gd = wf.gds[1]                              # the first layer's attention
    assert isinstance(gd.forward, GQAttention)
    vel = numpy.asarray(gd.vel_weights.map_read().mem)
    grads = [g["layers"][0]["attn"]["weights"] for g in parts]
    once = -0.5 * sum(grads)
    visits = -0.5 * sum(0.9 ** (T - 1 - t) * g
                        for t, g in enumerate(reversed(grads)))
    assert numpy.abs(vel - once).max() < 1e-5
    assert numpy.abs(vel - visits).max() > 1e-2
    assert int(gd.iteration.map_read().mem) == 1


def test_recomputed_backward_equals_the_stored_one(lm_config):
    """The program's step recomputes every layer in its backward from
    the layer's saved input; ``jax.grad`` of the program's own forward
    (the evaluation trace, which keeps its residuals as jax sees fit)
    gives the same gradients."""
    import jax
    _, make = lm_config
    root.lm.train.update({"learning_rate": 1.0, "gradient_moment": 0.0})
    wf = make()
    step = wf.xla_step
    comp = step.compiler
    _, (tokens, labels) = batches(wf)
    params0 = jax.tree_util.tree_map(numpy.asarray, comp.gather_params())

    def loss(params):
        def bind(ctx):
            ctx.set(wf.loader, "minibatch_data", tokens)
            ctx.set(wf.loader, "minibatch_labels", labels)
            ctx.set(wf.loader, "minibatch_size", numpy.int32(2))
        ctx = comp.trace_step(params, {}, {}, jax.random.PRNGKey(0),
                              False, step.eval_units, bind)
        return ctx.outputs["loss"]

    stored = jax.grad(loss)(params0)
    wf.run()
    params1 = comp.gather_params()
    checked = 0
    for unit, tree in stored.items():
        for name, grad in tree.items():
            moved = params0[unit][name] - numpy.asarray(params1[unit][name])
            scale = max(1.0, float(numpy.abs(grad).max()))
            assert numpy.abs(moved - numpy.asarray(grad)).max() \
                < 2e-5 * scale, (unit, name)
            checked += 1
    assert checked == 1 + 2 * 8 + 1 + 2 + 1


#: the attention cores a test runs, as ``root.lm.model`` selects them
CORES = {"dense": {}, "scan": {"attn_block": 16},
         "pallas": {"attn_block": 16, "attn_impl": "pallas"}}


def attention_layers(wf):
    return [u.name for u in wf.forwards if isinstance(u, GQAttention)]


def kept_cores(registry, wf):
    return [registry.counter_total("veles_loop_kept_cores_total",
                                   layer=name)
            for name in attention_layers(wf)]


def test_counters_and_exports_ride_the_metric_fetch(lm_config):
    _, make = lm_config
    wf = make()
    registry = telemetry.get_registry()
    before = registry.counter_total("veles_loop_steps_total")
    passes = registry.counter_total("veles_loop_passes_total")
    kept = kept_cores(registry, wf)
    tree, _, history, _, train = trained(wf)
    assert registry.counter_total("veles_loop_steps_total") == before + 1
    assert registry.counter_total("veles_loop_passes_total") == passes + T
    # the dense core keeps nothing: every recomputation runs it again
    assert len(kept) == 2 and kept_cores(registry, wf) == kept
    text = registry.render_prometheus()

    def gauges(name):
        return [float(line.split()[-1]) for line in text.splitlines()
                if line.startswith(name)]

    import jax
    tokens, labels, tables = ref._batch(train, MODEL)
    with jax.default_matmul_precision("highest"):
        terms = [ref.sequence_terms(tree, t, l, tables, MODEL, 16, 16)
                 for t, l in zip(tokens, labels)]
    ce = numpy.mean([numpy.asarray(c) for c, _ in terms], axis=(0, 2))
    mass = numpy.mean([numpy.asarray(p) for _, p in terms], axis=(0, 2))
    assert numpy.allclose(gauges("veles_loop_exit_loss{"), ce, atol=1e-5)
    assert numpy.allclose(gauges("veles_loop_exit_mass{"), mass,
                          atol=1e-6)
    assert abs(sum(gauges("veles_loop_exit_mass{")) - 1.0) < 1e-5
    assert abs(gauges("veles_loop_expected_exit_pass")[0]
               - (mass * numpy.arange(1, T + 1)).sum()) < 1e-5
    # the step's "loss" is the total: expected cross entropy less the
    # entropy term, not any exit's own
    assert abs(history[0]["train"]["loss"] - (ce * mass).sum()) > 1e-3
    # a flash core's every recomputed application takes the pass's
    # kept (out, lse): T a layer and step, one for each pass
    wf = make(**CORES["scan"])
    kept = kept_cores(registry, wf)
    wf.run()
    assert [now - was for now, was in
            zip(kept_cores(registry, wf), kept)] == [T, T]
    root.lm.model.update({"attn_block": None})


def equations(jaxpr, stack=""):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, with the
    whole name stack it runs under: (equation, stack)."""
    for eqn in jaxpr.eqns:
        path = stack + "/" + str(eqn.source_info.name_stack)
        yield eqn, path
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, path)


#: the operation that is a core's kernel, by core
KERNEL = {"dense": "dot_general", "scan": "scan", "pallas": "pallas_call"}


@pytest.mark.parametrize("core", sorted(CORES))
def test_recomputation_takes_the_flash_core_from_the_forward_pass(
        lm_config, core):
    """The backward pass's ``lax.scan`` body holds the attention core's
    backward kernels and, on a flash core, no forward kernel: the
    recomputed layer takes (out, lse) from the forward pass. The dense
    core keeps nothing (its residual is S x S a head): its forward runs
    again there."""
    import jax
    _, make = lm_config
    wf = make(**CORES[core])
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    backward = [(eqn, path) for eqn, path
                in equations(jax.make_jaxpr(fn)(*args).jaxpr)
                if eqn.primitive.name == "scan" and eqn.params["reverse"]]
    assert len(backward) == 1
    eqn, path = backward[0]
    kernels = {"fwd": 0, "bwd": 0}
    for inner, where in equations(eqn.params["jaxpr"].jaxpr, path):
        if inner.primitive.name == KERNEL[core] and "veles.core" in where:
            kernels["fwd" if "/veles.fwd.GQAttention." in where
                    else "bwd"] += 1
    layers = len(attention_layers(wf))
    assert kernels["bwd"] >= layers
    assert kernels["fwd"] == (2 * layers if core == "dense" else 0)
    root.lm.model.update({"attn_block": None, "attn_impl": None})


def test_metrics_published_names_the_sinks_a_step_filled():
    """``XLAStep._publish_metrics`` tells a unit which attributes this
    step set; a step that set none tells it nothing."""
    from veles.znicz_tpu.xla_step import XLAStep

    class Probe:
        told = None

        def metric_sinks(self):
            return [("a", "x"), ("b", "y")]

        def metrics_published(self, fresh):
            self.told = set(fresh)

    probe = Probe()
    step = XLAStep.__new__(XLAStep)
    step.forwards, step.evaluator, step.gds = [probe], None, []
    step._publish_metrics({"a": numpy.float32(1.5), "other": 3})
    assert probe.told == {"x"} and probe.x == 1.5
    probe.told = None
    step._publish_metrics({"other": 3})
    assert probe.told is None


def test_scopes_of_the_looped_step(lm_config):
    """Every looped unit's operations keep their unit scope; around
    them ``veles.pass`` and, for the backward's repeated forward,
    ``veles.recompute``; the one update under ``veles.update``."""
    _, make = lm_config
    wf = make()
    fn, args, _, _, _ = wf.xla_step._epoch_program(1)
    text = fn.lower(*args).as_text(debug_info=True)
    for needle in ("veles.pass/veles.fwd.GQAttention.GQAttention/",
                   "veles.pass/veles.recompute/veles.fwd.SwiGLUFFN.",
                   "veles.pass/veles.bwd.GDGQAttention.",
                   "veles.pass/veles.fwd.ExitGate.ExitGate/",
                   "veles.fwd.TokenDense.TokenDense/",
                   "veles.loss.EvaluatorLoopLM.evaluator/",
                   "veles.bwd.GDSwiGLUFFN.GDSwiGLUFFN/veles.update/",
                   "veles.bwd.GDTokenDense.GDTokenDense/veles.update/"):
        assert needle in text, needle
    # the solver runs outside the passes: nothing under veles.pass
    # is under veles.update
    assert "/veles.update" in text
    assert not [line for line in text.splitlines()
                if "veles.pass" in line and "/veles.update" in line]


def test_reference_walks_the_chain_rule_as_jax_grad_does(lm_config):
    """``sequence_gradients`` (one layer's pullback at a time, for the
    chip's memory) gives what ``jax.grad`` of the whole
    ``sequence_loss`` gives."""
    import jax
    _, make = lm_config
    wf = make()
    tree = ref.from_program(exported(wf), MODEL)
    _, (tokens, labels) = batches(wf)
    tables = ref.rope_tables(S, MODEL["head_dim"], MODEL["rope_theta"])
    with jax.default_matmul_precision("highest"):
        value, whole = jax.value_and_grad(ref.sequence_loss)(
            tree, tokens[0], labels[0], tables, MODEL, 16, 16)
    got, staged = ref.sequence_gradients(
        jax.device_put(tree), tokens[0], labels[0], MODEL)
    assert abs(got - float(value)) < 1e-4
    want, have = flat(whole), flat(staged)
    for path in want:
        scale = max(1.0, numpy.abs(want[path]).max())
        assert numpy.abs(have[path] - want[path]).max() < 2e-5 * scale, \
            path


# -- the exit distribution ---------------------------------------------------


def test_exit_mass_sums_to_one_and_matches_the_reference():
    import jax.numpy as jnp
    gen = numpy.random.RandomState(3)
    gate = jnp.asarray(gen.normal(0, 3.0, (4, 2, 5)), jnp.float32)
    p = numpy.exp(numpy.asarray(EvaluatorLoopLM.exit_log_mass(gate)))
    assert numpy.abs(p.sum(0) - 1.0).max() < 1e-6
    assert numpy.abs(p - numpy.asarray(ref.exit_mass(gate))).max() < 1e-6
    # saturated gates: no 0 * log 0
    far = jnp.asarray([[200.0], [-200.0], [0.0]], jnp.float32)
    logp = numpy.asarray(EvaluatorLoopLM.exit_log_mass(far))
    assert numpy.isfinite(logp).all()


def test_the_entropy_term_reaches_the_gate(lm_config):
    """With every exit's cross entropy equal, the expected loss does
    not depend on the gate (p sums to 1), so the gate's gradient is the
    entropy term's alone: zero at beta 0, not at beta 0.1; with unequal
    exits the weights' path adds to it."""
    import jax.numpy as jnp
    _, make = lm_config
    wf = make()
    ev = wf.evaluator
    gen = numpy.random.RandomState(4)
    gate = jnp.asarray(gen.normal(0, 1.0, (T, 2, S)), jnp.float32)

    def dgate(ce, beta):
        ev.entropy_weight = beta
        ctx = FlowContext(wf.xla_step.compiler, {}, {}, {}, None, True)
        ctx.set(wf.forwards[-2], "gate", gate)
        ctx.set(wf.loader, "minibatch_size", jnp.int32(2))
        return numpy.asarray(ev.loop_end(ctx, {
            "exit_ce": jnp.asarray(ce, jnp.float32),
            "exit_wrong": jnp.zeros((T,), jnp.int32)})["gate"])

    equal = numpy.full((T, 2, S), 2.5, numpy.float32)
    assert numpy.abs(dgate(equal, 0.0)).max() < 1e-8
    assert numpy.abs(dgate(equal, 0.1)).max() > 1e-5
    unequal = equal * numpy.arange(1, T + 1)[:, None, None]
    assert numpy.abs(dgate(unequal, 0.0)).max() > 1e-4


# -- the units' new switches, alone ------------------------------------------


def reference_sublayer(kind, model):
    import jax

    def block(p, x):
        eps = model["norm_eps"]
        with jax.default_matmul_precision("highest"):
            n = ref.rms(x, p["norm"], eps)
            if kind == "swiglu_ffn":
                out = ref.swiglu(n, p)
            else:
                tables = ref.rope_tables(x.shape[0], model["head_dim"],
                                         model["rope_theta"])
                out = ref.attention(n, p, model, tables, 16)
            return x + ref.rms(out, p["norm_out"], eps)

    return block


@pytest.mark.parametrize("case", ["attention", "swiglu"])
def test_sandwich_units_against_reference(case):
    """Output, input gradient and every parameter's gradient of a unit
    with the second gain (and, for the attention, without q/k norm)
    against ``jax.grad`` of the reference's sub-layer."""
    import jax
    import jax.numpy as jnp
    model = dict(LFM2_MODEL, heads=4, head_dim=16, norm_eps=1e-5)
    cls, kwargs = {
        "attention": (GQAttention, dict(heads=4, kv_heads=4,
                                        qk_norm=False, sandwich=True)),
        "swiglu": (SwiGLUFFN, dict(hidden=96, sandwich=True))}[case]
    feed, fwd, gd, x, err, comp = build(cls, **kwargs)
    params0, state0 = comp.gather_params(), comp.gather_state()
    assert ("q_norm" in params0[fwd.name]) is False
    assert "norm_out" in params0[fwd.name]
    y = numpy.asarray(xla_forward(comp, feed, fwd, params0, x))
    dx, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                               x, err)
    block = reference_sublayer(cls.MAPPING, model)
    p = {k: jnp.asarray(v) for k, v in params0[fwd.name].items()}

    def total(p, x):
        out = jax.vmap(lambda row: block(p, row))(x)
        return (out * err).sum(), out

    (_, want), (gp, gx) = jax.value_and_grad(
        total, argnums=(0, 1), has_aux=True)(p, jnp.asarray(x))
    assert numpy.abs(y - numpy.asarray(want)).max() < 2e-5
    assert numpy.abs(numpy.asarray(dx) - numpy.asarray(gx)).max() < 2e-4
    for name, g in gp.items():
        moved = numpy.asarray(params0[fwd.name][name]) \
            - numpy.asarray(params1[fwd.name][name])
        scale = max(1.0, float(numpy.abs(numpy.asarray(g)).max()))
        assert numpy.abs(moved - numpy.asarray(g)).max() \
            < 2e-4 * scale, name


# -- what stays as it was, and what is refused -------------------------------


def test_one_pass_builds_the_graph_of_before(lm_config):
    """``ut_steps`` 1, ``norm`` "pre", ``full_attention``: the layers'
    specs hold no new key, there is no loop, no gate, and the plain
    evaluator — the graph the accepted cells compile."""
    module, _ = lm_config
    root.lm.model.update({
        "layers": ["conv", "full_attention"], "dense_layers": 1,
        "norm": "pre", "ut_steps": 1, "moe_experts": 4, "moe_hidden": 16,
        "moe_top_k": 2, "kv_heads": 1})
    layers = module.build_layers()
    assert [spec["type"] for spec in layers] == [
        "embedding", "short_conv", "swiglu_ffn", "gqa_attention",
        "expert_ffn", "rms_norm", "token_dense"]
    assert set(layers[3]["->"]) == {
        "attn_block_size", "attn_impl", "pallas_tile", "heads",
        "kv_heads", "head_dim", "rope_theta", "eps"}
    assert set(layers[2]["->"]) == {"hidden", "eps"}
    prng.seed_all(5)
    wf = module.create_workflow()
    assert wf.loop is None
    assert type(wf.evaluator) is EvaluatorLM
    wf.initialize(device="cpu")
    assert wf.xla_step.compiler.loop is None
    attention = wf.forwards[3]
    assert set(attention.export_params()) == {
        "weights", "weights_out", "norm", "q_norm", "k_norm"}
    root.lm.model.update({"moe_experts": 0, "kv_heads": 2})


REFUSALS = {
    "unknown_operator": ({"layers": ["attention"]}, "plain_attention"),
    "unknown_norm": ({"norm": "post"}, "'pre' or 'sandwich'"),
    "loop_needs_pre_norm": ({"block": "post_ln", "layers": 2},
                            "needs block='pre_norm'"),
    "loop_over_experts": ({"dense_layers": 1, "moe_experts": 4,
                           "moe_hidden": 16}, "SwiGLU feed-forward"),
    "sandwich_over_conv": ({"ut_steps": 1,
                            "layers": ["conv", "plain_attention"]},
                           "norm='sandwich' is for attention"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_refusals(lm_config, case):
    module, _ = lm_config
    patch, text = REFUSALS[case]
    root.lm.model.update(patch)
    try:
        with pytest.raises(ValueError, match=text):
            module.create_workflow()
    finally:
        root.lm.model.update({"moe_experts": 0})


def test_looped_step_refuses_data_parallel(lm_config):
    module, _ = lm_config
    root.lm.parallel.update({"data": 2})
    try:
        with pytest.raises(ValueError, match="one chip"):
            module.build_layers()
    finally:
        root.lm.parallel.update({"data": 1})


# -- the planted faults of chip_grads_ouro.py --------------------------------


@pytest.fixture(scope="module")
def one_step():
    """One step of the tiny cell through ``chip_grads_ouro.py``'s own
    path: (module, reference tree, the batch, the cell, the program's
    change, the reference's)."""
    import chip_grads_ouro as grads
    cell = grads.one_step_cell(tiny=True)
    saved = root.lm.to_dict()       # the command line lands in root.lm
    try:
        initial, after, _ = grads.base.program_step(cell, 7, "cpu")
    finally:
        for key, value in saved.items():
            getattr(root.lm, key).update(value)
    model = cell["config"]["model"]
    tree = ref.from_program(initial["units"], model)
    program = grads.base.changes(tree, ref.from_program(after, model))
    want, _ = grads.base.reference_step(ref, tree, initial["train"], cell)
    return grads, tree, initial["train"][0], cell, program, want


def test_sound_step_reads_near_zero(one_step):
    grads, _, _, _, program, want = one_step
    d = grads.base.distances(program, want)
    assert set(d) == {"embedding", "layers.0.attn", "layers.0.ffn",
                      "layers.1.attn", "layers.1.ffn", "out_norm",
                      "gate", "head", "all"}
    assert max(d.values()) < 1e-4


#: fault: (the groups it must show in, the least it reads there)
FAULTS = {"pass_dropped": (("layers.0.attn", "layers.1.ffn"), 0.3),
          "four_updates": (("layers.0.attn", "head", "out_norm"), 0.2),
          "gate_detached": (("gate",), 0.3)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_caught(one_step, fault):
    """Each fault's step reads far from the sound reference's in the
    units it touches — and the sound program reads near zero there."""
    grads, tree, batch, cell, program, want = one_step
    got = grads.faulty_change(ref, tree, batch, cell, fault)
    d = grads.base.distances(got, want)
    sound = grads.base.distances(program, want)
    groups, least = FAULTS[fault]
    for group in groups:
        assert d[group] > least > 100 * sound[group], (group, d[group])


def test_bf16_logits_read_small_but_not_zero(one_step):
    """An exit's logits rounded to bf16 — on a TPU the program's own
    policy for the head's output — move the step by parts in ten
    thousand: above float32 rounding, far under any planted fault."""
    grads, tree, batch, cell, program, want = one_step
    got = grads.faulty_change(ref, tree, batch, cell, "logits_bf16")
    d = grads.base.distances(got, want)
    assert 1e-5 < d["all"] < 1e-2
