"""The compiled step names its work: every unit traces under
``veles.<role>.<Class>.<name>``, the solver under ``veles.update``, the
attention proper under ``veles.core``, and the three programs are
``veles_step``, ``veles_epoch_scan`` and ``veles_window_scan`` — what a
device trace shows (``tf_op``, ``XLA Modules``) and what the benchmark's
``reduce/scopes.py`` reads. Checked on the lowered programs' debug
info: nothing runs."""

import re

import numpy
import pytest

from veles import prng
from veles.config import root
from veles.loader.base import CLASS_TRAIN, CLASS_VALID
from veles.znicz_tpu.nn_units import GradientDescentBase
from veles.znicz_tpu.ops.attention import (
    GDMultiHeadAttention, MultiHeadAttention)

PROGRAMS = ("epoch", "step", "window", "validation")


def lowered(fn, *args):
    """(module name, the op names of the lowered program that hold a
    ``veles.`` scope)."""
    text = fn.lower(*args).as_text(debug_info=True)
    module = re.search(r"module @(\S+)", text).group(1)
    return module, set(re.findall(r'loc\("([^"]*veles\.[^"]*)"', text))


def program(step, which):
    """(expected module name, lowered module name, scoped op names,
    units traced) of one of the programs ``StepCompiler`` builds."""
    compiler, spec = step.compiler, step._batch_spec
    epoch_fn, args, _, _, _ = step._epoch_program(1)
    params, state, full, idxs, valids, hyper, key, offsets = args
    transform = getattr(step.loader, "xla_batch_transform", None)
    if which == "epoch":
        return ("jit_veles_epoch_scan",) + lowered(epoch_fn, *args) \
            + (step.train_units,)
    if which == "validation":   # the epoch program's validation segment
        seg = "c%d" % CLASS_VALID
        fn = compiler.build_epoch_scan(
            spec, [(seg, False, step.eval_units)], transform)
        return ("jit_veles_epoch_scan",) + lowered(
            fn, params, state, full, {seg: idxs[seg]},
            {seg: valids[seg]}, hyper, key, offsets) + (step.eval_units,)
    batch = step._gather_batch()
    if which == "step":
        fn = compiler.compile(spec, train=True)
        return ("jit_veles_step",) + lowered(
            fn, params, state, batch, hyper, key) + (step.train_units,)
    # a window holds minibatches as the loader stores them (the
    # transform runs inside the program): the first one of the epoch
    rows = idxs["c%d" % CLASS_TRAIN][0, 0]
    stacked = {name: bank[rows][None] for name, bank in full.items()}
    fn = compiler.compile_window_scan(
        spec, True, step.train_units,
        transform or (lambda name, t, train=False: t))
    return ("jit_veles_window_scan",) + lowered(
        fn, params, state, stacked,
        numpy.asarray([batch["batch_size"]], numpy.int32), hyper,
        key) + (step.train_units,)


def check(wf, which):
    step = wf.xla_step
    want, module, names, units = program(step, which)
    assert module == want
    roles = {id(u): "fwd" for u in wf.forwards}
    roles[id(wf.evaluator)] = "loss"
    roles.update((id(u), "bwd") for u in wf.gds if u is not None)
    assert units
    for unit in units:
        role = roles[id(unit)]
        assert unit.scope_role == role, unit.name
        scope = "veles.%s.%s.%s/" % (role, type(unit).__name__, unit.name)
        mine = [n for n in names if scope in n]
        # (at inference a unit may be the identity: dropout)
        assert mine or which == "validation", \
            "no operation under %s" % scope
        updates = [n for n in mine if "/veles.update/" in n]
        if role == "bwd" and getattr(unit.forward, "weights", None):
            assert updates, "%s: no veles.update" % scope
        if role != "bwd":
            assert not updates, updates[:3]
        if isinstance(unit, (MultiHeadAttention, GDMultiHeadAttention)):
            assert any("/veles.core/" in n for n in mine), scope
        else:
            assert not any("/veles.core/" in n for n in mine), scope
    # a sub-scope never stands alone
    for n in names:
        if "veles.update" in n or "veles.core" in n:
            assert re.search(r"veles\.(fwd|bwd)\.[^/]+/(.*/)?"
                             r"veles\.(update|core)/", n), n
    if which == "validation":
        assert [n for n in names if "veles.fwd." in n]
        assert [n for n in names if "veles.loss." in n]
        assert not [n for n in names if "veles.bwd." in n]
        assert not [n for n in names if "veles.update" in n]
    else:
        assert any(isinstance(u, GradientDescentBase) for u in units)


def tiny_lm(attn):
    from veles.znicz_tpu.models import transformer_lm
    prng.seed_all(25)
    saved_loader = root.lm.loader.to_dict()
    saved_model = root.lm.model.to_dict()
    root.lm.loader.update({"minibatch_size": 8, "n_train": 32,
                           "n_valid": 8, "seq_len": 16})
    root.lm.model.update({
        "dim": 32, "heads": 2, "layers": 2, "ffn_hidden": 64,
        "attn_block": None if attn == "dense" else 8,
        "attn_impl": "pallas" if attn == "pallas" else None})
    try:
        wf = transformer_lm.create_workflow(name="ScopesLM_" + attn)
        wf.initialize(device="cpu")
    finally:
        root.lm.loader.update(saved_loader)
        root.lm.model.update(saved_model)
    return wf


def tiny_convnet():
    from veles.znicz_tpu.models import imagenet
    from veles.znicz_tpu.standard_workflow import StandardWorkflow
    prng.seed_all(25)
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    layers = [
        {"type": "conv_relu", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
         "<-": dict(gd)},
        {"type": "norm", "->": {"n": 3, "alpha": 1e-4, "beta": 0.75,
                                "k": 2.0}},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2, "sliding": 2}},
        {"type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"type": "all2all_relu", "->": {"output_sample_shape": 8},
         "<-": dict(gd)},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": dict(gd)},
    ]
    saved = root.imagenet.loader.to_dict()
    root.imagenet.loader.update({
        "minibatch_size": 4, "n_train": 16, "n_valid": 8,
        "n_classes": 4, "scale": (20, 20), "crop": (16, 16)})
    try:
        wf = StandardWorkflow(
            None, name="ScopesConv", layers=layers,
            loader_factory=imagenet.make_loader,
            decision_config={"max_epochs": 1})
        wf.initialize(device="cpu")
    finally:
        root.imagenet.loader.update(saved)
    return wf


@pytest.fixture(scope="module")
def workflows():
    built = {}

    def get(key):
        if key not in built:
            built[key] = tiny_convnet() if key == "convnet" \
                else tiny_lm(key)
        return built[key]
    return get


@pytest.mark.parametrize("which", PROGRAMS)
@pytest.mark.parametrize("attn", ("dense", "scan", "pallas"))
def test_lm_programs_carry_unit_scopes(workflows, attn, which):
    wf = workflows(attn)
    mha = [f for f in wf.forwards if isinstance(f, MultiHeadAttention)]
    assert len(mha) == 2
    check(wf, which)
    # the path under test is the one the case names
    ctx = _Ctx(wf.xla_step.compiler)
    assert all(f._traced_mode(ctx, 16) == attn for f in mha)


class _Ctx:
    """What ``MultiHeadAttention._traced_mode`` asks of a flow
    context."""

    def __init__(self, compiler):
        self._compiler = compiler


@pytest.mark.parametrize("which", PROGRAMS)
def test_convnet_programs_carry_unit_scopes(workflows, which):
    wf = workflows("convnet")
    kinds = {type(u).__name__ for u in wf.xla_step.train_units}
    assert {"ConvRELU", "LRNormalizerForward", "MaxPooling",
            "All2AllRELU", "All2AllSoftmax", "GDRELUConv",
            "LRNormalizerBackward", "GDMaxPooling", "GDRELU",
            "GDSoftmax", "EvaluatorSoftmax"} <= kinds
    check(wf, which)
