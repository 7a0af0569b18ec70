"""The step's hyperparameters stay on the device between dispatches.

``XLAStep._device_hyper`` hands every dispatch path (epoch scan, stream
windows, per step) a tree of DEVICE arrays, uploaded once and again
only when a host value changed: a numpy leaf among a jit call's
arguments is one host-to-device transfer a dispatch, and one per device
under a mesh. Checked here: how often it uploads
(``veles_xla_hyper_uploads_total``), what is left on the host among a
dispatch's arguments (``veles_xla_dispatch_host_leaves``), and that the
parameters come out bit-equal to a TWIN workflow whose dispatches are
handed the fresh host tree (``step._gather_hyper()``, what every
dispatch was handed before)."""

import functools

import jax
import numpy
import pytest

from veles import prng, telemetry
from veles.config import root

UPLOADS = "veles_xla_hyper_uploads_total"
HOST_LEAVES = "veles_xla_dispatch_host_leaves"
DISPATCHES = "veles_xla_dispatch_seconds"


def uploads(kind=None):
    match = {} if kind is None else {"kind": kind}
    return telemetry.get_registry().counter_total(UPLOADS, **match)


def host_leaves(kind):
    return telemetry.gauge(HOST_LEAVES, "", ("kind",)).labels(kind).value


def dispatches(kind):
    return sum(
        child.count for items, child in telemetry.histogram(
            DISPATCHES, "", ("kind", "warm")).children()
        if ("kind", kind) in items)


def program_misses():
    return telemetry.get_registry().counter_total(
        "veles_xla_cache_misses_total")


def hand_fresh_host_tree(wf):
    """Make ``wf`` the twin: every dispatch gets the host tree built
    for it, numpy leaves and all."""
    step = wf.xla_step
    step._device_hyper = lambda kind: step._gather_hyper()
    return wf


def params_of(wf):
    leaves, treedef = jax.tree_util.tree_flatten(wf.xla_step.params)
    return treedef, [numpy.asarray(leaf) for leaf in leaves]


def assert_same_params(wf, twin):
    treedef, leaves = params_of(wf)
    twin_treedef, twin_leaves = params_of(twin)
    assert treedef == twin_treedef
    assert leaves
    for mine, theirs in zip(leaves, twin_leaves):
        assert numpy.array_equal(mine, theirs)


def kept_leaves(step):
    return jax.tree_util.tree_leaves(step._hyper_device)


# -- the workflows -------------------------------------------------------

@pytest.fixture(autouse=True, scope="module")
def _restore_config():
    import veles.znicz_tpu.models.mnist  # noqa: F401 (defaults)
    import veles.znicz_tpu.models.transformer_lm  # noqa: F401
    saved = {node: node.to_dict() for node in (
        root.lm.loader, root.lm.model, root.lm.parallel,
        root.mnist.loader)}
    saved_epochs = (root.lm.decision.get("max_epochs"),
                    root.mnist.decision.get("max_epochs"))
    yield
    for node, values in saved.items():
        node.update(values)
    root.lm.decision.max_epochs, root.mnist.decision.max_epochs = \
        saved_epochs


def tiny_lm(name, layers=2, epochs=3, data_parallel=1):
    """An initialized tiny LM that dispatches one epoch at a time, so
    ``epochs`` is its number of scan-mode dispatches."""
    from veles.znicz_tpu.models import transformer_lm
    prng.seed_all(33)
    root.lm.loader.update({"minibatch_size": 8, "n_train": 32,
                           "n_valid": 8, "seq_len": 16})
    root.lm.model.update({"dim": 32, "heads": 2, "layers": layers,
                          "ffn_hidden": 64, "attn_block": None,
                          "attn_impl": None})
    root.lm.parallel.update({"seq": 1, "model": 1, "expert": 1,
                             "pipe": 1, "data": data_parallel})
    root.lm.decision.max_epochs = epochs
    wf = transformer_lm.create_workflow(name=name)
    wf.initialize(device="cpu")
    wf.xla_step.epochs_per_dispatch = 1
    assert wf.xla_step.scan_mode
    return wf


def tiny_mlp(name, mode, epochs=3, zero_fill=False):
    """The MNIST perceptron on the path ``mode`` names: ``scan`` (the
    dataset on the device), ``stream`` (windows shipped up) or ``step``
    (one minibatch a dispatch, gathered on the host)."""
    from veles.loader.fullbatch import FullBatchLoader
    from veles.loader.stream import ArrayStreamLoader
    from veles.znicz_tpu.models import datasets, mnist  # noqa: F401
    from veles.znicz_tpu.ops.cutter import ZeroFiller
    from veles.znicz_tpu.standard_workflow import StandardWorkflow
    prng.seed_all(44)
    tx, ty, vx, vy = datasets.load_mnist(n_train=120, n_valid=40)
    data = numpy.concatenate([vx.reshape(len(vx), -1),
                              tx.reshape(len(tx), -1)]).astype(
                                  numpy.float32)
    labels = numpy.concatenate([vy, ty])
    class_lengths = [0, len(vx), len(tx)]

    def factory(wf):
        if mode == "stream":
            return ArrayStreamLoader(
                wf, name="loader", minibatch_size=20, data=data,
                labels=labels, class_lengths=class_lengths)
        loader = FullBatchLoader(wf, name="loader", minibatch_size=20)
        loader.original_data.mem = data.copy()
        loader.original_labels.mem = labels.copy()
        loader.class_lengths = list(class_lengths)
        if mode == "step":
            loader.supports_device_gather = False
        return loader

    wf = StandardWorkflow(
        None, name=name, layers=root.mnist.layers,
        loader_factory=factory,
        decision_config={"max_epochs": epochs, "fail_iterations": 50})
    if zero_fill:
        filler = ZeroFiller(wf, target=wf.forwards[0], name="zerofiller")
        filler.link_from(wf.gds[0])
    wf.initialize(device="cpu")
    step = wf.xla_step
    step.epochs_per_dispatch = 1
    assert (step.scan_mode, step.stream_mode) == \
        (mode == "scan", mode == "stream")
    return wf


# -- one upload a run, nothing of it left on the host ----------------------

@pytest.mark.parametrize("layers", (2, 4))
def test_scan_dispatches_upload_once(layers):
    wf = tiny_lm("HyperScan%d" % layers, layers=layers)
    step = wf.xla_step
    n_hyper = len(jax.tree_util.tree_leaves(step._gather_hyper()))
    assert n_hyper == 11 * (4 * layers + 2)
    wf.run()
    assert dispatches("epoch") == 3
    assert uploads() == uploads("epoch") == 1
    # what a dispatch still takes from the host: the index and the
    # valid-count matrices of the two classes and the step offsets
    assert host_leaves("epoch") == 5
    assert len(kept_leaves(step)) == n_hyper
    assert all(isinstance(leaf, jax.Array) for leaf in kept_leaves(step))


def test_scan_parameters_equal_the_fresh_host_trees():
    wf = tiny_lm("HyperScanKept")
    wf.run()
    twin = hand_fresh_host_tree(tiny_lm("HyperScanTwin"))
    before = uploads()
    twin.run()
    assert uploads() == before
    # the twin's dispatches took every scalar from the host
    assert host_leaves("epoch") == 5 + 11 * 10
    assert_same_params(wf, twin)


#: host leaves a dispatch of the perceptron keeps: a window's valid
#: counts; a minibatch's data, labels and size
MLP_HOST_LEAVES = {"stream": 1, "step": 3}


@pytest.mark.parametrize("mode", ("stream", "step"))
def test_stream_and_per_step_paths(mode):
    wf = tiny_mlp("Hyper_%s" % mode, mode)
    n_hyper = len(jax.tree_util.tree_leaves(wf.xla_step._gather_hyper()))
    assert n_hyper == 11 * len(wf.gds)
    wf.run()
    assert uploads() == uploads(mode) == 1
    assert host_leaves(mode) == MLP_HOST_LEAVES[mode]
    if mode == "stream":
        assert dispatches("stream") == 3
    twin = hand_fresh_host_tree(tiny_mlp("HyperTwin_%s" % mode, mode))
    twin.run()
    assert uploads() == 1
    assert host_leaves(mode) == MLP_HOST_LEAVES[mode] + n_hyper
    assert_same_params(wf, twin)


# -- a host-side change reaches the next dispatch -------------------------

def dispatch_edit_dispatch(wf, edit):
    """Two scan-mode dispatches, ``edit(wf)``, one more: the host's
    edits between dispatches that a rollback or a mask editor makes."""
    step = wf.xla_step
    step._dispatch_epoch()
    step._dispatch_epoch()
    edit(wf)
    step._dispatch_epoch()
    return wf


def cut_learning_rate(wf):
    for gd in wf.gds:
        gd.learning_rate *= 0.25


def mask_one_more_entry(wf):
    mask = wf.forwards[0].zero_mask
    mask.map_write()
    assert mask.mem[3, 5] == 1.0
    mask.mem[3, 5] = 0.0


@pytest.mark.parametrize("what", ("learning_rate", "zero_mask"))
def test_host_edit_reaches_the_next_dispatch(what):
    build, edit = {
        "learning_rate": (tiny_lm, cut_learning_rate),
        "zero_mask": (functools.partial(tiny_mlp, mode="scan",
                                        zero_fill=True),
                      mask_one_more_entry)}[what]
    wf = build("HyperEdit_" + what)
    step = wf.xla_step
    step._dispatch_epoch()
    step._dispatch_epoch()
    assert uploads() == 1
    fn = step._epoch_program()[0]
    misses, traced = program_misses(), fn._cache_size()
    edit(wf)
    step._dispatch_epoch()
    assert uploads() == 2
    # the same program took the new values: none built, none traced
    assert program_misses() == misses
    assert step._epoch_program()[0] is fn
    assert fn._cache_size() == traced
    step._dispatch_epoch()
    assert uploads() == 2

    twin = hand_fresh_host_tree(build("HyperEditTwin_" + what))
    dispatch_edit_dispatch(twin, edit).xla_step._dispatch_epoch()
    assert_same_params(wf, twin)
    unedited = dispatch_edit_dispatch(
        build("HyperEditNot_" + what), lambda wf: None)
    unedited.xla_step._dispatch_epoch()
    with pytest.raises(AssertionError):
        assert_same_params(wf, unedited)
    if what == "zero_mask":
        step.sync_host()
        assert wf.forwards[0].weights.map_read().mem[3, 5] == 0.0


# -- the kept tree goes where params and state are placed anew -------------

@pytest.mark.parametrize("how", ("restore_state", "initialize", "mesh"))
def test_replacing_params_drops_the_kept_tree(how):
    wf = tiny_lm("HyperDrop_" + how, epochs=8)
    step = wf.xla_step
    step._dispatch_epoch()
    assert uploads() == 1 and step._hyper_device is not None
    if how == "restore_state":
        wf.restore_state(wf.checkpoint_state())
    elif how == "initialize":
        wf.initialize(device="cpu")
        step.epochs_per_dispatch = 1
    else:
        from veles.znicz_tpu import parallel
        parallel.setup_data_parallel(
            wf, parallel.make_mesh({"data": 4}, wf.device.jax_devices))
    assert step._hyper_device is None
    step._dispatch_epoch()
    assert uploads() == 2
    want = {step.batch_sharding.mesh.devices.flat[i] for i in range(4)} \
        if how == "mesh" else {wf.device.jax_devices[0]}
    for leaf in kept_leaves(step):
        assert leaf.sharding.device_set == want
    step._dispatch_epoch()
    assert uploads() == 2


# -- four devices ---------------------------------------------------------

def test_data_parallel_keeps_a_replicated_tree():
    one = tiny_lm("HyperOneDevice")
    one.run()
    one_device_leaves = host_leaves("epoch")
    wf = tiny_lm("HyperDP4", data_parallel=4)
    step = wf.xla_step
    mesh = step.batch_sharding.mesh
    assert mesh.devices.size == 4
    before = uploads()
    wf.run()
    assert uploads() == before + 1
    leaves = kept_leaves(step)
    assert len(leaves) == 11 * 10
    for leaf in leaves:
        assert leaf.sharding.is_fully_replicated
        assert leaf.sharding.device_set == set(mesh.devices.flat)
    # under the mesh the index and valid-count matrices are put on the
    # devices before the call, so only the step offsets are left
    assert host_leaves("epoch") == 1 <= one_device_leaves
    for mine, theirs in zip(wf.decision.history, one.decision.history):
        for cls in ("validation", "train"):
            assert abs(mine[cls]["loss"] - theirs[cls]["loss"]) < 1e-4
