"""LM sharding from config alone (VERDICT item: ring/SP reachable
without touching units) + Megatron-style TP over the model axis.
Runs on the 8-device virtual CPU mesh (conftest)."""

import numpy
import pytest

import veles.prng as prng
from veles.config import root


@pytest.fixture(autouse=True, scope="module")
def _restore_lm_config():
    import veles.znicz_tpu.models.mnist  # noqa: defaults
    import veles.znicz_tpu.models.transformer_lm  # noqa: defaults
    saved_loader = root.lm.loader.to_dict()
    saved_epochs = root.lm.decision.get("max_epochs")
    # the combo tests borrow test_service.make_wf, which mutates
    # root.mnist — this module runs BEFORE test_mnist_functional
    saved_mnist = root.mnist.loader.to_dict()
    saved_mnist_epochs = root.mnist.decision.get("max_epochs")
    yield
    root.lm.loader.update(saved_loader)
    root.lm.decision.max_epochs = saved_epochs
    root.mnist.loader.update(saved_mnist)
    root.mnist.decision.max_epochs = saved_mnist_epochs


def _run_lm(name, parallel=None, max_epochs=3):
    prng.seed_all(777)
    from veles.znicz_tpu.models import transformer_lm
    saved = root.lm.parallel.to_dict()
    root.lm.loader.update({"minibatch_size": 32, "n_train": 256,
                           "n_valid": 64})
    root.lm.decision.max_epochs = max_epochs
    root.lm.parallel.update(parallel or
                            {"seq": 1, "model": 1, "data": 1})
    try:
        wf = transformer_lm.create_workflow(name=name)
        wf.initialize(device="cpu")
        wf.run()
    finally:
        root.lm.parallel.update(saved)
    return wf


@pytest.fixture(scope="module")
def dense_wf():
    return _run_lm("LMDense")


def _history(wf):
    return [h["validation"]["metric"] for h in wf.decision.history]


def test_lm_dense_learns(dense_wf):
    hist = _history(dense_wf)
    assert hist[-1] < hist[0], hist


def test_lm_ring_from_config(dense_wf):
    """root.lm.parallel.seq=8 routes attention through the ppermute
    ring; same seeds => same training trajectory as dense attention
    (ring softmax is numerically exact up to fp reassociation)."""
    wf = _run_lm("LMRing", {"seq": 8})
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    mha = [f for f in wf.forwards
           if isinstance(f, MultiHeadAttention)]
    assert mha and all(f.seq_mesh is not None for f in mha), \
        "config did not engage the ring path"
    ring, dense = _history(wf), _history(dense_wf)
    assert ring[-1] < ring[0]
    for a, b in zip(ring, dense):
        assert abs(a - b) < 0.05, (ring, dense)
    # the ring's neighbour hops must survive into the partitioned HLO
    from veles.znicz_tpu import parallel
    parallel.assert_collectives(wf.xla_step, ["collective-permute"])


def test_lm_ring_flash_inner_from_config(dense_wf):
    """root.lm.parallel.seq=4 + root.lm.model.attn_impl="scan" runs
    every ring step's LOCAL block through the flash kernels
    (parallel/ring.py inner-block composition, round 4); training
    trajectory still matches dense. (The Pallas inner is
    parity-tested at function level in test_parallel.py — interpret
    mode is too slow for a whole workflow.)"""
    saved_impl = root.lm.model.get("attn_impl")
    root.lm.model.attn_impl = "scan"
    try:
        wf = _run_lm("LMRingFlash", {"seq": 4})
    finally:
        root.lm.model.attn_impl = saved_impl
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    mha = [f for f in wf.forwards
           if isinstance(f, MultiHeadAttention)]
    assert mha and all(f.seq_mesh is not None for f in mha)
    # ...and the flash inner really engaged (seq_mesh alone is also
    # true for the dense-inner ring)
    assert all(f.attn_impl == "scan" for f in mha)

    class _Ctx:   # minimal resolver probe
        _compiler = wf.xla_step.compiler
    for f in mha:
        inner, block = f._ring_inner(_Ctx())
        assert inner == "scan" and block >= 1, (inner, block)
    ring, dense = _history(wf), _history(dense_wf)
    assert ring[-1] < ring[0]
    for a, b in zip(ring, dense):
        assert abs(a - b) < 0.05, (ring, dense)
    from veles.znicz_tpu import parallel
    parallel.assert_collectives(wf.xla_step, ["collective-permute"])


def test_lm_tensor_parallel_from_config(dense_wf):
    """root.lm.parallel.model=4 shards qkv/up column-wise and out/down
    row-wise; GSPMD inserts the collectives. Same math => same
    trajectory as the unsharded run."""
    wf = _run_lm("LMTP", {"model": 4})
    step = wf.xla_step
    assert step.param_sharding_map, "TP sharding map not installed"
    # params are REALLY sharded on the mesh
    import jax
    from veles.znicz_tpu.ops.attention import TransformerFFN
    ffn = next(f for f in wf.forwards if isinstance(f, TransformerFFN))
    leaf = step.params[ffn.name]["weights"]
    assert len(leaf.sharding.device_set) == 4
    spec = leaf.sharding.spec
    assert tuple(spec) == (None, "model"), spec
    tp, dense = _history(wf), _history(dense_wf)
    assert tp[-1] < tp[0]
    for a, b in zip(tp, dense):
        assert abs(a - b) < 0.05, (tp, dense)
    # row-sharded contractions must all-reduce in the partitioned HLO
    from veles.znicz_tpu import parallel
    parallel.assert_collectives(wf.xla_step, ["all-reduce"])


def test_lm_dp_plus_tp(dense_wf):
    """2-way data x 4-way model on one mesh."""
    wf = _run_lm("LMDPTP", {"data": 2, "model": 4})
    step = wf.xla_step
    assert step.batch_sharding is not None
    assert step.param_sharding_map
    from veles.znicz_tpu import parallel
    parallel.assert_collectives(step, ["all-reduce"])
    hist, dense = _history(wf), _history(dense_wf)
    assert hist[-1] < hist[0]
    for a, b in zip(hist, dense):
        assert abs(a - b) < 0.05, (hist, dense)


def test_lm_pallas_attention_under_dp_tp(dense_wf):
    """The Pallas attention path on a data x model mesh. On the chip
    GSPMD refuses to partition a Mosaic kernel, so under a mesh the
    unit runs it per shard inside shard_map (batch over data, heads
    over model); here the same wrapper runs the interpreted kernel,
    and the trajectory must still match the unsharded dense run."""
    saved = {k: root.lm.model.get(k)
             for k in ("attn_impl", "attn_block")}
    root.lm.model.update({"attn_impl": "pallas", "attn_block": 16})
    try:
        wf = _run_lm("LMPallasDPTP", {"data": 2, "model": 2})
    finally:
        root.lm.model.update(saved)
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    mha = [f for f in wf.forwards if isinstance(f, MultiHeadAttention)]
    # the axes come from the setup_* calls, not from names the unit
    # assumes
    assert mha and all((f.kernel_batch_axis, f.kernel_head_axis)
                       == ("data", "model") for f in mha)
    hlo = wf.xla_step.lowered_epoch_hlo(optimized=False)
    assert "sdy.manual_computation" in hlo, \
        "the kernel was not wrapped in shard_map"
    hist, dense = _history(wf), _history(dense_wf)
    assert hist[-1] < hist[0]
    for a, b in zip(hist, dense):
        assert abs(a - b) < 0.05, (hist, dense)


def test_lm_sp_plus_dp(dense_wf):
    """2-way data x 4-way seq on ONE composed mesh: the ring shards
    the sequence while the batch shards over data."""
    wf = _run_lm("LMSPDP", {"data": 2, "seq": 4})
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    mha = next(f for f in wf.forwards
               if isinstance(f, MultiHeadAttention))
    assert mha.seq_mesh is not None
    assert mha.seq_batch_axis == "data"
    assert dict(mha.seq_mesh.shape) == {"data": 2, "seq": 4}
    from veles.znicz_tpu import parallel
    parallel.assert_collectives(
        wf.xla_step, ["collective-permute", "all-reduce"])
    hist, dense = _history(wf), _history(dense_wf)
    assert hist[-1] < hist[0]
    for a, b in zip(hist, dense):
        assert abs(a - b) < 0.05, (hist, dense)


def test_dp_snapshot_resume_rollback_combo(tmp_path):
    """DP mesh x snapshotter x rollback together; resume re-places the
    params on the mesh."""
    import jax
    from tests.test_service import make_wf
    from veles.snapshotter import load_snapshot
    from veles.znicz_tpu import parallel

    wf = make_wf("DPSnapT", backend="cpu", snapdir=str(tmp_path))
    parallel.setup_data_parallel(wf, parallel.make_mesh({"data": 8}))
    wf.link_rollback(lr_cut=0.5, blowup_factor=50.0)
    wf.run()
    assert wf.snapshotter.destination

    state = load_snapshot(wf.snapshotter.destination)
    wf2 = make_wf("DPSnapT2", backend="cpu", max_epochs=3)
    parallel.setup_data_parallel(wf2, parallel.make_mesh({"data": 8}))
    wf2.restore_state(state)
    wf2.run()
    assert wf2.decision.epoch_number == 3
    leaf = jax.tree_util.tree_leaves(wf2.xla_step.params)[0]
    assert len(leaf.sharding.device_set) == 8


def test_tp_snapshot_resume(tmp_path, dense_wf):
    """TP-sharded LM params checkpoint and restore onto the mesh."""
    import jax
    from veles.snapshotter import load_snapshot

    wf = _run_lm("LMTPSnap", {"model": 4})
    from veles.snapshotter import Snapshotter
    snap = Snapshotter(wf, name="snap", directory=str(tmp_path))
    snap.decision = wf.decision
    path = snap.export_snapshot()
    state = load_snapshot(path)

    wf2 = _run_lm("LMTPSnap2", {"model": 4}, max_epochs=1)
    wf2.restore_state(state)
    step = wf2.xla_step
    from veles.znicz_tpu.ops.attention import TransformerFFN
    ffn = next(f for f in wf2.forwards
               if isinstance(f, TransformerFFN))
    leaf = step.params[ffn.name]["weights"]
    # restored AND still TP-sharded over the model axis
    assert len(leaf.sharding.device_set) == 4
    assert tuple(leaf.sharding.spec) == (None, "model")
    numpy.testing.assert_allclose(
        numpy.asarray(leaf),
        state["params"][ffn.name]["weights"], atol=1e-6)


def test_tp_grad_sync_accounting(dense_wf):
    """grad_sync_bytes still reports the full trainable payload."""
    from veles.znicz_tpu import parallel
    import jax
    host = jax.tree_util.tree_map(
        lambda a: numpy.asarray(a), dense_wf.xla_step.params)
    assert parallel.grad_sync_bytes(host) > 0
