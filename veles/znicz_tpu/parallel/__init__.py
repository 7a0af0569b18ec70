"""Device mesh / sharding / collectives — the distribution layer.

This replaces the reference's ZeroMQ+Twisted master↔slave fabric
(SURVEY.md §2.2, §5.8) with the TPU-native story: a
``jax.sharding.Mesh`` over the chips, named-sharding annotations on the
step's inputs, and XLA-inserted collectives riding ICI. Data
parallelism falls out of batch sharding (the weight-gradient
contraction over the sharded batch axis becomes an all-reduce — the
compiled analogue of ``apply_data_from_slave`` weight averaging, but
synchronous, SURVEY.md §3.3 note). Axis conventions:

* ``data``   — batch / data parallelism (DP)
* ``model``  — tensor parallelism (TP) for the Transformer units
* ``seq``    — sequence/context parallelism (ring attention)
* ``expert`` — expert parallelism (EP) for MoE units
* ``pipe``   — pipeline parallelism (PP) for the block-stack unit

Multi-host: `jax.distributed.initialize` + the same mesh spanning all
processes; DCN handles the inter-slice hops. See ``veles/server.py``
for the retained job-queue compat layer.
"""

import numpy


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None):
    """Join a multi-host mesh: thin wrapper over
    ``jax.distributed.initialize`` (SURVEY.md §5.8 "TPU-native
    equivalent"). After it returns, ``jax.devices()`` spans every
    host's chips and ``make_mesh`` lays axes across them — the SPMD
    analogue of the reference's master/slave topology, with DCN
    carrying the inter-host legs of the collectives. On Cloud TPU
    pods all three arguments auto-detect (pass nothing)."""
    import jax
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = int(num_processes)
    if process_id is not None:
        kwargs["process_id"] = int(process_id)
    jax.distributed.initialize(**kwargs)
    return jax.process_index(), jax.process_count()


#: collective op mnemonics -> the HLO opcodes that implement them
#: (async ops appear as <op>-start/<op>-done pairs; counting starts
#: avoids double-counting)
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter")


def collective_counts(step, n_epochs=1, hlo=None):
    """{opcode: count} of cross-device collectives in the OPTIMIZED
    (post-GSPMD-partitioning) HLO of the workflow step's next
    scan-mode dispatch — the strongest hardware-free evidence that a
    parallel mode actually distributes work instead of silently
    falling back to replication (VERDICT r2 "weak" #6): DP must show
    all-reduce (gradient sync), TP all-reduce (row-sharded
    contractions), EP all-to-all (token routing), ring-SP / PP
    collective-permute (neighbour hops). ``step``: an XLAStep whose
    shardings are already set up (``setup_*`` + ``refresh_device``).
    ``hlo``: that optimized HLO text, from a caller that already holds
    it (lowering it again means compiling again)."""
    import re
    text = hlo if hlo is not None else step.lowered_epoch_hlo(
        optimized=True, n_epochs=n_epochs)
    counts = {}
    for op in _COLLECTIVE_OPS:
        # match "op(" and the async "op-start(" spelling, not substrings
        # of longer opcodes
        n = len(re.findall(r"\b%s(?:-start)?\(" % re.escape(op), text))
        if n:
            counts[op] = n
    return counts


def assert_collectives(step, expected, n_epochs=1, hlo=None):
    """Assert the step's optimized HLO contains >=1 of each expected
    collective (and return the full counts). ``expected``: iterable of
    opcodes from ``_COLLECTIVE_OPS``; ``hlo`` as in
    :func:`collective_counts`."""
    counts = collective_counts(step, n_epochs=n_epochs, hlo=hlo)
    missing = [op for op in expected if not counts.get(op)]
    if missing:
        raise AssertionError(
            "expected collectives %s absent from the partitioned HLO "
            "(found %s) — the sharding silently degenerated to "
            "replication" % (missing, counts))
    return counts


def make_mesh(axes=None, devices=None):
    """Build a Mesh. ``axes``: dict name->size (ordered); ``None``
    means one 'data' axis over all visible devices. ``devices``
    defaults to jax's default platform; a workflow passes its own
    Device's set so ``-d tpu`` can only mesh TPU chips. A mesh smaller
    than the host takes the FIRST devices in enumeration order and
    leaves the rest idle — logged, because nothing else shows it."""
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    if axes is None:
        axes = {"data": len(devices)}
    names = tuple(axes)
    sizes = tuple(int(axes[n]) for n in names)
    n_need = int(numpy.prod(sizes))
    if n_need > len(devices):
        raise ValueError("mesh %r needs %d devices, have %d"
                         % (axes, n_need, len(devices)))
    if n_need < len(devices):
        import logging
        logging.getLogger("veles.parallel").info(
            "mesh %r uses %d of %d %s devices; the rest stay idle",
            axes, n_need, len(devices), devices[0].platform)
    grid = numpy.array(devices[:n_need], dtype=object).reshape(sizes)
    return Mesh(grid, names)


def batch_sharding(mesh, axis="data"):
    """Shard dim 0 (batch) over the data axis; replicate the rest."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(axis))


def replicated(mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def grad_sync_bytes(params):
    """The per-step gradient all-reduce volume (the analogue of the
    reference's 'slave grad-sync bandwidth' metric, SURVEY.md §6):
    bytes of every trainable parameter, which is what the DP
    all-reduce moves per step per link direction."""
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    return int(sum(numpy.prod(l.shape) * l.dtype.itemsize
                   for l in leaves))


def kernel_per_shard(kernel, mesh, batch_axis, head_axis, in_kinds,
                     out_kinds):
    """A Pallas attention kernel as a step over ``mesh`` needs it.
    GSPMD cannot split a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"),
    so under a mesh the call runs per shard: batch over
    ``batch_axis``, heads over ``head_axis`` (either may be None: that
    dim is then whole on every device, as it is over every other axis
    of the mesh) — attention is independent per sample and per head,
    so the body needs no collective. ``in_kinds`` / ``out_kinds``: one
    letter per argument / result, "t" for a (B,H,S,dh) tensor and "r"
    for a (B,H,S) row statistic. No mesh -> ``kernel`` unchanged."""
    if mesh is None:
        return kernel
    from jax.sharding import PartitionSpec as P
    from veles.znicz_tpu.parallel.ring import _shard_map
    spec = {"t": P(batch_axis, head_axis, None, None),
            "r": P(batch_axis, head_axis, None)}
    return _shard_map(
        mesh=mesh,
        in_specs=tuple(spec[kind] for kind in in_kinds),
        out_specs=tuple(spec[kind] for kind in out_kinds))(kernel)


def _attention_units(workflow):
    from veles.znicz_tpu.ops.attention import MultiHeadAttention
    return [fwd for fwd in workflow.forwards
            if isinstance(fwd, MultiHeadAttention)]


def setup_data_parallel(workflow, mesh=None, axis="data",
                        refresh=True):
    """Configure an initialized XLA workflow for DP over ``mesh``:
    batch tensors sharded over ``axis``, params/state replicated
    (clears any earlier TP sharding map — pass ``refresh=False`` when
    composing with :func:`setup_tensor_parallel`, which re-places)."""
    if mesh is None:
        mesh = make_mesh()
    step = workflow.xla_step
    if step is None:
        raise ValueError("workflow has no xla_step (numpy backend?)")
    step.sync_host()  # device values are the truth mid-run
    step.batch_sharding = batch_sharding(mesh, axis)
    step.param_sharding = replicated(mesh)
    step.param_sharding_map = {}
    for fwd in _attention_units(workflow):
        fwd.kernel_batch_axis = axis
        fwd.kernel_head_axis = None     # went with the TP map
    workflow.device.mesh = mesh
    if refresh:
        step.refresh_device()
    return mesh


def setup_sequence_parallel(workflow, mesh, axis="seq",
                            batch_axis=None):
    """Route every attention unit through the ring path (SP): K/V
    blocks stream around ``axis`` via ``ppermute`` instead of
    materialising (B,H,S,S) scores — see ``parallel/ring.py``. Call
    after ``initialize`` and before the first step (the choice bakes
    into the trace). The axis size must divide the sequence length.
    ``batch_axis`` names the mesh axis the batch dim is sharded over
    when composing SP with DP on one mesh."""
    n = mesh.shape[axis]
    units = _attention_units(workflow)
    if not units:
        raise ValueError("no attention units to sequence-parallelize")
    for fwd in units:
        s = fwd.input.shape[1]
        if s % n:
            raise ValueError(
                "%s axis size %d does not divide sequence "
                "length %d" % (axis, n, s))
        fwd.seq_mesh = mesh
        fwd.seq_axis = axis
        fwd.seq_batch_axis = batch_axis
    return mesh


def setup_expert_parallel(workflow, mesh, axis="expert", refresh=True,
                          routing="gather"):
    """Expert parallelism for MoE units: the leading (expert) dim of
    every stacked expert parameter — and its momentum state — is
    sharded over ``axis``, so each device holds E/n experts. The
    router stays replicated (every device routes every token — the
    (D,E) matmul is negligible).

    ``routing`` picks how tokens reach their expert's device:

    * ``"gather"`` (default): parameters shard, the dense
      dispatch/combine einsums stay as written, and GSPMD partitions
      them — which at our shapes lowers to an **all-gather of the
      token block** onto every expert shard. Fully distributed compute
      and expert memory, but O(E) token bandwidth: the small-mesh
      choice.
    * ``"alltoall"``: the canonical GShard exchange, explicit
      ``shard_map`` + ``lax.all_to_all`` (``parallel/expert.py``) —
      O(tokens) bandwidth, the at-scale choice. Tokens shard over
      EVERY mesh axis inside the exchange (the non-expert axes are
      derived from the mesh — nothing to pass when composing with
      DP/TP/SP on one mesh); the batch must divide the total device
      count. Capacity/aux become per-token-shard at >1 shards (see
      ``parallel/expert.py`` docstring)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles.znicz_tpu.ops.moe import MoEFFN
    if routing not in ("gather", "alltoall"):
        raise ValueError("routing must be 'gather' or 'alltoall', "
                         "got %r" % (routing,))
    step = workflow.xla_step
    if step is None:
        raise ValueError("workflow has no xla_step (numpy backend?)")
    n = mesh.shape[axis]
    smap = {}
    touched = 0
    for i, fwd in enumerate(workflow.forwards):
        if not isinstance(fwd, MoEFFN):
            continue
        if fwd.experts % n:
            raise ValueError(
                "%s: %s axis size %d does not divide expert count %d"
                % (fwd.name, axis, n, fwd.experts))
        if routing == "alltoall":
            fwd.ep_mesh = mesh
            fwd.ep_axis = axis
            # tokens shard over EVERY non-expert mesh axis inside the
            # exchange (merely replicating them along any axis would
            # duplicate the token exchange across its ranks — the
            # O(replication) traffic alltoall mode exists to
            # eliminate); expert/router grads psum back over these
            # axes in the backward (parallel/expert.py)
            fwd.ep_batch_axes = tuple(
                a for a in mesh.axis_names if a != axis)
        gd = workflow.gds[i] if i < len(workflow.gds) else None
        for key in ("weights", "bias", "weights2", "bias2"):
            sh = NamedSharding(
                mesh, P(*((axis,) + (None,) *
                          (getattr(fwd, key).mem.ndim - 1))))
            smap[(fwd.name, key)] = sh
            if gd is not None:
                # momentum, accumulation AND Adam second-moment state
                # shard like the param
                smap[(gd.name, "vel_" + key)] = sh
                smap[(gd.name, "acc_" + key)] = sh
                smap[(gd.name, "sq_" + key)] = sh
        rep = NamedSharding(mesh, P())
        smap[(fwd.name, "router")] = rep
        if gd is not None:
            smap[(gd.name, "vel_router")] = rep
            smap[(gd.name, "acc_router")] = rep
            smap[(gd.name, "sq_router")] = rep
        touched += 1
    if not touched:
        raise ValueError("no MoE units to expert-parallelize")
    step.sync_host()
    step.param_sharding_map.update(smap)
    if step.param_sharding is None:
        step.param_sharding = replicated(mesh)
    if step.batch_sharding is None:
        step.batch_sharding = replicated(mesh)
    workflow.device.mesh = mesh
    if refresh:
        step.refresh_device()
    return mesh


def setup_pipeline_parallel(workflow, mesh, axis="pipe",
                            microbatches=4, batch_axis=None,
                            refresh=True, schedule="gpipe"):
    """Pipeline parallelism for :class:`TransformerBlockStack` units:
    the stacked layer dim of every parameter (and its momentum /
    accumulation state) is sharded over ``axis`` — each stage owns
    L/P consecutive blocks — and the unit's traced path switches to
    the microbatch ``schedule`` (``parallel/pipeline.py``), where
    activations hop stages via ``ppermute`` and weights never move.

    ``schedule``: ``"gpipe"`` (forward stashes all M microbatch
    caches; backward replays them — peak stash M per stage) or
    ``"1f1b"`` (PipeDream-flush, peak stash min(M, P-s) caches at
    stage s). When every forward unit between the stack and the
    evaluator implements the tail_fwd/tail_bwd protocol and the
    evaluator provides ``mb_loss_grad`` (the stacked LM's token_dense
    → EvaluatorLM tail does), 1F1B folds the loss into the fused
    schedule as the last-stage err_fn and the train step runs ONE
    pipelined forward; otherwise it falls back to an un-stashed
    forward plus a rematerializing fused backward (two forwards).
    Both are leaf-for-leaf parity-tested through the workflow
    (tests/test_pipeline.py).

    ``batch_axis`` names the mesh axis the batch is sharded over when
    composing PP with DP on one mesh; ``microbatches`` must divide
    the (per-data-shard) minibatch size."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles.znicz_tpu.ops.transformer_stack import (
        TransformerBlockStack)
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError("schedule must be 'gpipe' or '1f1b', got %r"
                         % (schedule,))
    step = workflow.xla_step
    if step is None:
        raise ValueError("workflow has no xla_step (numpy backend?)")
    n = mesh.shape[axis]
    dp = mesh.shape[batch_axis] if batch_axis else 1
    smap = {}
    touched = 0
    for i, fwd in enumerate(workflow.forwards):
        if not isinstance(fwd, TransformerBlockStack):
            continue
        if fwd.layers % n:
            raise ValueError(
                "%s: %s axis size %d does not divide layer count %d"
                % (fwd.name, axis, n, fwd.layers))
        mb = workflow.loader.max_minibatch_size
        if (mb // dp) % microbatches:
            raise ValueError(
                "%s: %d microbatches do not divide the per-shard "
                "minibatch %d" % (fwd.name, microbatches, mb // dp))
        fwd.pipe_mesh = mesh
        fwd.pipe_axis = axis
        fwd.pipe_batch_axis = batch_axis
        fwd.pipe_microbatches = int(microbatches)
        fwd.pipe_schedule = schedule
        fwd.pipe_tail = None
        if schedule == "1f1b":
            # single-forward fold: the units between the stack and the
            # evaluator become the fused schedule's last-stage err_fn
            # when they all speak the loss-tail protocol
            tail = list(workflow.forwards[i + 1:])
            ev = getattr(workflow, "evaluator", None)
            foldable = (
                ev is not None
                and callable(getattr(ev, "mb_loss_grad", None))
                and all(callable(getattr(u, "tail_fwd", None))
                        and callable(getattr(u, "tail_bwd", None))
                        for u in tail))
            if foldable:
                fwd.pipe_tail = {"units": tail, "evaluator": ev}
            else:
                fwd.warning(
                    "1F1B loss tail %s -> %s is not foldable; the "
                    "train step will pay a second (un-stashed) "
                    "forward pass",
                    [type(u).__name__ for u in tail],
                    type(ev).__name__ if ev is not None else None)
        gd = workflow.gds[i] if i < len(workflow.gds) else None
        sh = NamedSharding(mesh, P(axis))
        for key in fwd.PARAMS:
            smap[(fwd.name, key)] = sh
            if gd is not None:
                smap[(gd.name, "vel_" + key)] = sh
                smap[(gd.name, "acc_" + key)] = sh
                smap[(gd.name, "sq_" + key)] = sh
        touched += 1
    if not touched:
        raise ValueError("no block-stack units to pipeline")
    step.sync_host()
    step.param_sharding_map.update(smap)
    if step.param_sharding is None:
        step.param_sharding = replicated(mesh)
    if step.batch_sharding is None:
        step.batch_sharding = replicated(mesh)
    workflow.device.mesh = mesh
    if refresh:
        step.refresh_device()
    return mesh


def setup_tensor_parallel(workflow, mesh, axis="model", refresh=True):
    """Megatron-style TP for the transformer units, the GSPMD way: no
    hand-written collectives — the qkv/up projections are
    column-sharded over ``axis``, the out/down projections row-sharded,
    and XLA's auto-partitioner inserts the all-reduces where the
    row-sharded contractions need them (SURVEY.md §7 design stance:
    'let XLA insert collectives'). Momentum state shards like its
    parameter so optimizer memory scales down with TP too."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles.znicz_tpu.ops.attention import (
        MultiHeadAttention, TransformerFFN)
    step = workflow.xla_step
    if step is None:
        raise ValueError("workflow has no xla_step (numpy backend?)")
    n = mesh.shape[axis]
    col = NamedSharding(mesh, P(None, axis))   # (D, k·D) split outputs
    row = NamedSharding(mesh, P(axis, None))   # (H, D) split inputs
    vec = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    smap = {}
    touched = 0
    for i, fwd in enumerate(workflow.forwards):
        gd = workflow.gds[i] if i < len(workflow.gds) else None

        def put(key, sh, vel_key=None):
            smap[(fwd.name, key)] = sh
            if gd is not None and vel_key:
                # momentum, accumulation AND Adam second-moment state
                # shard like the param
                smap[(gd.name, vel_key)] = sh
                smap[(gd.name, vel_key.replace("vel_", "acc_"))] = sh
                smap[(gd.name, vel_key.replace("vel_", "sq_"))] = sh
        if isinstance(fwd, MultiHeadAttention):
            if (fwd.heads % n) or fwd.seq_mesh is not None:
                continue   # head split impossible / ring owns attention
            put("weights", col, "vel_weights")
            put("bias", vec, "vel_bias")
            put("weights_out", row, "vel_weights_out")
            put("bias_out", rep, "vel_bias_out")
            fwd.kernel_head_axis = axis
            touched += 1
        elif isinstance(fwd, TransformerFFN):
            if fwd.hidden and fwd.hidden % n:
                continue
            put("weights", col, "vel_weights")
            put("bias", vec, "vel_bias")
            put("weights2", row, "vel_weights2")
            put("bias2", rep, "vel_bias2")
            touched += 1
    if not touched:
        raise ValueError("no TP-shardable units found")
    step.sync_host()
    # merge, don't assign: the setup_* family composes in any order
    # (setup_data_parallel owns the map reset)
    step.param_sharding_map.update(smap)
    if step.param_sharding is None:
        step.param_sharding = replicated(mesh)
    if step.batch_sharding is None:
        # same mesh, batch replicated: keeps every step input committed
        # to one device set so jit never sees mixed placements
        step.batch_sharding = replicated(mesh)
    workflow.device.mesh = mesh
    if refresh:
        step.refresh_device()
    return mesh
