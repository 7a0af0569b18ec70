"""Accelerated units and the graph→jit step compiler.

Re-design of ``veles/accelerated_units.py`` [U] (SURVEY.md §2.1
"Accelerated unit", §7 design stance). The reference dispatched each
unit's ``run`` to ``numpy_run`` / ``ocl_run`` / ``cuda_run`` and launched
one or more hand-written kernels per unit, with host↔device map/unmap
around every launch (§3.2 "Boundary crossings"). The TPU build keeps the
per-unit ``numpy_run`` oracle but replaces the per-unit kernel launches
wholesale: every accelerated unit additionally implements

* ``xla_init()`` — declare parameters/optimizer state (host-side numpy
  values living in its ``Array`` attrs, as the oracle path uses), and
* ``xla_run(ctx)`` — a **pure, jax-traceable** function that reads its
  inputs from a :class:`FlowContext` and writes its outputs back.

:class:`StepCompiler` walks the accelerated subgraph once, calls each
``xla_run`` under ``jax.jit`` tracing, and produces a single fused step
function ``step(params, state, batch, hyper) -> (params, state, outputs)``
— the entire forward/backward/update cycle is ONE XLA computation with
donated buffers, which is what makes this design TPU-native rather than
a port (SURVEY.md §3.2: the reference's per-unit launch overhead is
eliminated by construction).
"""

import time

import numpy

from veles import telemetry
from veles.backends import XLADevice, get_device
from veles.memory import Array
from veles.units import Unit
from veles.workflow import Workflow


def _compile_cache_event(kind, hit, build_seconds=None, start=None):
    """Registry bookkeeping for the step-program cache: hits vs
    (re)builds and the time spent tracing/jitting each program kind
    ('step' / 'epoch' / 'window')."""
    if hit:
        telemetry.counter(
            "veles_xla_cache_hits_total",
            "Compiled-program cache hits", ("kind",)).labels(kind).inc()
        return
    telemetry.counter(
        "veles_xla_cache_misses_total",
        "Compiled-program cache misses (trace + jit builds)",
        ("kind",)).labels(kind).inc()
    telemetry.histogram(
        "veles_xla_build_seconds",
        "Time spent building a step program (trace + jit wrap; XLA "
        "compiles lazily on first dispatch — see "
        "veles_xla_dispatch_seconds{warm=\"0\"})",
        ("kind",)).labels(kind).observe(build_seconds)
    if start is not None:
        telemetry.tracer.add_complete(
            "xla.build.%s" % kind, start, build_seconds, kind=kind)


class AcceleratedUnit(Unit):
    """A unit with a numpy oracle and a pure-jax implementation."""

    #: direction of the unit's work in the compiled step's scope name
    #: (``StepCompiler.trace_step``): "fwd", "bwd" or "loss"
    scope_role = "fwd"

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.device = None

    # -- lifecycle ----------------------------------------------------

    def initialize(self, device=None, **kwargs):
        super().initialize(**kwargs)
        if device is not None:
            self.device = device
        elif self.device is None and self.workflow is not None:
            self.device = getattr(self.workflow, "device", None)

    def init_vectors(self, *arrays):
        """Reference helper: ensure Arrays are allocated [U]."""
        for arr in arrays:
            if isinstance(arr, Array) and arr:
                arr.map_write()

    # -- backend dispatch ---------------------------------------------

    def run(self):
        """Host-graph execution path: oracle only. The XLA path never
        runs units one-by-one — it executes the compiled step (see
        AcceleratedWorkflow.run_step)."""
        self.numpy_run()

    def numpy_run(self):
        raise NotImplementedError(
            "%s lacks numpy_run" % type(self).__name__)

    # -- XLA contract --------------------------------------------------

    #: Names of Array attrs holding trainable parameters; the compiler
    #: lifts them into the params pytree keyed by unit name.
    PARAMS = ()
    #: Names of Array attrs holding mutable non-trainable state
    #: (momentum accumulators, running stats); lifted into state pytree.
    STATE = ()

    def xla_init(self):
        """Prepare parameter/state Arrays (defaults to nothing)."""

    def xla_run(self, ctx):
        """Pure traced computation; read/write via ctx."""
        raise NotImplementedError(
            "%s lacks xla_run" % type(self).__name__)

    # -- pytree lift/sink ---------------------------------------------

    def export_params(self):
        # copies, not views: callers hold these across in-place numpy
        # updates of the underlying Arrays
        return {name: numpy.array(getattr(self, name).map_read().mem)
                for name in self.PARAMS
                if isinstance(getattr(self, name, None), Array)
                and getattr(self, name)}

    def export_state(self):
        return {name: numpy.array(getattr(self, name).map_read().mem)
                for name in self.STATE
                if isinstance(getattr(self, name, None), Array)
                and getattr(self, name)}

    def import_params(self, tree):
        for name, value in tree.items():
            arr = getattr(self, name, None)
            if isinstance(arr, Array):
                arr.map_write()
                arr.mem = numpy.asarray(value, dtype=arr.dtype
                                        if arr else None)

    #: state restores through the same Array-attr path
    import_state = import_params


class FlowContext:
    """The tracing context handed to each unit's ``xla_run``.

    Holds named tensors produced so far plus this unit's view of the
    params/state pytrees and the PRNG key / train flag. Units read
    inputs (resolved through link_attrs wiring by the unit itself) and
    ``set`` their outputs.

    A unit that a loop (``veles/znicz_tpu/loop.py``) runs several times
    in one step runs each time in a VISIT: a child context (:meth:`visit`) that reads
    the step's values, parameters and state but keeps what the unit
    sets — its output, its pullbacks, its residuals, its exports — to
    itself, so one visit never overwrites another's and all of it dies
    with the loop iteration that made it; what a later visit needs the
    loop carries out by hand. A visit does not update parameters: the
    gradients a gradient unit hands to its solver there are added to
    the loop's float32 sums, ``grads`` (:meth:`defer`), and the solver
    runs once a step on the sum over the visits.
    """

    def __init__(self, compiler, params, state, hyper, key, train,
                 axis_name=None):
        self._compiler = compiler
        self.params = params        # full dict: unit name -> {attr: arr}
        self.state = state
        self.hyper = hyper          # dict of scalar hyperparams (lr, ...)
        self.key = key              # jax PRNG key folded per unit
        self.train = train          # python bool: compile-time variant
        self.axis_name = axis_name  # set when traced under shard_map
        self.values = {}            # (producer_unit_name, attr) -> tensor
        self.outputs = {}           # exported outputs (metrics etc.)
        #: the backward of what runs here follows in this context (a
        #: unit on ``jax.vjp`` leaves its pullback); False in a visit
        #: whose backward runs from a recomputation
        self.pullbacks = True
        #: a visit's deferred gradients, {gd unit name: {param: grad}};
        #: None outside a visit (the solver runs where the unit does)
        self.grads = None
        #: model-health plane (veles/model_health.py): when set, GD
        #: units export their per-layer stat vector as one extra fused
        #: output — a compile-time variant, keyed into the program
        #: caches below. ``stats_stride`` is the IN-GRAPH cadence: the
        #: reduces run under a lax.cond every Nth train step (sentinel
        #: rows otherwise), so the steady-state cost amortizes
        self.collect_stats = bool(
            getattr(compiler, "collect_stats", False)) and train
        self.stats_stride = int(
            getattr(compiler, "stats_stride", 1) or 1)

    def visit(self, pullbacks=True, grads=None):
        """A child context for one visit of a loop;
        ``grads``: the loop's float32 sums so far, {gd unit name:
        {parameter: sum}}, which this visit's gradients are added to."""
        import collections
        import copy
        child = copy.copy(self)
        child.params = dict(self.params)
        child.values = collections.ChainMap({}, self.values)
        child.outputs = {}
        child.pullbacks = pullbacks
        child.grads = {name: dict(sums)
                       for name, sums in (grads or {}).items()}
        return child

    def defer(self, unit, **grads):
        """In a visit: add ``grads`` to the loop's sums — each where
        it is made, so that no visit's gradients wait in memory for
        the others — and say True (the caller leaves its solver
        alone)."""
        if self.grads is None:
            return False
        sums = self.grads[unit.name]
        for name, grad in grads.items():
            if grad is not None:
                sums[name] = sums[name] + grad.astype(sums[name].dtype)
        return True

    # value routing ----------------------------------------------------

    def get(self, unit, attr):
        """Value of ``unit.attr``: a traced tensor if some xla_run
        produced it this trace, else the unit's host Array content as a
        constant (weights come from params instead)."""
        key = (unit.name, attr)
        if key in self.values:
            return self.values[key]
        # Follow link_attrs aliasing: reading a linked attr returns the
        # source object's value; find the real producer.
        src, src_attr = _resolve_link(unit, attr)
        key2 = (src.name, src_attr)
        if key2 in self.values:
            return self.values[key2]
        value = getattr(src, src_attr, None)
        if isinstance(value, Array):
            if not value:
                raise ValueError("unset Array %s.%s read during trace"
                                 % (src.name, src_attr))
            return value.devmem
        return value

    def set(self, unit, attr, tensor):
        self.values[(unit.name, attr)] = tensor
        # Mirror through any alias chain start as well.
        src, src_attr = _resolve_link(unit, attr)
        self.values[(src.name, src_attr)] = tensor

    # params/state ------------------------------------------------------

    def unit_params(self, unit):
        return self.params.get(unit.name, {})

    def unit_state(self, unit):
        return self.state.get(unit.name, {})

    def update_params(self, unit, **kv):
        self.params.setdefault(unit.name, {}).update(kv)

    def update_state(self, unit, **kv):
        self.state.setdefault(unit.name, {}).update(kv)

    def fold_key(self, unit):
        """A per-unit PRNG key, stable across steps via the step key."""
        import jax
        import zlib
        h = zlib.crc32(unit.name.encode()) & 0x7FFFFFFF
        return jax.random.fold_in(self.key, h)

    def export(self, name, tensor):
        """Expose a tensor in the step outputs (metrics, err counts)."""
        self.outputs[name] = tensor

    # collectives -------------------------------------------------------

    def pmean(self, tensor):
        """Cross-replica gradient mean. Under plain ``jit`` with sharded
        batches this is the identity — the batch contraction already
        sums across shards and XLA inserts the all-reduce (SURVEY.md §7
        stage 5). Under ``shard_map`` (explicit-collective mode) it is a
        real ``lax.pmean`` over the data axis."""
        if self.axis_name is None:
            return tensor
        import jax
        return jax.lax.pmean(tensor, self.axis_name)

    @property
    def act_dtype(self):
        """Dtype for tensors flowing BETWEEN units (outputs / err
        flows) — the mixed-precision activation policy. bf16 on TPU by
        default; master weights and solver state stay f32 (see
        ``XLADevice.act_dtype``)."""
        return self._compiler.device.act_dtype

    def dot(self, a, b):
        """MXU-friendly matmul: inputs cast to the device compute dtype
        (bfloat16 on TPU), accumulation in float32."""
        import jax.numpy as jnp
        cd = self._compiler.device.compute_dtype
        return jnp.matmul(a.astype(cd), b.astype(cd),
                          preferred_element_type=jnp.float32)

    def einsum(self, spec, *ops):
        """MXU-friendly einsum: same dtype contract as :meth:`dot`."""
        import jax.numpy as jnp
        cd = self._compiler.device.compute_dtype
        return jnp.einsum(spec, *[o.astype(cd) for o in ops],
                          preferred_element_type=jnp.float32)


def _resolve_link(unit, attr):
    """Follow LinkableAttribute aliases to the producing (unit, attr)."""
    from veles.mutable import LinkableAttribute
    seen = set()
    while True:
        if (id(unit), attr) in seen:
            return unit, attr
        seen.add((id(unit), attr))
        descr = type(unit).__dict__.get(attr)
        if isinstance(descr, LinkableAttribute):
            link = unit.__dict__.get("_linked_" + attr)
            if link is not None:
                unit, attr = link[0], link[1]
                continue
        return unit, attr


def _transform_key(transform):
    """Stable memo-key component for a loader's xla_batch_transform:
    bound methods are re-created per attribute access, so key on the
    owner's identity + function, not on the method object."""
    if transform is None:
        return None
    owner = getattr(transform, "__self__", None)
    func = getattr(transform, "__func__", transform)
    return (id(owner) if owner is not None else id(transform),
            getattr(func, "__qualname__", repr(func)))


class StepCompiler:
    """Trace an ordered list of accelerated units into one jitted step.

    ``order`` is the execution order of the accelerated cycle body
    (forwards → evaluator → gds), excluding host-side units (loader,
    decision, plotters) — exactly the partition SURVEY.md §7 stage 2
    prescribes.
    """

    def __init__(self, units, device: XLADevice, donate=True):
        self.units = list(units)
        self.device = device
        # donation is the TPU HBM lever; on the CPU platform it buys
        # nothing and jaxlib 0.4.37 was observed to flakily SEGFAULT
        # converting/awaiting outputs of donated programs on the
        # 8-virtual-device test mesh (use-after-free in the donated
        # aliasing path) — so only donate on real accelerators
        self.donate = bool(donate) and \
            getattr(device, "platform", None) != "cpu"
        #: in-graph model-stat collection (veles/model_health.py):
        #: toggled by XLAStep; both are part of every compile-cache
        #: key, since they change the traced program
        self.collect_stats = False
        self.stats_stride = 1
        #: the workflow's loop, where some units run several times a
        #: step (``znicz_tpu.loop.Loop``; set by XLAStep before anything
        #: is traced): ``loop.trace(compiler, ctx, units)`` then runs the
        #: step's units in place of ``trace_step``'s own walk
        self.loop = None
        self._compiled = {}

    # pytree assembly ---------------------------------------------------

    def gather_params(self):
        return {u.name: u.export_params() for u in self.units
                if u.export_params()}

    def gather_state(self):
        return {u.name: u.export_state() for u in self.units
                if u.export_state()}

    def scatter_params(self, params):
        for u in self.units:
            if u.name in params:
                u.import_params(params[u.name])

    def scatter_device_params(self, params):
        """Keep device values resident: mark unit Arrays device-dirty
        without a host round-trip."""
        for u in self.units:
            tree = params.get(u.name)
            if not tree:
                continue
            for attr, value in tree.items():
                arr = getattr(u, attr, None)
                if isinstance(arr, Array):
                    arr.set_device_value(value)

    # compilation -------------------------------------------------------

    def trace_step(self, params, state, hyper, key, train, units, bind):
        """The ONE step-body trace shared by per-step and scan
        compilation: build the context, bind the batch (caller-supplied
        closure), run every unit's ``xla_run`` under the scope
        ``veles.<role>.<Class>.<name>`` — the name a device trace shows
        for the unit's operations (``tf_op``). Where the workflow has a
        ``loop``, that runs the units (some several times each)."""
        ctx = FlowContext(self, dict(params), dict(state), hyper,
                          key, train)
        bind(ctx)
        if self.loop is not None:
            self.loop.trace(self, ctx, list(units))
            return ctx
        for unit in units:
            self.run_unit(ctx, unit)
        return ctx

    @staticmethod
    def unit_scope(unit):
        import jax
        return jax.named_scope("veles.%s.%s.%s" % (
            unit.scope_role, type(unit).__name__, unit.name))

    def run_unit(self, ctx, unit):
        if not ctx.train and getattr(unit, "train_only", False):
            return
        with self.unit_scope(unit):
            unit.xla_run(ctx)

    def build_step(self, batch_spec, train=True):
        """Return ``step(params, state, batch, hyper, key)``.

        ``batch_spec``: dict name -> (unit, attr) describing which unit
        attrs the batch tensors feed (e.g. the loader's minibatch).
        """
        import jax

        units = self.units

        def veles_step(params, state, batch, hyper, key):
            def bind(ctx):
                for name, (unit, attr) in batch_spec.items():
                    ctx.set(unit, attr, batch[name])
            ctx = self.trace_step(params, state, hyper, key, train,
                                  units, bind)
            return ctx.params, ctx.state, ctx.outputs

        donate = (0, 1) if (self.donate and train) else ()
        return jax.jit(veles_step, donate_argnums=donate)

    def compile(self, batch_spec, train=True):
        key = (tuple(sorted((name, unit.name, attr)
                            for name, (unit, attr) in batch_spec.items())),
               train, self.collect_stats, self.stats_stride)
        if key not in self._compiled:
            t0 = time.perf_counter()
            self._compiled[key] = self.build_step(batch_spec, train=train)
            _compile_cache_event("step", False,
                                 time.perf_counter() - t0, t0)
        else:
            _compile_cache_event("step", True)
        return self._compiled[key]

    # class-scan compilation (SURVEY.md §7 design stance, taken one
    # step further: not just one fused step, but a whole class segment
    # of an epoch as ONE lax.scan program — zero per-minibatch dispatch
    # or host sync; the dataset stays device-resident and minibatches
    # are gathered by index on device) -------------------------------

    def build_epoch_scan(self, batch_spec, segments, transform=None):
        """Return ``chunk(params, state, full, idxs, valids, hyper,
        key0, offsets) -> (params, state, {seg: stacked_outputs})``.

        ``transform``: the loader's ``xla_batch_transform`` applied on
        DEVICE to each gathered minibatch (uint8 bank -> cropped
        normalized float etc.); None = identity.

        ``segments``: list of ``(seg_key, train_flag, units)`` — one
        per loader class served each epoch, in serving order. ``full``:
        dict name -> whole-dataset device array; ``idxs[seg_key]``:
        (E, n_mb, mb) int32 row indices for E consecutive epochs;
        ``valids[seg_key]``: (n_mb,) true row counts (identical across
        epochs — class sizes don't change); ``offsets``: (E,) int32
        step index at each epoch's start (seeds the per-step PRNG keys
        exactly as E separate dispatches would).

        Structure: an outer ``lax.scan`` over epochs, an inner
        ``lax.scan`` per class segment whose iterations gather their
        minibatch from ``full`` on device and run the fused step body.
        E epochs become ONE XLA program with a single host round-trip
        for their metrics: the round-trip is a fixed per-dispatch
        cost, so chunking it across epochs amortizes it.
        """
        import jax
        import jax.numpy as jnp

        segments = [(k, t, list(us)) for k, t, us in segments]
        spec = dict(batch_spec)
        if transform is None:
            transform = lambda name, t, train=False: t

        def veles_epoch_scan(params, state, full, idxs, valids, hyper,
                             key0, offsets):
            def epoch_body(carry, xs):
                params, state = carry
                offset, idx_epoch = xs
                epoch_key = jax.random.fold_in(key0, offset)
                outs_all = {}
                for seg_i, (seg_key, train, units) in enumerate(segments):
                    seg_base_key = jax.random.fold_in(epoch_key, seg_i)

                    def body(carry, xs, _units=units, _train=train,
                             _key=seg_base_key):
                        params, state = carry
                        i, idx, valid = xs

                        def bind(ctx):
                            for name, (unit, attr) in spec.items():
                                if name == "batch_size":
                                    ctx.set(unit, attr, valid)
                                else:
                                    ctx.set(unit, attr, transform(
                                        name, full[name][idx],
                                        train=_train))
                        ctx = self.trace_step(
                            params, state, hyper,
                            jax.random.fold_in(_key, i), _train, _units,
                            bind)
                        return (ctx.params, ctx.state), ctx.outputs

                    idx_mat = idx_epoch[seg_key]
                    n_mb = idx_mat.shape[0]
                    (params, state), outs = jax.lax.scan(
                        body, (params, state),
                        (jnp.arange(n_mb), idx_mat, valids[seg_key]))
                    outs_all[seg_key] = outs
                return (params, state), outs_all

            (params, state), outs_all = jax.lax.scan(
                epoch_body, (params, state), (offsets, idxs))
            return params, state, outs_all

        donate = (0, 1) if self.donate else ()
        return jax.jit(veles_epoch_scan, donate_argnums=donate)

    def compile_epoch_scan(self, batch_spec, segments, transform=None):
        key = ("epoch",
               tuple(sorted((name, unit.name, attr)
                            for name, (unit, attr) in batch_spec.items())),
               tuple((k, t, tuple(u.name for u in us))
                     for k, t, us in segments),
               _transform_key(transform), self.collect_stats,
               self.stats_stride)
        if key not in self._compiled:
            t0 = time.perf_counter()
            self._compiled[key] = self.build_epoch_scan(
                batch_spec, segments, transform)
            _compile_cache_event("epoch", False,
                                 time.perf_counter() - t0, t0)
        else:
            _compile_cache_event("epoch", True)
        return self._compiled[key]

    # window-scan compilation (the STREAMING fast path: the dataset
    # does not fit on device, so stacked windows of minibatches are
    # shipped up and consumed by one scan program each — one dispatch
    # and one metric fetch per window instead of per minibatch) -------

    def build_window_scan(self, batch_spec, train, units, transform):
        """Return ``window(params, state, stacked, valids, hyper, key0)
        -> (params, state, stacked_outputs)``.

        ``stacked``: dict name -> (B, mb, ...) host-built minibatch
        stack; ``valids``: (B,) true row counts; ``transform``: the
        loader's ``xla_batch_transform`` (device-side uint8→float
        normalization etc.), applied per minibatch inside the scan.
        """
        import jax
        import jax.numpy as jnp

        units = list(units)
        spec = dict(batch_spec)

        def veles_window_scan(params, state, stacked, valids, hyper,
                              key0):
            def body(carry, xs):
                params, state = carry
                i, batch, valid = xs

                def bind(ctx):
                    for name, (unit, attr) in spec.items():
                        if name == "batch_size":
                            ctx.set(unit, attr, valid)
                        elif name in batch:
                            ctx.set(unit, attr,
                                    transform(name, batch[name],
                                              train=train))
                ctx = self.trace_step(
                    params, state, hyper, jax.random.fold_in(key0, i),
                    train, units, bind)
                return (ctx.params, ctx.state), ctx.outputs

            n_mb = valids.shape[0]
            (params, state), outs = jax.lax.scan(
                body, (params, state),
                (jnp.arange(n_mb), stacked, valids))
            return params, state, outs

        donate = (0, 1) if self.donate else ()
        return jax.jit(veles_window_scan, donate_argnums=donate)

    def compile_window_scan(self, batch_spec, train, units, transform):
        key = ("window",
               tuple(sorted((name, unit.name, attr)
                            for name, (unit, attr) in batch_spec.items())),
               train, tuple(u.name for u in units),
               _transform_key(transform), self.collect_stats,
               self.stats_stride)
        if key not in self._compiled:
            t0 = time.perf_counter()
            self._compiled[key] = self.build_window_scan(
                batch_spec, train, units, transform)
            _compile_cache_event("window", False,
                                 time.perf_counter() - t0, t0)
        else:
            _compile_cache_event("window", True)
        return self._compiled[key]


class AcceleratedWorkflow(Workflow):
    """Workflow owning a Device (reference ``AcceleratedWorkflow`` [U])."""

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.device = None

    def initialize(self, device=None, **kwargs):
        self.device = get_device(device)
        return super().initialize(device=self.device, **kwargs)

    @property
    def on_xla(self):
        return self.device is not None and self.device.is_xla
