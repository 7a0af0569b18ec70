"""XLAStep — the unit that executes the compiled training step.

This is the keystone of the TPU redesign (SURVEY.md §7 design stance &
stage 2). On the numpy backend the workflow executes units one-by-one;
on the XLA backend the whole accelerated cycle body (forwards →
evaluator → reversed GD chain) is traced ONCE by
:class:`veles.accelerated_units.StepCompiler` into a single jitted
``step(params, state, batch, hyper, key)`` with donated buffers, and
this unit replaces those units in the running graph:

    repeater → loader → **XLAStep** → decision → repeater

Parameters stay device-resident across steps (no host round-trips;
contrast the reference's per-unit map/unmap in SURVEY.md §3.2); the
loader's padded minibatch is placed onto the mesh with a batch
sharding, so data parallelism falls out of XLA auto-partitioning with
collectives over ICI.
"""

import collections
import functools
import itertools
import sys
import time

import numpy

from veles import telemetry
from veles.accelerated_units import StepCompiler
from veles.loader.base import CLASS_TRAIN
from veles.units import Unit


def _record_dispatch(kind, warm, start, dt, **args):
    """One fused-dispatch observation: wall time (metric fetch is the
    sync point, so this includes real device execution) split by
    program kind and warmth — a cold dispatch includes XLA
    compilation, which is where recompile time shows up."""
    telemetry.histogram(
        "veles_xla_dispatch_seconds",
        "Wall time of one fused dispatch incl. metric fetch "
        "(warm=\"0\" includes XLA compilation)",
        ("kind", "warm")).labels(kind, "1" if warm else "0").observe(dt)
    if telemetry.tracer.active:
        telemetry.tracer.add_complete(
            "xla.dispatch.%s" % kind, start, dt,
            warm=bool(warm), **args)


def _record_rates(kind, seconds, samples, tokens_per_sample):
    """Data throughput of one completed dispatch: ``samples`` (each of
    ``tokens_per_sample`` tokens, None where the data has no tokens)
    consumed in ``seconds`` of wall time."""
    if seconds <= 0:
        return
    if samples:
        telemetry.gauge(
            "veles_step_samples_per_second",
            "Samples consumed per second by the latest dispatch",
            ("kind",)).labels(kind).set(samples / seconds)
    if samples and tokens_per_sample:
        telemetry.gauge(
            "veles_step_tokens_per_second",
            "Tokens consumed per second by the latest dispatch "
            "(LM loaders)", ("kind",)).labels(kind).set(
                samples * tokens_per_sample / seconds)


#: the phases that tile the host's time from one dispatch to the next
PHASES = ("build", "launch", "fetch", "replay")
#: one ordinal a dispatch, unique in the process as the flight recorder
#: is: a parent span and its phase spans carry the same one
_ORDINALS = itertools.count()


def _record_phase(kind, phase, start, dt, **args):
    """One phase of a dispatch, as ``xla.dispatch.<kind>.<phase>`` in
    the tracer (``dispatch``: the parent span's ordinal) and in the
    histogram an operator scrapes: where the host's time between two
    steps goes, without a trace."""
    telemetry.histogram(
        "veles_xla_dispatch_phase_seconds",
        "Host wall time of one dispatch by phase: build (arguments and "
        "program look-up), launch (the jit call), fetch (wait for the "
        "device, one packed transfer), replay (serving the chunk's "
        "minibatches until the next dispatch)",
        ("kind", "phase")).labels(kind, phase).observe(dt)
    if telemetry.tracer.active:
        telemetry.tracer.add_complete(
            "xla.dispatch.%s.%s" % (kind, phase), start, dt, **args)


#: jax's duration event around every backend compile, a persistent
#: cache's load included (``jax._src.dispatch.BACKEND_COMPILE_EVENT``)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event, seconds, **_):
    if event == _COMPILE_EVENT:
        telemetry.counter(
            "veles_xla_compilations_total",
            "Backend compilations of the process, loads from the "
            "persistent cache included").inc()
        telemetry.counter(
            "veles_xla_compile_seconds_total",
            "Seconds inside those compilations and loads").inc(seconds)


@functools.cache
def _count_compilations():
    """Register the compile listener; cached, so once a process: jax
    keeps its listeners for the process's life, whatever registry is
    active."""
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)


def _compilations():
    return telemetry.get_registry().counter_total(
        "veles_xla_compilations_total")


def _record_host_leaves(kind, args):
    """How many leaves of a dispatch's arguments still live on the
    host: each is a host-to-device transfer inside the jit call, and
    under a mesh one per device."""
    import jax
    telemetry.gauge(
        "veles_xla_dispatch_host_leaves",
        "Host (non-jax.Array) leaves among the last dispatch's "
        "arguments: one transfer each, per device",
        ("kind",)).labels(kind).set(sum(
            not isinstance(leaf, jax.Array)
            for leaf in jax.tree_util.tree_leaves(args)))


class XLAStep(Unit):  # zlint: disable=checkpoint-state (params/state/step_index are checkpointed by NNWorkflow.checkpoint_state; the rest is per-dispatch bookkeeping reset by restore_state/initialize)
    """Runs the fused step; publishes evaluator metrics to the host."""

    def __init__(self, workflow, loader=None, forwards=(), evaluator=None,
                 gds=(), **kwargs):
        super().__init__(workflow, **kwargs)
        self.loader = loader
        self.forwards = list(forwards)
        self.evaluator = evaluator
        self.gds = list(gds)
        self.device = None
        self.compiler = None
        self.params = None
        self.state = None
        self.base_key = None
        self.step_index = 0
        #: model-health plane (veles/model_health.py): collect the
        #: per-layer in-graph stat vectors (one fused extra output per
        #: GD unit). Toggle BEFORE initialize, or via
        #: :meth:`set_stats_enabled` afterwards (clears the compiled
        #: program caches — the flag is a compile-time variant).
        self.collect_model_stats = True
        #: stat cadence: the reduces run IN-GRAPH every Nth train step
        #: (a lax.cond emits -1 sentinel rows in between, so the
        #: steady-state cost is the reduction pass divided by N), and
        #: the publish path materializes only the sampled rows. zlint
        #: ``stats-cadence`` bans materializing stat outputs outside
        #: that path. Set BEFORE initialize (compile-time stride).
        self.stats_interval = 8
        #: last step/epoch outputs fetched to host (key -> value)
        self.metrics = {}
        #: jax.sharding.NamedSharding for batch tensors (set by the
        #: parallel layer; None = single device)
        self.batch_sharding = None
        #: sharding for params/state (replicated under DP)
        self.param_sharding = None
        #: per-leaf override map {(unit_name, key): NamedSharding} —
        #: tensor parallelism (parallel.setup_tensor_parallel) shards
        #: individual weight matrices; unmapped leaves fall back to
        #: param_sharding
        self.param_sharding_map = {}

    # -- assembly ------------------------------------------------------

    @property
    def train_units(self):
        units = self.forwards + [self.evaluator] + \
            list(reversed(self.gds))
        return [u for u in units if u is not None]

    @property
    def eval_units(self):
        return [u for u in self.forwards + [self.evaluator]
                if u is not None]

    def initialize(self, device=None, **kwargs):
        super().initialize(**kwargs)
        self.device = device or getattr(self.workflow, "device", None)
        self.compiler = StepCompiler(self.train_units, self.device)
        _count_compilations()
        self.compiler.loop = getattr(self.workflow, "loop", None)
        self.compiler.collect_stats = bool(self.collect_model_stats)
        self.compiler.stats_stride = max(1, int(self.stats_interval))
        self.params = self._place_tree(self.compiler.gather_params())
        self.state = self._place_tree(self.compiler.gather_state())
        from veles import prng
        self.base_key = prng.get("xla_step").jax_key()
        self._batch_spec = self._build_batch_spec()
        self._train_fn = None
        self._eval_fn = None
        # class-scan fast path: whole class segments in one dispatch
        # when the dataset can live on device (SURVEY.md §3.2: the
        # reference pays per-unit launch overhead; we pay one launch
        # per epoch *class*)
        # Scan mode requires the loader to own its own minibatch order;
        # a distributed SLAVE gets index ranges pushed by the master
        # (apply_data_from_master), so it must stay per-step.
        self.scan_mode = bool(
            getattr(self.loader, "supports_device_gather", False)
            and not getattr(self.workflow, "is_slave", False))
        # streaming fast path: dataset stays on host, stacked windows
        # of minibatches ship up; one dispatch + one metric fetch per
        # window (SURVEY.md §7 stage 6 "async prefetch + double
        # buffering", done the XLA way)
        self.stream_mode = bool(
            not self.scan_mode
            and getattr(self.loader, "supports_streaming", False)
            and not getattr(self.workflow, "is_slave", False))
        if self.scan_mode or self.stream_mode:
            self.loader.device_gather = True
        #: streaming window bounds: device-side bytes per shipped
        #: window (one host->device transfer each) and minibatches per
        #: compiled scan
        self.max_window_bytes = 96 << 20
        self.max_window_minibatches = 64
        #: windows per metric fetch: a device->host fetch has a fixed
        #: latency, so draining several windows' outputs in ONE packed
        #: fetch amortizes it
        self.stream_fetch_windows = 4
        self._stage_pool = None
        self._last_put = None
        self._dispatched_epoch = None
        self._epoch_outs = {}
        self._epoch_pos = {}
        self._chunk_epoch0 = 0
        self._chunk_len = 0
        self._serving_epoch = None
        #: epochs fused into one dispatch: None = auto (adaptive: as
        #: many as fit in ``target_dispatch_seconds`` of device time,
        #: and never more than the decision's stop criteria provably
        #: allow); an int forces that chunk size
        self.epochs_per_dispatch = None
        #: auto-mode upper bound — bounds the stacked metrics buffer
        #: and the recompile count (each distinct chunk length is a
        #: separate XLA program)
        self.max_epochs_per_dispatch = 64
        #: auto mode sizes chunks to roughly this much wall time per
        #: dispatch: long enough to amortize the per-dispatch host
        #: round-trip, short enough to keep metrics/plots reasonably
        #: live
        self.target_dispatch_seconds = 2.0
        self._last_epoch_seconds = None
        self._seen_chunk_lengths = set()
        #: (end of the fetch, span arguments) of the last dispatch while
        #: its replay is open; a deque, because ``stop()`` may close it
        #: from another thread and ``pop()`` is one atomic step
        self._replay_open = collections.deque(maxlen=1)
        self._pre_epoch_params = None
        self._pre_epoch_state = None
        self._pre_epoch_step_index = 0
        self._keep_entry_requested = False
        #: epoch whose entry copy is currently held (stream/per-step
        #: modes take the copy at the first serve of each epoch)
        self._entry_epoch = None

    def _build_batch_spec(self):
        spec = {
            "data": (self.loader, "minibatch_data"),
            "batch_size": (self.loader, "minibatch_size"),
        }
        if self.loader.minibatch_labels:
            spec["labels"] = (self.loader, "minibatch_labels")
        targets = getattr(self.loader, "minibatch_targets", None)
        if targets is not None and targets:
            spec["targets"] = (self.loader, "minibatch_targets")
        return spec

    # -- per-step ------------------------------------------------------

    def _gather_batch(self):
        import jax
        batch = {}
        for name, (unit, attr) in self._batch_spec.items():
            value = getattr(unit, attr)
            if hasattr(value, "map_read"):
                value = value.map_read().mem
            batch[name] = numpy.asarray(value)
        if self.batch_sharding is not None:
            batch = {
                k: jax.device_put(
                    v, self.batch_sharding if v.ndim else None)
                for k, v in batch.items()}
        return batch

    def _batch_axis(self):
        """Mesh axis the minibatch dim shards over, or None when the
        batch sharding is replicated (TP-only mesh)."""
        spec = self.batch_sharding.spec
        return spec[0] if len(spec) else None

    def _pad_batch_dim(self, arr, dim):
        """Pad ``dim`` (the within-minibatch dim) to a multiple of the
        batch axis size by repeating the last row — `valids` masking
        zeroes the pad rows' loss/gradient contribution."""
        from veles.memory import roundup
        axis = self._batch_axis()
        if axis is None:
            return arr
        n_dev = self.batch_sharding.mesh.shape[axis]
        mb = arr.shape[dim]
        mb_pad = roundup(mb, n_dev)
        if mb_pad == mb:
            return arr
        last = [slice(None)] * arr.ndim
        last[dim] = slice(-1, None)
        pad = numpy.repeat(arr[tuple(last)], mb_pad - mb, axis=dim)
        return numpy.concatenate([arr, pad], axis=dim)

    def _gather_hyper(self):
        # custom trainers (Kohonen/RBM) bake their schedules into the
        # trace/state and expose no hyperparams()
        return {gd.name: gd.hyperparams() for gd in self.gds
                if hasattr(gd, "hyperparams")}

    def _replicated(self):
        """Placement of what every device reads whole: replicated over
        the step's mesh, or None (the default device) without one."""
        if self.batch_sharding is None:
            return None
        from veles.znicz_tpu import parallel
        return parallel.replicated(self.batch_sharding.mesh)

    def _device_hyper(self, kind):
        """The hyperparameter tree a dispatch hands its program: the
        units' host values as DEVICE arrays, kept between dispatches
        and uploaded again only when a host value changed (an lr cut
        of a rollback, a mask edit). A numpy leaf of a jit call's
        arguments is one transfer a dispatch and one per device; the
        schedules run inside the step from a counter in the state, so
        between such edits these values never move. The programs see
        the same avals either way: nothing retraces."""
        import jax
        host = self._gather_hyper()
        leaves, treedef = jax.tree_util.tree_flatten(host)
        # bytes, not the arrays: a mask leaf may alias the unit's
        # memory, and an edit in place must still read as a change
        sig = (treedef, [(leaf.shape, leaf.dtype.str, leaf.tobytes())
                         for leaf in leaves])
        if sig != self._hyper_sig:
            self._hyper_device = _device_tree(host, self._replicated())
            self._hyper_sig = sig
            telemetry.counter(
                "veles_xla_hyper_uploads_total",
                "Uploads of the hyperparameter tree (a dispatch whose "
                "host values equal the last upload's makes none)",
                ("kind",)).labels(kind).inc()
        return self._hyper_device

    def run(self):
        if not self.scan_mode and self._keep_epoch_entry:
            # stream/per-step: the first serve of an epoch sees the
            # epoch-ENTRY params (valid is served before train), so
            # copy them here; scan mode copies inside _dispatch_epoch
            self._keep_entry_now()
        if self.scan_mode or self.stream_mode:
            self._run_fused_mode()
        else:
            self._run_per_step()

    def _keep_entry_now(self):
        if self.loader.epoch_number == self._entry_epoch:
            return
        import jax
        import jax.numpy as jnp
        copy = (lambda t: jax.tree_util.tree_map(jnp.copy, t))
        self._pre_epoch_params = copy(self.params)
        self._pre_epoch_state = copy(self.state)
        self._pre_epoch_step_index = self.step_index
        self._entry_epoch = self.loader.epoch_number

    def _run_fused_mode(self):
        loader = self.loader
        if self._dispatched_epoch is None or \
                loader.epoch_number >= self._chunk_epoch0 + self._chunk_len:
            if self.scan_mode:
                self._dispatch_epoch()
            else:
                self._dispatch_stream_epoch()
        if loader.epoch_number != self._serving_epoch:
            self._serving_epoch = loader.epoch_number
            self._epoch_pos = {cls: 0 for cls in self._epoch_outs}
        e = loader.epoch_number - self._chunk_epoch0
        cls = loader.minibatch_class
        pos = self._epoch_pos[cls]
        self._publish_metrics(
            {k: v[e][pos] for k, v in self._epoch_outs[cls].items()})
        self._epoch_pos[cls] = pos + 1

    def _epochs_per_dispatch(self):
        """How many epochs may be fused into the next dispatch WITHOUT
        changing semantics: never past a point where the decision could
        stop (max_epochs bound, or patience running out — improvement
        inside the chunk only ever extends patience), and only 1 when
        epoch-entry snapshots are kept (their params copy is per-chunk).
        """
        if self._keep_epoch_entry:
            return 1
        decision = getattr(self.workflow, "decision", None)
        if self.epochs_per_dispatch is not None:
            chunk = max(1, int(self.epochs_per_dispatch))
        elif decision is None:
            return 1
        else:
            if self._last_epoch_seconds is None:
                # no timing yet (first dispatch also pays compilation):
                # measure one epoch before scaling up
                chunk = 1
            else:
                chunk = int(self.target_dispatch_seconds
                            / max(self._last_epoch_seconds, 1e-4))
            chunk = min(max(chunk, 1), self.max_epochs_per_dispatch)
            # quantize to a power of two: each distinct chunk length is
            # a separate compiled program, so bound the ramp to
            # ~log2(cap) compiles (the decision bounds below may still
            # cut an exact tail chunk — one more compile at the very
            # end of training)
            chunk = 1 << (chunk.bit_length() - 1)
        # host-side epoch observers (NNRollback etc.) may bound fusion:
        # a dispatch must never run past a point where they could act
        for u in getattr(self.workflow, "_units", ()):
            bound = getattr(u, "max_fused_epochs", None)
            if callable(bound):
                chunk = min(chunk, max(1, int(bound())))
        # stop-criterion bounds apply to FORCED chunk sizes too: a
        # dispatch must never run past a point where the decision could
        # stop, or final params would drift from decision.history
        if decision is not None:
            if decision.max_epochs is not None:
                chunk = min(chunk,
                            decision.max_epochs - decision.epoch_number)
            if decision.fail_iterations is not None:
                chunk = min(chunk, decision.fail_iterations
                            - decision._epochs_since_best)
        return max(1, chunk)

    def _epoch_program(self, n_epochs=None):
        """(fn, args, n_epochs, serves_per_epoch, classes): the EXACT
        compiled program and arguments the next scan-mode dispatch
        will run. Shared by ``_dispatch_epoch`` and the HLO
        introspection path (``lowered_epoch_hlo``) so what gets
        inspected can never drift from what gets executed.
        Repeatable: ``peek_epoch_orders`` is cached/idempotent, the
        hyperparameters' upload happens once per value, and
        ``jax.jit(...).lower`` neither executes nor donates."""
        import jax
        loader = self.loader
        if n_epochs is None:
            n_epochs = self._epochs_per_dispatch()
        orders = loader.peek_epoch_orders(n_epochs)
        n_epochs = len(orders)
        full = loader.device_full_arrays(
            None if self.batch_sharding is None
            else self.param_sharding)  # replicate dataset on the mesh
        classes = [cls for cls, _ in loader._order]
        segments, idxs, valids = [], {}, {}
        serves_per_epoch = 0
        for cls in classes:
            train = cls == CLASS_TRAIN
            seg_key = "c%d" % cls
            segments.append((
                seg_key, train,
                self.train_units if train else self.eval_units))
            mats = []
            for order in orders:
                idx_mat, vl = loader.class_schedule(cls, order)
                mats.append(idx_mat)
            idx_stack = numpy.stack(mats)        # (E, n_mb, mb)
            serves_per_epoch += idx_stack.shape[1]
            if self.batch_sharding is not None:
                # shard the within-minibatch (batch) dim over the data
                # axis: on-device gathers execute shard-local and DP
                # falls out of XLA auto-partitioning. An empty spec
                # (TP-only mesh) replicates instead.
                from jax.sharding import NamedSharding, PartitionSpec
                mesh = self.batch_sharding.mesh
                axis = self._batch_axis()
                idx_stack = self._pad_batch_dim(idx_stack, 2)
                idx_stack = jax.device_put(idx_stack, NamedSharding(
                    mesh, PartitionSpec(None, None, axis)))
                vl = jax.device_put(vl, self._replicated())
            idxs[seg_key] = idx_stack
            valids[seg_key] = vl
        fn = self.compiler.compile_epoch_scan(
            self._batch_spec, segments,
            getattr(loader, "xla_batch_transform", None))
        offsets = numpy.int32(
            self.step_index
            + serves_per_epoch * numpy.arange(n_epochs, dtype=numpy.int64))
        args = (self.params, self.state, full, idxs, valids,
                self._device_hyper("epoch"), self.base_key, offsets)
        return fn, args, n_epochs, serves_per_epoch, classes

    def lowered_epoch_hlo(self, optimized=True, n_epochs=1):
        """HLO text of the next scan-mode dispatch's program, lowered
        with the REAL sharded arguments. ``optimized=True`` returns the
        post-GSPMD-partitioning module — the one whose collective ops
        (all-reduce / all-to-all / collective-permute / all-gather /
        reduce-scatter) prove how work is actually distributed on the
        mesh (SURVEY.md §4 "TPU build translation"; VERDICT r2 #5)."""
        fn, args, _, _, _ = self._epoch_program(n_epochs)
        lowered = fn.lower(*args)
        if not optimized:
            return lowered.as_text()
        return lowered.compile().as_text()

    def _dispatch_epoch(self):
        """Run a CHUNK of whole epochs (every class segment, serving
        order) as one compiled program; fetch all stacked metrics in
        one host round-trip. The host's time is recorded in four
        phases (``PHASES``) that tile it from one dispatch to the next:
        ``launch`` + ``fetch`` are the ``xla.dispatch.epoch`` span,
        ``build`` + ``replay`` what lies outside it. The first three
        are also ``jax.profiler.TraceAnnotation``s, which put the same
        intervals on a device trace's clock (one flag test each while
        no profiler session runs)."""
        import jax
        annotate = jax.profiler.TraceAnnotation
        loader = self.loader
        t_build = time.perf_counter()
        self._close_replay(t_build)
        ordinal = next(_ORDINALS)
        with annotate("veles.dispatch.build"):
            fn, args, n_epochs, serves_per_epoch, classes = \
                self._epoch_program()
            # Stash a CONSISTENT epoch-entry view (params + optimizer
            # state + step counter — the point the epoch's validation
            # metric describes, since valid is served before train):
            # improved-gated snapshots must save THESE, not the
            # post-train values (per-step-mode / reference semantics,
            # SURVEY.md §3.4). Only paid for when a snapshotter can
            # consume it.
            if self._keep_epoch_entry:
                import jax.numpy as jnp
                copy = (lambda t: jax.tree_util.tree_map(jnp.copy, t))
                self._pre_epoch_params = copy(self.params)
                self._pre_epoch_state = copy(self.state)
                self._pre_epoch_step_index = self.step_index
            self.step_index += serves_per_epoch * n_epochs
            _record_host_leaves("epoch", args)
            compiled = _compilations()
        t0 = time.perf_counter()
        with annotate("veles.dispatch.launch"):
            self.params, self.state, outs = fn(*args)
        t_fetch = time.perf_counter()
        with annotate("veles.dispatch.fetch"):
            host_outs = _fetch_tree(outs)
        dt = time.perf_counter() - t0
        warm = n_epochs in self._seen_chunk_lengths
        # ``compiles``: what was compiled or loaded inside the span,
        # counted and not guessed as ``warm`` is
        _record_dispatch("epoch", warm, t0, dt, epochs=n_epochs,
                         dispatch=ordinal,
                         compiles=int(_compilations() - compiled))
        ids = {"dispatch": ordinal, "epochs": n_epochs}
        for phase, start, end in (("build", t_build, t0),
                                  ("launch", t0, t_fetch),
                                  ("fetch", t_fetch, t0 + dt)):
            _record_phase("epoch", phase, start, end - start, **ids)
        self._replay_open.append((t0 + dt, ids))
        _record_rates("epoch", dt, n_epochs * int(loader.total_samples),
                      self._tokens_per_sample())
        if warm:
            # a clean (compile-free) run of this program: usable for
            # sizing the next chunk
            self._last_epoch_seconds = dt / n_epochs
        else:
            self._seen_chunk_lengths.add(n_epochs)
        self._epoch_outs = {cls: host_outs["c%d" % cls]
                            for cls in classes}
        self._epoch_pos = {cls: 0 for cls in classes}
        self._serving_epoch = loader.epoch_number
        self._chunk_epoch0 = loader.epoch_number
        self._chunk_len = n_epochs
        self._dispatched_epoch = loader.epoch_number

    def _close_replay(self, now):
        """Record the open ``replay`` phase, if there is one: from the
        end of the last dispatch's fetch to ``now``, the entry of the
        next dispatch or the workflow's stop."""
        try:
            start, ids = self._replay_open.pop()
        except IndexError:
            return
        _record_phase("epoch", "replay", start, now - start, **ids)

    def stop(self):
        super().stop()
        self._close_replay(time.perf_counter())

    def print_dispatch_phases(self, stream=sys.stderr, newest=24):
        """One line of the run's end (``Launcher.run``, beside the
        per-unit table): where each of the newest scan-mode dispatches
        the flight recorder still holds spent its host time, and how
        many programs it compiled or loaded. A stalled dispatch reads
        here as a long ``launch`` with ``compiles`` 1 (a compilation or
        a cache load) or a long ``fetch`` (the device, or the host
        thread's wake-up)."""
        parent = "xla.dispatch.epoch"
        rows = {}
        for _, ev in telemetry.tracer.flight_spans():
            name, args = ev["name"], ev.get("args", {})
            if name.startswith(parent) and "dispatch" in args:
                row = rows.setdefault(args["dispatch"], {})
                if name == parent:
                    row["compiles"] = args["compiles"]
                else:
                    row[name[len(parent) + 1:]] = ev["dur"] / 1e3
        found = [(k, rows[k]) for k in sorted(rows) if "compiles" in rows[k]]
        if not found:
            return
        counted = sum(
            child.count for items, child in telemetry.histogram(
                "veles_xla_dispatch_seconds", "",
                ("kind", "warm")).children() if ("kind", "epoch") in items)
        stream.write(
            "dispatch phases: %s ms, compiles; %d dispatches counted, %d "
            "in the flight recorder, the newest %d: %s\n" % (
                "/".join(PHASES), counted, len(found),
                min(newest, len(found)), "; ".join(
                    "#%d %s c%d" % (k, "/".join(
                        "%.1f" % row[p] if p in row else "-"
                        for p in PHASES), row["compiles"])
                    for k, row in found[-newest:])))

    # -- streaming dispatch -------------------------------------------

    def _window_minibatches(self):
        """Minibatches per shipped window, bounded by device bytes and
        the scan length. Sized from the loader's STREAMED sample spec
        (e.g. uint8 images), not the float host mirror."""
        loader = self.loader
        spec = getattr(loader, "sample_spec", None)
        if spec is not None:
            per_mb = loader.max_minibatch_size * sum(
                int(numpy.prod(shape, dtype=numpy.int64) or 1)
                * numpy.dtype(dt).itemsize
                for shape, dt in spec().values())
        else:
            per_mb = loader.minibatch_data.mem.nbytes
            if loader.minibatch_labels:
                per_mb += loader.minibatch_labels.mem.nbytes
            if getattr(loader, "minibatch_targets", None) is not None \
                    and loader.minibatch_targets:
                per_mb += loader.minibatch_targets.mem.nbytes
        w = max(1, int(self.max_window_bytes // max(per_mb, 1)))
        return min(w, self.max_window_minibatches)

    def _finish_put(self):
        """Wait for the in-flight window upload (if any). Called
        before every device→host fetch, so an upload and a fetch are
        never in flight together."""
        import jax
        if self._last_put is not None:
            jax.block_until_ready(self._last_put)
            self._last_put = None

    def _put_window(self, stacked):
        """Ship a stacked window up, sharding the within-minibatch dim
        over the data axis under DP (pad rows repeat the last sample;
        the evaluator's valid-row mask zeroes their contribution).

        Transfers are serialized one-in-flight: each call first waits
        for the PREVIOUS window's transfer, so the current upload
        still overlaps the previous window's compute."""
        import jax
        self._finish_put()
        if self.batch_sharding is None:
            out = {k: jax.device_put(v) for k, v in stacked.items()}
            self._last_put = list(out.values())
            return out
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.batch_sharding.mesh
        axis = self._batch_axis()
        out = {}
        for k, v in stacked.items():
            out[k] = jax.device_put(
                self._pad_batch_dim(v, 1),
                NamedSharding(mesh, PartitionSpec(None, axis)))
        self._last_put = list(out.values())
        return out

    def _dispatch_stream_epoch(self):
        """Stream ONE epoch: for each class segment, ship windows of
        stacked minibatches and run a compiled scan per window.
        Pipelined two ways: window staging (host decode/augment) runs
        in a background thread two windows ahead, and each window's
        metric fetch is deferred until the NEXT window has been
        dispatched — the fetch's round-trip overlaps device compute
        instead of serializing with it."""
        import concurrent.futures
        import jax
        t_epoch0 = time.perf_counter()
        loader = self.loader
        if self._stage_pool is None:
            self._stage_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="%s-stage" % self.name)
        plan = loader.epoch_plan()
        hyper = self._device_hyper("stream")
        w_size = self._window_minibatches()
        spans = []         # (cls, valids_slice, idx_rows)
        for cls, idx_mat, valids in plan:
            for lo in range(0, len(idx_mat), w_size):
                hi = min(lo + w_size, len(idx_mat))
                spans.append((cls, valids[lo:hi], idx_mat[lo:hi]))
        # lazy staging with depth-2 backpressure: completed windows
        # must never pile up in host RAM ahead of the device
        stage_depth = 2
        staged = []

        def stage(j):
            cls, _, rows = spans[j]
            staged.append(self._stage_pool.submit(
                loader.materialize_window, cls, rows))
        for j in range(min(stage_depth, len(spans))):
            stage(j)
        outs_per_cls = {cls: [] for cls, _, _ in plan}
        pending = []       # (cls, device outputs) — fetch lags by one
        for i, (cls, valids_w, _) in enumerate(spans):
            train = cls == CLASS_TRAIN
            units = self.train_units if train else self.eval_units
            fn = self.compiler.compile_window_scan(
                self._batch_spec, train, units,
                loader.xla_batch_transform)
            host_window = staged.pop(0).result()
            # fetch ORDER: wait out the previous upload, fetch metrics
            # while no h2d is in flight (see _finish_put), and only
            # then start the next upload
            self._finish_put()
            if len(pending) > self.stream_fetch_windows:
                _drain_pending(pending, outs_per_cls, keep=1)
            stacked = self._put_window(host_window)
            if i + stage_depth < len(spans):
                stage(i + stage_depth)
            key0 = jax.random.fold_in(self.base_key, self.step_index)
            self.step_index += len(valids_w)
            args = (self.params, self.state, stacked, valids_w, hyper,
                    key0)
            _record_host_leaves("stream", args)
            self.params, self.state, outs = fn(*args)
            pending.append((cls, outs))
        self._finish_put()
        _drain_pending(pending, outs_per_cls, keep=0)
        self._epoch_outs = {
            cls: {k: numpy.concatenate(
                [w[k] for w in ws])[None]      # add the epoch dim
                for k in ws[0]}
            for cls, ws in outs_per_cls.items()}
        self._epoch_pos = {cls: 0 for cls in self._epoch_outs}
        self._serving_epoch = loader.epoch_number
        self._chunk_epoch0 = loader.epoch_number
        self._chunk_len = 1
        self._dispatched_epoch = loader.epoch_number
        # warmth is per window-shape signature, not first-call-only:
        # a new span layout (window count/lengths change with dataset
        # or cap retunes) re-traces under jit and must land in the
        # warm="0" (includes-compilation) histogram series
        sig = tuple(sorted({(cls, len(rows))
                            for cls, _, rows in spans}))
        seen = getattr(self, "_stream_sigs", None)
        if seen is None:
            seen = self._stream_sigs = set()
        warm = sig in seen
        seen.add(sig)
        dt_epoch = time.perf_counter() - t_epoch0
        _record_dispatch("stream", warm, t_epoch0, dt_epoch,
                         windows=len(spans))
        _record_rates("stream", dt_epoch, int(loader.total_samples),
                      self._tokens_per_sample())

    def _run_per_step(self):
        import jax
        train = self.loader.minibatch_class == CLASS_TRAIN
        if train:
            if self._train_fn is None:
                self._train_fn = self.compiler.compile(
                    self._batch_spec, train=True)
            fn = self._train_fn
        else:
            if self._eval_fn is None:
                self.compiler.units = self.eval_units
                self._eval_fn = self.compiler.compile(
                    self._batch_spec, train=False)
                self.compiler.units = self.train_units
            fn = self._eval_fn
        batch = self._gather_batch()
        key = jax.random.fold_in(self.base_key, self.step_index)
        self.step_index += 1
        args = (self.params, self.state, batch,
                self._device_hyper("step"), key)
        _record_host_leaves("step", args)
        t0 = time.perf_counter()
        params, state, outputs = fn(*args)
        if train:
            self.params, self.state = params, state
        self._publish_metrics(outputs)
        # _publish_metrics fetched scalar metrics, so the wall time
        # above includes real device execution, not just the enqueue
        _record_rates("step", time.perf_counter() - t0,
                      int(getattr(self.loader, "minibatch_size", 0) or 0),
                      self._tokens_per_sample())

    def _tokens_per_sample(self):
        """Tokens one sample carries, for the tokens/s gauge: an LM
        loader's minibatch is a (mb, S) integer id matrix — anything
        else has no token notion and returns None."""
        mem = getattr(getattr(self.loader, "minibatch_data", None),
                      "mem", None)
        if mem is not None and getattr(mem, "ndim", 0) == 2 \
                and mem.dtype.kind in "iu":
            return int(mem.shape[1])
        return None

    def set_stats_enabled(self, enabled):
        """Toggle in-graph model-stat collection. The flag is a
        compile-time variant, so the cached per-step programs are
        dropped (scan/window programs re-key through the compiler
        cache on their next dispatch)."""
        enabled = bool(enabled)
        if enabled == self.collect_model_stats:
            return
        self.collect_model_stats = enabled
        if self.compiler is not None:
            self.compiler.collect_stats = enabled
            self._train_fn = None
            self._eval_fn = None

    def _stats_due(self):
        """The gate of the model-health publish path (zlint
        ``stats-cadence``): the cadence itself is enforced IN-GRAPH —
        ``export_layer_stats`` strides the reduces by
        ``stats_interval`` and emits ``-1`` sentinel rows in between
        — so the host side only filters. Disabled collection means
        nothing may materialize at all."""
        return bool(self.collect_model_stats)

    def _publish_model_stats(self, stats):
        """The ONE sanctioned materialization point for in-graph stat
        outputs: gate first, then materialize the tiny per-layer
        vectors and drop the in-graph stride's sentinel rows (a
        negative weight norm cannot occur naturally; NaN rows compare
        False and are KEPT — they are the signal)."""
        if not self._stats_due():
            return
        host = {}
        for layer, vec in stats.items():
            row = numpy.asarray(vec, numpy.float64).reshape(-1)
            if row.shape[0] >= 2 and row[1] < 0.0:
                continue
            host[layer] = row
        if not host:
            return
        from veles import model_health
        model_health.get_model_monitor().observe_stats(
            host, step_index=self.step_index)

    def _publish_metrics(self, outputs):
        """Hand step metrics to the host side. Every unit may declare
        ``metric_sinks() -> [(output_key, attr_name), ...]`` — the
        evaluator base declares n_err/loss; custom trainers (Kohonen,
        RBM) publish their own. A unit with ``metrics_published(attrs)``
        is told, once its sinks are filled, which attributes this step
        set (scalars only). Stat outputs (the model-health plane's
        per-layer vectors) are split off first and published at the
        stats cadence."""
        from veles import model_health
        stats, outputs = model_health.take_stats(outputs)
        if stats:
            self._publish_model_stats(stats)
        for unit in self.train_units:
            sinks = getattr(unit, "metric_sinks", None)
            if sinks is None:
                continue
            fresh = set()
            for key, attr in sinks():
                if key not in outputs:
                    continue
                value = outputs[key]
                if getattr(value, "ndim", 0):
                    # array metric (e.g. confusion matrix): ACCUMULATE
                    # into the unit's host Array, matching the numpy
                    # oracle's `mem += counts` semantics
                    arr = getattr(unit, attr, None)
                    if arr is not None and hasattr(arr, "map_write") \
                            and arr:
                        arr.map_write()
                        arr.mem += numpy.asarray(value)
                    continue
                value = float(value) if hasattr(value, "dtype") \
                    and value.dtype.kind == "f" else int(value)
                setattr(unit, attr, value)
                fresh.add(attr)
            told = getattr(unit, "metrics_published", None)
            if told is not None and fresh:
                told(fresh)

    # -- host sync -----------------------------------------------------

    @property
    def _keep_epoch_entry(self):
        """Epoch-entry copies cost a params+state duplicate on device;
        keep them when a snapshotter/rollback exists OR someone has
        asked for a snapshot view before (evaluated per dispatch, so a
        snapshotter linked after initialize still works). All execution
        modes keep entries: scan mode copies at dispatch, stream and
        per-step modes at the first serve of each epoch."""
        return (self._keep_entry_requested
                or getattr(self.workflow, "snapshotter", None) is not None
                or getattr(self.workflow, "rollback", None) is not None)

    def snapshot_view(self, at_valid=False):
        """A CONSISTENT (params, state, step_index) triple.

        ``at_valid=True`` returns the state the current epoch's
        validation metric was measured on (scan mode trains the whole
        epoch in one dispatch, so the live values are one train segment
        ahead of the metric that gated the snapshot)."""
        if at_valid:
            if self._pre_epoch_params is not None:
                return (self._pre_epoch_params, self._pre_epoch_state,
                        self._pre_epoch_step_index)
            if not self._keep_entry_requested:
                # start keeping entries for future epochs and be loud:
                # this checkpoint's params are post-train of the epoch
                self._keep_entry_requested = True
                if self.step_index:
                    self.warning(
                        "snapshot_view(at_valid) before any epoch-entry "
                        "copy exists: saving post-train params for this "
                        "epoch; subsequent epochs will keep entry copies")
        return self.params, self.state, self.step_index

    def sync_host(self, at_valid=False):
        """Write device-resident params/state back into the unit
        Arrays (before snapshot / numpy cross-check)."""
        params, state, _ = self.snapshot_view(at_valid)
        self.compiler.scatter_device_params(params)
        for u in self.compiler.units:
            tree = state.get(u.name)
            if not tree:
                continue
            for attr, value in tree.items():
                arr = getattr(u, attr, None)
                if arr is not None and hasattr(arr, "set_device_value"):
                    arr.set_device_value(value)
        for u in self.compiler.units:
            for name in getattr(u, "PARAMS", ()) + getattr(u, "STATE", ()):
                arr = getattr(u, name, None)
                if arr is not None and getattr(arr, "map_read", None) \
                        and arr:
                    arr.map_read()

    def _place_tree(self, tree):
        """device_put a {unit: {key: array}} tree honouring the
        per-leaf TP sharding map, default param_sharding otherwise."""
        import jax
        # wherever params/state are placed anew the device or the mesh
        # may be another one: the kept hyperparameters go with them
        self._hyper_sig = self._hyper_device = None
        if not self.param_sharding_map:
            return _device_tree(tree, self.param_sharding)
        return {
            uname: {
                key: jax.device_put(
                    arr, self.param_sharding_map.get(
                        (uname, key), self.param_sharding))
                for key, arr in sub.items()}
            for uname, sub in tree.items()}

    def refresh_device(self):
        """Re-upload params/state after host-side mutation (snapshot
        resume, master weight push). For a mid-run sharding change call
        sync_host() first — host Arrays are the source of truth here."""
        self.params = self._place_tree(self.compiler.gather_params())
        self.state = self._place_tree(self.compiler.gather_state())


def _drain_pending(pending, outs_per_cls, keep):
    """Fetch all but the newest ``keep`` pending window outputs in ONE
    packed d2h transfer (latency amortization; the kept windows keep
    the device pipeline ahead of the host)."""
    take = pending[:len(pending) - keep] if keep else list(pending)
    if not take:
        return
    del pending[:len(take)]
    fetched = _fetch_tree([o for _, o in take])
    for (c, _), o in zip(take, fetched):
        outs_per_cls[c].append(o)


def _device_tree(tree, sharding=None):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), tree)


_PACK_CACHE = {}


def _fetch_tree(tree):
    """Fetch a pytree of device arrays with ONE d2h transfer: pack all
    leaves into a single f32 vector on device, transfer once, unpack on
    host (each transfer pays its own round-trip).

    32-bit leaves are BITCAST (lossless, however large the ints);
    narrower dtypes widen losslessly through f32; 64-bit dtypes are
    rejected rather than silently truncated."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    for leaf in leaves:
        if leaf.dtype.itemsize > 4:
            raise TypeError(
                "_fetch_tree cannot pack %s losslessly" % leaf.dtype)
    sig = tuple((l.shape, str(l.dtype)) for l in leaves)
    if sig not in _PACK_CACHE:
        def pack(ls):
            parts = []
            for l in ls:
                if l.dtype.itemsize == 4:
                    parts.append(lax.bitcast_convert_type(
                        l, jnp.float32).ravel())
                else:
                    parts.append(l.astype(jnp.float32).ravel())
            return jnp.concatenate(parts)
        _PACK_CACHE[sig] = jax.jit(pack)
    flat = numpy.asarray(_PACK_CACHE[sig](leaves))
    out, off = [], 0
    for leaf in leaves:
        size = int(numpy.prod(leaf.shape)) if leaf.shape else 1
        piece = flat[off:off + size]
        if leaf.dtype.itemsize == 4:
            piece = piece.view(numpy.dtype(str(leaf.dtype)))
        else:
            piece = piece.astype(leaf.dtype)
        out.append(piece.reshape(leaf.shape))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)
