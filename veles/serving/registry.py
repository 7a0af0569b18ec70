"""Model registry: named models, versions, hot reload.

Each registered model is a :class:`ServedModel` wiring one
:class:`ArchiveModel` (the weights + architecture, from an
``export_inference`` artifact directory) into an
:class:`InferenceEngine` (compiled forward cache) and a
:class:`MicroBatcher` (request coalescing). A model may additionally
be refreshed from a snapshotter checkpoint — local file or
``http(s)://`` URI through :class:`veles.snapshotter.HTTPSnapshotStore`
— which is how a serving process tracks a training run's best
checkpoint without re-exporting.

Hot reload (:meth:`ModelRegistry.reload`) re-reads the model's source
in place and atomically swaps it under the SAME name with a bumped
version; in-flight batches finish on the old params, the next batch
sees the new ones. When the architecture signature is unchanged the
engine keeps its compiled programs (params are runtime arguments) —
reload costs one host→device upload, no recompilation.
"""

import os
import threading
import time

from veles import telemetry
from veles.logger import Logger
from veles.serving.batcher import MicroBatcher
from veles.serving.engine import InferenceEngine
from veles.serving.model import ArchiveModel

_C_REFRESH_FAILURES = telemetry.LazyChild(lambda: telemetry.counter(
    "veles_serving_refresh_failures_total",
    "Hot reloads that failed and degraded to the loaded version",
    ("model",)))


class ServedModel:
    """One registry entry: model + engine + batcher + metadata."""

    def __init__(self, name, model, engine, batcher, source,
                 checkpoint=None, refresh_store=None):
        self.name = name
        self.model = model
        self.engine = engine
        self.batcher = batcher
        self.source = source
        self.checkpoint = checkpoint
        #: snapshot-store target (dir or http base) the refresh poll
        #: scans for newer healthy checkpoints (ISSUE 16 rolling
        #: refresh); derived from ``checkpoint`` when unset
        self.refresh_store = refresh_store
        self.version = 1
        self.loaded_at = time.time()
        #: lazy decode plane (ISSUE 11): built by
        #: ModelRegistry.decoder() on the first /v1/generate for a
        #: generative archive — a classifier-only registry never pays
        #: for a KV pool
        self.decoder = None
        self._decoder_lock = threading.Lock()
        self._closed = False
        #: readiness signal (veles/health.py): False only while a
        #: REQUESTED warmup is still compiling the bucket ladder — a
        #: model loaded without warmup compiles on first request and
        #: must not wedge readiness (the probe would reject the very
        #: request that warms it)
        self.warm = True

    def predict(self, rows, timeout_ms=None, trace=None, tenant=None):
        return self.batcher.predict(rows, timeout_ms=timeout_ms,
                                    trace=trace, tenant=tenant)

    def cache_bytes(self):
        """Forward-cache memory ESTIMATE for this entry (ISSUE 10
        memory accounting): the params pytree (host copy, plus the
        device upload on the jit backend) and a per-compiled-bucket
        input+output buffer guess. A size proxy the health ring can
        trend, not an allocator meter."""
        from veles.serving.quant import tree_nbytes
        params = tree_nbytes(self.model.params)
        total = params * (2 if self.engine.backend == "jit" else 1)
        sample = self.model.input_sample_shape
        if sample:
            row = 4
            for d in sample:
                row *= int(d)
            # x2: the batch buffer in and a same-order output out
            total += sum(b * row * 2
                         for b in self.engine.compiled_buckets)
        decoder = self.decoder
        if decoder is not None:
            # the paged KV pool is preallocated forward-cache memory
            # too (ISSUE 11): slots exist whether or not occupied
            total += decoder.engine.pool.nbytes()
        return total

    def describe(self):
        from veles.serving.decode import DecodePlan
        doc = {
            "name": self.name,
            "version": self.version,
            "workflow": self.model.workflow_name,
            "source": self.source,
            "checkpoint": self.checkpoint,
            "input_sample_shape": self.model.input_sample_shape,
            "units": [s["type"] for s in self.model.units],
            "backend": self.engine.backend,
            "platform": self.engine.platform,
            "quantize": self.engine.quantize,
            "compiled_buckets": self.engine.compiled_buckets,
            "loaded_at": self.loaded_at,
            "generative": DecodePlan.probe(self.model),
        }
        decoder = self.decoder
        if decoder is not None:
            doc["decode"] = {
                "kv_pool_slots": decoder.engine.pool.n_slots,
                "max_len": decoder.engine.max_len,
            }
        return doc

    def close(self, zero_gauge=True):
        """``zero_gauge=False`` is the hot-reload path (see
        MicroBatcher.close). The decoder handoff happens under
        _decoder_lock so an unload racing a first /v1/generate can
        never leak a just-built decode plane: either close() takes
        it here, or the builder sees _closed and refuses."""
        with self._decoder_lock:
            self._closed = True
            decoder = self.decoder
            self.decoder = None
        if decoder is not None:
            decoder.close()
        self.batcher.close(zero_gauge=zero_gauge)


class ModelRegistry(Logger):
    """Thread-safe name -> :class:`ServedModel` map."""

    def __init__(self, backend="auto", max_batch=64, max_queue=256,
                 max_wait_ms=2.0, default_timeout_ms=1000.0,
                 decode_slots=8, decode_max_len=256,
                 decode_max_queue=64, quantize_weights="none"):
        self.name = "registry"
        self.backend = backend
        #: at-rest weight quantization (serving/quant.py, ISSUE 14):
        #: every loaded model's params ride int8/fp8 host AND device,
        #: densified at dispatch — validated here so a typo'd
        #: --quantize-weights fails at configuration time
        from veles.serving.quant import validate_mode
        validate_mode(quantize_weights, "quantize_weights")
        self.quantize_weights = quantize_weights
        self.max_batch = int(max_batch)
        self.max_queue = int(max_queue)
        self.max_wait_ms = float(max_wait_ms)
        self.default_timeout_ms = float(default_timeout_ms)
        #: decode-plane geometry (ISSUE 11): KV pool width (the shared
        #: decode batch) and per-slot sequence length
        self.decode_slots = int(decode_slots)
        self.decode_max_len = int(decode_max_len)
        self.decode_max_queue = int(decode_max_queue)
        self._lock = threading.Lock()
        self._models = {}
        #: per-model count of failed hot reloads (checkpoint store
        #: down, bad archive): the registry DEGRADES — keeps serving
        #: the loaded version — instead of dying, and these counters
        #: plus the store's circuit-breaker state surface the
        #: degradation through /metrics
        self._refresh_failures = {}

    # -- lifecycle -----------------------------------------------------

    def load(self, name, source, checkpoint=None, warmup=False,
             refresh_store=None):
        """Load (or replace) model ``name`` from artifact directory
        ``source``; optionally refresh its params from ``checkpoint``
        and precompile the bucket ladder. ``refresh_store`` records
        the snapshot-store target :meth:`refresh_newest` polls."""
        model = ArchiveModel.from_dir(source)
        if checkpoint:
            model.load_checkpoint(checkpoint)
        with self._lock:
            old = self._models.get(name)
            if old is not None and \
                    old.model.signature() == model.signature():
                # same architecture: swap params, keep the compiled
                # cache and the running batcher
                old.model = model
                old.engine.set_model(model, params_only=True)
                if old.decoder is not None:
                    # decode programs keep too (params are runtime
                    # args); in-flight sequences finish on whichever
                    # tree their next step reads — same contract as
                    # in-flight predict batches
                    old.decoder.engine.set_params(model)
                old.source = source
                old.checkpoint = checkpoint
                if refresh_store:
                    old.refresh_store = refresh_store
                old.version += 1
                old.loaded_at = time.time()
                self._version_gauge(name).set(old.version)
                self.info("model %s reloaded in place -> v%d",
                          name, old.version)
                return old
            engine = InferenceEngine(model, backend=self.backend,
                                     max_batch=self.max_batch,
                                     quantize=self.quantize_weights)
            batcher = MicroBatcher(
                engine.predict, max_batch=self.max_batch,
                max_queue=self.max_queue,
                max_wait_ms=self.max_wait_ms,
                default_timeout_ms=self.default_timeout_ms,
                name="batcher-%s" % name, model=name)
            entry = ServedModel(name, model, engine, batcher, source,
                                checkpoint, refresh_store=refresh_store)
            if old is not None:
                entry.version = old.version + 1
                if refresh_store is None:
                    entry.refresh_store = old.refresh_store
            self._models[name] = entry
        self._version_gauge(name).set(entry.version)
        self._checkpoint_gauges(name)
        # scrape-time evaluation: buckets compile lazily and reloads
        # swap entries, so a stored value would go stale immediately.
        # Unloaded names read 0 (the series stays, the memory is gone).
        telemetry.gauge(
            "veles_serving_forward_cache_bytes",
            "Estimated bytes held by the model's forward cache "
            "(params + compiled bucket buffers; veles/profiling.py "
            "memory accounting)", ("model",)).labels(
                name).set_function(
                    lambda n=name: self._entry_cache_bytes(n))
        if old is not None:
            # close OUTSIDE the lock: draining the old batcher (and
            # the old decode plane's worker + KV pool, when one was
            # built) can block for seconds and must not stall get()
            # for every other model's request threads. The
            # replacement batcher owns the model's queue-gauge
            # series now — don't zero it.
            old.close(zero_gauge=False)
        if warmup:
            entry.warm = False
            try:
                entry.engine.warmup()
            finally:
                entry.warm = True
        self.info("model %s v%d loaded from %s (%d units, backend "
                  "%s)", name, entry.version, source,
                  len(model.units), entry.engine.backend)
        return entry

    def reload(self, name):
        """Hot reload from the entry's recorded source+checkpoint.

        A refresh failure (flapping snapshot endpoint — possibly
        fast-failed by its circuit breaker — or a half-written
        archive) must not take down a serving process that has a
        perfectly good model in memory: the failure is counted and
        the CURRENT entry keeps serving unchanged."""
        entry = self.get(name)
        try:
            return self.load(name, entry.source,
                             checkpoint=entry.checkpoint)
        except Exception as exc:
            with self._lock:
                self._refresh_failures[name] = \
                    self._refresh_failures.get(name, 0) + 1
                n = self._refresh_failures[name]
            _C_REFRESH_FAILURES.get().labels(name).inc()
            telemetry.record_event("reload_failed", model=name,
                                   error=str(exc))
            self.warning(
                "hot reload of %s failed (%s: %s; failure #%d) — "
                "still serving v%d", name, type(exc).__name__, exc,
                n, entry.version)
            return entry

    # -- rolling refresh (ISSUE 16) ------------------------------------

    def refresh_newest(self, name, store_target=None):
        """The refresh poll: scan the model's snapshot store for the
        newest HEALTHY checkpoint and hot-load it when it is newer
        than what is served.

        Every diverged blob encountered on the way down is skipped
        WITH ITS NAME in the log, an event in the flight recorder and
        a count in ``veles_checkpoint_diverged_skips_total`` — a
        wedged rollout must be diagnosable from one scrape. Corrupt
        and legacy blobs fall through silently (the scan already
        ranks them last). Store/transport failures degrade like
        :meth:`reload`: counted, logged, still serving.

        -> the loaded checkpoint path, or None (nothing newer, or
        the refresh degraded)."""
        from veles import snapshotter
        entry = self.get(name)
        target = store_target or entry.refresh_store
        if target is None and entry.checkpoint:
            # a concrete checkpoint path implies its store
            ckpt = str(entry.checkpoint)
            target = (ckpt.rsplit("/", 1)[0]
                      if ckpt.startswith(("http://", "https://"))
                      else os.path.dirname(ckpt))
        if not target:
            raise ValueError(
                "model %r has no snapshot store to refresh from "
                "(pass store_target or load with refresh_store=)"
                % name)
        served_wall = entry.model.checkpoint_meta.get("wall_time")
        try:
            infos = snapshotter.scan_checkpoints(target)
        except Exception as exc:
            with self._lock:
                self._refresh_failures[name] = \
                    self._refresh_failures.get(name, 0) + 1
            _C_REFRESH_FAILURES.get().labels(name).inc()
            self.warning("refresh poll of %s: store scan of %s failed "
                         "(%s: %s) — still serving v%d", name, target,
                         type(exc).__name__, exc, entry.version)
            return None
        for info in infos:
            if info.status != "valid":
                continue
            if info.wall_time is not None and served_wall \
                    and info.wall_time <= float(served_wall):
                break               # nothing newer than what we serve
            if info.health_verdict == "diverged":
                snapshotter._count_diverged_skip()
                telemetry.record_event("refresh_skipped_diverged",
                                       model=name,
                                       checkpoint=info.name)
                self.warning(
                    "refresh poll of %s SKIPPED diverged checkpoint "
                    "%s — still serving v%d (staleness reflects the "
                    "skip)", name, info.name, entry.version)
                continue
            path = ("%s/%s" % (str(target).rstrip("/"), info.name)
                    if str(target).startswith(("http://", "https://"))
                    else os.path.join(str(target), info.name))
            try:
                self.load(name, entry.source, checkpoint=path,
                          refresh_store=target)
            except Exception as exc:
                with self._lock:
                    self._refresh_failures[name] = \
                        self._refresh_failures.get(name, 0) + 1
                _C_REFRESH_FAILURES.get().labels(name).inc()
                telemetry.record_event("reload_failed", model=name,
                                       error=str(exc))
                self.warning(
                    "refresh of %s from %s failed (%s: %s) — still "
                    "serving v%d", name, path, type(exc).__name__,
                    exc, entry.version)
                return None
            telemetry.record_event("refresh_loaded", model=name,
                                   checkpoint=info.name,
                                   wall_time=info.wall_time)
            return path
        return None

    def _checkpoint_gauges(self, name):
        """Scrape-time gauges over the served checkpoint's MANIFEST:
        the absolute walls the rolling-refresh orchestrator compares
        across replicas, and the model's own staleness point."""
        from veles.continual import install_point_gauge
        telemetry.gauge(
            "veles_serving_checkpoint_wall_seconds",
            "MANIFEST wall time of the served checkpoint (0 = "
            "serving the export archive, no checkpoint loaded)",
            ("model",)).labels(name).set_function(
                lambda n=name: self._ckpt_meta(n, "wall_time"))
        telemetry.gauge(
            "veles_serving_checkpoint_ingest_wall_seconds",
            "MANIFEST ingest_wall of the served checkpoint (0 = no "
            "continual stamp)", ("model",)).labels(name).set_function(
                lambda n=name: self._ckpt_meta(n, "ingest_wall"))
        install_point_gauge(
            "serving:%s" % name,
            lambda n=name: self._ckpt_meta(n, "ingest_wall") or None)

    def _ckpt_meta(self, name, key):
        with self._lock:
            entry = self._models.get(name)
        if entry is None:
            return 0.0
        value = entry.model.checkpoint_meta.get(key)
        try:
            return float(value)
        except (TypeError, ValueError):
            return 0.0

    def unload(self, name):
        with self._lock:
            entry = self._models.pop(name)
            # a future model loaded under the same name must not
            # inherit this one's degradation history
            self._refresh_failures.pop(name, None)
        entry.close()

    def close(self):
        with self._lock:
            entries = list(self._models.values())
            self._models.clear()
        for entry in entries:
            entry.close()

    def _entry_cache_bytes(self, name):
        with self._lock:
            entry = self._models.get(name)
        return entry.cache_bytes() if entry is not None else 0

    @staticmethod
    def _version_gauge(name):
        return telemetry.gauge(
            "veles_serving_model_version",
            "Currently served model version", ("model",)).labels(name)

    # -- refresh-target admission --------------------------------------

    @staticmethod
    def _within_store(root, target):
        """True when ``target`` stays inside ``root`` (URL-prefix for
        http stores, normpath-prefix for directories — ``..`` hops
        are normalized away before the check)."""
        if root.startswith(("http://", "https://")):
            root = root.rstrip("/")
            return target == root or target.startswith(root + "/")
        root_abs = os.path.normpath(os.path.abspath(root))
        t_abs = os.path.normpath(os.path.abspath(target))
        return t_abs == root_abs or t_abs.startswith(root_abs + os.sep)

    def resolve_refresh_target(self, entry, checkpoint=None,
                               store=None):
        """Admission bound for client-supplied refresh targets (zlint
        ``untrusted-path``): ``POST /refresh`` bodies cross the HTTP
        trust boundary, so a path they name must stay within a store
        this entry was CONFIGURED with server-side — its
        ``refresh_store``, the directory of its loaded checkpoint, or
        its artifact source. -> ``(checkpoint, store)`` admitted
        values (None where absent); raises ValueError (-> 400) for
        anything outside those roots."""
        roots = []
        if entry.refresh_store:
            roots.append(str(entry.refresh_store))
        if entry.checkpoint:
            ckpt = str(entry.checkpoint)
            roots.append(ckpt.rsplit("/", 1)[0]
                         if ckpt.startswith(("http://", "https://"))
                         else (os.path.dirname(ckpt) or "."))
        if entry.source:
            roots.append(str(entry.source))
        admitted = []
        for target in (checkpoint, store):
            if target is None or target == "":
                admitted.append(None)
                continue
            if not isinstance(target, str):
                raise ValueError("refresh target must be a string "
                                 "path, got %s"
                                 % type(target).__name__)
            if not any(self._within_store(root, target)
                       for root in roots):
                raise ValueError(
                    "refresh target %r is outside the model's "
                    "configured stores — load the entry with "
                    "refresh_store= to allow a new location" % target)
            admitted.append(target)
        return tuple(admitted)

    # -- lookup --------------------------------------------------------

    def get(self, name):
        with self._lock:
            try:
                return self._models[name]
            except KeyError:
                raise KeyError("no model %r (serving: %s)"
                               % (name, sorted(self._models) or "none"))

    def names(self):
        with self._lock:
            return sorted(self._models)

    def decoder(self, name):
        """The model's continuous-batching decode plane, built on
        first use (:class:`~veles.serving.decode.ContinuousBatcher`).
        Raises :class:`KeyError` for unknown names and
        :class:`ValueError` when the archive cannot generate (not an
        LM: no leading embedding / non-causal attention)."""
        entry = self.get(name)
        decoder = entry.decoder
        if decoder is not None:
            return decoder
        from veles.serving.decode import (ContinuousBatcher,
                                          GenerativeEngine)
        with entry._decoder_lock:
            if entry._closed:
                # raced an unload/replace: the entry will never be
                # served again, so a decoder built now would leak
                raise KeyError("model %r was unloaded" % name)
            if entry.decoder is None:
                engine = GenerativeEngine(
                    entry.model, n_slots=self.decode_slots,
                    max_len=self.decode_max_len,
                    name="decode-engine-%s" % name)
                entry.decoder = ContinuousBatcher(
                    engine, max_queue=self.decode_max_queue,
                    name="decode-%s" % name, model=name)
                self.info(
                    "decode plane for %s: %d KV slots x %d tokens "
                    "(%.1f MB pool)", name, engine.pool.n_slots,
                    engine.max_len, engine.pool.nbytes() / 1048576.0)
            return entry.decoder

    def describe(self):
        with self._lock:
            entries = list(self._models.values())
        return [e.describe() for e in entries]

    def metrics(self):
        with self._lock:
            entries = list(self._models.items())
            failures = dict(self._refresh_failures)
        out = {}
        for name, e in entries:
            m = dict(e.batcher.metrics(), version=e.version,
                     compiled_buckets=e.engine.compiled_buckets,
                     refresh_failures=failures.get(name, 0))
            store = self._checkpoint_store(e.checkpoint)
            if store is not None:
                m["checkpoint_store"] = store.metrics()
            decoder = e.decoder
            if decoder is not None:
                # the decode plane's view: tokens/s, KV occupancy,
                # queue — what velescli top renders per target
                m["decode"] = decoder.metrics()
            out[name] = m
        return out

    @staticmethod
    def _checkpoint_store(checkpoint):
        if not checkpoint or not str(checkpoint).startswith(
                ("http://", "https://")):
            return None
        from veles.snapshotter import store_for
        return store_for(str(checkpoint))[0]
