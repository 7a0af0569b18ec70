"""Abstract minibatch server.

Re-design of ``veles/loader/base.py`` [U] (SURVEY.md §2.3 "Loader
base"). Semantics preserved: three sample classes served in class order
(TEST=0 → VALID=1 → TRAIN=2) within each epoch; the train class is
reshuffled every epoch with a seeded generator; ``last_minibatch`` fires
on the final minibatch of each class and ``epoch_ended`` on the final
minibatch of the epoch; in distributed runs the loader is the unit whose
master→slave payload is minibatch index ranges (SURVEY.md §3.3).
"""

import numpy

from veles import prng, telemetry
from veles.distributable import IDistributable
from veles.memory import Array
from veles.mutable import Bool
from veles.units import Unit

CLASS_TEST, CLASS_VALID, CLASS_TRAIN = 0, 1, 2
TRIAGE = ("test", "validation", "train")


class Loader(Unit, IDistributable):
    """Base minibatch server unit.

    Subclasses implement :meth:`load_data` (fill ``class_lengths``,
    prepare storage) and :meth:`fill_minibatch` (materialise the rows of
    ``minibatch_indices`` into ``minibatch_data``/``minibatch_labels``).
    """

    negotiates_on_connect = True
    #: True when the whole dataset can live device-resident and
    #: minibatches can be gathered by index on device (enables the
    #: class-scan fast path in XLAStep)
    supports_device_gather = False
    #: True when the loader can materialize minibatch windows on demand
    #: (host decode/augment) for the streaming fast path in XLAStep —
    #: the dataset does NOT need to fit on device; data is shipped in
    #: stacked windows with the metrics fetched once per window
    supports_streaming = False

    def __init__(self, workflow, minibatch_size=100, shuffle=True,
                 prng_key="loader", normalization_type=None,
                 normalization_parameters=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self.max_minibatch_size = int(minibatch_size)
        self.shuffle_enabled = bool(shuffle)
        self.prng = prng.get(prng_key)
        #: pluggable input normalizer (SURVEY.md §2.3 "Normalizers");
        #: fitted on TRAIN data, applied per loader subclass
        from veles.normalization import factory
        self.normalizer = factory(normalization_type,
                                  **(normalization_parameters or {}))
        self._normalization_applied = False

        #: samples per class: [test, valid, train]
        self.class_lengths = [0, 0, 0]
        self.minibatch_data = Array()
        self.minibatch_labels = Array()
        #: regression targets (MSE workflows); empty when unused
        self.minibatch_targets = Array()
        self.minibatch_indices = Array()
        #: number of *valid* (non-padding) rows in the current minibatch
        self.minibatch_size = 0
        self.minibatch_class = CLASS_TRAIN
        self.minibatch_offset = 0

        #: set by XLAStep in scan mode: host minibatch filling is
        #: skipped (the device gathers rows itself)
        self.device_gather = False

        self.epoch_number = 0
        self.epoch_ended = Bool(False)
        self.last_minibatch = Bool(False)
        #: live gate mirror: True while serving train minibatches (GD
        #: units' gate_skip is its inverse)
        self.train_phase = Bool(True)

        # epoch iteration state
        self._order = []          # [(cls, ndarray-of-global-indices)]
        self._cls_pos = 0
        self._idx_pos = 0

        # distributed: master-side queue of pending (cls, lo, hi) jobs
        self._pending_jobs = []
        self._inflight = {}

        # telemetry: epoch counter/gauge plus per-class minibatch and
        # sample counters (samples-per-second = rate() over the scrape)
        self._tele_epochs = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_loader_epochs_total", "Epochs served",
                ("loader",)).labels(self.name))
        self._tele_epoch_gauge = telemetry.LazyChild(
            lambda: telemetry.gauge(
                "veles_loader_epoch", "Current epoch number",
                ("loader",)).labels(self.name))
        self._tele_serve = {}     # cls -> (minibatches, samples)

    # -- to be implemented by subclasses ------------------------------

    def load_data(self):
        """Discover the dataset: set class_lengths, allocate storage."""
        raise NotImplementedError

    def create_minibatch_data(self):
        """Allocate ``minibatch_data`` (padded to max_minibatch_size)."""
        raise NotImplementedError

    def fill_minibatch(self):
        """Fill minibatch arrays for ``minibatch_indices[:minibatch_size]``."""
        raise NotImplementedError

    # -- derived quantities -------------------------------------------

    @property
    def total_samples(self):
        return int(sum(self.class_lengths))

    def class_offset(self, cls):
        return int(sum(self.class_lengths[:cls]))

    @property
    def effective_batches_per_epoch(self):
        mb = self.max_minibatch_size
        return sum((n + mb - 1) // mb for n in self.class_lengths)

    # -- lifecycle -----------------------------------------------------

    def apply_normalization(self):
        """Fit + apply ``self.normalizer`` (subclass hook). The base
        FAILS LOUDLY when a normalizer was configured on a loader that
        has no implementation — a silently-dropped normalization_type
        would train on raw data without warning."""
        from veles.normalization import NoneNormalizer
        if not isinstance(self.normalizer, NoneNormalizer):
            raise NotImplementedError(
                "%s does not implement pluggable normalization "
                "(normalization_type=%r); use a full-batch loader or "
                "normalize in load_data/fill_minibatch"
                % (type(self).__name__, self.normalizer.NAME))

    def initialize(self, **kwargs):
        super().initialize(**kwargs)
        if self.total_samples == 0:
            self.load_data()
        if self.total_samples == 0:
            raise ValueError("%s loaded an empty dataset" % self.name)
        if not self._normalization_applied:   # idempotent on resume
            self.apply_normalization()
            self._normalization_applied = True
        self.create_minibatch_data()
        if not self.minibatch_indices:
            self.minibatch_indices.reset(
                numpy.zeros(self.max_minibatch_size, dtype=numpy.int32))
        self._start_epoch(first=True)

    def _class_indices(self, cls):
        off = self.class_offset(cls)
        idx = numpy.arange(off, off + self.class_lengths[cls],
                           dtype=numpy.int32)
        if cls == CLASS_TRAIN and self.shuffle_enabled:
            idx = idx[self.prng.permutation(len(idx))]
        return idx

    def _generate_order(self):
        return [(cls, self._class_indices(cls))
                for cls in (CLASS_TEST, CLASS_VALID, CLASS_TRAIN)
                if self.class_lengths[cls] > 0]

    def _start_epoch(self, first=False):
        if first:
            # fresh run / resume: any pre-generated orders are stale
            self._future_orders = []
        else:
            self.epoch_number += 1
            self._tele_epochs.get().inc()
        self._tele_epoch_gauge.get().set(self.epoch_number)
        future = getattr(self, "_future_orders", None)
        if not first and future:
            # consume the order peek_epoch_orders pre-generated (the
            # multi-epoch dispatch path); the PRNG already advanced
            self._order = future.pop(0)
        else:
            self._order = self._generate_order()
        self._cls_pos = 0
        self._idx_pos = 0

    def peek_epoch_orders(self, n):
        """Orders for the current epoch and the next ``n-1``, cached so
        subsequent ``_start_epoch`` calls serve EXACTLY these (shuffles
        come from the same PRNG stream in the same sequence — a chunked
        run is bit-identical to an unchunked one). Enables XLAStep to
        compile several epochs into one device program."""
        if not hasattr(self, "_future_orders"):
            self._future_orders = []
        while len(self._future_orders) < n - 1:
            self._future_orders.append(self._generate_order())
        return [self._order] + self._future_orders[:n - 1]

    # -- serving -------------------------------------------------------

    @staticmethod
    def pad_indices(chunk, size):
        """THE static-shape padding convention, used identically by the
        per-step and scan paths: pad rows repeat the last index (and
        evaluators mask rows past the true count)."""
        padded = numpy.empty(size, dtype=numpy.int32)
        padded[:len(chunk)] = chunk
        if len(chunk) < size:
            padded[len(chunk):] = chunk[-1] if len(chunk) else 0
        return padded

    def _serve_chunk(self, cls, chunk):
        """Publish one minibatch: class/gates bookkeeping + padding."""
        self.minibatch_class = cls
        self.train_phase << (cls == CLASS_TRAIN)
        self.minibatch_size = len(chunk)
        tele = self._tele_serve.get(cls)
        if tele is None:
            cname = TRIAGE[cls]
            tele = self._tele_serve[cls] = (
                telemetry.LazyChild(
                    lambda n=cname: telemetry.counter(
                        "veles_loader_minibatches_total",
                        "Minibatches served", ("loader", "cls"))
                    .labels(self.name, n)),
                telemetry.LazyChild(
                    lambda n=cname: telemetry.counter(
                        "veles_loader_samples_total",
                        "Samples served", ("loader", "cls"))
                    .labels(self.name, n)))
        tele[0].get().inc()
        tele[1].get().inc(len(chunk))
        self.minibatch_indices.map_invalidate()
        self.minibatch_indices.mem[...] = self.pad_indices(
            chunk, self.max_minibatch_size)
        if not self.device_gather:
            self.fill_minibatch()

    def class_schedule(self, cls, order=None):
        """(idx_mat (n_mb, mb) int32, valids (n_mb,) int32) — the full
        minibatch schedule of ``cls`` for the given epoch order (default:
        the CURRENT epoch; the class-scan fast path consumes a whole
        class in one dispatch)."""
        for c, indices in (self._order if order is None else order):
            if c != cls:
                continue
            mb = self.max_minibatch_size
            n_mb = (len(indices) + mb - 1) // mb
            idx_mat = numpy.empty((n_mb, mb), numpy.int32)
            valids = numpy.empty(n_mb, numpy.int32)
            for i in range(n_mb):
                chunk = indices[i * mb:(i + 1) * mb]
                idx_mat[i] = self.pad_indices(chunk, mb)
                valids[i] = len(chunk)
            return idx_mat, valids
        raise ValueError("class %d not in this epoch's order" % cls)

    # -- streaming fast-path hooks (see XLAStep._dispatch_stream_epoch) --

    def epoch_plan(self):
        """[(cls, idx_mat, valids), ...] for the CURRENT epoch in
        serving order, without advancing serving state."""
        return [(cls, *self.class_schedule(cls))
                for cls, _ in self._order]

    def materialize_window(self, cls, idx_mat):
        """dict name -> (B, mb, ...) host arrays for the given rows of
        minibatch indices (B minibatches). Streaming loaders override
        to decode/augment; the base gathers nothing."""
        raise NotImplementedError(
            "%s does not support streaming" % self.name)

    def xla_batch_transform(self, name, tensor, train=False):
        """Traced per-minibatch transform applied on DEVICE to streamed
        batch tensors (e.g. uint8 -> normalized float, so the host→
        device link carries bytes, not floats). ``train`` distinguishes
        phase-dependent augmentation (mirroring etc. must never touch
        eval minibatches). Default: identity."""
        return tensor

    def run(self):
        self.epoch_ended << False
        self.last_minibatch << False
        if self._cls_pos >= len(self._order):
            self._start_epoch()
        cls, indices = self._order[self._cls_pos]
        mb = self.max_minibatch_size
        lo = self._idx_pos
        hi = min(lo + mb, len(indices))
        self.minibatch_offset = lo
        self._serve_chunk(cls, indices[lo:hi])
        self._idx_pos = hi
        if hi >= len(indices):
            self.last_minibatch << True
            self._cls_pos += 1
            self._idx_pos = 0
            if self._cls_pos >= len(self._order):
                self.epoch_ended << True

    # -- checkpoint support (resume restarts the in-flight epoch) ------

    def get_state(self):
        state = {"epoch_number": self.epoch_number,
                 "prng_state": dict(self.prng._gen.bit_generator.state)}
        norm = self.normalizer.state()
        if norm:
            # fitted input statistics ride the checkpoint so an
            # inference-only restore (no train data to re-fit from)
            # still normalizes identically
            state["normalizer"] = norm
        return state

    def set_state(self, state):
        self.epoch_number = int(state["epoch_number"])
        self.prng._gen.bit_generator.state = state["prng_state"]
        norm = state.get("normalizer")
        if norm:
            name = norm.get("__name__")
            if name and name != self.normalizer.NAME:
                # the checkpoint's normalizer wins over the (possibly
                # default) loader config — silently grafting fitted
                # stats onto the wrong class would skip normalization
                from veles.normalization import from_state
                self.warning(
                    "restoring %r normalizer from checkpoint "
                    "(loader was configured with %r)",
                    name, self.normalizer.NAME)
                self.normalizer = from_state(norm)
            else:
                self.normalizer.set_state(norm)
        # restart the in-flight epoch (snapshots happen at the valid/
        # train boundary; replaying the epoch's eval classes is cheap)
        self._start_epoch(first=True)

    # -- IDistributable: ship minibatch index ranges (SURVEY.md §3.3) --

    def generate_data_for_slave(self, slave=None):
        """Pop the next minibatch job; ``None`` signals the epoch's job
        queue is exhausted (the master then aggregates the epoch and
        calls :meth:`master_start_epoch` for the next one)."""
        if not self._pending_jobs:
            return None
        job = self._pending_jobs.pop(0)
        self._inflight.setdefault(slave, []).append(job)
        return job

    def _ensure_dist_prng(self):
        """The master-side shuffle stream, created on first use — ONE
        place owns the derivation, so epoch start and master-restart
        restore (server.py) can never drift apart."""
        if not hasattr(self, "_dist_prng"):
            from veles.prng import RandomGenerator
            self._dist_prng = RandomGenerator(
                "%s.dist" % self.name, self.prng.state_seed + 0x9E3779B9)
        return self._dist_prng

    def master_start_epoch(self):
        """Master side: (re)fill the job queue for one epoch. Uses a
        dedicated generator derived from the loader seed, so master-mode
        shuffles never desynchronize the local serving PRNG (fixed-seed
        reproducibility contract)."""
        self._ensure_dist_prng()
        mb = self.max_minibatch_size
        for cls in (CLASS_TEST, CLASS_VALID, CLASS_TRAIN):
            if self.class_lengths[cls] == 0:
                continue
            off = self.class_offset(cls)
            indices = numpy.arange(off, off + self.class_lengths[cls],
                                   dtype=numpy.int32)
            if cls == CLASS_TRAIN and self.shuffle_enabled:
                indices = indices[self._dist_prng.permutation(len(indices))]
            for lo in range(0, len(indices), mb):
                self._pending_jobs.append(
                    (cls, indices[lo:lo + mb].tolist()))

    def apply_data_from_master(self, data):
        if data is None:
            return
        cls, idx_list = data
        self._serve_chunk(cls, numpy.asarray(idx_list, dtype=numpy.int32))

    def generate_data_for_master(self):
        return None

    def apply_data_from_slave(self, data, slave=None):
        if slave in self._inflight and self._inflight[slave]:
            self._inflight[slave].pop(0)

    def drop_slave(self, slave=None):
        """Re-queue in-flight minibatches of a dead slave (§5.3);
        -> how many were requeued."""
        jobs = self._inflight.pop(slave, [])
        for job in jobs:
            self._pending_jobs.insert(0, job)
        return len(jobs)
