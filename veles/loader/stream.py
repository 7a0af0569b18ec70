"""Streaming loaders — datasets that do NOT live device-resident.

The reference's ImageNet-tier loaders stream from disk with host-side
augmentation (SURVEY.md §2.3 "Image loaders", §7 stage 6 "host async
prefetch + device_put double-buffering"). The TPU translation is the
XLAStep streaming mode: the loader materializes WINDOWS of stacked
minibatches on the host (decode/augment in a thread pool, overlapped
with device compute), XLAStep ships each window up once (image data
travels as uint8) and runs a compiled scan over the window's minibatches; metrics come back in one
fetch per window.

This module provides the array-backed base used directly for synthetic
benchmarks and as the machinery under ``veles.loader.image``, plus the
continual-training ingest tier (ISSUE 16): a :class:`StreamSource`
(seekable sample feed), :class:`ContinualStreamLoader` (bounded
async host-side prefetch through a daemon producer thread, per-round
stream cursor, per-slave shard assignment over the lease machinery)
— the input half of the ``veles/continual.py`` closed loop. Device
double-buffering for the windows this loader stages lives in
``XLAStep._put_window`` (one upload in flight, overlapped with the
previous window's compute).
"""

import concurrent.futures
import threading
import time

import numpy

from veles import telemetry
from veles.loader.base import (CLASS_TEST, CLASS_VALID, CLASS_TRAIN,
                               Loader)


class StreamLoader(Loader):
    """Streams minibatch windows; subclasses produce individual samples.

    Contract: implement :meth:`load_data` (set ``class_lengths``) and
    :meth:`materialize_samples` (global indices -> dict of per-sample
    arrays). Decoding parallelism and window stacking live here.
    """

    supports_streaming = True
    #: True when materialize_samples is vectorized numpy (GIL-bound):
    #: the window is produced in ONE call — fanning rows out to decode
    #: threads only adds GIL thrash. File/image loaders (whose decode
    #: releases the GIL inside the codec) leave this False.
    window_vectorized = False

    def __init__(self, workflow, prefetch_workers=8, **kwargs):
        super().__init__(workflow, **kwargs)
        self.prefetch_workers = int(prefetch_workers)
        self._pool = None

    @property
    def pool(self):
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.prefetch_workers,
                thread_name_prefix="%s-decode" % self.name)
        return self._pool

    # -- subclass surface ---------------------------------------------

    def materialize_samples(self, indices, train=None):
        """dict name -> (len(indices), ...) host arrays for the given
        GLOBAL sample indices. ``train`` carries the phase of the
        CLASS being materialized: the fused dispatch builds every
        window of an epoch up front, so ``self.train_phase`` (the
        live serving gate) must NOT be consulted there — None means
        "derive from train_phase" (the per-serve oracle path)."""
        raise NotImplementedError

    def sample_spec(self):
        """dict name -> (shape, dtype) of ONE sample, used to allocate
        the (never host-filled) minibatch template Arrays."""
        raise NotImplementedError

    # -- Loader plumbing ----------------------------------------------

    def create_minibatch_data(self):
        spec = self.sample_spec()
        shape, dtype = spec["data"]
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size,) + tuple(shape), dtype))
        if "labels" in spec:
            lshape, ldtype = spec["labels"]
            self.minibatch_labels.reset(numpy.zeros(
                (self.max_minibatch_size,) + tuple(lshape), ldtype))
        if "targets" in spec:
            tshape, tdtype = spec["targets"]
            self.minibatch_targets.reset(numpy.zeros(
                (self.max_minibatch_size,) + tuple(tshape), tdtype))

    def fill_minibatch(self):
        """Host path (numpy oracle / per-step mode): materialize just
        this minibatch."""
        idx = self.minibatch_indices.mem[:self.minibatch_size]
        batch = self.materialize_samples(numpy.asarray(idx))
        pad = self.max_minibatch_size - len(idx)
        for name, arr in batch.items():
            target = {"data": self.minibatch_data,
                      "labels": self.minibatch_labels,
                      "targets": self.minibatch_targets}[name]
            target.map_invalidate()
            target.mem[:len(idx)] = arr
            if pad:
                target.mem[len(idx):] = arr[-1:]

    def materialize_window(self, cls, idx_mat):
        """Stack B minibatches: one vectorized call over the whole
        window when the producer is numpy-bound, else decode rows in
        the thread pool (one future per minibatch)."""
        train = cls == CLASS_TRAIN
        idx_mat = numpy.asarray(idx_mat)
        if self.window_vectorized:
            b, mb = idx_mat.shape
            flat = self.materialize_samples(idx_mat.reshape(-1),
                                            train=train)
            return {name: arr.reshape((b, mb) + arr.shape[1:])
                    for name, arr in flat.items()}
        futures = [self.pool.submit(self.materialize_samples, row,
                                    train)
                   for row in idx_mat]
        batches = [f.result() for f in futures]
        return {name: numpy.stack([b[name] for b in batches])
                for name in batches[0]}


class ArrayStreamLoader(StreamLoader):
    """Streaming view over in-memory arrays (synthetic benchmarks, and
    the honest stand-in for 'dataset too big for HBM' testing: nothing
    is device-resident; every window travels the host→device link)."""

    window_vectorized = True

    def __init__(self, workflow, data=None, labels=None, targets=None,
                 class_lengths=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self._data = data
        self._labels = labels
        self._targets = targets
        if class_lengths is not None:
            self.class_lengths = list(class_lengths)

    def load_data(self):
        if self._data is None:
            raise ValueError("%s: data unset" % self.name)

    def sample_spec(self):
        spec = {"data": (self._data.shape[1:], self._data.dtype)}
        if self._labels is not None:
            spec["labels"] = (self._labels.shape[1:], self._labels.dtype)
        if self._targets is not None:
            spec["targets"] = (self._targets.shape[1:],
                               self._targets.dtype)
        return spec

    def materialize_samples(self, indices, train=None):
        out = {"data": self._data[indices]}
        if self._labels is not None:
            out["labels"] = self._labels[indices]
        if self._targets is not None:
            out["targets"] = self._targets[indices]
        return out


# -- continual ingest (ISSUE 16) ---------------------------------------


class StreamSource:
    """A seekable, unbounded sample feed: the ingest side of the
    continual loop. ``fetch(start, count)`` may BLOCK until the
    requested positions exist (a stalled upstream is exactly the
    staleness-SLO scenario) and must be safe to call for any already-
    produced position — resume and shard takeover both re-fetch."""

    def spec(self):
        """dict name -> (per-sample shape tuple, dtype)."""
        raise NotImplementedError

    def fetch(self, start, count):
        """dict name -> (count, ...) host arrays for stream positions
        ``[start, start + count)``."""
        raise NotImplementedError

    def close(self):
        pass


class ArraySource(StreamSource):
    """In-memory source cycling over fixed arrays — the synthetic
    stand-in for an endless feed (position ``p`` serves row
    ``p % len(data)``), and the deterministic backend behind the
    chaos tests' HTTP ingest."""

    def __init__(self, data, labels=None, targets=None):
        self._arrays = {"data": numpy.asarray(data)}
        if labels is not None:
            self._arrays["labels"] = numpy.asarray(labels)
        if targets is not None:
            self._arrays["targets"] = numpy.asarray(targets)

    def spec(self):
        return {name: (arr.shape[1:], arr.dtype)
                for name, arr in self._arrays.items()}

    def fetch(self, start, count):
        n = len(self._arrays["data"])
        idx = numpy.arange(start, start + count, dtype=numpy.int64) % n
        return {name: arr[idx] for name, arr in self._arrays.items()}


class ContinualStreamLoader(StreamLoader):
    """Endless stream served as fixed-size training ROUNDS.

    Each epoch ("round") consumes the next ``round_samples`` stream
    positions; a small pinned validation set (the stream's first
    ``valid_samples`` positions) judges improvement so the snapshot
    gate keeps working. Global train index ``g`` maps statelessly to
    stream position ``g - class_offset(CLASS_TRAIN)`` — indices are
    self-describing, so master→slave jobs need no cursor sync and a
    job replayed after restart re-fetches the same samples.

    Host-side prefetch: a daemon producer thread pulls blocks of
    ``max_minibatch_size`` samples from the source into a bounded
    position-keyed buffer (at most ``prefetch_blocks`` resident, the
    producer blocks when full), so decode/transport overlaps device
    compute and the dataset never needs to fit in memory. Reads grab
    references under the lock and assemble outside it — safe under
    XLAStep's concurrent (depth-2) window staging.

    Checkpoint state carries the stream cursor: a resumed run
    continues at the next round's first position — no replay, no
    skip (mid-round snapshots restart the in-flight round, the same
    contract as the base loader's in-flight epoch).
    """

    window_vectorized = True

    def __init__(self, workflow, source=None, round_samples=1024,
                 valid_samples=0, shards=1, prefetch_blocks=16,
                 fetch_retry_s=0.5, **kwargs):
        kwargs.setdefault("shuffle", False)   # stream order IS the order
        super().__init__(workflow, **kwargs)
        self.source = source
        self.round_samples = int(round_samples)
        self.valid_samples = int(valid_samples)
        #: shard partitions per round (master mode): train job k goes
        #: to the slave holding shard ``(first_index // mb) % shards``
        self.shards = max(1, int(shards))
        self.prefetch_blocks = max(2, int(prefetch_blocks))
        self.fetch_retry_s = float(fetch_retry_s)
        #: absolute stream position where the CURRENT round starts
        #: (advances by round_samples the moment a round's last
        #: minibatch is served — an epoch-boundary checkpoint resumes
        #: at the next round)
        self.cursor_base = None
        #: wall time the newest sample arrived from the source — the
        #: ingest clock the staleness SLO measures against
        #: (veles/continual.py stamps it into checkpoint MANIFESTs)
        self.last_ingest_wall = 0.0
        self._valid = None
        self._gen_ahead = 0
        # prefetch plane (all guarded by _cond)
        self._cond = threading.Condition()
        self._blocks = {}            # block id -> dict name -> arrays
        self._next_block = None
        self._demand_block = -1
        self._served_floor = 0       # positions below this are done
        self._producer = None
        self._producer_stop = False
        self._reset_seq = 0
        # lease machinery: distinct slave identity -> shard index
        self._slave_shards = {}
        self._tele_fetch_failures = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_stream_fetch_failures_total",
                "Ingest-source fetches that failed and were retried "
                "(a stalled stream grows this while staleness climbs)",
                ("loader",)).labels(self.name))
        self._tele_buffer = telemetry.LazyChild(
            lambda: telemetry.gauge(
                "veles_stream_prefetch_blocks",
                "Sample blocks resident in the prefetch buffer",
                ("loader",)).labels(self.name))

    # -- dataset shape -------------------------------------------------

    @property
    def block_samples(self):
        return self.max_minibatch_size

    def load_data(self):
        if self.source is None:
            raise ValueError("%s: source unset" % self.name)
        if self.valid_samples:
            self._valid = self.source.fetch(0, self.valid_samples)
            with self._cond:
                self.last_ingest_wall = time.time()
        self.class_lengths = [0, self.valid_samples,
                              self.round_samples]
        if self.cursor_base is None:
            # fresh start: the stream's head fed the validation set
            self.cursor_base = self.valid_samples

    def sample_spec(self):
        return {name: (tuple(shape), numpy.dtype(dtype))
                for name, (shape, dtype) in self.source.spec().items()}

    # -- round scheduling ----------------------------------------------

    def _generate_order(self):
        order = []
        for cls in (CLASS_TEST, CLASS_VALID):
            if self.class_lengths[cls] > 0:
                order.append((cls, self._class_indices(cls)))
        off = self.class_offset(CLASS_TRAIN)
        start = self.cursor_base + self._gen_ahead * self.round_samples
        # int32: the minibatch plumbing's index dtype — a ~2.1e9
        # lifetime sample ceiling, loudly enforced
        if start + self.round_samples + off > numpy.iinfo(numpy.int32).max:
            raise OverflowError(
                "%s: stream position %d overflows the int32 index "
                "plumbing" % (self.name, start + self.round_samples))
        order.append((CLASS_TRAIN, numpy.arange(
            off + start, off + start + self.round_samples,
            dtype=numpy.int32)))
        self._gen_ahead += 1
        return order

    def _start_epoch(self, first=False):
        if first:
            self._gen_ahead = 0
        super()._start_epoch(first)

    def run(self):
        super().run()
        if bool(self.epoch_ended):
            # the round's stream window is consumed the moment its
            # last minibatch is served: an epoch-boundary checkpoint
            # resumes at the NEXT round
            self.cursor_base += self.round_samples
            self._gen_ahead = max(0, self._gen_ahead - 1)

    # -- prefetch plane ------------------------------------------------

    def _ensure_producer(self, first_block):
        if self._producer is not None and self._producer.is_alive():
            return
        if self._next_block is None:
            self._next_block = int(first_block)
        self._producer_stop = False
        self._producer = threading.Thread(
            target=self._produce, args=(self._reset_seq,),
            daemon=True, name="%s-ingest" % self.name)
        self._producer.start()

    def _produce(self, seq):
        bs = self.block_samples
        while True:
            with self._cond:
                while (not self._producer_stop
                       and seq == self._reset_seq
                       and len(self._blocks) >= self.prefetch_blocks
                       and self._next_block > self._demand_block):
                    self._cond.wait(1.0)
                if self._producer_stop or seq != self._reset_seq:
                    return
                block = self._next_block
            try:
                batch = self.source.fetch(block * bs, bs)
            except Exception as exc:
                self._tele_fetch_failures.get().inc()
                self.warning("ingest fetch @%d failed (%s: %s) — "
                             "retrying", block * bs,
                             type(exc).__name__, exc)
                time.sleep(self.fetch_retry_s)
                continue
            with self._cond:
                if self._producer_stop or seq != self._reset_seq:
                    return
                self._blocks[block] = batch
                self._next_block = block + 1
                self.last_ingest_wall = time.time()
                self._tele_buffer.get().set(len(self._blocks))
                self._cond.notify_all()

    def _gather_stream(self, positions):
        bs = self.block_samples
        needed = sorted({int(p) // bs for p in positions})
        with self._cond:
            self._ensure_producer(needed[0])
            self._demand_block = max(self._demand_block, needed[-1])
            self._cond.notify_all()
            while True:
                if self._producer_stop:
                    raise RuntimeError("%s stopped while a window was "
                                       "being materialized" % self.name)
                if all(b in self._blocks for b in needed):
                    break
                self._cond.wait(1.0)
                self._ensure_producer(needed[0])
            grabbed = {b: self._blocks[b] for b in needed}
            # forward-only stream: once grabbed (local refs keep the
            # arrays alive), positions at or below this window's top
            # are never demanded again — evict fully-passed blocks
            self._served_floor = max(self._served_floor,
                                     int(positions.max()) + 1)
            floor_block = self._served_floor // bs
            for b in [b for b in self._blocks if b < floor_block]:
                del self._blocks[b]
            self._tele_buffer.get().set(len(self._blocks))
            self._cond.notify_all()
        names = next(iter(grabbed.values())).keys()
        return {name: numpy.stack(
            [grabbed[int(p) // bs][name][int(p) % bs]
             for p in positions])
            for name in names}

    def materialize_samples(self, indices, train=None):
        indices = numpy.asarray(indices)
        off = self.class_offset(CLASS_TRAIN)
        if len(indices) and int(indices[0]) < off:
            # windows are per class: the whole request is the pinned
            # validation set
            return {name: arr[indices]
                    for name, arr in self._valid.items()}
        return self._gather_stream(indices.astype(numpy.int64) - off)

    def stop(self):
        with self._cond:
            self._producer_stop = True
            self._cond.notify_all()
        super().stop()

    # -- checkpoint: the stream cursor ---------------------------------

    def get_state(self):
        state = super().get_state()
        state["stream_cursor"] = {
            "cursor_base": int(self.cursor_base or 0),
            "ingest_wall": float(self.last_ingest_wall),
        }
        return state

    def set_state(self, state):
        cursor = state.get("stream_cursor")
        if cursor:
            with self._cond:
                self.cursor_base = int(cursor["cursor_base"])
                self.last_ingest_wall = float(
                    cursor.get("ingest_wall", 0.0))
                # drop buffered blocks from the pre-restore position;
                # in-flight producer inserts are fenced by the seq
                self._reset_seq += 1
                self._blocks.clear()
                self._next_block = None
                self._demand_block = -1
                self._served_floor = int(self.cursor_base)
                self._cond.notify_all()
        super().set_state(state)

    # -- per-slave shard assignment (lease machinery) ------------------

    def _job_shard(self, job):
        """Shard of a pending job, derived from CONTENT (the absolute
        first index), so the master's persist/restore path — which
        round-trips plain ``(cls, idx_list)`` pairs — keeps working."""
        cls, idx = job
        if cls != CLASS_TRAIN or self.shards <= 1 or not idx:
            return None
        return (int(idx[0]) // self.max_minibatch_size) % self.shards

    def _shard_for(self, slave):
        shard = self._slave_shards.get(slave)
        if shard is None:
            used = set(self._slave_shards.values())
            free = [s for s in range(self.shards) if s not in used]
            shard = free[0] if free \
                else len(self._slave_shards) % self.shards
            self._slave_shards[slave] = shard
            self.info("stream shard %d/%d -> slave %s", shard,
                      self.shards, slave)
            telemetry.record_event("stream_shard_assigned",
                                   loader=self.name, slave=str(slave),
                                   shard=shard, shards=self.shards)
        return shard

    def master_start_epoch(self):
        mb = self.max_minibatch_size
        for cls in (CLASS_TEST, CLASS_VALID):
            if self.class_lengths[cls] == 0:
                continue
            off = self.class_offset(cls)
            indices = numpy.arange(off, off + self.class_lengths[cls],
                                   dtype=numpy.int32)
            for lo in range(0, len(indices), mb):
                self._pending_jobs.append(
                    (cls, indices[lo:lo + mb].tolist()))
        off = self.class_offset(CLASS_TRAIN)
        start = int(self.cursor_base)
        for lo in range(0, self.round_samples, mb):
            hi = min(lo + mb, self.round_samples)
            self._pending_jobs.append(
                (CLASS_TRAIN, [off + start + j for j in range(lo, hi)]))
        # queue filled == round claimed: the master persist that
        # follows the epoch carries the NEXT round's cursor, and the
        # in-flight jobs it folds back re-serve this one exactly once
        self.cursor_base = start + self.round_samples

    def generate_data_for_slave(self, slave=None):
        if not self._pending_jobs:
            return None
        shard = self._shard_for(slave)
        assigned = set(self._slave_shards.values())
        pick = steal = None
        for i, job in enumerate(self._pending_jobs):
            s = self._job_shard(job)
            if s is None or s == shard:
                pick = i
                break
            if steal is None and s not in assigned:
                steal = i
        if pick is None:
            # shards with no live owner (a slave died or never
            # arrived) must not wedge the round: steal their work
            pick = steal
        if pick is None:
            # someone else's shard — the master answers "wait", the
            # slave polls again
            return None
        job = self._pending_jobs.pop(pick)
        self._inflight.setdefault(slave, []).append(job)
        return job

    def drop_slave(self, slave=None):
        self._slave_shards.pop(slave, None)
        return super().drop_slave(slave)
