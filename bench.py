"""Benchmark entry: prints ONE JSON line for the driver.

Primary metric: MNIST training steps/sec on the TPU, ``vs_baseline`` =
speedup over the reference-style numpy backend on the same host
(BASELINE.json: "samples/MNIST: 2-layer All2All softmax (numpy_run CPU
baseline)"). ``extra`` carries the other rows measured in the same
run, and ``extra["device"]`` names the device they were measured on.

A run that finds no TPU exits non-zero with the error and prints no
row: a CPU timing is never written under a device metric's name.

Measurement method: the XLA path dispatches CHUNKS of whole epochs as
one XLA program (see ``XLAStep._dispatch_epoch``); timing starts after
the first chunk (covers compilation), each subsequent chunk is timed
individually — its packed metric fetch is the synchronization point —
and BOTH the best and the median chunk rate are reported.

Key convention: every PRIMARY key — the headline ``value`` and
``extra`` keys like ``lm_57M_tokens_per_sec`` — carries the MEDIAN
chunk rate; the fastest chunk is recorded under the explicit ``*_best``
suffix. Every timed chunk carries its full share of dispatch +
metric-fetch cost; nothing is served from pre-computed results.

Work counts come from the telemetry registry (ISSUE 3): every row's
numerator is a delta of the SAME ``veles_loader_*_total`` counters the
runtime increments per served minibatch (``_train_counter``), so bench
figures and a /metrics scrape of the same run can never disagree.
"""

import argparse
import glob
import json
import os
import re
import sys
import time

def device_matmul_tflops(n=8192, reps_lo=16, reps_hi=80):
    """Calibration row: a fixed DEVICE-ONLY bf16 matmul rate, taken at
    the start and the end of a run, so a run whose device slowed down
    half way is visible in its own record.

    Method: chained n³ matmuls under one ``lax.scan`` dispatch — each
    result feeds the next (independent identical dispatches get CSE'd
    into one execution) — with a scalar readback as the sync point.
    The rate comes from the DIFFERENCE between a ``reps_hi`` and a
    ``reps_lo`` run, which cancels any constant dispatch overhead."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    import numpy

    gen = numpy.random.Generator(numpy.random.PCG64(7))
    a = jnp.asarray(gen.standard_normal((n, n), numpy.float32),
                    jnp.bfloat16)
    b = jnp.asarray(gen.standard_normal((n, n), numpy.float32)
                    / numpy.sqrt(n), jnp.bfloat16)

    def chain(reps, samples=3):
        @jax.jit
        def run(a, b):
            def step(c, _):
                return jnp.matmul(
                    c, b, preferred_element_type=jnp.bfloat16), ()
            c, _ = lax.scan(step, a, None, length=reps)
            return c.astype(jnp.float32).sum()
        float(run(a, b))                   # compile + warm
        best = float("inf")
        for _ in range(samples):           # min-of-N
            t0 = time.perf_counter()
            float(run(a, b))               # scalar readback = sync
            best = min(best, time.perf_counter() - t0)
        return best

    dt = chain(reps_hi) - chain(reps_lo)
    if dt <= 0:
        raise RuntimeError(
            "calibration difference non-positive (%.3fs) — timing "
            "noise swamped the measurement" % dt)
    flops = 2.0 * n ** 3 * (reps_hi - reps_lo)
    return flops / dt / 1e12


def lm_train_flops_per_token(dim, layers, ffn_hidden, vocab, seq):
    """Attention-AWARE train FLOPs per token (VERDICT r4 #2 — the
    6·N-only form under-counts long-context rows where attention
    FLOPs rival the matmul params'):

    * matmul parameters: 6 FLOPs each (2 fwd + 4 bwd) over qkv/out/
      ffn/vocab-head weights. The EMBEDDING table is excluded — the
      lookup is a gather, not a matmul (this makes the figures here
      slightly stricter than round 4's 6·N_total arithmetic, which
      credited the 12.6M-param embedding as compute);
    * attention score/context matmuls, CAUSAL coverage: per layer per
      sequence 6·S(S+1)·dim FLOPs (2 fwd + 4 bwd matmuls over the
      S(S+1)/2 causal pairs) -> 6·(S+1)·dim per token per layer.
      Causal, not the 12·L·S·d full-square form: MFU counts the
      FLOPs a perfect implementation NEEDS. The Pallas kernels (auto
      from S=256 up) skip the masked half via their fori_loop bounds
      once a row is more than one tile (S>=1024); the one-tile kernels
      at S<=512 and the scan-flash path below compute the full square
      and mask (a cond skip measured slower in the scan —
      parallel/flash.py), which simply reads as lower MFU here."""
    n_mm = layers * (4 * dim * dim + 2 * dim * ffn_hidden) \
        + dim * vocab
    return 6.0 * n_mm + 6.0 * layers * (seq + 1) * dim


#: the at-scale LM rows: ONE place for each row's loader/model config
#: — the throughput function AND its MFU accounting both read these,
#: so a retune cannot desynchronize the two
LM_ROWS = {
    "57M": (
        {"minibatch_size": 8, "n_train": 512, "n_valid": 32,
         "seq_len": 512, "vocab": 32, "max_period": 8},
        {"dim": 768, "heads": 12, "layers": 8, "ffn_hidden": 3072,
         "attn_block": 256}),
    "57M_s8k": (
        # B=8 from the round-5 sweep (104.7k vs 103.4k at B=4, 88k at
        # the round-4 B=2; the fused backward freed the memory room)
        {"minibatch_size": 8, "n_train": 64, "n_valid": 8,
         "seq_len": 8192, "vocab": 32, "max_period": 8},
        {"dim": 768, "heads": 12, "layers": 8, "ffn_hidden": 3072,
         "attn_block": 256}),
    "110M": (
        {"minibatch_size": 8, "n_train": 512, "n_valid": 32,
         "seq_len": 512, "vocab": 16384, "max_period": 8},
        {"dim": 768, "heads": 12, "layers": 12, "ffn_hidden": 3072,
         "attn_block": 256}),
    "110M_s8k": (
        # B=4 from the round-5 sweep (66.8k = 35.2% MFU vs 62.4k at
        # the round-4 B=2; B=8 exceeds HBM — 17.5G vs 15.75G)
        {"minibatch_size": 4, "n_train": 32, "n_valid": 4,
         "seq_len": 8192, "vocab": 16384, "max_period": 8},
        {"dim": 768, "heads": 12, "layers": 12, "ffn_hidden": 3072,
         "attn_block": 256}),
    "345M": (
        {"minibatch_size": 8, "n_train": 256, "n_valid": 16,
         "seq_len": 512, "vocab": 16384, "max_period": 8},
        {"dim": 1024, "heads": 16, "layers": 24, "ffn_hidden": 4096,
         "attn_block": 256}),
}


def _row_flops_per_token(row):
    ld, md = LM_ROWS[row]
    return lm_train_flops_per_token(
        md["dim"], md["layers"], md["ffn_hidden"], ld["vocab"],
        ld["seq_len"])


def _mfu(extra, key, mfu_key, row):
    """Derive an MFU figure from a recorded median tokens/sec row,
    against the bf16 peak of the device the run is on — from THE
    table (``veles/perf.py``); a device_kind it does not hold is an
    error, never a default."""
    if key not in extra:
        return
    from veles import perf
    kind = extra["device"]["kind"]
    peak = perf.peak_flops_of(kind)
    if peak is None:
        raise RuntimeError(
            "no bf16 peak for device_kind %r in veles/perf.py — add "
            "the device to the table before quoting an MFU" % kind)
    extra[mfu_key] = round(
        extra[key] * _row_flops_per_token(row) / peak, 4)


def _build_mnist(backend, name, mb=100, n_train=6000, n_valid=1000,
                 max_epochs=None):
    import veles.prng as prng
    prng.seed_all(99)
    from veles.config import root
    from veles.znicz_tpu.models import mnist
    root.mnist.loader.minibatch_size = mb
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = n_valid
    if max_epochs is not None:
        root.mnist.decision.max_epochs = max_epochs
        # patience must exceed the dispatch chunk (see _xla_throughput)
        root.mnist.decision.fail_iterations = 100000
    wf = mnist.create_workflow(name=name)
    wf.initialize(device=backend)
    return wf


def _train_counter(loader, kind="minibatches", scale=1.0):
    """A cumulative work-count reader over the telemetry registry
    (ISSUE 3): bench rows and runtime metrics read the SAME
    ``veles_loader_*_total{cls="train"}`` counters the loader
    increments per served minibatch, so the two can never disagree.
    ``kind``: 'minibatches' (steps) or 'samples' (images; × seq =
    tokens via ``scale``)."""
    from veles import telemetry
    name = "veles_loader_%s_total" % kind

    def read():
        return telemetry.get_registry().counter_total(
            name, loader=loader.name, cls="train") * scale
    return read


def _mnist_numpy_stepper(name="BenchNumpy"):
    """(one_step, steps_done) for a freshly built numpy MNIST
    workflow — shared by the baseline row and the profiler-overhead
    row so both price the same training loop."""
    from veles.loader.base import CLASS_TRAIN
    wf = _build_mnist("numpy", name)
    loader = wf.loader
    steps_done = _train_counter(loader)

    def one_step():
        loader.run()
        while loader.minibatch_class != CLASS_TRAIN:
            loader.run()
        for u in wf.forwards:
            u.run()
        wf.evaluator.run()
        for gd in reversed(wf.gds):
            gd.run()

    return one_step, steps_done


def numpy_steps_per_sec(n_steps=30):
    one_step, steps_done = _mnist_numpy_stepper()
    one_step()  # warm caches
    c0 = steps_done()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        one_step()
    return (steps_done() - c0) / (time.perf_counter() - t0)


def profiler_overhead_pct(n_steps=60):
    """ISSUE 10 satellite: percent slowdown of the numpy MNIST train
    loop while the sampling profiler runs at its default rate
    (veles/profiling.py; the acceptance bound is < 3%%). Measured
    off-on-off so ambient host drift cancels: overhead = 1 -
    rate(on) / mean(rate(off_before), rate(off_after)), floored at 0
    (noise can make the profiled run the faster one)."""
    from veles.profiling import SamplingProfiler
    one_step, _ = _mnist_numpy_stepper("BenchProfOverhead")
    one_step()  # warm caches

    def rate():
        t0 = time.perf_counter()
        for _ in range(n_steps):
            one_step()
        return n_steps / (time.perf_counter() - t0)

    r_before = rate()
    profiler = SamplingProfiler()
    profiler.start()
    try:
        r_on = rate()
    finally:
        profiler.stop()
    r_off = (r_before + rate()) / 2.0
    return max((1.0 - r_on / r_off) * 100.0, 0.0)


def _profiler_row(extra):
    """Record the profiler-overhead bench guarded (a host-side row).
    Directionality: the key says 'overhead', so the self-check flags
    it when it goes UP."""
    try:
        extra["profiler_overhead_pct"] = round(
            profiler_overhead_pct(), 2)
    except Exception as exc:
        extra["profiler_overhead_pct_error"] = str(exc)[:200]


def model_stats_overhead_pct(measure_chunks=2):
    """ISSUE 15 satellite: percent step-time cost of the in-graph
    model-health stats (per-GD-unit grad/weight/update norms +
    non-finite counts fused into the compiled step —
    veles/model_health.py). Measured off-on-off on the SAME XLA MNIST
    chunk loop the throughput row uses, so ambient drift cancels:
    overhead = 1 - rate(on) / mean(rate(off_before), rate(off_after)),
    floored at 0. Each toggle re-keys the compiled program
    (collect_stats is part of the compile-cache key) and
    _timed_chunks' warmup chunk absorbs the rebuild before timing.
    Acceptance: < 2%."""
    wf = _build_mnist("xla", "BenchStatsOverhead", max_epochs=4096)
    loader, step = wf.loader, wf.xla_step
    step.epochs_per_dispatch = 16
    counter = _train_counter(loader)

    def rate(enabled):
        step.set_stats_enabled(enabled)
        best, _median = _timed_chunks(loader, step, counter,
                                      measure_chunks)
        return best

    r_off1 = rate(False)
    r_on = rate(True)
    r_off2 = rate(False)
    r_off = (r_off1 + r_off2) / 2.0
    return max((1.0 - r_on / r_off) * 100.0, 0.0)


def _model_stats_row(extra):
    """Record the model-stats-overhead bench guarded (runs on any jax
    backend). Key says 'overhead' -> the self-check flags UP moves."""
    try:
        extra["model_stats_overhead_pct"] = round(
            model_stats_overhead_pct(), 2)
    except Exception as exc:
        extra["model_stats_overhead_pct_error"] = str(exc)[:200]


def _run_one_chunk(loader, step):
    """Serve exactly one dispatch chunk (the serve that crosses into an
    undispatched epoch triggers the next chunk). The ONE place that
    reads XLAStep's chunk bookkeeping."""
    while True:
        loader.run()
        step.run()
        if bool(loader.epoch_ended) and \
                loader.epoch_number + 1 >= \
                step._chunk_epoch0 + step._chunk_len:
            return


def _timed_chunks(loader, step, counter, measure_chunks):
    """(best_rate, median_rate) over ``measure_chunks`` individually
    timed chunks, after one warmup chunk that covers compilation.
    ``counter()`` is a cumulative registry reader (_train_counter);
    each chunk's rate is its counter delta over its wall time.
    Per-chunk timing (not a sum): the chunk's metric fetch blocks on
    device completion — the fetch inside _run_one_chunk is the
    synchronization point — so each chunk is one complete
    measurement; the fastest and the median are both kept (same
    convention as bench_alexnet)."""
    _run_one_chunk(loader, step)
    rates = []
    for _ in range(measure_chunks):
        c0 = counter()
        t0 = time.perf_counter()
        _run_one_chunk(loader, step)
        rates.append((counter() - c0)
                     / (time.perf_counter() - t0))
    rates.sort()
    return rates[-1], rates[len(rates) // 2]


def xla_mnist_bench(measure_chunks=2):
    """MNIST steps/s on the XLA path, chunk-aligned timing.

    The chunk size is pinned to the adaptive mode's steady state for
    this workload (auto mode ramps 1 → 64 over a few dispatches; the
    pin just skips timing the ramp)."""
    wf = _build_mnist("xla", "BenchXLA", max_epochs=1024)
    loader, step = wf.loader, wf.xla_step
    step.epochs_per_dispatch = 64
    best, median = _timed_chunks(
        loader, step, _train_counter(loader), measure_chunks)
    return best, median, _grad_sync_bytes(step)


def _grad_sync_bytes(step):
    """Bytes of gradient all-reduced per step
    under DP (equals the trainable-param payload the reference's
    master/slave link shipped per update)."""
    from veles.znicz_tpu import parallel
    import jax
    host = jax.tree_util.tree_map(lambda a: __import__("numpy").asarray(a),
                                  step.params)
    return parallel.grad_sync_bytes(host)


def _wire_tx_bytes():
    """tx-side frame bytes from the SAME ``veles_wire_bytes_total``
    counters the runtime increments — excluding slave-labelled
    absorbed copies (co-located master+slave share one registry and
    the slave pushes its counter state to the master; counting those
    too would double every frame)."""
    from veles import telemetry
    state = telemetry.get_registry().counter_state(
        exclude_label_keys=("slave",))
    return sum(v for (name, items), v in state.items()
               if name == "veles_wire_bytes_total"
               and ("direction", "tx") in items)


def _slave_jobs_total():
    """Cumulative ``veles_slave_jobs_done_total`` from the registry,
    EXCLUDING slave-labelled absorbed copies (co-located master+slave
    share one registry and the master re-absorbs each slave's pushed
    state under a ``slave="<id>"`` label — counting those too would
    double every job)."""
    from veles import telemetry
    state = telemetry.get_registry().counter_state(
        exclude_label_keys=("slave",))
    return sum(v for (name, items), v in state.items()
               if name == "veles_slave_jobs_done_total")


def _dist_wire_row(codec, n_slaves=1, max_epochs=2):
    """One co-located master + ``n_slaves`` run over real sockets on
    the numpy backend (the row measures the WIRE protocol, not
    compute — it runs, and means the same thing, with or without a
    TPU); -> (wire bytes per served job, jobs per second). Both
    numerators come from the SAME registry counters the runtime
    increments (``veles_wire_bytes_total`` /
    ``veles_slave_jobs_done_total``), so the row and a /metrics
    scrape of the run can never disagree."""
    import threading
    from veles.client import SlaveClient
    from veles.server import MasterServer
    master = _build_mnist("numpy", "BenchWireM%d%s" % (n_slaves, codec),
                          mb=50, n_train=500, n_valid=100,
                          max_epochs=max_epochs)
    server = MasterServer(master, "127.0.0.1:0",
                          max_epochs=max_epochs, grad_codec=codec)
    server.start_background()
    try:
        # guarded from the very first statement after the server is
        # live: a slave-workflow build that raises here used to leak
        # the master's serving thread, listener and workflow for the
        # rest of the bench process (zlint resource-leak)
        address = "127.0.0.1:%d" % server.bound_address[1]
        slaves = []
        for i in range(n_slaves):
            wf = _build_mnist("numpy", "BenchWireS%d%s-%d"
                              % (n_slaves, codec, i), mb=50,
                              n_train=500, n_valid=100,
                              max_epochs=max_epochs)
            wf.is_slave = True
            slaves.append(wf)
        ok = [0] * n_slaves
        errors = []

        def pump(i):
            try:
                ok[i] = SlaveClient(
                    slaves[i], address,
                    name="bench-%s-%d" % (codec, i),
                    grad_codec=codec).run_forever()
            except Exception as exc:   # surfaced below: a dead-slave
                errors.append(exc)     # row must be an _error entry,
                                       # never a bogus data point

        before = _wire_tx_bytes()
        jobs_before = _slave_jobs_total()
        threads = [threading.Thread(target=pump, args=(i,))
                   for i in range(n_slaves)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.request_stop()
    wall = time.perf_counter() - t0
    moved = _wire_tx_bytes() - before
    total_jobs = _slave_jobs_total() - jobs_before
    if errors:
        raise RuntimeError("slave failed: %s" % errors[0])
    if not total_jobs or not sum(ok):
        raise RuntimeError("no jobs completed — nothing to measure")
    if server.faults["codec_fallbacks"]:
        raise RuntimeError("codec %r fell back to 'none' — the row "
                           "would measure the wrong thing" % codec)
    return moved / total_jobs, total_jobs / wall


def _grad_codec_rows(extra):
    """The 318,040-byte plateau as a tracked, falsifiable trajectory:
    measured wire bytes per sync step for EVERY codec, plus a 2-slave
    distributed throughput row (protocol-level steps/s, none vs int8
    — co-located numpy processes, so this prices the wire+codec path,
    not device scaling)."""
    for codec in ("none", "bf16", "int8", "topk"):
        key = "grad_sync_wire_bytes_per_step_%s" % codec
        try:
            bytes_per_job, _ = _dist_wire_row(codec, n_slaves=1)
            extra[key] = int(round(bytes_per_job))
        except Exception as exc:
            extra[key + "_error"] = str(exc)[:200]
    for codec in ("none", "int8"):
        key = "dist_2slave_steps_per_sec_%s" % codec
        try:
            _, steps_per_sec = _dist_wire_row(codec, n_slaves=2)
            extra[key] = round(steps_per_sec, 1)
        except Exception as exc:
            extra[key + "_error"] = str(exc)[:200]


def _dist_scaling_rows(extra, codec="int8"):
    """ROADMAP item 3's missing half-row: protocol-level scaling
    efficiency at N=1/2/4/8 co-located slaves over the reactor wire
    plane under the shipped ``int8`` codec —
    ``dist_scaling_steps_per_sec_nN`` (jobs/s from the same
    ``veles_slave_jobs_done_total`` registry counters the runtime
    increments) plus the derived ``dist_scaling_efficiency_nN`` =
    rate(N) / (N x rate(1)). Co-located numpy processes price the
    wire + codec + dispatch path, not device scaling; efficiency
    falling with N is the thread/GIL ceiling the reactor is meant to
    lift, which is exactly why the trajectory is recorded.
    Directional self-check: down = bad for BOTH key families (they
    are throughput/efficiency figures, not byte counts)."""
    rates = {}
    for n in (1, 2, 4, 8):
        key = "dist_scaling_steps_per_sec_n%d" % n
        try:
            _, steps_per_sec = _dist_wire_row(codec, n_slaves=n)
            rates[n] = steps_per_sec
            extra[key] = round(steps_per_sec, 1)
        except Exception as exc:
            extra[key + "_error"] = str(exc)[:200]
    for n in (2, 4, 8):
        if n in rates and rates.get(1):
            extra["dist_scaling_efficiency_n%d" % n] = round(
                rates[n] / (n * rates[1]), 3)


def _xla_throughput(create_workflow, cfg, counter_kind, scale,
                    epochs_per_dispatch, name, measure_chunks=1):
    """Shared build-and-time scaffold: seed, size the dataset via the
    sample's config section, init on the XLA device, time whole
    dispatch chunks; rates come from the telemetry registry's
    ``veles_loader_*`` counters (see ``_train_counter``);
    -> (best, median) count units per second."""
    import veles.prng as prng
    prng.seed_all(99)
    cfg.decision.max_epochs = 1024
    # patience must exceed the chunk size: XLAStep clamps even forced
    # dispatch chunks to fail_iterations - epochs_since_best, so the
    # sample default of 50 silently clips 64-epoch chunks (and shrinks
    # them further as patience drains — ADVICE-grade variance)
    cfg.decision.fail_iterations = 100000
    wf = create_workflow(name=name)
    wf.initialize(device="xla")
    loader, step = wf.loader, wf.xla_step
    step.epochs_per_dispatch = epochs_per_dispatch
    best, median = _timed_chunks(
        loader, step, _train_counter(loader, counter_kind, scale),
        measure_chunks)
    return best, median


def xla_cifar_images_per_sec(measure_chunks=3):
    """Conv-stack throughput (images/sec) on the XLA device."""
    from veles.config import root
    from veles.znicz_tpu.models import cifar10
    root.cifar.loader.update({"minibatch_size": 100, "n_train": 2000,
                              "n_valid": 400})
    # 64 epochs per dispatch: the r3 pin of 16 under-amortized the
    # per-chunk metric fetch on this small model (r4 sweep: 167k at
    # 16, 256k at 64, flat at 128+)
    return _xla_throughput(
        cifar10.create_workflow, root.cifar, "samples", 1,
        epochs_per_dispatch=64, name="BenchCifar",
        measure_chunks=measure_chunks)


def _lm_throughput(loader_cfg, model_cfg, name, epochs_per_dispatch,
                   measure_chunks):
    """Shared LM bench scaffold: save/override/restore the LM config,
    then time dispatch chunks.

    Runs with the engine defaults (bf16 compute + bf16 activation
    policy on TPU): since round 3's mixed-precision policy — bf16
    tensors BETWEEN units, f32 master weights and solver state, f32
    loss/softmax/stat math — bf16 WINS on the 57M LM too (205k vs
    195k tok/s on a v5e; round 2's per-matmul-cast design lost ~4%
    here, which is why it used to pin float32)."""
    from veles.config import root
    from veles.znicz_tpu.models import transformer_lm
    saved_loader = root.lm.loader.to_dict()
    saved_model = root.lm.model.to_dict()
    root.lm.loader.update(loader_cfg)
    root.lm.model.update(model_cfg)
    seq = root.lm.loader.seq_len
    try:
        # tokens/sec = train samples/sec × seq (samples counter from
        # the registry)
        return _xla_throughput(
            transformer_lm.create_workflow, root.lm, "samples", seq,
            epochs_per_dispatch=epochs_per_dispatch, name=name,
            measure_chunks=measure_chunks)
    finally:
        # full restore: every key the overrides touch exists in the
        # sample defaults, so Config.update round-trips cleanly
        root.lm.loader.update(saved_loader)
        root.lm.model.update(saved_model)


def lm_tokens_per_sec(measure_chunks=3):
    """Transformer-LM training throughput (tokens/sec) on the XLA
    device — the north star's NEW config (BASELINE config #5).
    64 epochs per dispatch (r4 sweep: 13.9M tok/s at the old 8,
    21.8M at 64 — the toy model is fetch-amortization-bound)."""
    return _lm_throughput(
        {"minibatch_size": 64, "n_train": 2048, "n_valid": 256,
         "seq_len": 128}, {}, "BenchLM", 64, measure_chunks)


def lm_scale_tokens_per_sec(measure_chunks=3):
    """Transformer-LM throughput at REAL model scale (57.5M params:
    dim 768, 12 heads, 8 layers, ffn 3072, S=512) — the recorded
    large-model number. Config is the measured round-3 optimum from the v5e sweep:
    batch 8 / attn_block 256 (248k median tok/s vs 220k at the old
    batch 16 / block 128)."""
    return _lm_throughput(*LM_ROWS["57M"], "BenchLMScale", 4,
                          measure_chunks)


def lm_base_tokens_per_sec(measure_chunks=3):
    """Transformer-BASE LM throughput (canonical 12-layer config:
    dim 768, 12 heads, ffn 3072, vocab 16384 -> ~110M params with the
    embedding + output head; SURVEY §2.8 "Transformer-base LM" /
    VERDICT r3 weak #5 — the 8-layer 57M flagship under-read it).
    S=512, batch/attn_block from the round-4 v5e sweep."""
    return _lm_throughput(*LM_ROWS["110M"], "BenchLMBase", 4,
                          measure_chunks)


def lm_base_s8k_tokens_per_sec(measure_chunks=3):
    """The 110M transformer-base at S=8192 (long-context row, auto
    impl policy — Pallas flash takes over at this length)."""
    return _lm_throughput(*LM_ROWS["110M_s8k"], "BenchLMBaseLong", 1,
                          measure_chunks)


def lm_longctx_tokens_per_sec(measure_chunks=3):
    """57.5M-param LM at S=8192 (long-context row): blocked attention
    with the AUTO impl policy — the Pallas flash kernels run at this
    length (an earlier builder's 2.6x over the XLA scan end-to-end on
    a v5e; the rule and its measured table, from S=256 up since PR 27:
    ops/attention.py PALLAS_AUTO_MIN_S)."""
    return _lm_throughput(*LM_ROWS["57M_s8k"], "BenchLMLongCtx", 1,
                          measure_chunks)


def lm_345m_tokens_per_sec(measure_chunks=3):
    """~345M-param LM (24 layers, dim 1024, 16 heads, ffn 4096,
    vocab 16384 — GPT-2-medium shape) at S=512: the scale-past-110M
    row, batch from the round-5 v5e sweep."""
    return _lm_throughput(*LM_ROWS["345M"], "BenchLM345M", 2,
                          measure_chunks)


def serving_throughput_rps(duration=0.6, clients=8,
                           quantize="none"):
    """Inference-path row (ISSUE 1): requests/sec through the
    veles.serving micro-batcher, IN PROCESS (no sockets — this
    measures batching + forward dispatch, not HTTP parsing).

    Builds an un-trained tiny MNIST MLP, exports its archive, loads it
    through the registry on the numpy backend (device-independent: the
    row runs, and means the same thing, with or without a TPU) and
    hammers it from ``clients`` threads of single-sample requests —
    the serving shape where dynamic batching is the whole game.
    ``quantize`` prices the at-rest weight-quantized deployment
    (ISSUE 14): same load, int8/fp8 params densified per dispatch.
    -> (requests/sec, batch_fill_ratio, forward_cache_bytes) — the
    cache figure read from the SAME ``veles_serving_forward_cache_
    bytes`` gauge a /metrics scrape of the process would see."""
    import tempfile
    import threading
    import numpy
    import veles.prng as prng
    prng.seed_all(99)
    from veles import telemetry
    from veles.config import root
    from veles.serving import ModelRegistry
    from veles.znicz_tpu.models import mnist
    saved = {k: root.mnist.loader.get(k)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.mnist.loader.update({"minibatch_size": 50, "n_train": 200,
                              "n_valid": 50})
    try:
        wf = mnist.create_workflow(name="BenchServe")
        wf.initialize(device="numpy")
        with tempfile.TemporaryDirectory() as tmp:
            wf.export_inference(tmp)
            registry = ModelRegistry(backend="numpy", max_batch=64,
                                     max_queue=4096, max_wait_ms=1.0,
                                     quantize_weights=quantize)
            try:
                # a failed warm/predict used to skip the close and
                # leak the registry's batcher threads for the rest
                # of the bench process (zlint resource-leak)
                entry = registry.load("mnist", tmp)
                x = wf.loader.original_data.mem[:1].astype(
                    numpy.float32)
                entry.predict(x)                  # warm
                cache_bytes = telemetry.get_registry().gauge(
                    "veles_serving_forward_cache_bytes",
                    labels=("model",)).labels("mnist").value
                stop = time.perf_counter() + duration
                counts = [0] * clients

                def client(i):
                    while time.perf_counter() < stop:
                        entry.predict(x, timeout_ms=10000)
                        counts[i] += 1

                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(clients)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
                fill = entry.batcher.metrics()["batch_fill_ratio"]
            finally:
                registry.close()
        return sum(counts) / dt, fill, cache_bytes
    finally:
        root.mnist.loader.update(saved)


def _routed_http_hammer(base, payload, duration, clients):
    """Hammer one HTTP predict endpoint from ``clients`` threads for
    ``duration`` seconds; -> (requests/sec, sorted latencies). Only
    COMPLETED requests count — a failure mid-window would otherwise
    read as a latency win."""
    import threading
    import urllib.request
    stop = time.perf_counter() + duration
    lats = [[] for _ in range(clients)]

    def client(i):
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            req = urllib.request.Request(
                base + "/v1/predict", data=payload,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
            except Exception:
                continue
            lats[i].append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    flat = sorted(v for per in lats for v in per)
    if not flat:
        raise RuntimeError("no routed request completed")
    return len(flat) / dt, flat


def _p99(lats):
    return lats[min(int(len(lats) * 0.99), len(lats) - 1)]


def _wait_ready(url, timeout_s=90.0, path="/readyz"):
    import urllib.request
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url + path, timeout=2):
                return True
        except Exception:
            time.sleep(0.2)
    raise RuntimeError("%s%s never answered 200" % (url, path))


def routed_serving_rows(duration=1.0, clients=4):
    """ISSUE 13 acceptance rows: requests/sec against ONE serving
    replica hit directly over HTTP vs through ``velescli route``'s
    proxy in front of it (proxy overhead, bounded by the >= 0.85x
    acceptance ratio), plus routed p99 with a 2-replica fleet while
    one replica is BROWNED OUT (BrownoutProxy latency + scrape
    timeout -> ejection) next to the healthy-fleet p99 — the router
    must keep the brownout p99 within 2x of healthy.

    Topology is REAL: each replica is a ``velescli serve`` process
    and the overhead row's router is a ``velescli route`` process
    (numpy backend, forced-CPU jax) — co-located single-interpreter
    measurement would price GIL contention between client, router
    and replica threads, not the proxy hop. The brownout pair runs
    the router in-process (identical topology on both sides of THAT
    ratio) because it polls the controller's ejection state
    directly."""
    import tempfile
    import veles.prng as prng
    prng.seed_all(99)
    from veles.chaos import BrownoutProxy
    from veles.config import root
    from veles.router import (FleetController, RouterFrontend,
                              SubprocessExecutor)
    from veles.znicz_tpu.models import mnist
    saved = {k: root.mnist.loader.get(k)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.mnist.loader.update({"minibatch_size": 50, "n_train": 200,
                              "n_valid": 50})
    velescli = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "velescli.py")
    closers = []
    try:
        wf = mnist.create_workflow(name="BenchRouted")
        wf.initialize(device="numpy")
        x = wf.loader.original_data.mem[:1].astype("float32")
        payload = json.dumps({"model": "mnist",
                              "inputs": x.tolist()}).encode()
        with tempfile.TemporaryDirectory() as tmp:
            wf.export_inference(tmp)
            serve_exec = SubprocessExecutor(
                [sys.executable, velescli, "serve", "--model",
                 "mnist=%s" % tmp, "--backend", "numpy", "--port",
                 "{port}", "--max-wait-ms", "1"],
                start_timeout=120.0, env={"JAX_PLATFORMS": "cpu"})
            closers.append(serve_exec.close)
            url_a = serve_exec.launch()
            url_b = serve_exec.launch()
            if url_a is None or url_b is None:
                raise RuntimeError("replica subprocess never became "
                                   "healthy")
            for url in (url_a, url_b):
                _wait_ready(url)        # model warm, not just alive

            # direct: the single-replica ceiling the proxy is priced
            # against (warm each path before its timed window)
            _routed_http_hammer(url_a, payload, 0.1, 1)
            direct_rps, _ = _routed_http_hammer(
                url_a, payload, duration, clients)

            route_exec = SubprocessExecutor(
                [sys.executable, velescli, "route", url_a, "--port",
                 "{port}", "--interval", "0.3", "--scrape-timeout",
                 "0.5"],
                start_timeout=120.0, env={"JAX_PLATFORMS": "cpu"})
            closers.append(route_exec.close)
            router_url = route_exec.launch()
            if router_url is None:
                raise RuntimeError("router subprocess never became "
                                   "healthy")
            _wait_ready(router_url)     # >= 1 backend admitted
            _routed_http_hammer(router_url, payload, 0.1, 1)
            routed_rps, _ = _routed_http_hammer(
                router_url, payload, duration, clients)

            # 2-replica fleet, one browned out: p99 through the
            # router after ejection vs the healthy-fleet p99
            proxy = BrownoutProxy(
                ("127.0.0.1", int(url_b.rsplit(":", 1)[1])))
            closers.append(proxy.close)
            fleet_ctl = FleetController(
                [url_a, proxy.url], interval=0.3, scrape_timeout=0.5)
            closers.append(fleet_ctl.close)
            fleet_router = RouterFrontend(fleet_ctl, port=0)
            closers.append(fleet_router.close)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and not (
                    # ticks >= 1: the INIT doc already lists both
                    # backends as admitted before any scrape ran
                    fleet_ctl.status_doc["ticks"] >= 1
                    and fleet_ctl.status_doc["admitted"] == 2):
                time.sleep(0.05)
            _routed_http_hammer(fleet_router.url, payload, 0.1, 1)
            _, healthy_lats = _routed_http_hammer(
                fleet_router.url, payload, duration, clients)
            proxy.brownout(2.0)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and not any(
                    b["state"] == "ejected"
                    for b in fleet_ctl.status_doc["backends"]):
                time.sleep(0.05)
            _, brown_lats = _routed_http_hammer(
                fleet_router.url, payload, duration, clients)
        return {"routed_rps_direct": round(direct_rps, 1),
                "routed_rps_via_router": round(routed_rps, 1),
                "routed_p99_healthy_s": round(_p99(healthy_lats), 4),
                "routed_p99_brownout_s": round(_p99(brown_lats), 4)}
    finally:
        for close in reversed(closers):
            try:
                close()
            except Exception:
                pass
        root.mnist.loader.update(saved)


def _routed_rows(extra):
    """Record the router bench guarded (device-independent row).
    Directionality: the rps keys read down = bad (throughput), the
    p99 keys up = bad ("p99" is in _LOWER_BETTER)."""
    try:
        extra.update(routed_serving_rows())
    except Exception as exc:
        extra["routed_rps_error"] = str(exc)[:200]


def _serving_row(extra):
    """Record the serving bench guarded: a failure lands in an _error
    key, never in the exit code."""
    try:
        rps, fill, cache = serving_throughput_rps()
        extra["serving_throughput_rps"] = round(rps, 1)
        extra["serving_batch_fill_ratio"] = round(fill, 3)
        extra["serving_cache_bytes_f32"] = int(cache)
    except Exception as exc:
        extra["serving_throughput_rps_error"] = str(exc)[:200]


def _quantized_serving_rows(extra):
    """ISSUE 14 acceptance rows: the SAME serving load with int8
    at-rest weights — requests/sec (quantized-vs-f32 throughput as a
    tracked pair; the numpy backend prices the per-dispatch dequant,
    an accelerator fuses it) and the forward-cache shrink, read from
    the same ``veles_serving_forward_cache_bytes`` gauge the runtime
    exports (acceptance: ≤ 55% of the f32 figure). Directionality:
    rps down = bad, bytes up = bad."""
    try:
        rps, _, cache = serving_throughput_rps(quantize="int8")
        extra["serving_throughput_rps_int8"] = round(rps, 1)
        extra["serving_cache_bytes_int8"] = int(cache)
    except Exception as exc:
        # both rows vanish together, so both carry the _error key the
        # trajectory tooling looks for next to a missing row
        extra["serving_throughput_rps_int8_error"] = str(exc)[:200]
        extra["serving_cache_bytes_int8_error"] = str(exc)[:200]


def continual_staleness_s(rounds=2):
    """ISSUE 16 row: end-to-end staleness at the TRAINER point right
    after a continual round completes — seconds between the last
    ingested sample's arrival and "now", with the stream served
    through the real prefetch plane (producer thread, bounded block
    buffer). Steady state for the loop is "this stays near zero"."""
    import numpy
    from veles.loader.stream import ArraySource, ContinualStreamLoader
    from veles.workflow import Workflow
    rng = numpy.random.RandomState(5)
    wf = Workflow(None, name="BenchContinual")
    ld = ContinualStreamLoader(
        wf, name="loader", minibatch_size=32,
        source=ArraySource(
            rng.uniform(-1, 1, (256, 16)).astype(numpy.float32),
            rng.randint(0, 4, 256).astype(numpy.int32)),
        round_samples=128, valid_samples=32)
    try:
        ld.initialize()
        done = 0
        while done < rounds:
            ld.run()
            if bool(ld.epoch_ended):
                done += 1
        return max(0.0, time.time() - ld.last_ingest_wall)
    finally:
        ld.stop()


def rolling_refresh_downtime_s():
    """ISSUE 16 row: wall time of ONE in-place registry hot swap on a
    tiny MNIST model — the window a rolling refresh holds a drained
    replica out of the fleet (the roll itself never fails requests:
    the replica is drained first; this prices how long the roll
    takes per replica)."""
    import tempfile
    import veles.prng as prng
    from veles.config import root
    from veles.serving import ModelRegistry
    from veles.znicz_tpu.models import mnist
    prng.seed_all(41)
    saved = {k: root.mnist.loader.get(k)
             for k in ("minibatch_size", "n_train", "n_valid")}
    root.mnist.loader.update({"minibatch_size": 50, "n_train": 200,
                              "n_valid": 50})
    try:
        wf = mnist.create_workflow(name="BenchRefresh")
        wf.initialize(device="numpy")
        with tempfile.TemporaryDirectory() as tmp:
            wf.export_inference(tmp)
            registry = ModelRegistry(backend="numpy", max_batch=64,
                                     max_queue=256, max_wait_ms=1.0)
            try:
                registry.load("mnist", tmp, warmup=True)
                t0 = time.perf_counter()
                registry.reload("mnist")
                return time.perf_counter() - t0
            finally:
                registry.close()
    finally:
        root.mnist.loader.update(saved)


def _continual_rows(extra):
    """Record the continual-loop pair guarded (device-independent
    rows). Directionality: both are in _LOWER_BETTER — staleness or
    refresh downtime creeping up is the loop decaying."""
    try:
        extra["staleness_seconds_steady_state"] = round(
            continual_staleness_s(), 4)
    except Exception as exc:
        extra["staleness_seconds_steady_state_error"] = str(exc)[:200]
    try:
        extra["rolling_refresh_downtime_s"] = round(
            rolling_refresh_downtime_s(), 4)
    except Exception as exc:
        extra["rolling_refresh_downtime_s_error"] = str(exc)[:200]


def bias_grad_step_seconds(n=65536, k=96, reps=10):
    """ISSUE 14 tentpole row: wall seconds of ONE bias-gradient
    dispatch — relu-derivative mask + f32-accumulating reduction over
    ``n`` batch·space rows × ``k`` channels (a conv1-class shape) —
    through the hand-fused Pallas kernel (ops/pallas_grads.py — what
    the ``fused_bias_grad`` hatch dispatches once
    $VELES_FUSED_BIAS_GRAD=1). Scalar readback is the sync point; the
    median of ``reps`` timed calls is returned."""
    import jax
    import jax.numpy as jnp
    import numpy
    from veles.znicz_tpu.ops import pallas_grads as PG

    gen = numpy.random.Generator(numpy.random.PCG64(17))
    err = jnp.asarray(gen.standard_normal((n, k), numpy.float32),
                      jnp.bfloat16)
    y = jnp.asarray(gen.standard_normal((n, k), numpy.float32),
                    jnp.bfloat16)
    fn = jax.jit(lambda e, yy: PG.bias_grad(e, yy, "strict_relu",
                                            interpret=False))
    float(fn(err, y).sum())                 # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(fn(err, y).sum())             # readback = sync
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _bias_grad_row(extra):
    try:
        extra["bias_grad_step_seconds"] = round(
            bias_grad_step_seconds(), 6)
    except Exception as exc:
        extra["bias_grad_step_seconds_error"] = str(exc)[:200]


def _lm_decode_export(tmp):
    """Export a tiny LM archive (untrained — decode rows price the
    serving machinery, not model quality) for the generate rows."""
    import veles.prng as prng
    prng.seed_all(99)
    from veles.config import root
    from veles.znicz_tpu.models import transformer_lm
    saved_loader = root.lm.loader.to_dict()
    saved_model = root.lm.model.to_dict()
    root.lm.loader.update({"minibatch_size": 8, "n_train": 64,
                           "n_valid": 16, "seq_len": 16, "vocab": 32,
                           "max_period": 8})
    root.lm.model.update({"dim": 64, "heads": 4, "layers": 2,
                          "ffn_hidden": 128, "moe_experts": 0,
                          "attn_block": None, "attn_impl": None,
                          "stacked": False})
    try:
        wf = transformer_lm.create_workflow(name="BenchDecode")
        wf.initialize(device="numpy")
        wf.export_inference(tmp)
    finally:
        root.lm.loader.update(saved_loader)
        root.lm.model.update(saved_model)


def generate_decode_tokens_per_sec(streams=8, max_tokens=32,
                                   prompt_len=8):
    """ISSUE 11 acceptance rows: aggregate decode tokens/s for
    ``streams`` concurrent generations through the CONTINUOUS batcher
    (shared decode batch, KV slot per stream) vs the same requests
    decoded SEQUENTIALLY one at a time (slot pool of 1 — the same
    machinery, so batch fill is the only difference), plus the median
    submit->first-token latency under the concurrent load. Both
    engines warm one generation first so neither timed row pays an
    XLA compile. -> (sequential tok/s, continuous tok/s, first-token
    median seconds)."""
    import tempfile
    from veles.serving.decode import (ContinuousBatcher,
                                      GenerativeEngine)
    from veles.serving.model import ArchiveModel
    with tempfile.TemporaryDirectory() as tmp:
        _lm_decode_export(tmp)
        model = ArchiveModel.from_dir(tmp)
        prompts = [[(3 * i + j) % 32 for j in range(prompt_len)]
                   for i in range(streams)]

        def run(n_slots, concurrent):
            engine = GenerativeEngine(model, n_slots=n_slots,
                                      max_len=64)
            batcher = ContinuousBatcher(
                engine, max_queue=2 * streams,
                model="bench-decode-%d" % n_slots)
            try:
                # warm: compiles the prompt bucket + the step program
                batcher.generate(prompts[0], max_tokens=4,
                                 wait_s=300)
                t0 = time.perf_counter()
                firsts = []
                if concurrent:
                    handles = [batcher.submit(
                        p, max_tokens=max_tokens) for p in prompts]
                    for h in handles:
                        h.wait(600)
                    firsts = sorted(h.t_first - h.t_submit
                                    for h in handles)
                else:
                    for p in prompts:
                        batcher.generate(p, max_tokens=max_tokens,
                                         wait_s=600)
                dt = time.perf_counter() - t0
            finally:
                batcher.close()
            return streams * max_tokens / dt, firsts

        seq_rate, _ = run(1, False)
        cont_rate, firsts = run(streams, True)
        return seq_rate, cont_rate, \
            firsts[len(firsts) // 2] if firsts else None


def _generate_rows(extra):
    """The decode-plane trajectory (device-independent: numpy-export
    + jax-CPU decode — runs, and means the same thing, with or
    without a TPU). Directional self-check: tokens/s down = bad,
    first-token latency up = bad ("latency" is in _LOWER_BETTER)."""
    try:
        seq, cont, first = generate_decode_tokens_per_sec()
        extra["generate_tokens_per_sec_sequential"] = round(seq, 1)
        extra["generate_tokens_per_sec_continuous"] = round(cont, 1)
        if first is not None:
            extra["generate_first_token_latency_s"] = round(first, 4)
    except Exception as exc:
        extra["generate_tokens_per_sec_error"] = str(exc)[:200]


def lint_full_tree_seconds():
    """Wall time of one full-tree zlint pass over the veles package —
    the analyzer's own cost as a tracked trajectory (up = bad: the
    key contains "seconds", which --self-check reads as
    lower-is-better). The shared-engine refactor is held to < 2x the
    pre-refactor wall time by this row."""
    import veles
    from veles.analysis import analyze_paths
    pkg = os.path.dirname(os.path.abspath(veles.__file__))
    t0 = time.perf_counter()
    findings = analyze_paths([pkg], base=os.path.dirname(pkg))
    dt = time.perf_counter() - t0
    if findings:
        raise RuntimeError(
            "full-tree lint found %d violation(s) — the row would "
            "time a dirty tree" % len(findings))
    return dt


def lint_full_tree_warm_seconds():
    """Wall time of a WARM cached full-tree zlint pass (--cache): a
    priming run fills a fresh cache directory, the timed run answers
    from it. Tracks the incremental-analysis win — the acceptance
    floor is warm <= 50% of cold (up = bad, "seconds" key)."""
    import tempfile

    import veles
    from veles.analysis import analyze_paths
    from veles.analysis.cache import AnalysisCache
    pkg = os.path.dirname(os.path.abspath(veles.__file__))
    base = os.path.dirname(pkg)
    with tempfile.TemporaryDirectory() as tmp:
        cache = AnalysisCache(tmp)
        analyze_paths([pkg], base=base, cache=cache)        # prime
        t0 = time.perf_counter()
        findings = analyze_paths([pkg], base=base,
                                 cache=AnalysisCache(tmp))
        dt = time.perf_counter() - t0
    if findings:
        raise RuntimeError(
            "full-tree lint found %d violation(s) — the row would "
            "time a dirty tree" % len(findings))
    return dt


def _lint_row(extra):
    try:
        extra["lint_full_tree_seconds"] = round(
            lint_full_tree_seconds(), 3)
    except Exception as exc:
        extra["lint_full_tree_seconds_error"] = str(exc)[:200]
    try:
        extra["lint_full_tree_warm_seconds"] = round(
            lint_full_tree_warm_seconds(), 3)
    except Exception as exc:
        extra["lint_full_tree_warm_seconds_error"] = str(exc)[:200]


def _record(extra, key, fn):
    """Run one bench row; primary key = median, ``_best`` = fastest
    chunk (see the module docstring's key convention)."""
    try:
        best, median = fn()
        extra[key] = round(median, 1)
        extra[key + "_best"] = round(best, 1)
    except Exception as exc:   # keep the primary metric robust
        extra[key + "_error"] = str(exc)[:200]


# -- self-check: the bench trajectory as a first-class diff ------------

#: keys where SMALLER is better (wire bytes, profiler overhead,
#: first-token latency, the analyzer's own wall time); everything
#: else numeric in the report is a throughput/efficiency figure where
#: bigger wins
_LOWER_BETTER = ("bytes", "overhead", "latency", "seconds", "p99",
                 "staleness", "downtime", "shed", "rejected")

#: keys where BIGGER is better EVEN IF a lower-better substring ever
#: lands in the same key: an MFU ratio is a utilization figure, down
#: = bad, and an MFU regression must be flagged in its own right —
#: not only via the throughput row it was derived from (ISSUE 14
#: satellite; covered by the directionality fixture in test_health).
#: routed_capacity_rps_at_p99_slo carries "p99" in its name but IS a
#: capacity figure (ISSUE 18's loadgen row): down = bad.
_HIGHER_BETTER = ("mfu", "routed_capacity")

#: keys that are environment stamps, not performance rows
_SELF_CHECK_SKIP = ("calibration",)


def _latest_bench_artifact(directory=None):
    """Newest ``BENCH_r*.json`` next to this file (natural-sorted by
    round number), or None."""
    directory = directory or os.path.dirname(os.path.abspath(__file__))
    def round_no(path):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        return int(m.group(1)) if m else -1
    files = [p for p in glob.glob(os.path.join(directory,
                                               "BENCH_r*.json"))
             if round_no(p) >= 0]
    return max(files, key=round_no) if files else None


def _flatten_rows(report):
    """One {key: number} dict out of a bench report — the primary
    metric under its name plus every numeric ``extra`` row (error
    strings, provenance dicts and *_best duplicates excluded: the
    deltas compare the stable median convention keys)."""
    rows = {}
    if isinstance(report.get("value"), (int, float)) \
            and report.get("metric"):
        rows[str(report["metric"])] = float(report["value"])
    for key, value in (report.get("extra") or {}).items():
        if not isinstance(value, (int, float)) \
                or isinstance(value, bool):
            continue
        if key.endswith("_best") \
                or any(s in key for s in _SELF_CHECK_SKIP):
            continue
        rows[key] = float(value)
    return rows


def self_check(report, threshold_pct=10.0, baseline_path=None,
               stream=None):
    """Compare this run's rows against the latest recorded bench
    artifact and print per-row deltas — WARN-ONLY (the trajectory was
    previously invisible without manually diffing BENCH_r*.json; this
    never changes the exit code or the report). A row regresses when
    it moves more than ``threshold_pct`` percent in its bad direction
    (down for throughput, up for byte counts); -> the regressed keys.
    """
    # resolve the stream at CALL time, never as a parameter default: a
    # def-time ``stream=sys.stderr`` binds whatever object sys.stderr
    # was when this module FIRST imported — under pytest that is the
    # importing test's capture buffer, and every later test's capsys
    # then reads empty (the test_serving-before-test_health order
    # flake, ISSUE 10 satellite)
    if stream is None:
        stream = sys.stderr
    path = baseline_path or _latest_bench_artifact()
    if path is None:
        print("self-check: no BENCH_r*.json baseline found — "
              "nothing to compare", file=stream)
        return []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as exc:
        print("self-check: cannot read %s (%s) — skipped"
              % (path, exc), file=stream)
        return []
    old = _flatten_rows(doc.get("parsed") or doc)
    new = _flatten_rows(report)
    common = sorted(set(old) & set(new))
    if not common:
        print("self-check: no comparable rows vs %s" % path,
              file=stream)
        return []
    print("self-check vs %s (threshold ±%g%%):"
          % (os.path.basename(path), threshold_pct), file=stream)
    regressed = []
    for key in common:
        was, now = old[key], new[key]
        if was == 0:
            continue
        pct = (now - was) / abs(was) * 100.0
        lower_better = (not any(s in key for s in _HIGHER_BETTER)
                        and any(s in key for s in _LOWER_BETTER))
        bad = pct > threshold_pct if lower_better \
            else pct < -threshold_pct
        flag = "  << REGRESSION" if bad else ""
        if bad:
            regressed.append(key)
        print("  %-44s %14.6g -> %14.6g  %+7.1f%%%s"
              % (key, was, now, pct, flag), file=stream)
    dropped = sorted(set(old) - set(new))
    if dropped:
        # a silently vanished row reads as "fine" without this line
        print("  (rows in baseline but not this run: %s)"
              % ", ".join(dropped), file=stream)
    print("self-check: %d row(s) compared, %d regression(s) beyond "
          "±%g%% (warn-only)" % (len(common), len(regressed),
                                 threshold_pct), file=stream)
    return regressed


def _parse_args(argv):
    p = argparse.ArgumentParser(
        prog="bench.py",
        description="Benchmark entry: prints ONE JSON report line; "
                    "--self-check additionally diffs the rows "
                    "against the latest BENCH_r*.json (warn-only)")
    p.add_argument("--self-check", action="store_true",
                   help="compare this run's rows to the newest "
                        "BENCH_r*.json and print per-row deltas to "
                        "stderr (never changes the exit code)")
    p.add_argument("--self-check-threshold", type=float, default=10.0,
                   metavar="PCT",
                   help="flag rows moving more than PCT%% in their "
                        "bad direction (default 10)")
    p.add_argument("--self-check-baseline", default=None,
                   metavar="PATH",
                   help="explicit baseline artifact (default: "
                        "newest BENCH_r*.json next to bench.py)")
    return p.parse_args(argv)


def main(argv=None):
    args = _parse_args(argv)

    def emit(report):
        print(json.dumps(report))
        if args.self_check:
            self_check(report,
                       threshold_pct=args.self_check_threshold,
                       baseline_path=args.self_check_baseline)
        return 0

    # the device first: a bench with no TPU under it has nothing to
    # report. A failed device query raises; the CPU prints no row.
    from veles import backends
    backends.enable_compile_cache()
    device = backends.device_report()
    if not backends.is_tpu(device["platform"]):
        print("bench.py measures the TPU; jax found %s — no row "
              "printed" % json.dumps(device), file=sys.stderr)
        return 1
    extra = {"device": device}
    try:
        # calibration FIRST, and again at the end: see
        # device_matmul_tflops
        extra["calibration_matmul8k_bf16_tflops"] = round(
            device_matmul_tflops(), 1)
    except Exception as exc:
        extra["calibration_error"] = str(exc)[:200]
    base = numpy_steps_per_sec()
    fast, fast_median, grad_bytes = xla_mnist_bench(measure_chunks=3)
    extra.update({
        # the DP all-reduce payload (static param bytes — kept for
        # cross-round comparability) ...
        "grad_sync_bytes_per_step": int(grad_bytes),
        "mnist_numpy_steps_per_sec": round(base, 2),
        "mnist_train_steps_per_sec_best": round(fast, 2),
    })
    # ... and the MEASURED wire bytes per sync, per codec (ISSUE 7)
    _grad_codec_rows(extra)
    # N-slave scaling over the reactor wire plane (ISSUE 9)
    _dist_scaling_rows(extra)
    _record(extra, "cifar_conv_images_per_sec", xla_cifar_images_per_sec)

    def alexnet_row():
        # import inside so ANY failure (import or run) lands in the
        # row's _error key instead of killing the remaining rows
        from bench_alexnet import alexnet_images_per_sec
        median, best = alexnet_images_per_sec()
        return best, median           # _record wants (best, median)

    _record(extra, "alexnet_synth_images_per_sec", alexnet_row)
    _record(extra, "lm_train_tokens_per_sec", lm_tokens_per_sec)
    _record(extra, "lm_57M_tokens_per_sec", lm_scale_tokens_per_sec)
    _record(extra, "lm_57M_s8k_tokens_per_sec",
            lm_longctx_tokens_per_sec)
    _record(extra, "lm_110M_tokens_per_sec", lm_base_tokens_per_sec)
    _record(extra, "lm_110M_s8k_tokens_per_sec",
            lm_base_s8k_tokens_per_sec)
    _record(extra, "lm_345M_tokens_per_sec", lm_345m_tokens_per_sec)
    _serving_row(extra)
    # int8 at-rest weights: quantized-vs-f32 rps + the cache shrink
    # (ISSUE 14; gauge-sourced, acceptance <= 55% of f32)
    _quantized_serving_rows(extra)
    # continual-loop staleness + per-replica refresh downtime
    # (ISSUE 16; both down = good — the loop decays upward)
    _continual_rows(extra)
    # one bias-grad dispatch at a conv1-class shape through the
    # fused_bias_grad auto path (ISSUE 14; up = bad)
    _bias_grad_row(extra)
    # direct vs routed RPS + brownout p99 through the router tier
    # (ISSUE 13; proxy overhead and failover quality as trajectories)
    _routed_rows(extra)
    # continuous-batching decode vs sequential per-request decode
    # (ISSUE 11; the acceptance multiple at 8 concurrent streams)
    _generate_rows(extra)
    # sampling-profiler cost on the same MNIST loop (ISSUE 10; the
    # acceptance bound is < 3% at the default 97 Hz)
    _profiler_row(extra)
    # in-graph model-health stats cost, off-on-off on the XLA chunk
    # loop (ISSUE 15; acceptance < 2%, up = bad)
    _model_stats_row(extra)
    # the analyzer's own full-tree cost (ISSUE 12; up = bad)
    _lint_row(extra)
    # attention-aware MFU for every at-scale LM row (VERDICT r4 #2):
    # median tok/s x train-FLOPs/token over the v5e bf16 peak, shapes
    # read from the SAME LM_ROWS entry the throughput row used
    for row in LM_ROWS:
        _mfu(extra, "lm_%s_tokens_per_sec" % row, "lm_%s_mfu" % row,
             row)
    # the ROADMAP-item-3 headline under its canonical name: the
    # transformer-base long-context MFU (the ~35%-at-S=8192 gap this
    # arc attacks), duplicated from the per-row key so the trajectory
    # has ONE stable handle across config retunes (down = bad)
    if "lm_110M_s8k_mfu" in extra:
        extra["lm_mfu_s8192"] = extra["lm_110M_s8k_mfu"]
    try:
        extra["calibration_matmul8k_bf16_tflops_end"] = round(
            device_matmul_tflops(), 1)
    except Exception as exc:
        extra["calibration_end_error"] = str(exc)[:200]
    # which data fed each number: real on-disk datasets or the
    # synthetic stand-ins (zero-egress environments have no choice,
    # but the record keeps every figure honest — VERDICT r2 item 4)
    from veles.znicz_tpu.models.datasets import data_provenance
    extra["data"] = {k: v.get("source", "?")
                     for k, v in data_provenance().items()}
    # the runtime's own per-step accounting (ISSUE 6 perf ledger,
    # veles/perf.py): recorded in the same artifact so the bench
    # arithmetic and the scraped veles_step_* families can be
    # cross-checked — a walker bug or a dispatch path that skips the
    # ledger shows up as a visible disagreement here
    from veles import telemetry as _telemetry
    _reg = _telemetry.get_registry()
    extra["runtime_step_flops_total"] = int(
        _reg.counter_total("veles_step_flops_total"))
    extra["runtime_step_bytes_total"] = int(
        _reg.counter_total("veles_step_bytes_total"))
    return emit({
        "metric": "mnist_train_steps_per_sec",
        "value": round(fast_median, 2),
        "unit": "steps/s",
        "vs_baseline": round(fast_median / base, 3),
        "extra": extra,
    })


if __name__ == "__main__":
    sys.exit(main())
