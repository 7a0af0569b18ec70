"""Generative decode serving: paged KV cache + continuous batching.

The one-shot ``/v1/predict`` plane (engine.py/batcher.py) prices a
whole forward per request; an LM deployment lives in the DECODE loop
— one token per step per sequence, each step needing the sequence's
K/V history. This module is that plane, vLLM/Orca-style, sized to the
repo's compile-once stance:

* **Paged KV cache** (:class:`KVPool`) — every attention layer's K/V
  for up to ``n_slots`` concurrent sequences lives in ONE
  preallocated device buffer per layer, ``(n_slots, H, max_len, dh)``.
  A sequence is admitted by GRANTING a slot index, not by allocating:
  prefill writes the slot's whole K/V row, decode scatters one
  position per step, and a finished/dropped sequence just returns its
  index to the free list. ``veles_serving_forward_cache_bytes``
  accounting extends over the pool (``KVPool.nbytes``).

* **Compiled program cache** (:class:`GenerativeEngine`) — the decode
  twin of ``engine.py``'s per-(model, bucket) cache: one
  ``prefill_b{P}`` program per power-of-two PROMPT bucket (full causal
  forward over the padded prompt + first-token sample + KV write into
  the granted slot) and ONE ``decode_step`` program for the whole
  pool (every slot advances one position per call — the per-sequence
  position vector is the batch-joinable carry from
  ``znicz_tpu/generate.py``). Parameters are runtime arguments, so a
  hot reload keeps every compiled program.

* **Continuous batcher** (:class:`ContinuousBatcher`) — a decode loop
  generalizing the micro-batcher's deadline/shedding machinery to
  long-lived sequences: new requests are admitted into the IN-FLIGHT
  decode batch at step boundaries (prefill in the request's bucket,
  then the sequence joins the shared step), EOS/max-token/cancelled
  sequences free their slots mid-flight, queue admission is bounded
  (:class:`~veles.serving.batcher.QueueFull` -> HTTP 503) and expired
  queue entries never reach prefill. Tokens are pushed to a
  per-request callback as they decode — what the frontend streams as
  chunked HTTP.

The decode math is NOT re-derived here: prefill walks the archive's
unit specs through the SAME shared formulas the training units and
``model.py`` use (``dense_attention_core_fwd``, ``block_fwd``,
``FORWARD_OPS``), and the per-step attention update is
``generate.attn_decode``/``block_decode`` — one copy of the math
repo-wide, pinned by the decode-equals-offline-generate test.

Instruments (all labelled by model): ``veles_serving_decode_*``
counters/gauges, ``veles_serving_kv_pool_slots`` /
``veles_serving_kv_slots_in_use``,
``veles_serving_generated_tokens_total``,
``veles_serving_first_token_seconds``.
"""

import collections
import threading
import time

import numpy

from veles import telemetry
from veles.logger import Logger
from veles.serving import tenants
from veles.serving.batcher import (DeadlineExceeded, QueueFull,
                                   timeout_seconds)
from veles.serving.model import FORWARD_OPS

#: decoded-token attribution by resolved tenant (ISSUE 18; bounded —
#: values are tenant-resolver output only, zlint telemetry-hygiene)
_T_TOKENS = telemetry.LazyChild(
    lambda: telemetry.counter(
        "veles_serving_tenant_tokens_total",
        "Tokens decoded by resolved tenant", ("tenant",)))

#: unit types that are sequence-free at decode time — one token's
#: activations flow through the SAME forward formula model.py serves
_TOKEN_TYPES = frozenset({
    "layernorm", "token_dense", "token_dense_relu",
    "transformer_ffn", "moe_ffn", "activation_tanh",
    "activation_relu", "activation_str", "activation_sigmoid",
})

#: default per-request decode budget when the client sends none
DEFAULT_MAX_TOKENS = 16

#: decode-loop wedge threshold (seconds without a completed step
#: while sequences are active) before healthy() reports not-ready —
#: generous enough to cover a first-request XLA compile
WEDGE_AFTER_S = 60.0


class DecodePlan:
    """Ordered decode walk over an :class:`ArchiveModel`'s unit
    specs: ``steps`` is ``(kind, spec, cache_index)`` with kinds
    ``embed`` / ``attn`` / ``stack`` / ``token``; attention-bearing
    steps get KV cache indices. Raises :class:`ValueError` for
    archives that cannot generate (no leading embedding, non-causal
    attention, unsupported unit types)."""

    def __init__(self, steps, cache_specs, dim, vocab):
        self.steps = steps
        #: per-cache (heads, head_dim) — one entry per attention
        #: layer, stacks contribute one per inner layer
        self.cache_specs = cache_specs
        self.dim = dim
        self.vocab = vocab

    @property
    def n_caches(self):
        return len(self.cache_specs)

    @classmethod
    def from_archive(cls, model):
        specs = model.units
        if not specs or specs[0]["type"] != "embedding":
            raise ValueError(
                "not a generative archive: the first unit must be an "
                "embedding (got %s)"
                % (specs[0]["type"] if specs else "no units"))
        emb = specs[0]
        dim = int(emb["config"]["dim"])
        vocab = int(emb["config"]["vocab_size"])
        steps = [("embed", emb, None)]
        cache_specs = []
        for spec in specs[1:]:
            t = spec["type"]
            cfg = spec.get("config", {})
            if t == "attention":
                if not cfg.get("causal"):
                    raise ValueError(
                        "%s: generation needs causal attention"
                        % spec["name"])
                steps.append(("attn", spec, len(cache_specs)))
                cache_specs.append(
                    (int(cfg["heads"]), dim // int(cfg["heads"])))
            elif t == "transformer_stack":
                if not cfg.get("causal"):
                    raise ValueError(
                        "%s: generation needs causal attention"
                        % spec["name"])
                steps.append(("stack", spec, len(cache_specs)))
                heads = int(cfg["heads"])
                cache_specs.extend(
                    [(heads, dim // heads)] * int(cfg["layers"]))
            elif t == "dropout":
                continue            # identity at inference
            elif t in _TOKEN_TYPES:
                steps.append(("token", spec, None))
            else:
                raise ValueError(
                    "cannot decode through unit %s (type %r)"
                    % (spec.get("name"), t))
        return cls(steps, cache_specs, dim, vocab)

    @classmethod
    def probe(cls, model):
        """True iff the archive can generate (cheap spec walk)."""
        try:
            cls.from_archive(model)
            return True
        except ValueError:
            return False

    def positions_limit(self, params):
        """Longest sequence the exported positions table supports
        (None = no positional embedding, unbounded)."""
        tree = params.get(self.steps[0][1]["name"], {})
        pos = tree.get("positions")
        return None if pos is None else int(pos.shape[0])


class KVPool:
    """The paged KV cache: one preallocated (n_slots, H, max_len, dh)
    K and V buffer per attention layer. Slots are the admission
    currency — :meth:`grant` pops a free index (None when full),
    :meth:`release` returns it. The arrays themselves are swapped
    wholesale by the engine's jitted programs (prefill writes a slot
    row, decode_step scatters one position per active row); stale K/V
    in a released slot is harmless — the next grant's prefill
    overwrites the full row and the position mask hides the rest.

    NOT thread-safe by itself: the continuous batcher serializes
    grant/release under its own lock."""

    def __init__(self, cache_specs, n_slots, max_len):
        import jax.numpy as jnp
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.K = [jnp.zeros((self.n_slots, h, self.max_len, dh),
                            jnp.float32) for h, dh in cache_specs]
        self.V = [jnp.zeros((self.n_slots, h, self.max_len, dh),
                            jnp.float32) for h, dh in cache_specs]
        self._free = list(range(self.n_slots - 1, -1, -1))

    def grant(self):
        return self._free.pop() if self._free else None

    def release(self, slot):
        self._free.append(slot)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def in_use(self):
        return self.n_slots - len(self._free)

    def nbytes(self):
        """Preallocated pool bytes (the forward-cache accounting
        extension: these pages exist whether or not any sequence
        occupies them)."""
        return sum(int(numpy.prod(a.shape)) * 4
                   for a in self.K) * 2


def _sample_tokens(logits, temp, key):
    """Per-row sampling with a PER-SEQUENCE temperature vector:
    ``temp[b] == 0`` rows take the argmax, others sample the softmax
    at their own temperature — one program serves a batch mixing
    greedy and sampled requests."""
    import jax
    import jax.numpy as jnp
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe = jnp.maximum(temp, jnp.float32(1e-6))
    sampled = jax.random.categorical(
        key, logits / safe[..., None], axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


class GenerativeEngine(Logger):
    """Compiled prefill/decode executor + KV pool for ONE generative
    :class:`ArchiveModel`. All device work happens on the continuous
    batcher's decode thread; only :meth:`set_params` (hot reload) is
    called from elsewhere, and params swap atomically (one attribute
    store — in-flight sequences finish on whichever tree their next
    step reads, the same contract the predict engine has)."""

    def __init__(self, model, n_slots=8, max_len=256, donate=None,
                 name="decode-engine"):
        self.name = name
        from veles import backends
        backends.enable_compile_cache()
        self.plan = DecodePlan.from_archive(model)
        limit = self.plan.positions_limit(model.params)
        if limit is not None and limit < max_len:
            # the exported positions table bounds the horizon: past
            # it there is no position embedding to look up
            self.info("clamping max_len %d -> %d (exported positions "
                      "table)", max_len, limit)
            max_len = limit
        self.max_len = int(max_len)
        self.pool = KVPool(self.plan.cache_specs, n_slots,
                           self.max_len)
        if donate is None:
            # pool-buffer donation is an accelerator win; the CPU
            # donation path is a known use-after-free hazard in this
            # jaxlib (see StepCompiler) — never donate there
            donate = backends.default_platform() != "cpu"
        self.donate = bool(donate)
        self._compiled_prefill = {}   # prompt bucket -> jitted fn
        self._step_fn = None
        self.compile_seconds = {}
        self.set_params(model)
        import jax
        self._key = jax.random.PRNGKey(0)
        self._fold = 0

    def set_params(self, model):
        """(Re-)upload the model's params — the hot-reload path; every
        compiled program keeps working (params are arguments)."""
        import jax
        trees = [model.params.get(spec["name"], {})
                 for _, spec, _ in self.plan.steps]
        self._params = jax.device_put(trees)

    # -- bucket math ---------------------------------------------------

    def prompt_bucket(self, n):
        """Smallest power-of-two prompt bucket >= n (caps at
        max_len)."""
        if n > self.max_len:
            raise ValueError("prompt of %d exceeds max_len %d"
                             % (n, self.max_len))
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.max_len)

    @property
    def compiled_buckets(self):
        return sorted(self._compiled_prefill)

    # -- program builders ----------------------------------------------

    def _build_prefill(self, bucket):
        """One jitted program per prompt bucket: full causal forward
        over the padded prompt, first-token sample at the true last
        position, and the slot's K/V row written into the pool.
        Right-padding is sound under causal attention: pad positions
        can only influence positions AFTER the prompt, which decode
        overwrites (K/V scatter at pos) or masks (arange > pos)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from veles.znicz_tpu.ops.attention import (
            dense_attention_core_fwd)
        from veles.znicz_tpu.parallel.pipeline import block_fwd

        steps = self.plan.steps
        pad = self.max_len - bucket

        def split(t, heads):
            b, s, d = t.shape
            return t.reshape(b, s, heads, d // heads) \
                .transpose(0, 2, 1, 3)

        def merge(t):
            b, h, s, dh = t.shape
            return t.transpose(0, 2, 1, 3).reshape(b, s, h * dh)

        def prefill(ptrees, poolK, poolV, slot, ids, length, temp,
                    key):
            # quantized at-rest weights densify INSIDE the trace
            # (serving/quant.py): matmul-consumer trees dequantize
            # whole (the convert+scale fuses into the consumer), the
            # embedding gathers its 1-byte rows FIRST and dequantizes
            # only the slice — the consumer there is a gather, and
            # densifying the vocab table per dispatch would erase the
            # bandwidth saving
            from veles.serving.quant import dense_params, gather_rows
            emb, ptrees = ptrees[0], [
                dense_params(jnp, t) for t in ptrees[1:]]
            x = gather_rows(jnp, emb["weights"], ids)
            pos_table = emb.get("positions")
            if pos_table is not None:
                x = x + gather_rows(jnp, pos_table,
                                    slice(None, bucket))
            caches = [None] * self.plan.n_caches
            for (kind, spec, ci), p in zip(steps[1:], ptrees):
                cfg = spec.get("config", {})
                if kind == "attn":
                    heads = int(cfg["heads"])
                    d = x.shape[-1]
                    qkv = jnp.matmul(x, p["weights"])
                    if p.get("bias") is not None:
                        qkv = qkv + p["bias"]
                    q = split(qkv[..., :d], heads)
                    k = split(qkv[..., d:2 * d], heads)
                    v = split(qkv[..., 2 * d:], heads)
                    scale = numpy.float32(
                        1.0 / numpy.sqrt(d // heads))
                    _, ctx = dense_attention_core_fwd(
                        jnp, q, k, v, True, scale)
                    y = jnp.matmul(merge(ctx), p["weights_out"])
                    if p.get("bias_out") is not None:
                        y = y + p["bias_out"]
                    if cfg.get("residual"):
                        y = y + x
                    caches[ci] = (k, v)
                    x = y
                elif kind == "stack":
                    heads = int(cfg["heads"])
                    eps = float(cfg["eps"])
                    for l in range(int(cfg["layers"])):
                        lp = {k2: p[k2][l] for k2 in p}
                        x, cache = block_fwd(jnp, x, lp, heads, True,
                                             eps)
                        caches[ci + l] = (cache["k"], cache["v"])
                else:
                    x = FORWARD_OPS[spec["type"]](jnp, x, p, spec)
            logits = lax.dynamic_index_in_dim(x[0], length - 1, 0,
                                              keepdims=False)
            tok = _sample_tokens(logits[None], temp[None], key)[0]
            for ci, (k, v) in enumerate(caches):
                k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
                poolK[ci] = lax.dynamic_update_slice(
                    poolK[ci], k, (slot, 0, 0, 0))
                poolV[ci] = lax.dynamic_update_slice(
                    poolV[ci], v, (slot, 0, 0, 0))
            return tok, poolK, poolV

        donate = (1, 2) if self.donate else ()
        return jax.jit(prefill, donate_argnums=donate)

    def _build_step(self):
        """THE decode program: every pool slot advances one position.
        Inactive slots (pos 0, token 0) compute a wasted lane — the
        price of a single static-shape program — and their sampled
        output is simply ignored host-side."""
        import jax
        import jax.numpy as jnp
        from veles.znicz_tpu.generate import attn_decode, block_decode

        steps = self.plan.steps

        def step(ptrees, poolK, poolV, tokens, pos, temp, key):
            # see prefill: matmul trees densify whole, the embedding
            # gathers its 1-byte rows first
            from veles.serving.quant import dense_params, gather_rows
            emb, ptrees = ptrees[0], [
                dense_params(jnp, t) for t in ptrees[1:]]
            key, sub = jax.random.split(key)
            x = gather_rows(jnp, emb["weights"], tokens)[:, None, :]
            pos_table = emb.get("positions")
            if pos_table is not None:
                x = x + gather_rows(jnp, pos_table, pos)[:, None, :]
            for (kind, spec, ci), p in zip(steps[1:], ptrees):
                cfg = spec.get("config", {})
                if kind == "attn":
                    x, (poolK[ci], poolV[ci]) = attn_decode(
                        x, pos, (poolK[ci], poolV[ci]), p,
                        int(cfg["heads"]),
                        p.get("bias") is not None,
                        bool(cfg.get("residual")))
                elif kind == "stack":
                    heads = int(cfg["heads"])
                    eps = float(cfg["eps"])
                    for l in range(int(cfg["layers"])):
                        lp = {k2: p[k2][l] for k2 in p}
                        x, (poolK[ci + l], poolV[ci + l]) = \
                            block_decode(
                                x, pos, (poolK[ci + l],
                                         poolV[ci + l]),
                                lp, heads, eps)
                else:
                    x = FORWARD_OPS[spec["type"]](jnp, x, p, spec)
            nxt = _sample_tokens(x[:, 0, :], temp, sub)
            return nxt, poolK, poolV, key

        donate = (1, 2) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _compiled(self, bucket):
        fn = self._compiled_prefill.get(bucket)
        if fn is None:
            t0 = time.perf_counter()
            fn = self._build_prefill(bucket)
            self._compiled_prefill[bucket] = fn
            self.compile_seconds[bucket] = time.perf_counter() - t0
        return fn

    def warmup(self, buckets=None):
        """Pre-build the prompt-bucket prefill ladder and the decode
        step program (jit wrappers; XLA still compiles lazily at the
        first call per shape — one warm generation makes it real);
        -> compile_seconds. Bench and tests call this so timed rows
        never pay a build."""
        from veles.serving.engine import bucket_sizes
        for b in buckets or bucket_sizes(self.max_len):
            self._compiled(int(b))
        if self._step_fn is None:
            t0 = time.perf_counter()
            self._step_fn = self._build_step()
            self.compile_seconds["step"] = time.perf_counter() - t0
        return dict(self.compile_seconds)

    # -- execution (decode thread only) --------------------------------

    def prefill_into(self, slot, prompt, temperature):
        """Run the prompt's bucket prefill, write the slot's K/V row,
        sample the first token; -> int token."""
        import jax
        import jax.numpy as jnp
        n = len(prompt)
        bucket = self.prompt_bucket(n)
        ids = numpy.zeros((1, bucket), numpy.int32)
        ids[0, :n] = prompt
        self._fold += 1
        sub = jax.random.fold_in(self._key, self._fold)
        t0 = time.perf_counter()
        fn = self._compiled(bucket)
        tok, self.pool.K, self.pool.V = fn(
            self._params, self.pool.K, self.pool.V,
            jnp.int32(slot), jnp.asarray(ids),
            jnp.int32(n), jnp.float32(temperature), sub)
        if telemetry.tracer.active:
            telemetry.tracer.add_complete(
                "serving.prefill", t0, time.perf_counter() - t0,
                bucket=bucket, slot=int(slot))
        return int(tok)

    def step(self, tokens, pos, temp):
        """One decode step over the WHOLE pool; arrays are (n_slots,)
        host vectors; -> (n_slots,) next tokens (host)."""
        import jax.numpy as jnp
        if self._step_fn is None:
            t0 = time.perf_counter()
            self._step_fn = self._build_step()
            self.compile_seconds["step"] = time.perf_counter() - t0
        nxt, self.pool.K, self.pool.V, self._key = self._step_fn(
            self._params, self.pool.K, self.pool.V,
            jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(temp), self._key)
        return numpy.asarray(nxt)


class GenRequest:
    """One generation: prompt in, tokens out (pushed to
    ``on_token`` as they decode, collected in :attr:`tokens`).
    Token/done callbacks may be attached AFTER submission
    (:meth:`set_on_token` replays the backlog under the emission
    lock, so no token is lost or duplicated)."""

    def __init__(self, prompt, max_tokens, temperature, eos,
                 deadline, trace=None, tenant=None):
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.eos = eos
        self.deadline = deadline
        self.trace = trace
        #: resolved tenant (ISSUE 18) + virtual finish tag: KV slots
        #: are granted least-tag-first so one tenant's burst cannot
        #: monopolise the decode batch (see ContinuousBatcher)
        self.tenant = tenant
        self.vft = 0.0
        self.t_submit = time.perf_counter()
        self.t_first = None         # wall of the first decoded token
        self.tokens = []
        self.finish_reason = None
        self.error = None
        self.done = threading.Event()
        self.slot = None
        self.cancelled = None       # reason string once cancelled
        self._lock = threading.Lock()
        self._on_token = None
        self._on_done = None
        self._notify = None         # batcher wake hook

    # -- client side ---------------------------------------------------

    def cancel(self, reason="cancelled"):
        """Stop decoding this request at the next step boundary and
        free its KV slot (client disconnect, shutdown). Safe from any
        thread; a finished request is untouched."""
        with self._lock:
            if self.done.is_set() or self.cancelled is not None:
                return
            self.cancelled = str(reason)
            notify = self._notify
        if notify is not None:
            notify()

    def set_on_token(self, fn):
        """Attach the per-token callback; tokens already decoded are
        replayed first (in order, under the emission lock)."""
        with self._lock:
            for tok in self.tokens:
                fn(tok)
            self._on_token = fn

    def set_on_done(self, fn):
        with self._lock:
            if not self.done.is_set():
                self._on_done = fn
                return
        fn(self)

    def wait(self, timeout=None):
        """Block until done; -> the token list (raises the failure
        error if any)."""
        if not self.done.wait(timeout):
            raise DeadlineExceeded("generation still running after "
                                   "%.1fs" % (timeout or 0))
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    # -- decode-thread side --------------------------------------------

    def _emit(self, tok):
        with self._lock:
            if self.t_first is None:
                self.t_first = time.perf_counter()
            self.tokens.append(tok)
            cb = self._on_token
            if cb is not None:
                try:
                    cb(tok)
                except Exception:
                    # a consumer callback must never kill the SHARED
                    # decode loop (its other sequences are innocent)
                    pass

    def _finish(self, reason=None, error=None):
        with self._lock:
            self.finish_reason = reason
            self.error = error
            cb = self._on_done
            self._on_done = None
            self.done.set()
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass


class ContinuousBatcher(Logger):
    """The decode loop: admission at step boundaries, shared decode
    batch, mid-flight slot recycling, bounded queue. One worker
    thread owns every device dispatch; public methods only touch the
    queue/bookkeeping under the lock."""

    def __init__(self, engine, max_queue=64,
                 default_timeout_ms=30000.0, name="decode",
                 model=None):
        self.name = name
        self.model = model or name
        self.engine = engine
        self.max_queue = int(max_queue)
        self.default_timeout = float(default_timeout_ms) / 1000.0
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._queue = collections.deque()
        self._active = {}           # slot -> GenRequest
        # weighted-fair slot grants (ISSUE 18): virtual time + last
        # finish tag per tenant, cost = prompt + token budget over
        # the tenant's priority weight. FIFO-equivalent with one
        # tenant (or no tenant table installed).
        self._vtime = 0.0
        self._vfinish = {}
        self._running = True
        self.last_step = time.monotonic()
        n_slots = engine.pool.n_slots
        # host-side carry vectors for the whole pool (inactive slots
        # ride along at pos 0 / token 0 / temp 0)
        self._tokens = numpy.zeros(n_slots, numpy.int32)
        self._pos = numpy.zeros(n_slots, numpy.int32)
        self._temp = numpy.zeros(n_slots, numpy.float32)
        #: (wall, n_tokens) per completed step for the tokens/s view
        self._step_log = collections.deque(maxlen=4096)
        label = (self.model,)
        self._c_requests = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_decode_requests_total",
                "Generation requests admitted to the decode queue",
                ("model",)).labels(*label))
        self._c_shed = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_decode_shed_total",
                "Generation requests shed on a full decode queue "
                "(503)", ("model",)).labels(*label))
        self._c_expired = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_decode_expired_total",
                "Generation requests expired before a KV slot grant "
                "(504)", ("model",)).labels(*label))
        self._c_tokens = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_generated_tokens_total",
                "Tokens decoded across all sequences",
                ("model",)).labels(*label))
        self._c_steps = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_decode_steps_total",
                "Shared decode steps executed (each advances every "
                "active sequence one token)", ("model",)).labels(
                    *label))
        self._c_finished = telemetry.LazyChild(
            lambda: telemetry.counter(
                "veles_serving_decode_finished_total",
                "Finished generations by reason",
                ("model", "reason")))
        self._g_queue = telemetry.LazyChild(
            lambda: telemetry.gauge(
                "veles_serving_decode_queue_depth",
                "Generation requests waiting for a KV slot",
                ("model",)).labels(*label))
        self._g_slots = telemetry.LazyChild(
            lambda: telemetry.gauge(
                "veles_serving_kv_slots_in_use",
                "KV pool slots occupied by in-flight sequences",
                ("model",)).labels(*label))
        self._g_pool = telemetry.LazyChild(
            lambda: telemetry.gauge(
                "veles_serving_kv_pool_slots",
                "Preallocated KV pool slots (decode batch width)",
                ("model",)).labels(*label))
        self._h_first = telemetry.LazyChild(
            lambda: telemetry.histogram(
                "veles_serving_first_token_seconds",
                "Submit -> first streamed token",
                ("model",)).labels(*label))
        self._g_pool.get().set(n_slots)
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="%s-worker" % name)
        self._thread.start()

    # -- client side ---------------------------------------------------

    def submit(self, prompt, max_tokens=None, temperature=0.0,
               eos=None, timeout_ms=None, trace=None, tenant=None):
        """Enqueue one generation; -> :class:`GenRequest`. Raises
        :class:`QueueFull` (admission backpressure) or
        :class:`ValueError` (prompt/budget outside the pool
        geometry). ``timeout_ms`` bounds the wait for a KV slot, not
        the decode itself (a granted sequence runs to completion).
        ``tenant`` (resolver output) keys the weighted-fair slot
        grants."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must have at least one token")
        try:
            max_tokens = (DEFAULT_MAX_TOKENS if max_tokens is None
                          else int(max_tokens))
        except OverflowError:
            # int(float('inf')): keep the client-fixable 400 contract
            raise ValueError("max_tokens must be a finite integer, "
                             "got %r" % (max_tokens,))
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if len(prompt) + max_tokens > self.engine.max_len:
            raise ValueError(
                "prompt %d + max_tokens %d exceeds the KV slot "
                "length %d" % (len(prompt), max_tokens,
                               self.engine.max_len))
        timeout = timeout_seconds(timeout_ms, self.default_timeout)
        req = GenRequest(prompt, max_tokens, float(temperature),
                         None if eos is None else int(eos),
                         time.monotonic() + timeout, trace=trace,
                         tenant=tenant)
        with self._lock:
            if not self._running:
                raise RuntimeError("decode batcher is closed")
            if len(self._queue) >= self.max_queue:
                self._c_shed.get().inc()
                raise QueueFull(
                    "decode queue full (%d waiting, max %d)"
                    % (len(self._queue), self.max_queue))
            self._c_requests.get().inc()
            # fair-share tag: a sequence's cost is its whole KV
            # claim (prompt + token budget) over the tenant's weight
            start = max(self._vtime, self._vfinish.get(tenant, 0.0))
            req.vft = start + (len(prompt) + max_tokens) \
                / tenants.weight(tenant)
            self._vfinish[tenant] = req.vft
            req._notify = self._notify
            self._queue.append(req)
            self._g_queue.get().set(len(self._queue))
            self._wake.notify()
        return req

    def generate(self, prompt, max_tokens=None, temperature=0.0,
                 eos=None, timeout_ms=None, wait_s=120.0):
        """submit + wait: -> the generated token list."""
        return self.submit(prompt, max_tokens=max_tokens,
                           temperature=temperature, eos=eos,
                           timeout_ms=timeout_ms).wait(wait_s)

    def _notify(self):
        with self._lock:
            self._wake.notify()

    # -- worker --------------------------------------------------------

    def _admit_locked(self):
        """Sweep the queue: expired/cancelled requests fail WITHOUT
        prefill (even while the pool is saturated — a dead entry must
        not pin the bounded queue and shed live traffic), live ones
        take free KV slots in least-virtual-finish-tag order (ISSUE
        18: weighted fairness across tenants — FIFO when every tag
        came from one tenant); the rest keep their arrival order; ->
        the requests to prefill. Lock held."""
        live = []
        now = time.monotonic()
        while self._queue:
            req = self._queue.popleft()
            if req.cancelled is not None:
                self._finish_locked(req, req.cancelled)
            elif req.deadline < now:
                self._c_expired.get().inc()
                req._finish(error=DeadlineExceeded(
                    "no KV slot before deadline"))
                self._count_finish("expired")
            else:
                live.append(req)
        admitted = []
        if live and self.engine.pool.free_slots:
            granted = set()
            for req in sorted(live, key=lambda r: (r.vft,
                                                   r.tenant or "")):
                if not self.engine.pool.free_slots:
                    break
                req.slot = self.engine.pool.grant()
                self._active[req.slot] = req
                self._vtime = max(self._vtime, req.vft)
                admitted.append(req)
                granted.add(id(req))
            if granted:
                live = [r for r in live if id(r) not in granted]
        self._queue.extend(live)    # arrival order preserved
        self._g_queue.get().set(len(self._queue))
        self._g_slots.get().set(self.engine.pool.in_use)
        return admitted

    def _count_finish(self, reason):
        self._c_finished.get().labels(self.model, reason).inc()

    def _finish_locked(self, req, reason, error=None):
        """Free the slot (if granted) and complete the request.
        Lock held (slot bookkeeping); the done callback fires after
        via GenRequest._finish's own lock."""
        if req.slot is not None:
            self._active.pop(req.slot, None)
            self.engine.pool.release(req.slot)
            self._temp[req.slot] = 0.0
            self._pos[req.slot] = 0
            self._tokens[req.slot] = 0
            req.slot = None
            self._g_slots.get().set(self.engine.pool.in_use)
        self._count_finish(reason if error is None else "error")
        req._finish(reason=reason, error=error)
        if telemetry.tracer.active:
            args = {"model": self.model, "tokens": len(req.tokens),
                    "reason": reason or "error"}
            if req.trace is not None:
                args.update(req.trace.child().span_args())
            telemetry.tracer.add_complete(
                "serving.decode", req.t_submit,
                time.perf_counter() - req.t_submit, **args)

    def _deliver(self, req, tok):
        """Emit one decoded token and decide whether the sequence is
        done; -> finish reason or None (keeps decoding)."""
        req._emit(tok)
        self._c_tokens.get().inc()
        if req.tenant is not None:
            _T_TOKENS.get().labels(req.tenant).inc()
        if req.cancelled is not None:
            return req.cancelled
        if req.eos is not None and tok == req.eos:
            return "eos"
        if len(req.tokens) >= req.max_tokens:
            return "length"
        return None

    def _worker(self):
        while True:
            with self._lock:
                while self._running and not self._queue \
                        and not self._active:
                    self._wake.wait()
                if not self._running:
                    self._drain_locked()
                    return
                admitted = self._admit_locked()
            for req in admitted:
                try:
                    tok = self.engine.prefill_into(
                        req.slot, req.prompt, req.temperature)
                except Exception as exc:
                    self.warning("prefill failed: %s: %s",
                                 type(exc).__name__, exc)
                    with self._lock:
                        self._finish_locked(req, None, error=exc)
                    continue
                self._h_first.get().observe(
                    time.perf_counter() - req.t_submit)
                reason = self._deliver(req, tok)
                if reason is not None:
                    with self._lock:
                        self._finish_locked(req, reason)
                    continue
                # the sequence joins the shared decode batch: its
                # first generated token is the next step's input at
                # position len(prompt)
                self._tokens[req.slot] = tok
                self._pos[req.slot] = len(req.prompt)
                self._temp[req.slot] = req.temperature
            with self._lock:
                active = dict(self._active)
            self.last_step = time.monotonic()
            if not active:
                continue
            try:
                nxt = self.engine.step(self._tokens, self._pos,
                                       self._temp)
            except Exception as exc:
                self.warning("decode step failed: %s: %s",
                             type(exc).__name__, exc)
                with self._lock:
                    for req in list(self._active.values()):
                        self._finish_locked(req, None, error=exc)
                continue
            self._c_steps.get().inc()
            self._step_log.append((time.monotonic(), len(active)))
            self.last_step = time.monotonic()
            for slot, req in active.items():
                tok = int(nxt[slot])
                self._pos[slot] += 1
                reason = self._deliver(req, tok)
                if reason is not None:
                    with self._lock:
                        self._finish_locked(req, reason)
                else:
                    self._tokens[slot] = tok

    def _drain_locked(self):
        closed = RuntimeError("decode batcher closed")
        while self._queue:
            self._finish_locked(self._queue.popleft(), None,
                                error=closed)
        for req in list(self._active.values()):
            self._finish_locked(req, None, error=closed)
        self._g_queue.get().set(0)

    # -- operational surface -------------------------------------------

    def healthy(self):
        """(ok, reason) for the ``serving:<port>:decode`` readiness
        check: the worker must be alive, and while sequences are
        active the loop must keep completing steps."""
        if not self._thread.is_alive():
            if self._running:
                return False, "decode worker dead"
            return True, None           # closed deliberately
        with self._lock:
            busy = bool(self._active or self._queue)
        if busy and time.monotonic() - self.last_step > WEDGE_AFTER_S:
            return False, ("decode loop wedged (%.0fs since last "
                           "step)" % (time.monotonic()
                                      - self.last_step))
        return True, None

    def metrics(self, rate_window=10.0):
        """JSON view for ``/metrics.json`` and ``velescli top``."""
        now = time.monotonic()
        with self._lock:
            queued = len(self._queue)
            in_use = self.engine.pool.in_use
            recent = sum(n for t, n in self._step_log
                         if t > now - rate_window)
        first = self._h_first.get()
        out = {
            "queue_depth": queued,
            "kv_slots_in_use": in_use,
            "kv_pool_slots": self.engine.pool.n_slots,
            "kv_pool_bytes": self.engine.pool.nbytes(),
            "max_len": self.engine.max_len,
            "requests_total": int(self._c_requests.get().value),
            "generated_tokens_total": int(
                self._c_tokens.get().value),
            "steps_total": int(self._c_steps.get().value),
            "tokens_per_sec": round(recent / rate_window, 2),
        }
        p50 = first.percentile(0.5)
        if p50 is not None:
            out["first_token_ms_p50"] = round(p50 * 1000, 3)
            out["first_token_ms_p99"] = round(
                first.percentile(0.99) * 1000, 3)
        return out

    def close(self):
        """Stop the worker; queued AND in-flight requests fail with
        a closed error (their slots are released)."""
        with self._lock:
            self._running = False
            self._wake.notify_all()
        self._thread.join(timeout=10)
        with self._lock:
            if self._thread.is_alive():
                return              # wedged in a step; daemon thread
            self._drain_locked()
