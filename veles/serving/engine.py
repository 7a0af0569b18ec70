"""Per-(model, bucket) compiled forward cache.

The serving twin of ``xla_step.py``'s compile-once stance: instead of
dispatching units one by one, a model's whole forward chain is traced
ONCE per padded batch bucket into a single jitted program with a
donated batch buffer (the input batch is engine-built scratch, so XLA
may reuse it for the first layer's output). Buckets are powers of two
up to ``max_batch`` — the batcher pads every micro-batch up to the
next bucket, so a handful of programs serve every batch size and no
request ever waits on a fresh compile after :meth:`warmup`.

``backend="numpy"`` evaluates the same pure function with plain numpy
(the oracle path — zero compile cost, useful for tests and tiny
models); ``backend="jit"`` uses jax on its default platform, and
``"auto"`` means ``"jit"`` (jax is a hard dependency).
"""

import threading
import time

from veles import telemetry


def bucket_sizes(max_batch):
    """The power-of-two bucket ladder: 1, 2, 4, ... max_batch."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b <<= 1
    out.append(max_batch)
    return out


class InferenceEngine:
    """Compiled forward executor for ONE :class:`ArchiveModel`.

    Thread-safe: the compile cache is lock-protected; execution itself
    is free-running (pure functions, no shared buffers)."""

    def __init__(self, model, backend="auto", max_batch=64,
                 donate=None, quantize="none"):
        if backend == "auto":
            backend = "jit"
        if backend not in ("numpy", "jit"):
            raise ValueError("backend must be auto|numpy|jit, got %r"
                             % (backend,))
        from veles.serving.quant import validate_mode
        validate_mode(quantize)
        #: at-rest weight quantization mode (serving/quant.py):
        #: set_model re-quantizes the model's params IN PLACE, so the
        #: host at-rest copy and the device upload both ride 1
        #: byte/element; apply() densifies at dispatch
        self.quantize = quantize
        self.backend = backend
        self.max_batch = int(max_batch)
        self._lock = threading.Lock()
        self._compiled = {}          # batch shape -> compiled program
        self._building = {}          # batch shape -> threading.Event
        self.compile_seconds = {}    # bucket -> trace+compile time
        self._model = None
        self._jit_apply = None
        self._device_params = None
        #: jax platform the jit backend runs on (None for numpy) —
        #: reported by /v1/models and the serve CLI's first stdout
        #: line, so a caller can refuse a server that landed on the CPU
        self.platform = None
        if backend == "jit":
            from veles import backends
            backends.enable_compile_cache()
            self.platform = backends.default_platform()
        if donate is None:
            # donation is a TPU/GPU win; on CPU jax only warns
            donate = self.platform not in (None, "cpu")
        self.donate = bool(donate)
        self.set_model(model)

    # -- model swap (hot reload) ---------------------------------------

    def set_model(self, model, params_only=False):
        """Swap the served model. ``params_only=True`` (same
        architecture — caller checked ``signature()``) keeps every
        compiled program and just re-uploads the params; otherwise the
        compile cache is invalidated."""
        with self._lock:
            self._model = model
            if not params_only:
                self._compiled.clear()
                self.compile_seconds = {}
                self._jit_apply = None
            if self.quantize != "none":
                # at-rest swap: a checkpoint refresh writes f32 leaves
                # back into the tree; re-quantizing here keeps host
                # AND device at 1 byte/element (already-quantized
                # leaves pass through untouched). Compiled programs
                # stay valid — the quantized payload and its scale are
                # runtime pytree leaves, exactly like plain params.
                from veles.serving.quant import quantize_tree
                model.params = quantize_tree(model.params,
                                             self.quantize)
            if self.backend == "jit":
                import jax
                self._device_params = jax.device_put(model.params)
            else:
                self._device_params = model.params

    @property
    def model(self):
        return self._model

    # -- bucket math ---------------------------------------------------

    def bucket_for(self, n):
        """Smallest power-of-two bucket >= n (caps at max_batch)."""
        if n > self.max_batch:
            raise ValueError("batch %d exceeds max_batch %d"
                             % (n, self.max_batch))
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.max_batch)

    # -- compilation ---------------------------------------------------

    def _compile(self, shape):
        """Compiled program for a padded batch of ``shape`` — keyed on
        the FULL shape, so archives without a recorded
        input_sample_shape (no-loader exports) still compile from the
        real request shape."""
        while True:
            with self._lock:
                fn = self._compiled.get(shape)
                if fn is not None:
                    return fn
                pending = self._building.get(shape)
                if pending is None:
                    # claim the build; concurrent first requests at
                    # the same shape WAIT instead of each paying a
                    # duplicate multi-second compile
                    self._building[shape] = threading.Event()
                    if self._jit_apply is None:
                        import functools
                        import jax
                        import jax.numpy as jnp
                        self._jit_apply = jax.jit(
                            functools.partial(self._model.apply, jnp),
                            donate_argnums=(1,) if self.donate
                            else ())
                    jit_apply = self._jit_apply
                    break
            pending.wait()
        import jax
        import numpy
        try:
            t0 = time.perf_counter()
            compiled = jit_apply.lower(
                self._device_params,
                jax.ShapeDtypeStruct(shape, numpy.float32)).compile()
            dt = time.perf_counter() - t0
            if telemetry.tracer.active:
                telemetry.tracer.add_complete(
                    "serving.compile", t0, dt, bucket=shape[0])
            with self._lock:
                # params are a runtime ARGUMENT of the compiled
                # program, so a params_only hot reload keeps this
                # cache valid
                self._compiled[shape] = compiled
                self.compile_seconds[shape[0]] = dt
            return compiled
        finally:
            with self._lock:
                self._building.pop(shape).set()

    def warmup(self, buckets=None):
        """Precompile the bucket ladder so first requests never pay a
        trace+compile; returns {bucket: seconds}."""
        if self.backend != "jit" \
                or self._model.input_sample_shape is None:
            return {}
        for b in buckets or bucket_sizes(self.max_batch):
            self._compile((int(b),) + self._model.input_sample_shape)
        return dict(self.compile_seconds)

    @property
    def compiled_buckets(self):
        with self._lock:
            return sorted(shape[0] for shape in self._compiled)

    # -- execution -----------------------------------------------------

    def predict(self, x):
        """Run the forward on (n, *sample) rows; pads up to the bucket
        and slices the pad rows back off. -> (outputs, bucket)."""
        import numpy
        x = numpy.ascontiguousarray(x, numpy.float32)
        n = x.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            pad = numpy.repeat(x[-1:], bucket - n, axis=0)
            x = numpy.concatenate([x, pad], axis=0)
        if self.backend == "numpy":
            y = self._model.apply(numpy, self._device_params, x)
        else:
            y = numpy.asarray(self._compile(x.shape)(
                self._device_params, x))
        return y[:n], bucket
