"""Per-step performance accounting (ISSUE 6 tentpole, piece 3).

The bench harness computes FLOPs and MFU offline; the RUNTIME never
knew how much arithmetic a compiled step performs, so throughput
regressions (the ``grad_sync_bytes_per_step`` plateau, the S=8192 MFU
gap — ROADMAP items 3/4) were bench-only numbers invisible to a
scrape. This module closes that gap:

* :func:`program_cost` derives FLOPs and bytes for a jitted step
  program **from its jaxpr** at compile time — ``lax.scan`` trip
  counts are multiplied through (XLA's own HLO cost analysis counts a
  ``while`` body ONCE, which under-reports an epoch-scan program by
  the scan length), ``pjit``/``remat``/``custom_*`` regions are
  walked recursively, ``dot_general``/``conv_general_dilated`` get
  exact multiply-add counts and everything else is estimated at one
  flop per output element;
* :class:`PerfLedger` caches one :class:`StepCost` per compiled
  program and publishes the ``veles_step_*`` metric families on every
  dispatch (see ``XLAStep``): ``veles_step_flops_total{kind}``,
  ``veles_step_bytes_total{kind}``, ``veles_step_mfu_ratio{kind}``
  (when the device peak is known — :func:`device_peak_flops`),
  ``veles_step_flops_per_second{kind}`` and samples/tokens-per-second
  gauges. One Prometheus scrape now carries honest compute
  accounting next to the wire counters
  (``veles_wire_bytes_total{direction}``, ``veles/server.py``).

Cost model caveats: FLOPs are lower-bound arithmetic counts (no
fusion modelling); ``bytes`` sums every equation's output footprint
(scan-multiplied) — a proxy for memory traffic, not an HBM simulator.
Both are deterministic functions of the jaxpr, which is what makes
them comparable across runs and hosts.
"""

import logging
import os
import threading
import time
import weakref

import numpy

from veles import telemetry


class StepCost:
    """Cost of ONE call of a compiled program. ``precision`` is the
    program's dominant matmul input class ("bf16" | "int8" | "fp8" —
    by dot-FLOPs share), so the MFU gauge scores a low-precision
    program against the peak those matmuls actually have."""

    __slots__ = ("flops", "bytes", "io_bytes", "precision")

    def __init__(self, flops=0.0, bytes=0.0, io_bytes=0.0,
                 precision="bf16"):
        self.flops = float(flops)
        self.bytes = float(bytes)
        self.io_bytes = float(io_bytes)
        self.precision = precision

    def __repr__(self):
        return ("StepCost(flops=%.4g, bytes=%.4g, io_bytes=%.4g, "
                "precision=%s)" % (self.flops, self.bytes,
                                   self.io_bytes, self.precision))


def _size(shape):
    return int(numpy.prod(shape, dtype=numpy.int64)) if shape else 1


def _aval_bytes(aval):
    try:
        return _size(aval.shape) * numpy.dtype(aval.dtype).itemsize
    except (TypeError, AttributeError):
        return 0


def _dot_flops(eqn):
    """2 · |out| · K for ``dot_general`` (multiply-add = 2 flops)."""
    out = eqn.outvars[0].aval
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    k = 1
    for d in lhs_contract:
        k *= lhs.shape[d]
    return 2.0 * _size(out.shape) * k


def _conv_flops(eqn):
    """2 · |out| · (kernel footprint per output feature)."""
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_feature_dim = dn.rhs_spec[0]
    per_out = 1
    for i, d in enumerate(rhs.shape):
        if i != out_feature_dim:
            per_out *= d
    return 2.0 * _size(out.shape) * per_out


def _inner_jaxprs(eqn):
    """(multiplier, jaxpr) pairs for an equation's nested programs."""
    params = eqn.params
    name = eqn.primitive.name
    if name == "scan":
        return [(int(params.get("length", 1)), params["jaxpr"])]
    if name == "while":
        # trip count is data-dependent: count the body ONCE (explicit
        # under-estimate; the training paths use scan, not while)
        return [(1, params["body_jaxpr"])]
    if name == "cond":
        # either branch may run: charge the most expensive one
        branches = params.get("branches", ())
        if not branches:
            return []
        costed = [(1, b) for b in branches]
        return [max(costed, key=lambda mb: _jaxpr_cost(
            getattr(mb[1], "jaxpr", mb[1]))[0])]
    out = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            out.append((1, params[key]))
    if "branches" in params and not out:
        out.extend((1, b) for b in params["branches"])
    return out


def _dot_precision(eqn):
    """Precision class of one dot_general by BOTH input dtypes: a
    dot only runs at an 8-bit rate when both operands share the
    class — a mixed int8×bf16 dot (e.g. a fused dequant consumer)
    upcasts and runs the wide rate, and scoring it against the
    doubled 8-bit peak would under-report MFU ~2x."""
    def cls(var):
        try:
            name = numpy.dtype(var.aval.dtype).name
        except (TypeError, AttributeError):
            return "bf16"
        if name in ("int8", "uint8"):
            return "int8"
        if name.startswith("float8"):
            return "fp8"
        return "bf16"
    lhs, rhs = cls(eqn.invars[0]), cls(eqn.invars[1])
    return lhs if lhs == rhs else "bf16"


def _jaxpr_cost(jaxpr, dot_prec=None):
    """(flops, bytes) of one jaxpr execution, recursing into nested
    programs with their trip-count multipliers. ``dot_prec`` (when a
    dict is passed) accumulates dot-FLOPs per precision class — the
    input to the program-precision call."""
    flops = 0.0
    nbytes = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner = _inner_jaxprs(eqn)
        if inner:
            for mult, sub in inner:
                sub_prec = {} if dot_prec is not None else None
                f, b = _jaxpr_cost(getattr(sub, "jaxpr", sub),
                                   sub_prec)
                flops += mult * f
                nbytes += mult * b
                if dot_prec is not None:
                    for k, v in sub_prec.items():
                        dot_prec[k] = dot_prec.get(k, 0.0) + mult * v
            continue
        if name == "dot_general":
            f = _dot_flops(eqn)
            flops += f
            if dot_prec is not None:
                k = _dot_precision(eqn)
                dot_prec[k] = dot_prec.get(k, 0.0) + f
        elif name == "conv_general_dilated":
            flops += _conv_flops(eqn)
        else:
            # elementwise/reduce estimate: one flop per output element
            flops += sum(_size(v.aval.shape) for v in eqn.outvars
                         if hasattr(v.aval, "shape"))
        nbytes += sum(_aval_bytes(v.aval) for v in eqn.outvars)
    return flops, nbytes


def program_cost(fn, args):
    """Trace ``fn(*args)`` to a jaxpr (no XLA compilation, no
    execution, nothing donated) and walk it; -> :class:`StepCost`.
    The dominant dot-input precision class rides along so MFU is
    scored against the right peak for int8/fp8 programs."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    dot_prec = {}
    flops, nbytes = _jaxpr_cost(closed.jaxpr, dot_prec)
    io_bytes = sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
    io_bytes += sum(_aval_bytes(v.aval) for v in closed.jaxpr.outvars)
    precision = max(dot_prec, key=dot_prec.get) if dot_prec else "bf16"
    return StepCost(flops, nbytes, io_bytes, precision)


# -- device peak --------------------------------------------------------

#: peak FLOP/s per chip by precision class and device_kind substring
#: (vendor datasheet numbers; MFU is relative to THIS). ``bf16`` is
#: the dense bf16-input/f32-accumulate MXU rate every training row
#: uses; ``int8`` is the doubled-throughput 8-bit MXU rate on the
#: generations that have one (v5e/v5p/v6 — v2-v4 run int8 at the bf16
#:  rate); ``fp8`` is native only on v6-class chips, elsewhere fp8
#: matmuls upcast and the honest peak is the bf16 entry (the
#: fallback). A low-precision program scored against the bf16 peak
#: would silently over-report MFU by up to 2x — the reason
#: ``veles_step_mfu_ratio`` resolves its peak per program precision.
_PEAK_FLOPS_BY_KIND = {
    "bf16": (
        ("TPU v6", 918e12),
        ("TPU v5p", 459e12),
        ("TPU v5e", 197e12),
        ("TPU v5 lite", 197e12),
        ("TPU v4", 275e12),
        ("TPU v3", 123e12),
        ("TPU v2", 45e12),
    ),
    "int8": (
        ("TPU v6", 1836e12),
        ("TPU v5p", 918e12),
        ("TPU v5e", 394e12),
        ("TPU v5 lite", 394e12),
    ),
    "fp8": (
        ("TPU v6", 1836e12),
    ),
}

#: per-precision env overrides (the escape hatch for new hardware and
#: deterministic tests); VELES_PEAK_FLOPS keeps its pre-existing
#: meaning = the bf16/default peak
_PEAK_ENV = {"bf16": "VELES_PEAK_FLOPS",
             "int8": "VELES_PEAK_FLOPS_INT8",
             "fp8": "VELES_PEAK_FLOPS_FP8"}


def peak_flops_of(device_kind, precision="bf16"):
    """THE table lookup: peak FLOP/s of a ``jax.Device.device_kind``
    for ``precision`` ("bf16" | "int8" | "fp8"), or None for a kind
    the table does not hold. A precision with no entry for the device
    falls back to the bf16 row — the rate those matmuls actually run
    at. The runtime gauge (:func:`device_peak_flops`) treats None as
    "no MFU"; ``bench.py`` treats it as an error."""
    kind = str(device_kind).lower()
    for table in (_PEAK_FLOPS_BY_KIND.get(precision, ()),
                  _PEAK_FLOPS_BY_KIND["bf16"]):
        for sub, peak in table:
            if sub.lower() in kind:
                return peak
    return None


def device_peak_flops(precision="bf16"):
    """Peak FLOP/s of the default device for ``precision``, or None
    when unknown (CPU, unrecognized kind). ``$VELES_PEAK_FLOPS`` (and
    ``_INT8``/``_FP8``) override the table."""
    env = os.environ.get(_PEAK_ENV.get(precision, "VELES_PEAK_FLOPS"))
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        return None
    return peak_flops_of(kind, precision)


# -- the ledger ---------------------------------------------------------


class PerfLedger:
    """Per-program cost cache + the ``veles_step_*`` publisher.

    ``cost()`` analyzes a program once per (program, shape signature)
    key; ``record_dispatch()`` turns (cost, wall seconds, work
    counts) into registry updates. Both are cheap after the first
    call per program, so the per-dispatch overhead is a handful of
    counter ops."""

    def __init__(self):
        self._lock = threading.Lock()
        self._costs = {}
        self._kids = {}

    def cost(self, key, fn, args):
        """The cached :class:`StepCost` for ``key``, analyzing
        ``fn(*args)`` on first sight. Analysis failures degrade to a
        zero cost — accounting must never break a dispatch path — and
        are logged with their traceback, once per program (the zero
        is cached like any other cost): a silent zero reads as "this
        step does no arithmetic" on every later scrape.

        Callers key by ``id(fn)``, so each entry holds a weakref to
        its program: a later function reallocated at a freed id must
        re-analyze, not inherit the dead program's cost, and dead
        entries are dropped instead of accumulating forever."""
        with self._lock:
            entry = self._costs.get(key)
            if entry is not None:
                ref, cost = entry
                if ref is None or ref() is fn:
                    return cost
                del self._costs[key]      # id reused by a new program
        t0 = time.perf_counter()
        try:
            cost = program_cost(fn, args)
        except Exception:
            logging.getLogger("veles.perf").warning(
                "cost analysis of program %r failed; its "
                "veles_step_flops/bytes/mfu series stay at zero",
                key, exc_info=True)
            cost = StepCost()
        if telemetry.tracer.active:
            telemetry.tracer.add_complete(
                "perf.analyze", t0, time.perf_counter() - t0,
                flops=cost.flops)
        try:
            ref = weakref.ref(fn)
        except TypeError:
            ref = None                    # plain-callable fallback
        with self._lock:
            # opportunistic sweep: entries whose program died free up
            # with the next analysis instead of growing unboundedly
            dead = [k for k, (r, _) in self._costs.items()
                    if r is not None and r() is None]
            for k in dead:
                del self._costs[k]
            self._costs[key] = (ref, cost)
        return cost

    def sizes(self):
        """Memory-accounting view (``veles/profiling.py`` exports it
        as ``veles_perf_ledger_*`` gauges): live cached programs and
        their summed per-call I/O footprint estimate — a size proxy
        for what the compiled-program cache pins, not an HBM meter."""
        with self._lock:
            entries = list(self._costs.values())
        programs, est = 0, 0.0
        for ref, cost in entries:
            if ref is not None and ref() is None:
                continue                 # program died; sweep pending
            programs += 1
            est += cost.io_bytes
        return {"programs": programs, "est_bytes": est}

    def _children(self, kind):
        with self._lock:
            kids = self._kids.get(kind)
            if kids is None:
                kids = self._kids[kind] = {
                    "flops": telemetry.LazyChild(
                        lambda k=kind: telemetry.counter(
                            "veles_step_flops_total",
                            "Arithmetic performed by compiled step "
                            "programs (jaxpr-derived)",
                            ("kind",)).labels(k)),
                    "bytes": telemetry.LazyChild(
                        lambda k=kind: telemetry.counter(
                            "veles_step_bytes_total",
                            "Equation-output bytes of compiled step "
                            "programs (memory-traffic proxy)",
                            ("kind",)).labels(k)),
                    "fps": telemetry.LazyChild(
                        lambda k=kind: telemetry.gauge(
                            "veles_step_flops_per_second",
                            "Achieved FLOP/s of the latest dispatch",
                            ("kind",)).labels(k)),
                    "mfu": telemetry.LazyChild(
                        lambda k=kind: telemetry.gauge(
                            "veles_step_mfu_ratio",
                            "Achieved FLOP/s over the device peak "
                            "(VELES_PEAK_FLOPS overrides the table)",
                            ("kind",)).labels(k)),
                    "sps": telemetry.LazyChild(
                        lambda k=kind: telemetry.gauge(
                            "veles_step_samples_per_second",
                            "Samples consumed per second by the "
                            "latest dispatch", ("kind",)).labels(k)),
                    "tps": telemetry.LazyChild(
                        lambda k=kind: telemetry.gauge(
                            "veles_step_tokens_per_second",
                            "Tokens consumed per second by the "
                            "latest dispatch (LM loaders)",
                            ("kind",)).labels(k)),
                }
        return kids

    def record_dispatch(self, kind, cost, seconds, samples=None,
                        tokens=None):
        """Account one completed dispatch of a program costing
        ``cost`` per call that took ``seconds`` wall time and
        consumed ``samples``/``tokens`` of data."""
        kids = self._children(kind)
        if cost is not None and cost.flops:
            kids["flops"].get().inc(cost.flops)
            if seconds > 0:
                fps = cost.flops / seconds
                kids["fps"].get().set(fps)
                peak = device_peak_flops(
                    getattr(cost, "precision", None) or "bf16")
                if peak:
                    kids["mfu"].get().set(fps / peak)
        if cost is not None and cost.bytes:
            kids["bytes"].get().inc(cost.bytes)
        if seconds > 0:
            if samples:
                kids["sps"].get().set(samples / seconds)
            if tokens:
                kids["tps"].get().set(tokens / seconds)


#: process-wide ledger (mirrors the telemetry registry's stance: one
#: spine, views on top)
ledger = PerfLedger()
