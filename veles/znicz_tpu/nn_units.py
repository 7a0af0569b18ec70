"""NN base unit families + forward↔gradient registry.

Re-design of znicz ``nn_units.py`` [U] (SURVEY.md §2.4 "NN base units"):

* :class:`Forward` — base for forward-propagation units: owns
  ``weights``/``bias`` with configurable fillings, ``weights_transposed``
  and ``include_bias`` knobs.
* :class:`GradientDescentBase` — base for explicit backward units:
  learning rate (+ bias multiplier), L1/L2 ``weights_decay``,
  ``gradient_moment`` momentum, gradient accumulation; emits
  ``err_input`` for the preceding GD unit.
* The **MatchingObject registry**: forwards register a config name
  (``"all2all_tanh"``) and each gradient unit registers which forward it
  backpropagates, so StandardWorkflow can auto-wire the reversed GD
  chain (SURVEY.md §2.4 intro).
* :class:`NNWorkflow` — AcceleratedWorkflow with the canonical slots
  (loader / forwards / evaluator / decision / gds) of the reference.

DP note (SURVEY.md §2.2): per-unit ``generate_data_for_slave`` /
``apply_data_from_slave`` weight-averaging hooks live on
GradientDescentBase, preserving the reference's master↔slave contract
for the compat layer; the hot path is sharded-batch ``psum`` inside the
jitted step instead.
"""

import functools

import numpy

from veles import prng
from veles.accelerated_units import AcceleratedUnit, AcceleratedWorkflow
from veles.distributable import IDistributable
from veles.memory import Array
from veles.workflow import Workflow

# ---------------------------------------------------------------------------
# MatchingObject registry (reference: metaclass MatchingObject [U])

_FORWARD_BY_NAME = {}
_GRADIENT_FOR = {}


def forward_unit(name):
    """Class decorator: register a Forward unit under a config name."""
    def deco(cls):
        cls.MAPPING = name
        _FORWARD_BY_NAME[name] = cls
        return cls
    return deco


def gradient_for(forward_cls):
    """Class decorator: register a GD unit as the backward pair of a
    Forward class."""
    def deco(cls):
        cls.FORWARD = forward_cls
        _GRADIENT_FOR[forward_cls] = cls
        return cls
    return deco


def forward_by_name(name):
    try:
        return _FORWARD_BY_NAME[name]
    except KeyError:
        raise KeyError("unknown layer type %r (known: %s)"
                       % (name, ", ".join(sorted(_FORWARD_BY_NAME))))


def gradient_unit_for(forward_cls):
    for cls in forward_cls.__mro__:
        if cls in _GRADIENT_FOR:
            return _GRADIENT_FOR[cls]
    raise KeyError("no gradient unit registered for %s"
                   % forward_cls.__name__)


def known_layer_types():
    return sorted(_FORWARD_BY_NAME)


# ---------------------------------------------------------------------------


class Forward(AcceleratedUnit):
    """Base forward unit: input → output with optional weights/bias."""

    MAPPING = None
    PARAMS = ("weights", "bias")
    #: hint for StandardWorkflow: unit consumes loss gradient chain
    trainable = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = None            # linked from producer
        self.output = Array()
        self.weights = Array()
        self.bias = Array()
        self.include_bias = kwargs.get("include_bias", True)
        self.weights_transposed = kwargs.get("weights_transposed", False)
        self.weights_filling = kwargs.get("weights_filling", "uniform")
        self.weights_stddev = kwargs.get("weights_stddev", None)
        self.bias_filling = kwargs.get("bias_filling", "constant")
        self.bias_stddev = kwargs.get("bias_stddev", 0.0)
        self.prng = prng.get(kwargs.get("prng_key", "default"))
        #: dtype of the unit's output in a compiled step (and, in the
        #: units of a residual stream, of its err_input): None is the
        #: device's activation type; "float32" keeps a residual stream
        #: or logits in float32 (``stream_dtype``)
        self.output_dtype = kwargs.get("output_dtype")

    # weight materialisation ------------------------------------------

    def fill_array(self, arr, filling, stddev):
        if filling == "uniform":
            bound = stddev * numpy.sqrt(3.0)
            self.prng.fill_uniform(arr.mem, -bound, bound)
        elif filling == "gaussian":
            self.prng.fill_normal(arr.mem, 0.0, stddev)
        elif filling == "constant":
            arr.mem[...] = stddev
        else:
            raise ValueError("unknown filling %r" % filling)

    def default_weights_stddev(self, fan_in, fan_out):
        # Glorot scale: keeps activations in range across depths.
        return float(numpy.sqrt(2.0 / (fan_in + fan_out)))

    def init_weights(self, w_shape, fan_in, fan_out):
        stddev = self.weights_stddev or \
            self.default_weights_stddev(fan_in, fan_out)
        if not self.weights or self.weights.shape != tuple(w_shape):
            self.weights.reset(numpy.zeros(w_shape, numpy.float32))
            self.fill_array(self.weights, self.weights_filling, stddev)
        if self.include_bias and (
                not self.bias or self.bias.shape != (fan_out,)):
            self.bias.reset(numpy.zeros(fan_out, numpy.float32))
            if self.bias_filling != "constant" or self.bias_stddev:
                self.fill_array(self.bias, self.bias_filling,
                                self.bias_stddev or 0.01)

    def stream_dtype(self, ctx):
        """The dtype of this unit's output in the step ``ctx``
        compiles: ``output_dtype``, else the activation type."""
        return numpy.dtype(self.output_dtype) if self.output_dtype \
            else ctx.act_dtype

    @property
    def batch_size(self):
        return self.input.shape[0]

    def host_train_phase(self):
        """Whether the CURRENT minibatch is a training one, for the
        numpy oracle path (the compiled path reads ``ctx.train``).
        Units with train/eval behaviour splits (dropout, stochastic
        pooling) share this so phase detection has one definition."""
        loader = getattr(self.workflow, "loader", None)
        return bool(loader is None or loader.train_phase)

    def output_shape_for(self, input_shape):
        """Static shape inference; subclasses override."""
        raise NotImplementedError


def _update_scope(method):
    """Trace a solver method under the ``veles.update`` sub-scope, so
    that a device trace tells a GD unit's weight update from its
    gradient math."""
    @functools.wraps(method)
    def scoped(self, ctx, *args):
        import jax
        with jax.named_scope("veles.update"):
            return method(self, ctx, *args)
    return scoped


class GradientDescentBase(AcceleratedUnit, IDistributable):
    """Base backward unit: err_output → err_input + parameter update.

    Update rule (reference semantics [U], SURVEY.md §2.4 "FC backward"):
    ``grad += l2 * (1-l1_vs_l2) * W + l1 * l1_vs_l2 * sign(W)``;
    ``vel = moment * vel - lr * grad``; ``W += vel``. Separate lr /
    decay / moment multipliers for bias.
    """

    FORWARD = None
    scope_role = "bwd"
    STATE = ("vel_weights", "vel_bias", "acc_weights", "acc_bias",
             "sq_weights", "sq_bias", "acc_count", "iteration")
    #: (param_name, bias_like) for forward parameters BEYOND
    #: weights/bias (attention out-projection, FFN second layer, MoE
    #: router...). Velocity/accumulator Arrays ``vel_<p>``/``acc_<p>``
    #: are created automatically and appended to STATE by
    #: ``__init_subclass__``. ``bias_like`` selects the bias
    #: hyperparameter set (lr_bias, moment_bias, decay_bias) —
    #: matching the repo-wide convention that biases are not decayed
    #: by default.
    EXTRA_PARAMS = ()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        derived = [n for p, _ in cls.__dict__.get("EXTRA_PARAMS", ())
                   for n in ("vel_" + p, "acc_" + p, "sq_" + p)]
        if derived:
            cls.STATE = tuple(cls.STATE) + tuple(
                n for n in derived if n not in cls.STATE)

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        for pname, _ in self.EXTRA_PARAMS:
            setattr(self, "vel_" + pname, Array())
            setattr(self, "acc_" + pname, Array())
            setattr(self, "sq_" + pname, Array())
        self.err_output = None       # linked from the unit after us
        self.err_input = Array()     # produced for the unit before us
        self.forward = None          # paired Forward unit
        self.need_err_input = kwargs.get("need_err_input", True)
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get(
            "learning_rate_bias", self.learning_rate)
        self.weights_decay = kwargs.get("weights_decay", 0.0)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        self.l1_vs_l2 = kwargs.get("l1_vs_l2", 0.0)
        self.l1_vs_l2_bias = kwargs.get("l1_vs_l2_bias", self.l1_vs_l2)
        self.gradient_moment = kwargs.get("gradient_moment", 0.0)
        self.gradient_moment_bias = kwargs.get(
            "gradient_moment_bias", self.gradient_moment)
        #: update rule: "momentum" (reference semantics, the default)
        #: or "adam" (AdamW: decoupled weight decay, bias-corrected
        #: moments; beta1 = gradient_moment — set it to ~0.9 — and
        #: the L1 mix is momentum-only). ``vel_*`` holds the first
        #: moment, ``sq_*`` the second.
        self.solver = kwargs.get("solver", "momentum")
        if self.solver not in ("momentum", "adam"):
            raise ValueError("solver must be 'momentum' or 'adam', "
                             "got %r" % (self.solver,))
        self.adam_beta2 = float(kwargs.get("adam_beta2", 0.999))
        self.adam_eps = float(kwargs.get("adam_eps", 1e-8))
        #: host-adjustable multiplier applied AFTER the lr policy
        #: (NNRollback's lr cut uses this: policies like
        #: ArbitraryStepPolicy replace the base lr, so cutting
        #: ``learning_rate`` alone would be a silent no-op)
        self.lr_scale = 1.0
        #: accumulate gradients over N steps before applying
        self.accumulate_gradient = int(kwargs.get("accumulate_gradient", 1))
        #: hand-fused Pallas bias-grad escape hatch
        #: (ops/pallas_grads.py), the convert_reduce fix
        #: (docs/repro_convert_reduce.py). None = auto: the kernel
        #: takes over on a real TPU once $VELES_FUSED_BIAS_GRAD=1 —
        #: opt-in until a paired chip run times it inside a step
        #: (ROADMAP D4); True/False force either path
        self.fused_bias_grad = kwargs.get("fused_bias_grad")
        # lr schedules (SURVEY.md §2.4 "LR scheduling"): pure policies
        # evaluated inside the compiled step on the traced iteration
        # counter — see veles/znicz_tpu/lr_adjust.py
        from veles.znicz_tpu.lr_adjust import make_policy
        self.lr_policy = make_policy(kwargs.get("lr_policy"))
        self.lr_policy_bias = make_policy(
            kwargs.get("lr_policy_bias", kwargs.get("lr_policy")))
        self.vel_weights = Array()
        self.vel_bias = Array()
        self.acc_weights = Array()
        self.acc_bias = Array()
        self.sq_weights = Array()
        self.sq_bias = Array()
        self.acc_count = Array()
        #: train-minibatch counter driving the lr schedule (traced STATE
        #: so chunked epoch scans advance it on device)
        self.iteration = Array()

    # pairing ----------------------------------------------------------

    def setup_forward(self, forward):
        """Bind to the paired forward unit (weights/input/output access)."""
        self.forward = forward
        return self

    @property
    def include_bias(self):
        return self.forward.include_bias

    @property
    def weights_transposed(self):
        return self.forward.weights_transposed

    def initialize(self, **kwargs):
        super().initialize(**kwargs)
        if self.forward is None:
            raise ValueError("%s: setup_forward() not called" % self.name)
        f = self.forward
        if f.weights and (not self.vel_weights
                          or self.vel_weights.shape != f.weights.shape):
            self.vel_weights.reset(
                numpy.zeros_like(f.weights.mem))
        if f.include_bias and f.bias and (
                not self.vel_bias
                or self.vel_bias.shape != f.bias.shape):
            self.vel_bias.reset(numpy.zeros_like(f.bias.mem))
        if self.need_err_input and f.input is not None \
                and getattr(f.input, "shape", None):
            if not self.err_input \
                    or self.err_input.shape != f.input.shape:
                self.err_input.reset(
                    numpy.zeros(f.input.shape, numpy.float32))
        if self.accumulate_gradient > 1:
            if f.weights and not self.acc_weights:
                self.acc_weights.reset(numpy.zeros_like(f.weights.mem))
            if f.include_bias and f.bias and not self.acc_bias:
                self.acc_bias.reset(numpy.zeros_like(f.bias.mem))
            if not self.acc_count:
                self.acc_count.reset(numpy.zeros((), numpy.int32))
        if not self.iteration:
            self.iteration.reset(numpy.zeros((), numpy.int32))
        if self.solver == "adam":
            if f.weights and (not self.sq_weights
                              or self.sq_weights.shape
                              != f.weights.shape):
                self.sq_weights.reset(numpy.zeros_like(f.weights.mem))
            if f.include_bias and f.bias and (
                    not self.sq_bias
                    or self.sq_bias.shape != f.bias.shape):
                self.sq_bias.reset(numpy.zeros_like(f.bias.mem))
        for pname, _ in self.EXTRA_PARAMS:
            src = getattr(f, pname, None)
            if src is None or not src:
                continue
            vel = getattr(self, "vel_" + pname)
            if not vel or vel.shape != src.shape:
                vel.reset(numpy.zeros_like(src.mem))
            if self.accumulate_gradient > 1:
                acc = getattr(self, "acc_" + pname)
                if not acc or acc.shape != src.shape:
                    acc.reset(numpy.zeros_like(src.mem))
            if self.solver == "adam":
                sq = getattr(self, "sq_" + pname)
                if not sq or sq.shape != src.shape:
                    sq.reset(numpy.zeros_like(src.mem))

    # hyper-parameters: traced scalars, so changing them never retraces.
    # Built on the host at every dispatch; XLAStep._device_hyper keeps
    # them on the device and uploads again only when one changed ------

    def hyperparams(self):
        out = {
            "lr": numpy.float32(self.learning_rate),
            "lr_bias": numpy.float32(self.learning_rate_bias),
            "l2": numpy.float32(self.weights_decay),
            "l2_bias": numpy.float32(self.weights_decay_bias),
            "l1_vs_l2": numpy.float32(self.l1_vs_l2),
            "l1_vs_l2_bias": numpy.float32(self.l1_vs_l2_bias),
            "moment": numpy.float32(self.gradient_moment),
            "moment_bias": numpy.float32(self.gradient_moment_bias),
            "lr_scale": numpy.float32(self.lr_scale),
            "beta2": numpy.float32(self.adam_beta2),
            "adam_eps": numpy.float32(self.adam_eps),
        }
        # ZeroFiller mask rides along as a traced input (not a baked
        # constant) so host-side mask edits reach the compiled step
        mask = getattr(self.forward, "zero_mask", None)
        if mask is not None and mask:
            out["zero_mask"] = numpy.asarray(
                mask.map_read().mem, numpy.float32)
        return out

    # shared update math (xp = numpy or jax.numpy) ---------------------

    @staticmethod
    def apply_update(xp, w, vel, grad, lr, moment, l2, l1_vs_l2):
        reg = grad + w * (l2 * (1.0 - l1_vs_l2)) \
            + xp.sign(w) * (l2 * l1_vs_l2)
        vel = vel * moment - lr * reg
        return w + vel, vel

    def apply_update_adam(self, xp, w, m, v, grad, lr, beta1, beta2,
                          eps, l2, step):
        """AdamW: bias-corrected moments + DECOUPLED weight decay
        (``l2`` multiplies ``lr·w`` directly, not the gradient).
        ``step`` counts applied updates from 1."""
        m = beta1 * m + (1.0 - beta1) * grad
        v = beta2 * v + (1.0 - beta2) * grad * grad
        mhat = m / (1.0 - beta1 ** step)
        vhat = v / (1.0 - beta2 ** step)
        w = w - lr * (mhat / (xp.sqrt(vhat) + eps) + l2 * w)
        return w, m, v

    def _step_param(self, xp, w, vel, acc, grad, apply_now,
                    lr, moment, l2, l1_vs_l2, sq=None, t=0,
                    beta2=0.999, adam_eps=1e-8):
        """One (possibly accumulated) parameter step under the
        configured solver. With gradient accumulation, the update
        applies only when ``apply_now`` and the accumulator resets;
        otherwise the gradient just adds up. ``t`` is the pre-advance
        iteration counter (adam bias correction counts APPLIED steps).
        Returns (w, vel, acc, sq)."""
        adam = self.solver == "adam"
        g = grad if acc is None else acc + grad
        if adam:
            # applied-step count from 1 (iterations / accumulation)
            step = (t + 1) / max(1, self.accumulate_gradient)
            nw, nv, nsq = self.apply_update_adam(
                xp, w, vel, sq, g, lr, moment, beta2, adam_eps, l2,
                step)
        else:
            nw, nv = self.apply_update(xp, w, vel, g, lr, moment,
                                       l2, l1_vs_l2)
            nsq = sq
        if acc is None:
            return nw, nv, None, nsq
        w = xp.where(apply_now, nw, w)
        vel = xp.where(apply_now, nv, vel)
        # store the GROWN accumulator (g), zeroed once applied
        acc = xp.where(apply_now, xp.zeros_like(g), g)
        if adam:
            nsq = xp.where(apply_now, nsq, sq)
        return w, vel, acc, nsq

    @staticmethod
    def _scheduled_lr(xp, policy, base_lr, t):
        return base_lr if policy is None else policy(xp, base_lr, t)

    # numpy oracle update ---------------------------------------------

    def update_weights_numpy(self, grad_w, grad_b):
        f = self.forward
        t = int(self.iteration.map_read().mem) if self.iteration else 0
        lr_w = self._scheduled_lr(numpy, self.lr_policy,
                                  self.learning_rate, t) * self.lr_scale
        lr_b = self._scheduled_lr(numpy, self.lr_policy_bias,
                                  self.learning_rate_bias, t) \
            * self.lr_scale
        accumulating = self.accumulate_gradient > 1
        apply_now = True
        acc_w = acc_b = None
        if accumulating:
            self.acc_count.map_write()
            count = int(self.acc_count.mem) + 1
            apply_now = count >= self.accumulate_gradient
            self.acc_count.mem[...] = 0 if apply_now else count
            acc_w = self.acc_weights.map_write().mem
        adam = self.solver == "adam"
        sq_w = self.sq_weights.map_write().mem if adam else None
        f.weights.map_write()
        self.vel_weights.map_write()
        w, vel, acc, sq = self._step_param(
            numpy, f.weights.mem, self.vel_weights.mem, acc_w, grad_w,
            apply_now, lr_w, self.gradient_moment,
            self.weights_decay, self.l1_vs_l2, sq=sq_w, t=t,
            beta2=self.adam_beta2, adam_eps=self.adam_eps)
        f.weights.mem[...] = w
        self.vel_weights.mem[...] = vel
        if acc is not None:
            self.acc_weights.mem[...] = acc
        if sq is not None:
            self.sq_weights.mem[...] = sq
        if f.include_bias and grad_b is not None:
            if accumulating:
                acc_b = self.acc_bias.map_write().mem
            sq_b = self.sq_bias.map_write().mem if adam else None
            f.bias.map_write()
            self.vel_bias.map_write()
            b, velb, accb, sqb = self._step_param(
                numpy, f.bias.mem, self.vel_bias.mem, acc_b, grad_b,
                apply_now, lr_b,
                self.gradient_moment_bias, self.weights_decay_bias,
                self.l1_vs_l2_bias, sq=sq_b, t=t,
                beta2=self.adam_beta2, adam_eps=self.adam_eps)
            f.bias.mem[...] = b
            self.vel_bias.mem[...] = velb
            if accb is not None:
                self.acc_bias.mem[...] = accb
            if sqb is not None:
                self.sq_bias.mem[...] = sqb
        if self.iteration:
            self.iteration.map_write()
            self.iteration.mem[...] = t + 1

    # traced update ----------------------------------------------------

    def bias_grad_xla(self, ctx, err2d, y2d):
        """The f32 bias gradient ``Σ_rows err∘act'(y)`` through the
        hand-fused Pallas kernel (``ops/pallas_grads.py``), or None
        when the ``fused_bias_grad`` policy keeps the plain XLA
        reduction — call sites fall back to their own masked-reduce
        form then, so the escape hatch costs nothing when off."""
        from veles.backends import is_tpu
        on_tpu = is_tpu(ctx._compiler.device.platform)
        if self.fused_bias_grad is None:
            import os
            fused = (os.environ.get("VELES_FUSED_BIAS_GRAD") == "1"
                     and on_tpu)
        else:
            fused = bool(self.fused_bias_grad)
        if not fused:
            return None
        from veles.znicz_tpu.ops import pallas_grads as PG
        # interpret from the platform the step compiles for (a forced
        # fused_bias_grad=True in the CPU tests runs the interpreter)
        return PG.bias_grad(err2d, y2d, self.ACTIVATION,
                            interpret=not on_tpu)

    def export_layer_stats(self, ctx, t, grad_w, grad_b, old_w, new_w,
                           old_b, new_b):
        """One fused per-layer stat vector for the model-health plane
        (``veles/model_health.py``): gradient/weight/update L2 norms +
        a non-finite count, computed INSIDE the trace in f32 and
        exported under ``STAT_KEY_PREFIX + unit name`` — one fused
        extra output, no second dispatch.

        The cadence lives in the graph: a ``lax.cond`` on the
        iteration counter computes the reduces only every
        ``ctx.stats_stride``-th train step and emits a ``-1`` sentinel
        row otherwise, so the steady-state cost is the full reduction
        pass divided by the stride (measured 24% per-step on the CPU
        MNIST loop — the ``new_w - old_w`` delta keeps the pre-update
        params alive, defeating the in-place update fusion — vs <2%
        amortized). The host side (``XLAStep._publish_model_stats``)
        materializes the tiny vectors and skips sentinels."""
        import jax
        import jax.numpy as jnp
        from veles import model_health

        def compute():
            def ssq(v):
                return jnp.sum(jnp.square(v.astype(jnp.float32)))

            def bad(v):
                return jnp.sum(~jnp.isfinite(v)).astype(jnp.float32)

            g2 = ssq(grad_w)
            w2 = ssq(new_w)
            u2 = ssq(new_w.astype(jnp.float32)
                     - old_w.astype(jnp.float32))
            nf = bad(grad_w)
            if grad_b is not None and new_b is not None:
                g2_b = ssq(grad_b)
                w2_b = ssq(new_b)
                u2_b = ssq(new_b.astype(jnp.float32)
                           - old_b.astype(jnp.float32))
                nf_b = bad(grad_b)
            else:
                g2_b = w2_b = u2_b = nf_b = jnp.float32(0.0)
            gnorm = jnp.sqrt(g2 + g2_b)
            wnorm = jnp.sqrt(w2 + w2_b)
            ratio = jnp.sqrt(u2 + u2_b) / (wnorm + 1e-12)
            return jnp.stack([gnorm, wnorm, ratio, nf + nf_b])

        # FlowContext already coerced the stride to a python int (a
        # host-side compile-time constant, not a traced value)
        stride = getattr(ctx, "stats_stride", 1) or 1
        if stride > 1:
            vec = jax.lax.cond(
                t % stride == 0, compute,
                lambda: jnp.full((4,), -1.0, jnp.float32))
        else:
            vec = compute()
        ctx.export(model_health.STAT_KEY_PREFIX + self.name, vec)

    def apply_grads(self, ctx, grads):
        """The solver on ``grads`` = {parameter name: gradient}:
        ``weights`` (and ``bias``), then the ``EXTRA_PARAMS``."""
        self.update_weights_xla(ctx, grads["weights"], grads.get("bias"))
        extra = {n: grads[n] for n, _ in self.EXTRA_PARAMS if n in grads}
        if extra:
            self.update_extra_xla(ctx, extra)

    def update_weights_xla(self, ctx, grad_w, grad_b):
        """The solver on ``weights`` and ``bias``; in a visit of a loop
        the gradients go to the loop's sums instead
        (``FlowContext.defer``) and the solver runs once, later."""
        if not ctx.defer(self, weights=grad_w, bias=grad_b):
            self._update_weights(ctx, grad_w, grad_b)

    @_update_scope
    def _update_weights(self, ctx, grad_w, grad_b):
        import jax.numpy as jnp
        f = self.forward
        h = ctx.hyper[self.name]
        params = ctx.unit_params(f)
        state = ctx.unit_state(self)
        t = state["iteration"]
        lr_w = self._scheduled_lr(jnp, self.lr_policy, h["lr"], t) \
            * h["lr_scale"]
        lr_b = self._scheduled_lr(jnp, self.lr_policy_bias,
                                  h["lr_bias"], t) * h["lr_scale"]
        ctx.update_state(self, iteration=(t + 1).astype(jnp.int32))
        accumulating = self.accumulate_gradient > 1
        apply_now = True
        acc_w = acc_b = None
        if accumulating:
            count = state["acc_count"] + 1
            apply_now = count >= self.accumulate_gradient
            ctx.update_state(
                self, acc_count=jnp.where(apply_now, 0, count)
                .astype(jnp.int32))
            acc_w = state["acc_weights"]
        w, vel = params["weights"], state["vel_weights"]
        w0 = w                       # pre-update view for layer stats
        sq_w = state.get("sq_weights") if self.solver == "adam" \
            else None
        grad_w = ctx.pmean(grad_w)
        w, vel, acc, sq = self._step_param(
            jnp, w, vel, acc_w, grad_w.astype(w.dtype), apply_now,
            lr_w, h["moment"], h["l2"], h["l1_vs_l2"], sq=sq_w, t=t,
            beta2=h["beta2"], adam_eps=h["adam_eps"])
        # ZeroFiller mask (traced via hyperparams): pin masked entries
        # at zero INSIDE the trace — host-side mutation never reaches
        # device-resident params
        if "zero_mask" in h:
            w = w * h["zero_mask"].astype(w.dtype)
        ctx.update_params(f, weights=w)
        ctx.update_state(self, vel_weights=vel)
        if acc is not None:
            ctx.update_state(self, acc_weights=acc)
        if sq is not None:
            ctx.update_state(self, sq_weights=sq)
        b0 = b = None
        if f.include_bias and grad_b is not None:
            if accumulating:
                acc_b = state["acc_bias"]
            b, velb = params["bias"], state["vel_bias"]
            b0 = b                   # pre-update view for layer stats
            sq_b = state.get("sq_bias") if self.solver == "adam" \
                else None
            grad_b = ctx.pmean(grad_b)
            b, velb, accb, sqb = self._step_param(
                jnp, b, velb, acc_b, grad_b.astype(b.dtype), apply_now,
                lr_b, h["moment_bias"], h["l2_bias"],
                h["l1_vs_l2_bias"], sq=sq_b, t=t,
                beta2=h["beta2"], adam_eps=h["adam_eps"])
            ctx.update_params(f, bias=b)
            ctx.update_state(self, vel_bias=velb)
            if accb is not None:
                ctx.update_state(self, acc_bias=accb)
            if sqb is not None:
                ctx.update_state(self, sq_bias=sqb)
        if ctx.collect_stats:
            self.export_layer_stats(
                ctx, t, grad_w, grad_b if b is not None else None,
                w0, w, b0, b)

    # extra-parameter updates (EXTRA_PARAMS declarations) --------------

    def _hyper_set(self, bias_like):
        """(policy, moment, l2, l1_vs_l2) attribute picks for the
        weight vs bias hyperparameter families."""
        if bias_like:
            return (self.lr_policy_bias, self.gradient_moment_bias,
                    self.weights_decay_bias, self.l1_vs_l2_bias)
        return (self.lr_policy, self.gradient_moment,
                self.weights_decay, self.l1_vs_l2)

    def update_extra_numpy(self, grads):
        """Apply EXTRA_PARAMS updates with the same semantics as
        ``update_weights_numpy`` — which MUST have run first this step
        (it advances the iteration/accumulation counters; extras apply
        in lockstep: ``acc_count == 0`` after the main update iff this
        step applied). ``grads``: {param_name: grad or None}."""
        f = self.forward
        t = int(self.iteration.map_read().mem) - 1
        accumulating = self.accumulate_gradient > 1
        apply_now = (not accumulating
                     or int(self.acc_count.map_read().mem) == 0)
        for pname, bias_like in self.EXTRA_PARAMS:
            grad = grads.get(pname)
            if grad is None:
                continue
            policy, moment, l2, l1r = self._hyper_set(bias_like)
            lr = self._scheduled_lr(
                numpy, policy,
                self.learning_rate_bias if bias_like
                else self.learning_rate, t) * self.lr_scale
            arr = getattr(f, pname)
            vel = getattr(self, "vel_" + pname)
            acc = getattr(self, "acc_" + pname) if accumulating \
                else None
            sq = getattr(self, "sq_" + pname) \
                if self.solver == "adam" else None
            arr.map_write()
            vel.map_write()
            acc_mem = acc.map_write().mem if acc is not None else None
            sq_mem = sq.map_write().mem if sq is not None else None
            w, v, a, q = self._step_param(
                numpy, arr.mem, vel.mem, acc_mem, grad, apply_now,
                lr, moment, l2, l1r, sq=sq_mem, t=t,
                beta2=self.adam_beta2, adam_eps=self.adam_eps)
            arr.mem[...] = w
            vel.mem[...] = v
            if a is not None:
                acc.mem[...] = a
            if q is not None:
                sq.mem[...] = q

    def update_extra_xla(self, ctx, grads):
        """Traced twin of :meth:`update_extra_numpy`; call after
        ``update_weights_xla`` in the same ``xla_run`` (deferred with
        it in a visit of a loop)."""
        if not ctx.defer(self, **grads):
            self._update_extra(ctx, grads)

    @_update_scope
    def _update_extra(self, ctx, grads):
        import jax.numpy as jnp
        f = self.forward
        h = ctx.hyper[self.name]
        st = ctx.unit_state(self)
        t = st["iteration"] - 1   # main update advanced it
        accumulating = self.accumulate_gradient > 1
        apply_now = True if not accumulating else st["acc_count"] == 0
        for pname, bias_like in self.EXTRA_PARAMS:
            grad = grads.get(pname)
            if grad is None:
                continue
            policy, _, _, _ = self._hyper_set(bias_like)
            suffix = "_bias" if bias_like else ""
            lr = self._scheduled_lr(
                jnp, policy, h["lr_bias" if bias_like else "lr"],
                t) * h["lr_scale"]
            moment = h["moment" + suffix]
            l2 = h["l2" + suffix]
            l1r = h["l1_vs_l2" + suffix]
            w = ctx.unit_params(f)[pname]
            vel = st["vel_" + pname]
            acc = st.get("acc_" + pname) if accumulating else None
            sq = st.get("sq_" + pname) if self.solver == "adam" \
                else None
            w, vel, acc, sq = self._step_param(
                jnp, w, vel, acc, ctx.pmean(grad).astype(w.dtype),
                apply_now, lr, moment, l2, l1r, sq=sq, t=t,
                beta2=h["beta2"], adam_eps=h["adam_eps"])
            ctx.update_params(f, **{pname: w})
            ctx.update_state(self, **{"vel_" + pname: vel})
            if acc is not None:
                ctx.update_state(self, **{"acc_" + pname: acc})
            if sq is not None:
                ctx.update_state(self, **{"sq_" + pname: sq})

    # IDistributable compat layer (SURVEY.md §2.2) ---------------------

    def _wire_params(self):
        """(name, Array) pairs the master↔slave link carries: EVERY
        parameter the forward declares (attention/FFN units have more
        than weights/bias)."""
        f = self.forward
        out = []
        for name in getattr(f, "PARAMS", ("weights", "bias")):
            arr = getattr(f, name, None)
            if arr is not None and arr:
                out.append((name, arr))
        return out

    def _param_values(self):
        """Raw {name: float32 ndarray} of every wire parameter — the
        pre-codec view both payload directions encode from."""
        return {name: numpy.array(arr.map_read().mem)
                for name, arr in self._wire_params()}

    def _codec_for(self, slave=None):
        """The gradient wire codec (``veles/compression.py``) for one
        payload: on the master, the per-slave encoder minted at hello
        (``workflow.grad_codec_by_slave``, keyed by ``slave``); on the
        slave, the single negotiated encoder (``workflow.grad_codec``,
        set by SlaveClient.connect). ``None`` — in-process registries,
        codec "none", pre-codec setups — means passthrough."""
        wf = self.workflow
        if slave is not None:
            table = getattr(wf, "grad_codec_by_slave", None)
            if table is not None:
                return table.get(slave)
        return getattr(wf, "grad_codec", None)

    def generate_data_for_slave(self, slave=None):
        values = self._param_values()
        codec = self._codec_for(slave)
        if codec is None:
            return values
        # dense weight broadcast: encoded stateless (the canonical
        # fp32 weights live here, so broadcast error is fresh per job)
        return {name: codec.encode_broadcast(
            "%s/%s" % (self.name, name), value)
            for name, value in values.items()}

    def apply_data_from_master(self, data):
        if not data:
            return
        from veles import compression
        decoded = {k: compression.decode(v) for k, v in data.items()}
        for name, arr in self._wire_params():
            if name not in decoded:
                # fail loudly: silently skipping a declared parameter
                # would let it diverge across slaves with no error
                raise KeyError(
                    "%s: master payload missing %r (version skew?)"
                    % (self.name, name))
            arr.map_write()
            arr.mem[...] = decoded[name]
        # remember the basis the master handed us — the DECODED view,
        # exactly what the local weights now hold: updates ship as
        # DELTAS against it (the master can apply each slave's
        # training verbatim — a single-slave run reproduces standalone
        # training exactly, and concurrent slaves' contributions ADD
        # instead of each dragging the canonical weights halfway to
        # its own copy)
        self._master_basis = {
            k: numpy.array(v) for k, v in decoded.items()}

    def generate_data_for_master(self):
        basis = getattr(self, "_master_basis", None)
        if basis is None:
            return self._param_values()
        current = self._param_values()
        # apply_data_from_master guarantees the basis covers every
        # wire param, so a KeyError here is a real protocol bug
        deltas = {k: current[k] - basis[k] for k in current}
        codec = self._codec_for(None)
        if codec is None:
            return {"d" + k: v for k, v in deltas.items()}
        # the quantized/sparsified direction: deltas tolerate lossy
        # encoding because the codec's error-feedback residual folds
        # this sync's quantization error into the next delta
        return {"d" + k: codec.encode_update(
            "%s/%s" % (self.name, k), v)
            for k, v in deltas.items()}

    def apply_data_from_slave(self, data, slave=None):
        """Merge one slave's training into the canonical weights.

        Delta payloads (``dweights``/``dbias``/...) apply additively
        scaled by ``slave_merge_scale`` (default 1.0). Absolute
        payloads fall back to the reference's halfway parameter
        averaging [U]. Encoded entries are self-describing
        (``compression.decode``), so no per-slave codec state is
        consulted here."""
        if not data:
            return
        from veles import compression, model_health
        scale = float(getattr(self, "slave_merge_scale", 1.0))
        nonfinite = 0
        for key, arr in self._wire_params():
            if "d" + key in data:
                delta = compression.decode(data["d" + key])
                nonfinite += int((~numpy.isfinite(delta)).sum())
                arr.map_write()
                arr.mem[...] += scale * delta
            elif key in data:
                value = compression.decode(data[key])
                nonfinite += int((~numpy.isfinite(value)).sum())
                arr.map_write()
                arr.mem[...] = 0.5 * (arr.mem + value)
        # model-health plane: a NaN/inf inside a decoded delta is the
        # wire-side divergence signal — counted per layer (attributed
        # to the pushing slave) BEFORE it can burn an epoch; a clean
        # merge reports 0 so the step gauge recovers after a spike
        model_health.get_model_monitor().note_wire_nonfinite(
            self.name, nonfinite, slave=slave)


class NNWorkflow(AcceleratedWorkflow):
    """Workflow with the canonical NN slots (reference ``NNWorkflow``
    [U]): loader → forwards → evaluator → decision → gds cycle."""

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.loader = None
        self.forwards = []
        self.evaluator = None
        self.decision = None
        self.gds = []
        self.repeater = None
        self.snapshotter = None
        self.rollback = None
        self.xla_step = None
        self.plotters = []
        self.image_saver = None
        #: GraphicsServer streaming plot payloads (set by the Launcher)
        self.graphics = None
        #: distributed role (set by the Launcher); slaves receive their
        #: minibatch index ranges from the master
        self.is_slave = False
        #: gradient wire codec (veles/compression.py) — slave side:
        #: the negotiated encoder, set by SlaveClient.connect from the
        #: hello exchange; None = uncompressed
        self.grad_codec = None
        #: master side: slave_id -> per-slave encoder, owned/locked by
        #: MasterServer (minted at hello, dropped with the lease)
        self.grad_codec_by_slave = {}

    def export_inference(self, path):
        """Write the C++-engine archive (contents.json + .npy weights)
        for this workflow's forward chain — SURVEY.md §3.5."""
        from veles.export_inference import export_inference
        return export_inference(self, path)

    # -- XLA rewiring + slot-ordered initialization --------------------

    def _rewire_xla(self):
        """Replace per-unit execution of the accelerated body with the
        fused XLAStep (SURVEY.md §7 design stance)."""
        from veles.znicz_tpu.xla_step import XLAStep
        step = XLAStep(self, loader=self.loader, forwards=self.forwards,
                       evaluator=self.evaluator, gds=self.gds,
                       name="xla_step")
        for u in self.forwards + [self.evaluator] + self.gds:
            if u is not None:
                u.unlink_all()
        step.link_from(self.loader)
        self.decision.link_from(step)
        self.repeater.link_from(self.decision)
        self.xla_step = step
        return step

    def initialize(self, device=None, snapshot=False, **kwargs):
        """Slot-ordered init (loader first so shapes resolve), then the
        XLA rewire + step compiler when on an XLA device."""
        from veles.backends import get_device
        self.device = get_device(device)
        if self.on_xla and self.xla_step is None \
                and (self.forwards or self.gds):
            self._rewire_xla()
        ordered = [self.repeater, self.loader] + self.forwards
        if self.evaluator is not None:
            ordered.append(self.evaluator)
        ordered += [g for g in self.gds if g is not None]
        if self.decision is not None:
            ordered.append(self.decision)
        if self.xla_step is not None:
            ordered.append(self.xla_step)
        ordered = [u for u in ordered if u is not None]
        seen = set(id(u) for u in ordered)
        rest = [u for u in self._units
                if id(u) not in seen and u is not self]
        self._initialized = True
        for unit in ordered + rest:
            unit.initialize(device=self.device, **kwargs)
        return ordered + rest

    def run(self):
        super().run()
        if self.xla_step is not None:
            self.xla_step.sync_host()

    # -- checkpoint / resume (SURVEY.md §3.4, §5.4) --------------------

    def _stateful_units(self):
        seen = []
        for u in self.forwards + self.gds:
            if u is not None and (u.PARAMS or u.STATE):
                seen.append(u)
        return seen

    def stash_state(self, at_valid=False):
        """RAM copy of every stateful unit's params + optimizer state
        — the ONE snapshot mechanic both rollback actuators
        (NNRollback, model_health.WeightGuard) share; load it back
        with :meth:`restore_stash`. ``at_valid`` syncs the epoch-entry
        view first (the state the epoch's validation metric was
        measured on)."""
        if at_valid and self.xla_step is not None:
            self.xla_step.sync_host(at_valid=True)
        return {u.name: (u.export_params(), u.export_state())
                for u in self._stateful_units()}

    def restore_stash(self, stash):
        """Load a :meth:`stash_state` snapshot back into the unit
        Arrays and resume device residency.

        COPIES on the way in: ``Array.mem = asarray(...)`` aliases a
        same-dtype array rather than copying, so importing the stash
        arrays directly would let every subsequent in-place update
        (``mem[...] += delta``) corrupt the stash — a SECOND
        divergence would then "restore" post-spike values, silently
        breaking the rollback contract exactly under the repeated-
        fault regime it exists for."""
        for u in self._stateful_units():
            if u.name in stash:
                params, state = stash[u.name]
                u.import_params({k: numpy.array(v)
                                 for k, v in params.items()})
                u.import_state({k: numpy.array(v)
                                for k, v in state.items()})
        if self.xla_step is not None:
            self.xla_step.refresh_device()

    def checkpoint_state(self):
        """Structured pytree snapshot of everything needed to resume."""
        if self.xla_step is not None:
            self.xla_step.sync_host(at_valid=True)
        tree = {"params": {}, "state": {}, "meta": {
            "workflow": self.name, "run_number": self.run_number}}
        for u in self._stateful_units():
            p, s = u.export_params(), u.export_state()
            if p:
                tree["params"][u.name] = p
            if s:
                tree["state"][u.name] = s
        if self.decision is not None:
            tree["decision"] = self.decision.get_state()
        if self.loader is not None:
            tree["loader"] = self.loader.get_state()
        if self.rollback is not None:
            # divergence-rollback history must survive a RESTART, not
            # just a same-process restore: a resumed run that forgot
            # its best loss would re-stash a diverged state as "good"
            tree["rollback"] = self.rollback.get_state()
        lr_scales = {gd.name: float(gd.lr_scale) for gd in self.gds
                     if gd is not None and hasattr(gd, "lr_scale")}
        if lr_scales:
            # rollback cuts learning rates via lr_scale; losing the
            # cuts on resume would re-diverge at the pre-cut rate
            tree["lr_scales"] = lr_scales
        if self.xla_step is not None:
            # step counter consistent with the at_valid params/state
            tree["meta"]["step_index"] = \
                self.xla_step.snapshot_view(at_valid=True)[2]
        units = self._generic_state_units()
        if units:
            # any OTHER unit exposing get_state rides under "units"
            # (mirrors base Workflow.checkpoint_state): before this,
            # a stateful auxiliary unit — ImageSaver's epoch dirs,
            # say — was silently dropped from NN checkpoints and
            # restarted from constructor defaults on resume
            tree["units"] = {u.name: s for u, s in units}
        return tree

    def _generic_state_units(self):
        """(unit, state) pairs for units NOT already covered by the
        explicit decision/loader/rollback/params sections above."""
        handled = {id(u) for u in
                   [self.decision, self.loader, self.rollback,
                    self.xla_step] + self._stateful_units()
                   if u is not None}
        out = []
        for u in self._units:
            get = getattr(u, "get_state", None)
            if callable(get) and id(u) not in handled:
                state = get()
                if state:
                    out.append((u, state))
        return out

    def restore_state(self, tree):
        """Load a checkpoint_state() tree back into the (already
        initialized) workflow and resume device residency."""
        for u in self._stateful_units():
            if u.name in tree.get("params", {}):
                u.import_params(tree["params"][u.name])
            if u.name in tree.get("state", {}):
                u.import_state(tree["state"][u.name])
        if self.decision is not None and "decision" in tree:
            self.decision.set_state(tree["decision"])
        if self.loader is not None and "loader" in tree:
            self.loader.set_state(tree["loader"])
        if self.rollback is not None and "rollback" in tree:
            self.rollback.set_state(tree["rollback"])
        for name, scale in tree.get("lr_scales", {}).items():
            for gd in self.gds:
                if gd is not None and gd.name == name:
                    gd.lr_scale = float(scale)
        # the generic "units" section restores through the base loop
        # (unit_by_name + set_state, unknown names warned and skipped)
        Workflow.restore_state(self, tree)
        if self.xla_step is not None:
            self.xla_step.step_index = int(
                tree.get("meta", {}).get("step_index", 0))
            self.xla_step.refresh_device()
            self.xla_step._dispatched_epoch = None
