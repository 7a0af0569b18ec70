"""Unit pairs whose backward is ``jax.vjp`` of their traced forward
(ROADMAP D1, decided in PR 28).

A :class:`VjpForward` states its math once, as the pure function
``apply(ctx, params, x)``. In a training step ``xla_run`` traces it
through ``jax.vjp`` and leaves the pullback in the step's
:class:`FlowContext`; its :class:`GDVjp` half calls the pullback on the
incoming error and hands the parameter cotangents to the repo's solver
(``update_weights_xla`` / ``update_extra_xla``, under ``veles.update``).
Forward and backward run in ONE trace, so the pullback's residuals are
ordinary values of the compiled step. There is no hand-derived
backward and no numpy oracle: the oracle of these units is the float32
reference (``benchmark/reference/lfm2_moe.py``), and ``-d numpy``
refuses them.

The two product primitives below are the only rules written by hand,
and they state a precision policy, not a derivative: operands in the
device's compute type and float32 accumulation in BOTH directions, as
``FlowContext.dot`` gives the hand-written units. Left to jax, the
transposed products would take the float32 cotangent against a bf16
operand, and every weight gradient would be rounded to bf16 on its way
through the operand's cast.
"""

import functools

import numpy

from veles.memory import Array
from veles.znicz_tpu.nn_units import Forward, GradientDescentBase


def rms_norm(x, gain, eps):
    """``x * rsqrt(mean(x^2, last axis) + eps) * gain`` in float32."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def recomputed(fn):
    """``fn`` (arrays in, arrays out) whose backward runs it again:
    nothing of its inside lives from the forward to the backward, as
    under ``jax.checkpoint``, and the repeated forward waits behind an
    ``optimization_barrier`` for the cotangent it meets. Left free,
    XLA runs every recomputation of a step first and holds all their
    residuals at once (``znicz_tpu/loop.py``; PERF.md section 6,
    PR 32)."""
    import jax

    @jax.custom_vjp
    def run(*args):
        return fn(*args)

    def backward(args, cotangent):
        args, cotangent = jax.lax.optimization_barrier((args, cotangent))
        return jax.vjp(fn, *args)[1](cotangent)

    run.defvjp(lambda *args: (fn(*args), args), backward)
    return run


@functools.lru_cache(maxsize=None)
def _products(cd):
    """(dot, grouped_dot) for the compute dtype ``cd``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mm(a, b, out):
        return jnp.matmul(a.astype(cd), b.astype(cd),
                          preferred_element_type=f32).astype(out)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def dot(a, w, out):
        """``a (..., k) @ w (k, n)`` -> ``out`` dtype."""
        return mm(a, w, out)

    def dot_fwd(a, w, out):
        # the weight is kept as it came (the live parameter), the
        # activation in the compute type: no bf16 copy of a weight
        # lives from the forward to the backward
        return mm(a, w, out), (a.astype(cd), w, jnp.zeros((0,), a.dtype))

    def dot_bwd(out, saved, g):
        a, w, like = saved
        g = g.astype(cd)
        k, n = w.shape
        da = mm(g, w.T, like.dtype)
        dw = mm(a.reshape(-1, k).T, g.reshape(-1, n), w.dtype)
        return da, dw

    dot.defvjp(dot_fwd, dot_bwd)

    def rd(x, w, sizes, out):
        # the result type is asked of the product itself: the TPU's
        # grouped kernel accumulates in float32 either way, and a cast
        # after it would be a pass of its own over the float32 rows
        return jax.lax.ragged_dot(x.astype(cd), w.astype(cd), sizes,
                                  preferred_element_type=out)

    #: x (m, k), g (m, n) -> (groups, k, n): the rows of a group
    #: contracted against each other
    by_group = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=())

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def grouped_dot(x, w, sizes, out):
        """Row ``r`` of ``x (m, k)`` against ``w[group of r] (k, n)``;
        the groups are consecutive runs of ``sizes`` rows. Rows past
        the last group are NOT computed (the TPU's grouped kernel stops
        there) and hold no defined value: the caller masks them."""
        return rd(x, w, sizes, out)

    def grouped_fwd(x, w, sizes, out):
        return rd(x, w, sizes, out), (
            x.astype(cd), w, sizes, jnp.zeros((0,), x.dtype))

    def grouped_bwd(out, saved, g):
        x, w, sizes, like = saved
        g = g.astype(cd)
        dx = rd(g, w.swapaxes(1, 2), sizes, like.dtype)
        dw = jax.lax.ragged_dot_general(
            x, g, sizes, by_group, preferred_element_type=w.dtype)
        # dx leaves with dw: what consumes dx then runs after the
        # product has read x, and may overwrite what x was made from
        # in place (without the order XLA copies that buffer first)
        dx, dw = jax.lax.optimization_barrier((dx, dw))
        return dx, dw, None

    grouped_dot.defvjp(grouped_fwd, grouped_bwd)
    return dot, grouped_dot


class Products:
    """The matrix products of one traced unit: ``dot`` and
    ``grouped_dot`` under the step's precision policy, results in the
    activation type unless ``out`` says otherwise."""

    def __init__(self, ctx):
        device = ctx._compiler.device
        self.cd = numpy.dtype(device.compute_dtype)
        self.act = ctx.act_dtype
        self._dot, self._grouped = _products(self.cd)

    def dot(self, a, w, out=None):
        return self._dot(a, w, numpy.dtype(out or self.act))

    def grouped_dot(self, x, w, sizes, out=None):
        return self._grouped(x, w, sizes, numpy.dtype(out or self.act))


class VjpForward(Forward):
    """Forward half: ``y = apply(ctx, params, x)``, no bias.

    ``PARAMS`` names every array the unit owns; ``BUFFERS`` those of
    them no gradient reaches (they ride in the parameter tree and come
    back unchanged). Subclasses give ``param_specs`` and ``apply``.
    """

    PARAMS = ("weights",)
    BUFFERS = ()
    #: ``apply`` returns (y, aux): ``aux`` a dict of small outputs that
    #: ``export_aux`` hands to the step's metric fetch
    HAS_AUX = False

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.include_bias = False
        for name in self.PARAMS:
            if getattr(self, name, None) is None:
                setattr(self, name, Array())

    def output_shape_for(self, ishape):
        return tuple(ishape)

    def param_specs(self, ishape):
        """{name: (shape, filling)}: filling is ``"ones"``, ``"zeros"``,
        the (fan_in, fan_out) a Glorot-scaled draw takes, a float
        standard deviation of a normal draw, or ``fill(prng, mem)``."""
        raise NotImplementedError

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        for name, (shape, filling) in \
                self.param_specs(self.input.shape).items():
            arr = getattr(self, name)
            if arr and arr.shape == tuple(shape):
                continue
            arr.reset(numpy.zeros(shape, numpy.float32))
            if filling == "zeros":
                continue
            if filling == "ones":
                arr.mem[...] = 1.0
            elif isinstance(filling, float):
                self.fill_array(arr, "gaussian", filling)
            elif callable(filling):
                filling(self.prng, arr.mem)
            else:
                self.fill_array(
                    arr, self.weights_filling, self.weights_stddev
                    or self.default_weights_stddev(*filling))
        oshape = self.output_shape_for(self.input.shape)
        if not self.output or self.output.shape != oshape:
            self.output.reset(numpy.zeros(oshape, numpy.float32))

    def apply(self, ctx, p, x):
        """-> y, or (y, aux) where ``HAS_AUX``."""
        raise NotImplementedError

    def numpy_run(self):
        raise NotImplementedError(
            "%s has no numpy oracle: its backward is jax.vjp of its "
            "traced forward and its oracle the float32 reference; run "
            "it on an XLA device (-d cpu, tpu or xla)"
            % type(self).__name__)

    def traced(self, ctx, fn, *args, has_aux=False):
        """``fn(*args)``; in a training step through ``jax.vjp``, the
        pullback left for the gradient unit under ``fn``'s name (not
        in a visit whose backward runs from a later recomputation)."""
        if not (ctx.train and ctx.pullbacks):
            return fn(*args)
        import jax
        out = jax.vjp(fn, *args, has_aux=has_aux)
        ctx.set(self, "vjp_" + fn.__name__, out[1])
        return (out[0], out[2]) if has_aux else out[0]

    def split_params(self, ctx):
        p = ctx.unit_params(self)
        return ({k: v for k, v in p.items() if k not in self.BUFFERS},
                {k: v for k, v in p.items() if k in self.BUFFERS})

    def xla_run(self, ctx):
        x = ctx.get(self, "input")
        trainable, buffers = self.split_params(ctx)
        act = ctx.act_dtype

        def apply(tp, x):
            out = self.apply(ctx, dict(tp, **buffers), x)
            if self.HAS_AUX:
                return out[0].astype(act), out[1]
            return out.astype(act)

        out = self.traced(ctx, apply, trainable, x, has_aux=self.HAS_AUX)
        y, aux = out if self.HAS_AUX else (out, None)
        ctx.set(self, "output", y)
        if aux is not None and ctx.train:
            self.export_aux(ctx, aux)

    def export_aux(self, ctx, aux):
        raise NotImplementedError


class GDVjp(GradientDescentBase):
    """Backward half: the pullback of the forward's trace, then the
    solver. ``EXTRA_PARAMS`` lists the forward's trainable arrays
    beyond ``weights`` (gains take the bias hyper-parameters: they are
    not decayed)."""

    def numpy_run(self):
        self.forward.numpy_run()

    def pull(self, ctx, name, cotangent):
        return ctx.get(self.forward, "vjp_" + name)(cotangent)

    def xla_run(self, ctx):
        f = self.forward
        y = ctx.get(f, "output")
        err = ctx.get(self, "err_output").reshape(y.shape).astype(y.dtype)
        grads, dx = self.pull(ctx, "apply", err)
        if self.need_err_input:
            ctx.set(self, "err_input", dx.astype(ctx.act_dtype))
        self.apply_grads(ctx, grads)
