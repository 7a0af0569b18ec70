"""Tensor surgery units.

Re-design of znicz ``cutter.py`` + ``weights_zerofilling.py`` [U]
(SURVEY.md §2.4 "Tensor surgery"): crop a spatial window out of a 4-D
NHWC batch (+ its GD scatter-back), and a mask that pins chosen weight
entries at zero across updates.
"""

import numpy

from veles.memory import Array
from veles.units import Unit
from veles.znicz_tpu.nn_units import (
    Forward, GradientDescentBase, forward_unit, gradient_for)


@forward_unit("cutter")
class Cutter(Forward):
    """output = input[:, y:y+h, x:x+w, :]."""

    PARAMS = ()

    def __init__(self, workflow, padding=None, y=0, x=0, h=None, w=None,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        if padding is not None:       # reference-style (l, t, r, b)
            left, top, right, bottom = padding
            self.y, self.x = top, left
            self._trim = (bottom, right)
            self.h = self.w = None
        else:
            self.y, self.x, self.h, self.w = y, x, h, w
            self._trim = None
        self.include_bias = False

    def output_shape_for(self, ishape):
        b, hh, ww, c = ishape
        if self._trim is not None:
            bottom, right = self._trim
            return (b, hh - self.y - bottom, ww - self.x - right, c)
        return (b, self.h or hh - self.y, self.w or ww - self.x, c)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        oshape = self.output_shape_for(self.input.shape)
        if min(oshape[1:3]) <= 0:
            raise ValueError("%s cuts away everything" % self.name)
        if not self.output or self.output.shape != oshape:
            self.output.reset(numpy.zeros(oshape, numpy.float32))

    def _crop(self, x):
        oshape = self.output_shape_for(x.shape)
        return x[:, self.y:self.y + oshape[1],
                 self.x:self.x + oshape[2], :]

    def numpy_run(self):
        self.output.map_invalidate()
        self.output.mem[...] = self._crop(
            self.input.map_read().mem.astype(numpy.float32))

    def xla_run(self, ctx):
        ctx.set(self, "output", self._crop(ctx.get(self, "input")))


@gradient_for(Cutter)
class GDCutter(GradientDescentBase):
    """Scatter the error back into a zero tensor of the input shape."""

    STATE = ()

    def numpy_run(self):
        f = self.forward
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(f.output.shape)
        self.err_input.map_invalidate()
        ei = self.err_input.mem
        ei[...] = 0.0
        ei[:, f.y:f.y + err.shape[1], f.x:f.x + err.shape[2], :] = err
    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        err = ctx.get(self, "err_output")
        # batch dim from the traced error (scan-mode DP pads it past
        # the host-initialized Array shape)
        err = err.reshape((-1,) + f.output.shape[1:])
        ishape = (err.shape[0],) + f.input.shape[1:]
        ei = jnp.zeros(ishape, ctx.act_dtype)
        ei = ei.at[:, f.y:f.y + err.shape[1],
                   f.x:f.x + err.shape[2], :].set(
                       err.astype(ctx.act_dtype))
        ctx.set(self, "err_input", ei)


class ZeroFiller(Unit):
    """Pins masked weight entries at zero after every update (reference
    ``weights_zerofilling.ZeroFiller`` [U]). Wire it after a GD unit.

    On the XLA backend the compiled step keeps parameters
    device-resident and never re-reads host Arrays, so the mask is
    registered on the target Forward unit (``zero_mask``), handed over
    as a traced hyperparameter (compared at each dispatch, shipped when
    it changed: host-side mask edits stay live), and applied by
    ``GradientDescentBase.update_weights_xla``
    inside the trace; ``run`` then only acts on the numpy backend, so
    each backend applies the mask exactly once per step."""

    def __init__(self, workflow, target=None, mask=None, **kwargs):
        super().__init__(workflow, **kwargs)
        self.target = target       # Forward unit whose weights to mask
        self.mask = Array(mask) if mask is not None else Array()

    def initialize(self, **kwargs):
        super().initialize(**kwargs)
        if self.target is not None and self.target.weights and \
                not self.mask:
            self.mask.reset(
                numpy.ones_like(self.target.weights.mem))
        if self.target is not None:
            # traced path: the GD update multiplies by this mask
            self.target.zero_mask = self.mask
            # apply once up-front so the initial params respect the mask
            w = self.target.weights
            if w:
                w.map_write()
                w.mem *= self.mask.map_read().mem
                # XLAStep may have gathered params to device already
                # (it initializes before units linked after it) — push
                # the masked initial weights across
                step = getattr(self.workflow, "xla_step", None)
                if step is not None and step.params is not None:
                    step.refresh_device()

    def run(self):
        if getattr(self.workflow, "xla_step", None) is not None:
            return  # mask lives inside the compiled update
        w = self.target.weights
        w.map_write()
        w.mem *= self.mask.map_read().mem
