"""RMS norm unit pair: ``y = x * rsqrt(mean(x^2) + eps) * g`` over the
trailing dimension, no bias (the final norm of a pre-norm LM; the
pre-norms of its blocks live inside the block units and share
:func:`~veles.znicz_tpu.ops.vjp_units.rms_norm`)."""

from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.vjp_units import GDVjp, VjpForward, rms_norm


@forward_unit("rms_norm")
class RMSNorm(VjpForward):
    def __init__(self, workflow, eps=1e-5, **kwargs):
        super().__init__(workflow, **kwargs)
        self.eps = float(eps)

    def param_specs(self, ishape):
        return {"weights": ((ishape[-1],), "ones")}

    def apply(self, ctx, p, x):
        return rms_norm(x, p["weights"], self.eps)


@gradient_for(RMSNorm)
class GDRMSNorm(GDVjp):
    pass
