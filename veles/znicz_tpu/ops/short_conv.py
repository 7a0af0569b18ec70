"""Gated short convolution, the operator of a ``conv`` layer (LFM2's), as
a pre-norm residual block over (B, S, d):

    (Bg, Cg, u) = split3(rms(x; g) W_in)        W_in  d x 3d
    v           = Bg * u
    c_t         = sum_j w[:, j] * v_{t - (L-1) + j}   depthwise, causal,
                                                zeros before t = 0
    y           = x + (Cg * c) W_out            W_out d x d

It holds no attention: nothing here runs under ``veles.core``.
"""

from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, rms_norm)


@forward_unit("short_conv")
class ShortConv(VjpForward):
    PARAMS = ("weights", "conv", "weights_out", "norm")

    def __init__(self, workflow, kernel=3, eps=1e-5, **kwargs):
        super().__init__(workflow, **kwargs)
        self.kernel = int(kernel)
        self.eps = float(eps)

    def param_specs(self, ishape):
        d = ishape[-1]
        return {"weights": ((d, 3 * d), (d, 3 * d)),
                "conv": ((d, self.kernel), (self.kernel, 1)),
                "weights_out": ((d, d), (d, d)),
                "norm": ((d,), "ones")}

    def apply(self, ctx, p, x):
        import jax
        import jax.numpy as jnp
        mm = Products(ctx)
        f32 = jnp.float32
        taps = self.kernel

        @jax.checkpoint     # the backward gates and convolves again
        def mix(proj, conv):
            gate_in, gate_out, u = jnp.split(proj.astype(f32), 3, axis=-1)
            v = gate_in * u
            padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
            c = sum(conv[:, j] * padded[:, j:j + v.shape[1]]
                    for j in range(taps))
            return (gate_out * c).astype(mm.cd)

        proj = mm.dot(rms_norm(x, p["norm"], self.eps), p["weights"])
        out = mm.dot(mix(proj, p["conv"]), p["weights_out"], f32)
        return x.astype(f32) + out


@gradient_for(ShortConv)
class GDShortConv(GDVjp):
    EXTRA_PARAMS = (("conv", True), ("weights_out", False),
                    ("norm", True))
