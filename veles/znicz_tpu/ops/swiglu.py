"""SwiGLU feed-forward as a pre-norm residual block over (B, S, d):

    y = x + (silu(n W1) * (n W3)) W2,   n = rms(x; g)

``weights`` holds ``[W1 | W3]`` (d x 2f), so the two up-projections
are one product. With ``sandwich`` the sub-layer's float32 output takes
a gain of its own before the residual add: ``y = x + rms(...; g_out)``.
With ``recompute`` nothing of the sub-layer but its input lives from
the forward to the backward, which runs it again
(``vjp_units.recomputed``): the model's ``remat`` in the pre-norm block.
"""

from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, recomputed, rms_norm)


def swiglu(h13, keep=None):
    """``silu(h1) * h3`` of ``[h1 | h3]``, in float32; rows where
    ``keep`` is false read as zeros. The halves are parted (and masked)
    in the type they come in and widened after: widened first, the whole
    ``[h1 | h3]`` is written out in float32 before the product reads it
    (1.9 ms a layer for the expert layer's buffer on a v5e, PR 28)."""
    import jax
    import jax.numpy as jnp
    h1, h3 = jnp.split(h13, 2, axis=-1)
    if keep is not None:
        h1, h3 = jnp.where(keep, h1, 0), jnp.where(keep, h3, 0)
    return jax.nn.silu(h1.astype(jnp.float32)) * h3.astype(jnp.float32)


@forward_unit("swiglu_ffn")
class SwiGLUFFN(VjpForward):
    PARAMS = ("weights", "weights2", "norm")

    def __init__(self, workflow, hidden=None, eps=1e-5, sandwich=False,
                 recompute=False, **kwargs):
        self.sandwich = bool(sandwich)
        self.recompute = bool(recompute)
        if self.sandwich:
            self.PARAMS = type(self).PARAMS + ("norm_out",)
        super().__init__(workflow, **kwargs)
        if not hidden:
            raise ValueError("swiglu_ffn needs hidden")
        self.hidden = int(hidden)
        self.eps = float(eps)

    def param_specs(self, ishape):
        d, f = ishape[-1], self.hidden
        specs = {"weights": ((d, 2 * f), (d, f)),
                 "weights2": ((f, d), (f, d)),
                 "norm": ((d,), "ones")}
        if self.sandwich:
            specs["norm_out"] = ((d,), "ones")
        return specs

    def apply(self, ctx, p, x):
        if self.recompute:
            return recomputed(lambda p, x: self.ffn(ctx, p, x))(p, x)
        return self.ffn(ctx, p, x)

    def ffn(self, ctx, p, x):
        import jax
        import jax.numpy as jnp
        mm = Products(ctx)

        @jax.checkpoint     # the backward gates again, keeps h13 alone
        def down(h13, w2):
            return mm.dot(swiglu(h13).astype(mm.cd), w2, jnp.float32)

        h13 = mm.dot(rms_norm(x, p["norm"], self.eps), p["weights"])
        out = down(h13, p["weights2"])
        if self.sandwich:
            out = rms_norm(out, p["norm_out"], self.eps)
        return x.astype(jnp.float32) + out


@gradient_for(SwiGLUFFN)
class GDSwiGLUFFN(GDVjp):
    EXTRA_PARAMS = (("weights2", False), ("norm", True),
                    ("norm_out", True))
