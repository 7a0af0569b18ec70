"""Exit gate of a looped layer stack: one logit a token,

    gate = x . w + b          lambda = sigmoid(gate)

read off the state a pass of the stack leaves (``znicz_tpu.loop.Loop``).
The state itself goes on unchanged as ``output`` — the unit
sits in the chain between the stack's final norm and the head — and
``gate`` leaves the loop as a tap: the evaluator turns the passes'
gates into the exit distribution (``EvaluatorLoopLM``) and hands back
``err_gate``, which the gradient half adds to the state's error.
"""

from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.vjp_units import GDVjp, VjpForward


@forward_unit("exit_gate")
class ExitGate(VjpForward):
    PARAMS = ("weights", "gate_bias")
    #: per-visit values a loop stacks for the evaluator; their
    #: cotangents come back to the gradient unit as ``err_<tap>``
    TAPS = ("gate",)

    def param_specs(self, ishape):
        # zeros: every exit starts at lambda = 1/2
        return {"weights": ((ishape[-1],), "zeros"),
                "gate_bias": ((1,), "zeros")}

    def apply(self, ctx, p, x):
        import jax.numpy as jnp
        return jnp.einsum("...d,d->...", x.astype(jnp.float32),
                          p["weights"]) + p["gate_bias"]

    def xla_run(self, ctx):
        x = ctx.get(self, "input")
        trainable, _ = self.split_params(ctx)

        def apply(tp, x):
            return self.apply(ctx, tp, x)

        ctx.set(self, "gate", self.traced(ctx, apply, trainable, x))
        ctx.set(self, "output", x)


@gradient_for(ExitGate)
class GDExitGate(GDVjp):
    EXTRA_PARAMS = (("gate_bias", True),)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        err = ctx.get(self, "err_output")
        grads, dx = self.pull(ctx, "apply", ctx.get(self, "err_gate"))
        if self.need_err_input:
            ctx.set(self, "err_input",
                    (err.astype(jnp.float32) + dx.astype(jnp.float32))
                    .astype(ctx.act_dtype))
        self.apply_grads(ctx, grads)
