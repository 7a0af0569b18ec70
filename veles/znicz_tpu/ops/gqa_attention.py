"""Grouped-query causal self-attention as a pre-norm residual block
over (B, S, d): ``heads`` query heads read ``kv_heads`` K/V heads
(query head ``i`` reads K/V head ``i // (heads // kv_heads)``), RMS
norm on q and k over the head's width (one gain each, shared by the
heads), rotary positions on the whole head (half-split rotation):

    n       = rms(x; g)
    q, k, v = split(n W_qkv)            W_qkv d x (heads + 2 kv_heads) dh
    q, k    = rope(rms(q; g_q)), rope(rms(k; g_k))
    y       = x + merge(softmax(q k^T / sqrt(dh) + causal) v) W_o

That is the pre-norm placement, one gain before the sub-layer. With
``sandwich`` the sub-layer's float32 output takes a second gain before
the residual add, ``y = x + rms(merge(...) W_o; g_out)``; with
``qk_norm=False`` q and k go to the rotation as projected (no ``g_q``,
``g_k``); with ``rope=False`` there is no rotation and no position
anywhere (no tables are built); with ``gate=True`` the merged heads
pass an elementwise sigmoid gate read from the normed input,
``y = x + (merge(...) * sigmoid(n W_gate)) W_o``, and ``W_gate``
(d x heads dh) is the last columns of ``W_qkv``: one product makes
q, k, v and the gate.

The projections, norms and rotation are ``jax.vjp`` of their trace
(``ops/vjp_units.py``). The attention proper is the repo's own — the
Pallas kernels on a TPU from S=256 up, ``parallel/flash.py`` elsewhere
when ``attn_block_size`` is set, else the dense core — with the
backward each core brings, under ``veles.core`` in both directions. The
cores take equal head counts: K and V are repeated to the query heads
before the core, and the repeat's transpose sums their gradients over
each group (an index map inside the kernels is a later change).
"""

import numpy

from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.attention import (
    MultiHeadAttention, _core_scope, dense_attention_core_bwd,
    dense_attention_core_fwd)
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, rms_norm)


def rope_tables(seq, dh, theta):
    """(cos, sin), each (seq, dh / 2) float32, made in float64."""
    inv = theta ** (-numpy.arange(0, dh, 2, dtype=numpy.float64) / dh)
    angle = numpy.arange(seq, dtype=numpy.float64)[:, None] * inv[None]
    return (numpy.cos(angle).astype(numpy.float32),
            numpy.sin(angle).astype(numpy.float32))


def rope(t, cos, sin):
    """``t * cos + rotate_half(t) * sin`` over the last axis of
    (B, H, S, dh); the tables broadcast over batch and heads."""
    import jax.numpy as jnp
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def repeat_heads(t, group):
    """(B, kv, S, dh) -> (B, kv x group, S, dh): K/V head ``j`` under
    the query heads ``j x group ...``; the transpose jax derives sums
    the gradients over each group."""
    import jax.numpy as jnp
    return jnp.repeat(t, group, axis=1)


@forward_unit("gqa_attention")
class GQAttention(VjpForward, MultiHeadAttention):
    """Takes the attention proper from :class:`MultiHeadAttention` —
    the mode (``_traced_mode``) and the kernels of the flash modes,
    forward and backward (``core_fwd`` / ``core_bwd``: a change to the
    kernel path there reaches this unit); parameters, projections,
    norms and rotation are its own."""

    PARAMS = ("weights", "weights_out", "norm", "q_norm", "k_norm")

    def __init__(self, workflow, heads=4, kv_heads=None, head_dim=None,
                 rope_theta=1e6, eps=1e-5, qk_norm=True, sandwich=False,
                 rope=True, gate=False, **kwargs):
        kwargs.setdefault("residual", True)
        self.qk_norm = bool(qk_norm)
        self.sandwich = bool(sandwich)
        self.rope = bool(rope)
        self.gate = bool(gate)
        self.PARAMS = ("weights", "weights_out", "norm") \
            + (("q_norm", "k_norm") if self.qk_norm else ()) \
            + (("norm_out",) if self.sandwich else ())
        super().__init__(workflow, heads=heads, causal=True, **kwargs)
        self.kv_heads = int(kv_heads or heads)
        if self.heads % self.kv_heads:
            raise ValueError("%d query heads over %d K/V heads"
                             % (self.heads, self.kv_heads))
        self.head_dim = head_dim
        self.rope_theta = float(rope_theta)
        self.eps = float(eps)

    def param_specs(self, ishape):
        d = ishape[-1]
        dh = self.head_dim = int(self.head_dim or d // self.heads)
        wide = (self.heads * (1 + self.gate) + 2 * self.kv_heads) * dh
        specs = {"weights": ((d, wide), (d, wide)),
                 "weights_out": ((self.heads * dh, d),
                                 (self.heads * dh, d)),
                 "norm": ((d,), "ones")}
        if self.qk_norm:
            specs.update(q_norm=((dh,), "ones"), k_norm=((dh,), "ones"))
        if self.sandwich:
            specs["norm_out"] = ((d,), "ones")
        return specs

    def initialize(self, device=None, **kwargs):
        if self.seq_mesh is not None:
            raise ValueError("%s: the ring path takes equal head counts"
                             % self.name)
        VjpForward.initialize(self, device=device, **kwargs)

    @property
    def scale(self):
        return numpy.float32(1.0 / numpy.sqrt(self.head_dim))

    # -- the three stages ------------------------------------------------

    def project(self, ctx, p, x):
        """x -> q, k, v, each (B, heads, S, dh) in the compute type,
        and with ``gate`` the gate's input (B, S, heads x dh)."""
        import jax.numpy as jnp
        mm = Products(ctx)
        b, s, _ = x.shape
        h, kv, dh = self.heads, self.kv_heads, self.head_dim
        qkv = mm.dot(rms_norm(x, p["norm"], self.eps), p["weights"])
        q, k, v, *gate = jnp.split(
            qkv, [h * dh, (h + kv) * dh, (h + 2 * kv) * dh][:2 + self.gate],
            axis=-1)

        def heads(t, n):
            return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)

        if self.rope:
            cos, sin = rope_tables(s, dh, self.rope_theta)

        def turned(t, n, gain):
            t = heads(t, n)
            if self.qk_norm:
                t = rms_norm(t, p[gain], self.eps)
            return rope(t, cos, sin) if self.rope else t

        q, k = turned(q, h, "q_norm"), turned(k, kv, "k_norm")
        k, v = (repeat_heads(t, h // kv) for t in (k, heads(v, kv)))
        return tuple(t.astype(mm.cd) for t in (q, k, v)) + tuple(gate)

    def attend(self, ctx, mode, q, k, v):
        """-> (context by head, what the core's backward wants):
        the flash modes by ``MultiHeadAttention.core_fwd``, else the
        dense core."""
        if mode in ("pallas", "scan"):
            out, lse = self.core_fwd(ctx, mode, q, k, v)
            return out, (out, lse)
        import jax.numpy as jnp
        probs, out = dense_attention_core_fwd(
            jnp, q, k, v, True, self.scale, ctx.dot)
        return out, (probs,)

    def attend_bwd(self, ctx, mode, q, k, v, saved, dctx):
        if mode in ("pallas", "scan"):
            return self.core_bwd(ctx, mode, q, k, v, *saved, dctx)
        import jax.numpy as jnp
        return dense_attention_core_bwd(
            jnp, q, k, v, saved[0], dctx, self.scale, ctx.dot)

    def finish(self, ctx, p, merged, *gate):
        import jax
        import jax.numpy as jnp
        if gate:
            merged = (merged.astype(jnp.float32) * jax.nn.sigmoid(
                gate[0].astype(jnp.float32))).astype(merged.dtype)
        out = Products(ctx).dot(merged, p["weights_out"], jnp.float32)
        if self.sandwich:
            out = rms_norm(out, p["norm_out"], self.eps)
        return out

    def xla_run(self, ctx):
        import jax.numpy as jnp
        x = ctx.get(self, "input")
        p, _ = self.split_params(ctx)
        post = {k: p[k] for k in ("weights_out", "norm_out") if k in p}
        pre = {k: v for k, v in p.items() if k not in post}
        mode = self._traced_mode(ctx, x.shape[1])

        def project(pre, x):
            return self.project(ctx, pre, x)

        def finish(post, merged, *gate):
            return self.finish(ctx, post, merged, *gate)

        q, k, v, *gate = self.traced(ctx, project, pre, x)
        with _core_scope():
            out, saved = self.attend(ctx, mode, q, k, v)
        y = self.traced(ctx, finish, post, self._merge(out), *gate)
        ctx.set(self, "output",
                (x.astype(jnp.float32) + y).astype(ctx.act_dtype))
        if ctx.train:
            ctx.set(self, "core", (mode, q, k, v, saved))


@gradient_for(GQAttention)
class GDGQAttention(GDVjp):
    EXTRA_PARAMS = (("weights_out", False), ("norm", True),
                    ("q_norm", True), ("k_norm", True),
                    ("norm_out", True))

    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        x = ctx.get(f, "input")
        err = ctx.get(self, "err_output").reshape(x.shape)
        grads, dmerged, *dgate = self.pull(ctx, "finish",
                                           err.astype(jnp.float32))
        mode, q, k, v, saved = ctx.get(f, "core")
        with _core_scope():
            dq, dk, dv = f.attend_bwd(ctx, mode, q, k, v, saved,
                                      f._split(dmerged))
        pre, dx = self.pull(ctx, "project", tuple(
            t.astype(q.dtype) for t in (dq, dk, dv)) + tuple(dgate))
        if self.need_err_input:
            ctx.set(self, "err_input",
                    (dx.astype(jnp.float32) + err).astype(ctx.act_dtype))
        self.apply_grads(ctx, dict(grads, **pre))
