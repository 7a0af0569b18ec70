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
anywhere (no tables are built).

The rotation turns the first ``rotary_dim`` values of a head (default:
all of them), half-split over those, and the rest pass as projected:
``rope(t)[:r] = t[:r] cos + rotate_half(t[:r]) sin``, ``rope(t)[r:] =
t[r:]``. ``rope_scaling`` (a model's ``rope_parameters`` entry of
``rope_type`` "yarn") scales the tables (:func:`rope_tables`): the slow
frequencies divided by ``factor``, the fast ones kept, a linear ramp
between, cos and sin times ``attention_factor``.

With ``gate`` the heads' output passes a sigmoid gate read from the
normed input before ``W_o``, and the gate's matrix is the last columns
of ``W_qkv`` (one product makes q, k, v and the gate):
``"elementwise"`` (or True) one gate a value, ``W_gate`` d x heads dh,
``y = x + (merge(...) * sigmoid(n W_gate)) W_o``; ``"head"`` one gate
a head and token, ``W_gate`` d x heads, ``y = x + concat_h(sigmoid(n
W_gate)_h A_h) W_o`` (its mean is the gauge ``veles_attn_gate_mean
{layer}``: a gate that closes silences a layer before the loss shows
it).

With ``window`` W a query sees itself and the W - 1 tokens before it,
``M[t, s] = 0 if s <= t and t - s < W else -inf``: the flash kernels
skip the K tiles wholly older than the band as they skip those wholly
in the future (``parallel/pallas_attention.py``), the scan and the
dense core mask. The windowed attention proper runs under
``veles.window`` inside ``veles.core``, both directions; counters on
the step's metric fetch: ``veles_window_steps_total{layer}``,
``veles_window_pairs_total{layer}`` (query-key pairs inside the band a
step attended: the model's work) and
``veles_window_tile_pairs_total{layer}`` (pairs of the tiles the
kernels' loop bounds visit, the whole square where the scan or the
dense core runs: over the first, what the tile's rounding costs).

The projections, norms and rotation are ``jax.vjp`` of their trace
(``ops/vjp_units.py``). The attention proper is the repo's own — the
Pallas kernels on a TPU from S=256 up, ``parallel/flash.py`` elsewhere
when ``attn_block_size`` is set, else the dense core — with the
backward each core brings, under ``veles.core`` in both directions. The
cores take equal head counts: K and V are repeated to the query heads
before the core, and the repeat's transpose sums their gradients over
each group (an index map inside the kernels is a later change). The
repeat is part of ``project``'s trace, and the repeated K and V live
to the backward - but for a unit with a window or a per-head gate,
which keeps them at the K/V heads and repeats beside the core in both
directions (``GQAttention.repeat_late``).
"""

import contextlib

import numpy

from veles import telemetry
from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.attention import (
    MultiHeadAttention, _core_scope, dense_attention_core_bwd,
    dense_attention_core_fwd)
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, rms_norm)


def yarn_frequencies(inv, rotary_dim, theta, scaling):
    """YaRN's inverse frequencies from the plain ``inv`` (float64,
    ``rotary_dim / 2`` of them), by the published keys of ``scaling``:
    ``c(beta) = r ln(original / (2 pi beta)) / (2 ln theta)`` is the
    index whose wavelength fits ``beta`` times into the original
    context; below ``floor(c(beta_fast))`` a frequency is kept, above
    ``ceil(c(beta_slow))`` divided by ``factor``, a linear ramp
    between."""
    original = scaling["original_max_position_embeddings"]

    def index(beta):
        return rotary_dim * numpy.log(original / (2 * numpy.pi * beta)) \
            / (2 * numpy.log(theta))

    lo = max(numpy.floor(index(scaling.get("beta_fast", 32))), 0)
    hi = min(numpy.ceil(index(scaling.get("beta_slow", 1))),
             rotary_dim - 1)
    ramp = numpy.clip(
        (numpy.arange(rotary_dim // 2, dtype=numpy.float64) - lo)
        / max(hi - lo, 1e-3), 0, 1)
    return inv * (1 - ramp) + inv / scaling["factor"] * ramp


def rope_tables(seq, rotary_dim, theta, scaling=None):
    """(cos, sin), each (seq, rotary_dim / 2) float32, made in float64.
    ``scaling``: None, or a ``rope_type`` "yarn" entry of a model's
    ``rope_parameters`` (:func:`yarn_frequencies`; both tables times
    its ``attention_factor``, default ``0.1 ln(factor) + 1``)."""
    inv = theta ** (-numpy.arange(0, rotary_dim, 2, dtype=numpy.float64)
                    / rotary_dim)
    if scaling:
        inv = yarn_frequencies(inv, rotary_dim, theta, scaling)
    angle = numpy.arange(seq, dtype=numpy.float64)[:, None] * inv[None]
    cos, sin = numpy.cos(angle), numpy.sin(angle)
    if scaling:
        factor = scaling.get("attention_factor") \
            or 0.1 * numpy.log(scaling["factor"]) + 1.0
        cos, sin = cos * factor, sin * factor
    return cos.astype(numpy.float32), sin.astype(numpy.float32)


def rope(t, cos, sin):
    """``t * cos + rotate_half(t) * sin`` over the first ``2 x
    cos.shape[-1]`` values of the last axis of (B, H, S, dh), the rest
    as they are; the tables broadcast over batch and heads."""
    import jax.numpy as jnp
    turned = 2 * cos.shape[-1]
    if turned < t.shape[-1]:
        return jnp.concatenate(
            [rope(t[..., :turned], cos, sin), t[..., turned:]], -1)
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
    #: what ``gate`` may be -> the gate the unit has
    GATES = {False: None, True: "elementwise", "elementwise": "elementwise",
             "head": "head"}

    def __init__(self, workflow, heads=4, kv_heads=None, head_dim=None,
                 rope_theta=1e6, eps=1e-5, qk_norm=True, sandwich=False,
                 rope=True, gate=False, window=None, rotary_dim=None,
                 rope_scaling=None, **kwargs):
        kwargs.setdefault("residual", True)
        name = kwargs.get("name") or type(self).__name__
        if gate not in self.GATES:
            raise ValueError("%s: gate is False, 'elementwise' (or True) "
                             "or 'head', got %r" % (name, gate))
        if window is not None and int(window) < 1:
            raise ValueError("%s: a window holds the query itself, at "
                             "least 1 token, got %r" % (name, window))
        if rotary_dim is not None and (
                rotary_dim % 2 or rotary_dim < 2
                or (head_dim and rotary_dim > head_dim)):
            raise ValueError(
                "%s: rotary_dim is even and at most the head's %s "
                "values, got %r" % (name, head_dim or "", rotary_dim))
        if rope_scaling and rope_scaling.get("rope_type") != "yarn":
            raise ValueError("%s: rope_scaling of rope_type 'yarn' "
                             "alone, got %r" % (name, rope_scaling))
        self.qk_norm = bool(qk_norm)
        self.sandwich = bool(sandwich)
        self.rope = bool(rope)
        self.gate = self.GATES[gate]
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
        self.window = None if window is None else int(window)
        self.rotary_dim = rotary_dim
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        #: K and V live from the forward to the backward at their OWN
        #: heads and are repeated beside the core in each direction
        #: (:meth:`repeated`), where the unit has a window or a
        #: per-head gate: the operators whose groups made the repeat a
        #: memory item (9-fold at 72 heads over 8: 2 x 134 MB a layer at
        #: S = 8192, 1.1 GB over the five layers of its cell, which
        #: does not fit 16 GB with them: ``benchmark/rehearse.py``,
        #: PR 38). The other operators keep the repeat inside
        #: ``project``'s trace and the programs their cells were
        #: accepted with.
        self.repeat_late = self.window is not None or self.gate == "head"

    def gate_width(self):
        """Columns of ``W_gate``: the last of ``W_qkv``."""
        return {None: 0, "elementwise": self.heads * self.head_dim,
                "head": self.heads}[self.gate]

    def param_specs(self, ishape):
        d = ishape[-1]
        dh = self.head_dim = int(self.head_dim or d // self.heads)
        if self.rotary_dim is not None and self.rotary_dim > dh:
            raise ValueError("%s: rotary_dim %d exceeds the head's %d "
                             "values" % (self.name, self.rotary_dim, dh))
        wide = (self.heads + 2 * self.kv_heads) * dh + self.gate_width()
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
            qkv, [h * dh, (h + kv) * dh,
                  (h + 2 * kv) * dh][:2 + bool(self.gate)], axis=-1)

        def heads(t, n):
            return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)

        if self.rope:
            cos, sin = rope_tables(s, self.rotary_dim or dh,
                                   self.rope_theta, self.rope_scaling)

        def turned(t, n, gain):
            t = heads(t, n)
            if self.qk_norm:
                t = rms_norm(t, p[gain], self.eps)
            return rope(t, cos, sin) if self.rope else t

        q, k, v = turned(q, h, "q_norm"), turned(k, kv, "k_norm"), \
            heads(v, kv)
        if not self.repeat_late:
            k, v = (repeat_heads(t, h // kv) for t in (k, v))
        return tuple(t.astype(mm.cd) for t in (q, k, v)) + tuple(gate)

    def repeated(self, k, v, *after):
        """K and V as the cores take them, at the query heads: as
        ``project`` left them, or repeated here (``repeat_late``). In
        the backward the repeat waits, behind an optimization barrier,
        for the cotangent ``after`` it meets (``vjp_units.recomputed``'s
        rule): left free, XLA may find it equal to the forward's and
        keep that one alive."""
        if not self.repeat_late:
            return (k, v) + after
        if after:
            import jax
            k, v, *after = jax.lax.optimization_barrier((k, v) + after)
        group = self.heads // self.kv_heads
        return (repeat_heads(k, group), repeat_heads(v, group)) \
            + tuple(after)

    def grouped(self, dk, dv):
        """The cores' dk, dv at the K/V heads: each group's sum, added
        in float32 (the transpose of :meth:`repeated`)."""
        if not self.repeat_late:
            return dk, dv
        import jax.numpy as jnp
        b, _, s, dh = dk.shape
        return tuple(
            t.astype(jnp.float32).reshape(b, self.kv_heads, -1, s, dh)
            .sum(2).astype(t.dtype) for t in (dk, dv))

    def attend(self, ctx, mode, q, k, v):
        """-> (context by head, what the core's backward wants):
        the flash modes by ``MultiHeadAttention.core_fwd``, else the
        dense core."""
        with self.window_scope():
            if mode in ("pallas", "scan"):
                out, lse = self.core_fwd(ctx, mode, q, k, v)
                return out, (out, lse)
            import jax.numpy as jnp
            probs, out = dense_attention_core_fwd(
                jnp, q, k, v, True, self.scale, ctx.dot, self.window)
            return out, (probs,)

    def attend_bwd(self, ctx, mode, q, k, v, saved, dctx):
        with self.window_scope():
            if mode in ("pallas", "scan"):
                return self.core_bwd(ctx, mode, q, k, v, *saved, dctx)
            import jax.numpy as jnp
            return dense_attention_core_bwd(
                jnp, q, k, v, saved[0], dctx, self.scale, ctx.dot)

    def window_scope(self):
        """``veles.window``, inside ``veles.core``, where the unit has
        a window."""
        if self.window is None:
            return contextlib.nullcontext()
        import jax
        return jax.named_scope("veles.window")

    def finish(self, ctx, p, merged, *gate):
        """-> the sub-layer's output, and with a per-head gate
        ``(output, {"gate": the gates' mean})``."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        if self.gate == "head":
            gamma = jax.nn.sigmoid(gate[0].astype(f32))
            merged = (merged.astype(f32).reshape(
                gamma.shape + (self.head_dim,))
                * gamma[..., None]).reshape(merged.shape).astype(
                    merged.dtype)
        elif gate:
            merged = (merged.astype(f32) * jax.nn.sigmoid(
                gate[0].astype(f32))).astype(merged.dtype)
        out = Products(ctx).dot(merged, p["weights_out"], f32)
        if self.sandwich:
            out = rms_norm(out, p["norm_out"], self.eps)
        if self.gate == "head":
            return out, {"gate": jax.lax.stop_gradient(gamma).mean()}
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
        kept = ctx.get(self, "kept")
        if kept is not None:
            # a loop's recomputation, handed the core's (out, lse) by
            # the forward pass that made them: no second forward kernel
            out, saved = kept[0], kept
        else:
            kr, vr = self.repeated(k, v)
            with _core_scope():
                out, saved = self.attend(ctx, mode, q, kr, vr)
            if ctx.train and not ctx.pullbacks \
                    and mode in ("pallas", "scan"):
                # a loop's forward pass, whose backward recomputes this
                # unit: the flash cores' residual is O(S) a row, worth
                # keeping (``Loop``'s ``kept``); the dense core's is S²
                ctx.set(self, "kept", saved)
        y = self.traced(ctx, finish, post, self._merge(out), *gate,
                        has_aux=self.gate == "head")
        if self.gate == "head":
            y, aux = y
            if ctx.train:
                ctx.export("attn_gate_" + self.name, aux["gate"])
        ctx.set(self, "output",
                (x.astype(jnp.float32) + y).astype(self.stream_dtype(ctx)))
        if ctx.train:
            ctx.set(self, "core", (mode, q, k, v, saved))
            if self.window is not None:
                # rides the metric fetch: the host multiplies by the
                # rows when it arrives
                ctx.export("window_visited_" + self.name, jnp.int32(
                    self.visited_pairs(mode, x.shape[1])))

    # -- counters ----------------------------------------------------------

    def visited_pairs(self, mode, s):
        """Query-key pairs of one (batch, head) row that the attention
        proper visits: the tiles inside the kernels' loop bounds where
        they run, the whole square under the scan and the dense core,
        which mask and skip nothing."""
        if mode != "pallas":
            return s * s
        from veles.znicz_tpu.parallel import pallas_attention as PA
        blk = self._pallas_block(s)
        return PA.visited_pairs(s, blk, blk, self.window)

    def metric_sinks(self):
        return ([("attn_gate_" + self.name, "step_gate")]
                if self.gate == "head" else []) + (
            [("window_visited_" + self.name, "step_visited")]
            if self.window is not None else []) + [
            ("kept_" + self.name, "step_kept")]

    def metrics_published(self, fresh):
        """``XLAStep``'s hook, once a training step's sinks are filled."""
        if "step_kept" in fresh:
            telemetry.counter(
                "veles_loop_kept_cores_total", "Recomputed applications "
                "of an attention layer that took the core's out and lse "
                "from the loop's forward pass, training steps",
                ("layer",)).labels(self.name).inc(self.step_kept)
        if "step_gate" in fresh:
            telemetry.gauge(
                "veles_attn_gate_mean", "Last step: mean of an "
                "attention layer's per-head output gates", ("layer",)
            ).labels(self.name).set(self.step_gate)
        if "step_visited" not in fresh:
            return
        from veles.znicz_tpu.parallel import pallas_attention as PA
        b, s, _ = self.input.shape
        rows = b * self.heads
        telemetry.counter(
            "veles_window_pairs_total", "Query-key pairs inside the band "
            "that a windowed attention layer attended, training steps",
            ("layer",)).labels(self.name).inc(
                rows * PA.band_pairs(s, self.window))
        telemetry.counter(
            "veles_window_tile_pairs_total", "Query-key pairs of the "
            "tiles a windowed attention layer's kernels visited (the "
            "whole square where nothing skips), training steps",
            ("layer",)).labels(self.name).inc(rows * self.step_visited)
        telemetry.counter(
            "veles_window_steps_total", "Training steps a windowed "
            "attention layer ran", ("layer",)).labels(self.name).inc()


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
        k, v, dctx = f.repeated(k, v, f._split(dmerged))
        with _core_scope():
            dq, dk, dv = f.attend_bwd(ctx, mode, q, k, v, saved, dctx)
        dk, dv = f.grouped(dk, dv)
        pre, dx = self.pull(ctx, "project", tuple(
            t.astype(q.dtype) for t in (dq, dk, dv)) + tuple(dgate))
        if self.need_err_input:
            ctx.set(self, "err_input",
                    (dx.astype(jnp.float32) + err).astype(
                        f.stream_dtype(ctx)))
        self.apply_grads(ctx, dict(grads, **pre))
