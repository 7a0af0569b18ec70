"""Multi-head attention + transformer FFN + per-token dense (NEW).

The Transformer units the north star adds (BASELINE config #5;
SURVEY.md §5.7): explicit forward/backward as graph nodes, in the znicz
style — ``jax.grad`` is only a test oracle. All math is generic over
``xp`` so the numpy oracle and the traced path share one formula set.

Residual connections are INTERNAL to the attention/FFN units
(``residual=True`` ⇒ y = x + f(x)), so the backward stays a linear
chain like the rest of the zoo; stacking

    MHA(residual) → LayerNorm → FFN(residual) → LayerNorm

yields the classic post-LN transformer block.

Long-context, three regimes: the default single-chip path
materialises the (B,H,S,S) score matrix (fastest for short S);
``attn_block_size`` switches to blocked flash-style attention
(``parallel/flash.py`` — exact, O(S·block) score memory, single
chip); a ``seq_mesh`` shards the sequence ACROSS chips via the
``ppermute`` ring (``parallel/ring.py``).
"""

import contextlib
import functools

import numpy

from veles.memory import Array
from veles.znicz_tpu.nn_units import (
    Forward, GradientDescentBase, forward_unit, gradient_for)
from veles.znicz_tpu.ops import activations as A


def _pow2_divisor(s, cap):
    """Largest power-of-two divisor of ``s``, at most ``cap`` — the
    shared tile-size fallback for the flash/Pallas paths."""
    b = 1
    while b * 2 <= cap and s % (b * 2) == 0:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# per-token dense (operates on the trailing dim of (B, S, D))


class TokenDenseBase(Forward):
    """y = act(x · W + b) over the last axis, any leading shape."""

    ACTIVATION = "linear"

    def __init__(self, workflow, output_features=None, **kwargs):
        super().__init__(workflow, **kwargs)
        if not output_features:
            raise ValueError("token_dense needs output_features")
        self.output_features = int(output_features)

    def output_shape_for(self, ishape):
        return tuple(ishape[:-1]) + (self.output_features,)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        d = self.input.shape[-1]
        self.init_weights((d, self.output_features), d,
                          self.output_features)
        oshape = self.output_shape_for(self.input.shape)
        if not self.output or self.output.shape != oshape:
            self.output.reset(numpy.zeros(oshape, numpy.float32))

    def _forward(self, xp, x, w, b, dot):
        v = dot(x, w)
        if self.include_bias:
            v = v + b
        return A.ACTIVATIONS[self.ACTIVATION][0](xp, v)

    def numpy_run(self):
        x = self.input.map_read().mem.astype(numpy.float32)
        b = self.bias.map_read().mem if self.include_bias else None
        self.output.map_invalidate()
        self.output.mem[...] = self._forward(
            numpy, x, self.weights.map_read().mem, b, numpy.matmul)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        x = ctx.get(self, "input")
        p = ctx.unit_params(self)
        ctx.set(self, "output",
                self._forward(jnp, x, p["weights"], p.get("bias"),
                              ctx.dot)
                .astype(self.stream_dtype(ctx)))

    # -- loss-tail protocol (the 1F1B fold) ---------------------------
    # ops/transformer_stack.py replays the units BETWEEN the block
    # stack and the evaluator per microbatch inside the fused 1F1B
    # schedule (as the last-stage err_fn), so the schedule needs this
    # unit's forward and input-gradient as pure functions. Weight
    # gradients are NOT computed here — the unit's own GD does that
    # once, full-batch, outside the schedule.

    def tail_fwd(self, xp, x, p, dot):
        """Pure forward over explicit params (same math as xla_run)."""
        return self._forward(xp, x, p["weights"], p.get("bias"), dot)

    def tail_bwd(self, xp, y, p, err, dot):
        """Input gradient given this unit's OUTPUT ``y`` (the
        activation derivative is output-expressed, znicz style — see
        GDTokenDenseBase._backward, whose dx arm this mirrors)."""
        d = A.ACTIVATIONS[self.ACTIVATION][1](xp, y)
        dz = err if isinstance(d, float) else err * d
        return dot(dz, p["weights"].T)


@forward_unit("token_dense")
class TokenDense(TokenDenseBase):
    ACTIVATION = "linear"


@forward_unit("token_dense_relu")
class TokenDenseRELU(TokenDenseBase):
    ACTIVATION = "strict_relu"


class GDTokenDenseBase(GradientDescentBase):
    ACTIVATION = "linear"

    def _backward(self, xp, x, y, w, err, dot):
        d = A.ACTIVATIONS[self.ACTIVATION][1](xp, y)
        dz = err if isinstance(d, float) else err * d
        x2 = x.reshape(-1, x.shape[-1])
        dz2 = dz.reshape(-1, dz.shape[-1])
        grad_w = dot(x2.T, dz2)
        # bias grads accumulate in f32 even when dz flows bf16
        grad_b = dz2.sum(axis=0, dtype=xp.float32) \
            if self.include_bias else None
        dx = dot(dz, w.T) if self.need_err_input else None
        return dx, grad_w, grad_b

    def numpy_run(self):
        f = self.forward
        x = f.input.map_read().mem.astype(numpy.float32)
        y = f.output.map_read().mem
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(y.shape)
        dx, gw, gb = self._backward(numpy, x, y,
                                    f.weights.map_read().mem, err,
                                    numpy.matmul)
        if dx is not None:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self.update_weights_numpy(gw, gb)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        x = ctx.get(f, "input")
        y = ctx.get(f, "output")
        err = ctx.get(self, "err_output").reshape(y.shape)
        dx, gw, gb = self._backward(
            jnp, x, y, ctx.unit_params(f)["weights"], err, ctx.dot)
        if dx is not None:
            ctx.set(self, "err_input", dx.astype(ctx.act_dtype))
        self.update_weights_xla(ctx, gw, gb)


@gradient_for(TokenDense)
class GDTokenDense(GDTokenDenseBase):
    ACTIVATION = "linear"


@gradient_for(TokenDenseRELU)
class GDTokenDenseRELU(GDTokenDenseBase):
    ACTIVATION = "strict_relu"


# ---------------------------------------------------------------------------
# transformer FFN block: y = [x +] act(x·W1+b1)·W2+b2


@forward_unit("transformer_ffn")
class TransformerFFN(Forward):
    PARAMS = ("weights", "bias", "weights2", "bias2")
    ACTIVATION = "strict_relu"

    def __init__(self, workflow, hidden=None, residual=True, **kwargs):
        super().__init__(workflow, **kwargs)
        self.hidden = hidden
        self.residual = residual
        self.weights2 = Array()
        self.bias2 = Array()

    def output_shape_for(self, ishape):
        return tuple(ishape)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        d = self.input.shape[-1]
        hidden = self.hidden or 4 * d
        self.hidden = hidden
        self.init_weights((d, hidden), d, hidden)
        if not self.weights2 or self.weights2.shape != (hidden, d):
            self.weights2.reset(
                numpy.zeros((hidden, d), numpy.float32))
            self.fill_array(self.weights2, self.weights_filling,
                            self.weights_stddev
                            or self.default_weights_stddev(hidden, d))
            self.bias2.reset(numpy.zeros(d, numpy.float32))
        if not self.output or self.output.shape != self.input.shape:
            self.output.reset(
                numpy.zeros(self.input.shape, numpy.float32))

    def _forward(self, xp, x, w1, b1, w2, b2, dot):
        hcur = A.ACTIVATIONS[self.ACTIVATION][0](xp, dot(x, w1) + b1)
        y = dot(hcur, w2) + b2
        if self.residual:
            y = y + x
        return y, hcur

    def numpy_run(self):
        x = self.input.map_read().mem.astype(numpy.float32)
        y, hcur = self._forward(
            numpy, x, self.weights.map_read().mem,
            self.bias.map_read().mem,
            self.weights2.map_read().mem, self.bias2.map_read().mem,
            numpy.matmul)
        self.output.map_invalidate()
        self.output.mem[...] = y
        self._cache_h = hcur

    def xla_run(self, ctx):
        import jax.numpy as jnp
        x = ctx.get(self, "input")
        p = ctx.unit_params(self)
        y, hcur = self._forward(jnp, x, p["weights"], p["bias"],
                                p["weights2"], p["bias2"], ctx.dot)
        ctx.set(self, "output", y.astype(ctx.act_dtype))
        ctx.set(self, "cache_h", hcur)


@gradient_for(TransformerFFN)
class GDTransformerFFN(GradientDescentBase):
    EXTRA_PARAMS = (("weights2", False), ("bias2", True))

    def _backward(self, xp, x, w1, w2, hcur, err, dot):
        f = self.forward
        d = x.shape[-1]
        dh = dot(err, w2.T)
        dh = dh * A.ACTIVATIONS[f.ACTIVATION][1](xp, hcur)
        gw2 = dot(hcur.reshape(-1, f.hidden).T, err.reshape(-1, d))
        gb2 = err.reshape(-1, d).sum(axis=0, dtype=xp.float32)
        gw1 = dot(x.reshape(-1, d).T, dh.reshape(-1, f.hidden))
        gb1 = dh.reshape(-1, f.hidden).sum(axis=0, dtype=xp.float32)
        dx = dot(dh, w1.T)
        if f.residual:
            dx = dx + err
        return dx, gw1, gb1, gw2, gb2

    def numpy_run(self):
        f = self.forward
        x = f.input.map_read().mem.astype(numpy.float32)
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(x.shape)
        dx, gw1, gb1, gw2, gb2 = self._backward(
            numpy, x, f.weights.map_read().mem,
            f.weights2.map_read().mem, f._cache_h, err, numpy.matmul)
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self.update_weights_numpy(gw1, gb1)
        self.update_extra_numpy({"weights2": gw2, "bias2": gb2})

    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        x = ctx.get(f, "input")
        err = ctx.get(self, "err_output").reshape(x.shape)
        p = ctx.unit_params(f)
        hcur = ctx.get(f, "cache_h")
        dx, gw1, gb1, gw2, gb2 = self._backward(
            jnp, x, p["weights"], p["weights2"], hcur, err, ctx.dot)
        if self.need_err_input:
            ctx.set(self, "err_input", dx.astype(ctx.act_dtype))
        self.update_weights_xla(ctx, gw1, gb1)
        self.update_extra_xla(ctx, {"weights2": gw2, "bias2": gb2})


# ---------------------------------------------------------------------------
# multi-head attention

# The dense softmax-attention core — the ONE copy of the formula pair,
# shared by the unit below and the fused block stack
# (parallel/pipeline.py). q/k/v: (B, H, S, dh).


def _core_scope(xp=None):
    """The ``veles.core`` sub-scope of a traced attention unit: the
    attention proper, apart from the unit's projections and head
    transposes in a device trace (nothing for the numpy oracle)."""
    if xp is numpy:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope("veles.core")


def dense_attention_core_fwd(xp, q, k, v, causal, scale, dot=None,
                             window=None):
    """(probs, ctx) with ctx = softmax(qkᵀ·scale [+ causal mask])·v.
    ``dot``: matmul implementation (``ctx.dot`` on the traced path for
    bf16 MXU inputs; defaults to the plain xp matmul). ``window``: a
    causal query sees itself and the ``window - 1`` tokens before it
    (keys further back are masked like those after it)."""
    dot = dot or xp.matmul
    s = q.shape[2]
    scores = dot(q, k.transpose(0, 1, 3, 2)) * scale
    if causal:
        mask = numpy.triu(numpy.full((s, s), -1e9, numpy.float32), 1)
        if window is not None:
            mask += numpy.tril(
                numpy.full((s, s), -1e9, numpy.float32), -int(window))
        scores = scores + xp.asarray(mask)
    elif window is not None:
        raise ValueError("a window is of a causal row")
    probs = A.softmax(xp, scores)
    return probs, dot(probs, v)


def dense_attention_core_bwd(xp, q, k, v, probs, dctx, scale,
                             dot=None):
    """Backward of the core: (dq, dk, dv). The causal mask needs no
    re-application — masked probs are exactly zero."""
    dot = dot or xp.matmul
    dprobs = dot(dctx, v.transpose(0, 1, 3, 2))
    dv = dot(probs.transpose(0, 1, 3, 2), dctx)
    dscores = probs * (dprobs - (dprobs * probs)
                       .sum(axis=-1, keepdims=True))
    dscores = dscores * scale
    dq = dot(dscores, k)
    dk = dot(dscores.transpose(0, 1, 3, 2), q)
    return dq, dk, dv


@forward_unit("attention")
class MultiHeadAttention(Forward):
    """Causal (or full) multi-head self-attention over (B, S, D), with
    optional internal residual (y = x + attn(x)).

    Parameters: fused qkv projection ``weights`` (D, 3D) and output
    projection ``weights_out`` (D, D); biases optional.
    """

    PARAMS = ("weights", "bias", "weights_out", "bias_out")

    def __init__(self, workflow, heads=4, causal=True, residual=True,
                 **kwargs):
        super().__init__(workflow, **kwargs)
        self.heads = int(heads)
        self.causal = causal
        self.residual = residual
        #: a causal query sees itself and the ``window - 1`` tokens
        #: before it (None: all of them); set by the units that have
        #: one (``ops/gqa_attention.py``), read by the flash cores
        self.window = None
        self.weights_out = Array()
        self.bias_out = Array()
        #: jax Mesh with a sequence axis -> the traced path streams
        #: K/V around the ring (sequence parallelism) instead of
        #: materialising the (B,H,S,S) score matrix
        self.seq_mesh = None
        self.seq_axis = "seq"
        #: extra batch-dim sharding axis on a composed SPxDP mesh
        self.seq_batch_axis = None
        #: mesh axes the batch / the heads are split over, as
        #: parallel.setup_data_parallel / setup_tensor_parallel set
        #: them: what the Pallas kernels, which GSPMD cannot partition,
        #: are shard_map-ped over (parallel.kernel_per_shard)
        self.kernel_batch_axis = None
        self.kernel_head_axis = None
        #: single-chip long-context mode: block the K/V sequence so
        #: the (B,H,S,S) score matrix is never materialised (flash-
        #: style online softmax, exact — parallel/flash.py). Must
        #: divide the sequence length. None = dense.
        self.attn_block_size = kwargs.get("attn_block_size")
        #: "pallas" routes the blocked path through the hand-written
        #: Pallas TPU kernels (parallel/pallas_attention.py) instead
        #: of the lax.scan formulation; None/"scan" keeps the scan.
        #: Same exact math, same cache signature — a pure kernel swap.
        self.attn_impl = kwargs.get("attn_impl")
        if self.attn_impl not in (None, "scan", "pallas"):
            raise ValueError(
                "attn_impl must be None, 'scan' or 'pallas', got %r"
                % (self.attn_impl,))
        #: explicit Pallas kernel tile (None = the measured auto
        #: choice, _pallas_block): the VMEM escape hatch for head
        #: dims where the auto tile's scoped-VMEM footprint is too
        #: large. Must divide the (per-shard) sequence length.
        self.pallas_tile = kwargs.get("pallas_tile")

    def output_shape_for(self, ishape):
        return tuple(ishape)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        b, s, d = self.input.shape
        if d % self.heads and not getattr(self, "head_dim", None):
            # (a unit that states its head's width, ops/gqa_attention.py,
            # has heads x head_dim of its own beside d)
            raise ValueError("dim %d not divisible by %d heads"
                             % (d, self.heads))
        self.init_weights((d, 3 * d), d, 3 * d)
        if not self.weights_out or self.weights_out.shape != (d, d):
            self.weights_out.reset(numpy.zeros((d, d), numpy.float32))
            self.fill_array(self.weights_out, self.weights_filling,
                            self.weights_stddev
                            or self.default_weights_stddev(d, d))
        if self.include_bias:
            if not self.bias or self.bias.shape != (3 * d,):
                self.bias.reset(numpy.zeros(3 * d, numpy.float32))
            if not self.bias_out or self.bias_out.shape != (d,):
                self.bias_out.reset(numpy.zeros(d, numpy.float32))
        if not self.output or self.output.shape != self.input.shape:
            self.output.reset(
                numpy.zeros(self.input.shape, numpy.float32))

    # shared math ------------------------------------------------------

    def _split(self, t):
        b, s, d = t.shape
        h = self.heads
        return t.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)

    def _merge(self, t):
        b, h, s, dh = t.shape
        return t.transpose(0, 2, 1, 3).reshape(b, s, h * dh)

    def _fwd_core(self, xp, x, w, bqkv, wo, bo, dot=None):
        dot = dot or xp.matmul
        b, s, d = x.shape
        dh = d // self.heads
        qkv = dot(x, w)
        if self.include_bias:
            qkv = qkv + bqkv
        q = self._split(qkv[..., :d])
        k = self._split(qkv[..., d:2 * d])
        v = self._split(qkv[..., 2 * d:])
        scale = numpy.float32(1.0 / numpy.sqrt(dh))
        with _core_scope(xp):
            probs, ctx = dense_attention_core_fwd(
                xp, q, k, v, self.causal, scale, dot)
        merged = self._merge(ctx)
        y = dot(merged, wo)
        if self.include_bias:
            y = y + bo
        if self.residual:
            y = y + x
        return y, (q, k, v, probs, merged)

    def numpy_run(self):
        x = self.input.map_read().mem.astype(numpy.float32)
        y, cache = self._fwd_core(
            numpy, x, self.weights.map_read().mem,
            self.bias.map_read().mem if self.include_bias else None,
            self.weights_out.map_read().mem,
            self.bias_out.map_read().mem if self.include_bias else None)
        self.output.map_invalidate()
        self.output.mem[...] = y
        self._cache = cache

    #: blocked-attention auto policy: with ``attn_impl=None`` the
    #: Pallas kernels take the attention proper on a real TPU once S
    #: reaches this bound; below it the XLA scan (``parallel/flash.py``)
    #: does. Measured on a v5e with the 110M LM at 16,384 tokens a
    #: step: ``benchmark/run.py``'s S=512 cell with the sequence length
    #: and batch varied (PERF.md section 6, PR 27; tokens/s: scan |
    #: the general K-loop kernels by tile | the short-sequence kernels,
    #: one tile a row):
    #:
    #:   S=512 batch 32   111.3k | 512: 136.2k 256: 124.1k 128: 107.9k | 151.3k
    #:   S=256 batch 64   152.8k |             256: 138.6k 128: 125.4k | 159.0k
    #:   S=128 batch 128  167.8k |                         128: 131.2k | 155.1k
    #:
    #: At S=512 the scan's (B, H, S, block) score tile is 201 MB at
    #: batch 32 and every pass over it an HBM round trip; the kernels
    #: keep it in VMEM. The shorter S, the smaller that tile and the
    #: better the scan's one step fuses, while the kernels' cost a
    #: token does not fall with S. From 1024 up the kernels also SKIP
    #: the fully-masked K blocks through their causal loop bound,
    #: which the scan cannot. ``attn_impl="scan"`` forces the scan at
    #: any S.
    PALLAS_AUTO_MIN_S = 256
    #: ... and below S=1024 only where ``_pallas_block`` finds a tile
    #: of at least this size: the 128 tile lost to the scan at S=512,
    #: 256 and 128 (table above). From 1024 up any tile goes, as it
    #: always did.
    PALLAS_AUTO_MIN_TILE = 256

    def _auto_pallas(self, ctx, s):
        """Does ``attn_impl=None`` take the Pallas kernels for a
        (per-shard) sequence of length ``s``? The one rule behind
        ``_traced_mode`` and the ring's ``_ring_inner``."""
        if self.attn_impl is not None or s < self.PALLAS_AUTO_MIN_S \
                or self._pallas_interpret(ctx):
            return False
        return s >= 1024 or \
            self._pallas_block(s) >= self.PALLAS_AUTO_MIN_TILE

    def _traced_mode(self, ctx, s):
        """ONE dispatch resolver for the traced forward AND backward
        (they must agree — the cache layout follows the mode):
        "ring" | "pallas" | "scan" (blocked) | "dense"."""
        if self.seq_mesh is not None:
            return "ring"
        if self.attn_impl == "pallas":
            return "pallas"
        if not self.attn_block_size:
            return "dense"
        return "pallas" if self._auto_pallas(ctx, s) else "scan"

    @staticmethod
    def _pallas_interpret(ctx):
        """Pallas ``interpret`` flag for this trace, from the platform
        of the device the step COMPILES for — the same source
        ``_traced_mode`` dispatches on: real Mosaic kernels on a TPU,
        the interpreter elsewhere (a forced ``attn_impl="pallas"`` in
        the CPU tests)."""
        from veles.backends import is_tpu
        return not is_tpu(ctx._compiler.device.platform)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        x = ctx.get(self, "input")
        p = ctx.unit_params(self)
        mode = self._traced_mode(ctx, x.shape[1])
        names = ("q", "k", "v", "out_heads", "lse", "merged")
        if mode == "ring":
            y, cache = self._fwd_ring(jnp, x, p, ctx, ctx.dot)
        elif mode in ("pallas", "scan"):
            y, cache = self._fwd_flash(jnp, x, p, ctx, mode)
        else:
            y, cache = self._fwd_core(
                jnp, x, p["weights"], p.get("bias"), p["weights_out"],
                p.get("bias_out"), ctx.dot)
            names = ("q", "k", "v", "probs", "merged")
        ctx.set(self, "output", y.astype(ctx.act_dtype))
        for name, t in zip(names, cache):
            ctx.set(self, "cache_" + name, t)

    def _project_qkv(self, x, p, dot):
        d = x.shape[-1]
        qkv = dot(x, p["weights"])
        if self.include_bias:
            qkv = qkv + p["bias"]
        return (self._split(qkv[..., :d]),
                self._split(qkv[..., d:2 * d]),
                self._split(qkv[..., 2 * d:]))

    def _finish(self, x, merged, p, dot):
        y = dot(merged, p["weights_out"])
        if self.include_bias:
            y = y + p["bias_out"]
        if self.residual:
            y = y + x
        return y

    def _fwd_flash(self, xp, x, p, ctx, mode):
        """Forward of the single-chip flash modes ("scan": the
        ``lax.scan`` formulation, O(S·block) score memory; "pallas":
        the kernels), by ``core_fwd``. q/k/v live in the compute dtype
        (bf16 on TPU): every consumer is a matmul, the kernels' rows
        take half the VMEM (K/V ride whole rows — the difference
        between S=8k fitting and a scoped-vmem OOM), the scan's
        probs/ds tiles inherit it (halving their HBM traffic), and
        the backward caches cost half the memory."""
        cd = ctx._compiler.device.compute_dtype
        q, k, v = (t.astype(cd)
                   for t in self._project_qkv(x, p, ctx.dot))
        with _core_scope():
            out_heads, lse = self.core_fwd(ctx, mode, q, k, v)
        merged = self._merge(out_heads)
        y = self._finish(x, merged, p, ctx.dot)
        return y, (q, k, v, out_heads, lse, merged)

    def _pallas_block(self, s=None):
        """Pallas kernel tile for a sequence of length ``s`` (default:
        the unit's full sequence; the ring path passes its per-shard
        length): ``pallas_tile`` when set (the explicit VMEM escape
        hatch — must divide), else the largest power-of-two divisor
        of ``s`` up to 512 — the measured v5e optimum at both ends of
        the auto-select regime: at S=512 one 512 tile a row beat 256
        and 128 (the table above ``PALLAS_AUTO_MIN_S``; a sequence of
        one tile runs ``pallas_attention``'s short-sequence kernels).
        Above it (PERF.md section 6, PR 29: the K-loop kernels alone,
        a layer call of 32,768 tokens, 12 heads of 64, ms forward /
        fused backward by tile):

          S=8192   256: 13.30 / 20.29   512: 6.77 / 13.08   1024: 6.08 / 12.73
          S=4096                        512: 3.80 /  7.61   1024: 3.54 /  7.69
          S=2048                        512: 2.35 /  4.61   1024: 2.37 /  5.00
          S=1024                        512: 1.63 /  3.22   1024: 1.73 /  3.28

        (unequal tiles at S=8192: 1024x512 6.12 / 13.02, 512x1024
        6.45 / 13.43, 2048x512 6.49 / 13.63; a 2048 key tile does not
        fit VMEM). 1024 is 3% of a step faster from S=4096 up — a
        tile costs a fixed ~0.1 us of loop overhead whatever its
        size, against 6% more masked work on the diagonal — and was
        NOT taken: its kernels are 0.4 MB of code larger at each of a
        step's 36 call sites, and the 110M step's peak memory at
        S=8192 rose 19 MB where the 512 tile lowers it
        (``pallas_tile=1024`` sets it by hand). ``attn_block_size``
        tunes the SCAN formulation and does not constrain the kernel
        tile (honoring it cost 36-50% at long S, round 4)."""
        if s is None:
            s = self.input.shape[1]
        if self.pallas_tile:
            if s % self.pallas_tile:
                raise ValueError(
                    "%s: pallas_tile %d does not divide sequence "
                    "length %d" % (self.name, self.pallas_tile, s))
            return self.pallas_tile
        return _pow2_divisor(s, 512)

    def _pallas_on_mesh(self, ctx, kernel, in_kinds, out_kinds):
        """``kernel`` as the step's mesh needs it: unchanged without a
        mesh, else per shard over the axes ``parallel.setup_*`` named
        for this unit (``parallel.kernel_per_shard``)."""
        from veles.znicz_tpu import parallel
        return parallel.kernel_per_shard(
            kernel, ctx._compiler.device.mesh, self.kernel_batch_axis,
            self.kernel_head_axis, in_kinds, out_kinds)

    def core_fwd(self, ctx, mode, q, k, v):
        """The attention proper of the flash modes, "pallas" and
        "scan", on (B, H, S, dh) heads in the compute type ->
        (out_heads, lse). The one place that turns a mode into its
        kernel: a unit that brings its own projections
        (``ops/gqa_attention.py``) calls this and :meth:`core_bwd`,
        like the paths below, inside ``_core_scope()``."""
        if mode == "pallas":
            from veles.znicz_tpu.parallel import pallas_attention as PA
            blk = self._pallas_block()
            kernel = self._pallas_on_mesh(ctx, PA.jitted(
                PA.flash_attention_fwd, causal=self.causal,
                block_q=blk, block_k=blk,
                interpret=self._pallas_interpret(ctx),
                window=self.window), "ttt", "tr")
            return kernel(q, k, v)
        from veles.znicz_tpu.parallel import flash
        return flash.blocked_attention_fwd(
            q, k, v, causal=self.causal, block=self.attn_block_size,
            dot=ctx.dot, window=self.window)

    def core_bwd(self, ctx, mode, q, k, v, out_heads, lse, dctx):
        """-> (dq, dk, dv) of :meth:`core_fwd`; ``dctx`` by head."""
        cd = ctx._compiler.device.compute_dtype
        if mode == "pallas":
            from veles.znicz_tpu.parallel import pallas_attention as PA
            blk = self._pallas_block()
            kernel = self._pallas_on_mesh(ctx, PA.jitted(
                PA.flash_attention_bwd, causal=self.causal,
                block_q=blk, block_k=blk,
                interpret=self._pallas_interpret(ctx),
                window=self.window),
                "ttttrt", "ttt")     # q k v out lse dout -> dq dk dv
            return kernel(q, k, v, out_heads, lse, dctx.astype(cd))
        from veles.znicz_tpu.parallel import flash
        return flash.blocked_attention_bwd(
            q, k, v, out_heads, lse, dctx.astype(cd),
            causal=self.causal, block=self.attn_block_size,
            dot=ctx.dot, window=self.window)

    def _ring_inner(self, ctx):
        """(inner, block) for the ring path — which kernel each ring
        step's LOCAL block runs (round-4 composition of the measured
        single-chip flash wins with cross-chip SP). Shared by forward
        and backward (the cache layout is the same either way, but
        the traced programs must agree). Policy mirrors
        ``_traced_mode``: explicit ``attn_impl`` wins; auto takes the
        Pallas kernels on a real TPU where the PER-SHARD sequence
        passes the same rule (``_auto_pallas``); a set
        ``attn_block_size`` routes
        the local block through the scan flash; otherwise the fused
        dense block (the short-shard default)."""
        s_loc = self.input.shape[1] // self.seq_mesh.shape[self.seq_axis]
        if self.attn_impl == "pallas":
            inner = "pallas"
        elif self.attn_impl == "scan":
            inner = "scan"
        elif self._auto_pallas(ctx, s_loc):
            inner = "pallas"
        elif self.attn_block_size:
            inner = "scan"
        else:
            return None, None
        if inner == "pallas":
            # the kernel picks its own measured-optimum tile
            return inner, self._pallas_block(s_loc)
        # scan inner: attn_block_size when it divides the SHARD
        # length, else the largest power-of-two divisor — NOT a loud
        # error: attn_block_size is tuned against the global S, and
        # the per-shard length is a deployment detail (the same
        # config must run at seq=1 and seq=8), so a non-dividing
        # value degrades to the nearest workable tile
        if self.attn_block_size and s_loc % self.attn_block_size == 0:
            return inner, self.attn_block_size
        return inner, _pow2_divisor(s_loc, 128)

    def _fwd_ring(self, xp, x, p, ctx, dot):
        """Sequence-parallel forward: qkv projection under
        auto-sharding, attention proper via the ppermute ring (each
        step's local block optionally through the flash kernels)."""
        from veles.znicz_tpu.parallel import ring
        inner, block = self._ring_inner(ctx)
        q, k, v = self._project_qkv(x, p, dot)
        if inner is not None:
            cd = ctx._compiler.device.compute_dtype
            q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
        with _core_scope():
            out_heads, lse = ring.ring_self_attention(
                q, k, v, self.seq_mesh, axis=self.seq_axis,
                causal=self.causal, batch_axis=self.seq_batch_axis,
                inner=inner, block=block, dot=dot,
                interpret=self._pallas_interpret(ctx))
        merged = self._merge(out_heads)
        y = self._finish(x, merged, p, dot)
        return y, (q, k, v, out_heads, lse, merged)


@gradient_for(MultiHeadAttention)
class GDMultiHeadAttention(GradientDescentBase):
    """Hand-written attention backward (verified vs jax.grad)."""

    EXTRA_PARAMS = (("weights_out", False), ("bias_out", True))

    def _bwd_core(self, xp, x, w, wo, cache, err, dot=None):
        dot = dot or xp.matmul
        f = self.forward
        b, s, d = x.shape
        dh = d // f.heads
        q, k, v, probs, merged = cache
        scale = numpy.float32(1.0 / numpy.sqrt(dh))

        gwo = dot(merged.reshape(-1, d).T, err.reshape(-1, d))
        gbo = err.reshape(-1, d).sum(axis=0, dtype=xp.float32)
        dmerged = dot(err, wo.T)
        dctx = f._split(dmerged)                       # (B,H,S,dh)
        with _core_scope(xp):
            dq, dk, dv = dense_attention_core_bwd(
                xp, q, k, v, probs, dctx, scale, dot)
        dqkv = xp.concatenate(
            [f._merge(dq), f._merge(dk), f._merge(dv)], axis=-1)
        gw = dot(x.reshape(-1, d).T, dqkv.reshape(-1, 3 * d))
        gb = dqkv.reshape(-1, 3 * d).sum(axis=0, dtype=xp.float32)
        dx = dot(dqkv, w.T)
        if f.residual:
            dx = dx + err
        return dx, gw, gb, gwo, gbo

    def numpy_run(self):
        f = self.forward
        x = f.input.map_read().mem.astype(numpy.float32)
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(x.shape)
        dx, gw, gb, gwo, gbo = self._bwd_core(
            numpy, x, f.weights.map_read().mem,
            f.weights_out.map_read().mem, f._cache, err)
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self.update_weights_numpy(gw, gb if f.include_bias else None)
        self.update_extra_numpy({
            "weights_out": gwo,
            "bias_out": gbo if f.include_bias else None})

    def _bwd_outer(self, xp, x, p, ctx, err, attn_bwd):
        """Shared backward scaffolding for the cached (out_heads, lse)
        paths: output projection grads, then ``attn_bwd(q, k, v,
        out_heads, lse, dctx) -> (dq, dk, dv)``, then the qkv
        projection grads + residual."""
        f = self.forward
        d = x.shape[-1]
        dot = ctx.dot
        q, k, v, out_heads, lse, merged = (
            ctx.get(f, "cache_" + n)
            for n in ("q", "k", "v", "out_heads", "lse", "merged"))
        gwo = dot(merged.reshape(-1, d).T, err.reshape(-1, d))
        gbo = err.reshape(-1, d).sum(axis=0, dtype=xp.float32)
        dmerged = dot(err, p["weights_out"].T)
        dctx = f._split(dmerged)
        with _core_scope():
            dq, dk, dv = attn_bwd(q, k, v, out_heads, lse, dctx)
        dqkv = xp.concatenate(
            [f._merge(dq), f._merge(dk), f._merge(dv)], axis=-1)
        gw = dot(x.reshape(-1, d).T, dqkv.reshape(-1, 3 * d))
        gb = dqkv.reshape(-1, 3 * d).sum(axis=0, dtype=xp.float32)
        dx = dot(dqkv, p["weights"].T)
        if f.residual:
            dx = dx + err
        return dx, gw, gb, gwo, gbo

    def _bwd_ring(self, xp, x, p, ctx, err):
        """Sequence-parallel backward via the ring (dk/dv circulate a
        full circle back to their home shards); the inner-block kernel
        resolves identically to the forward's."""
        from veles.znicz_tpu.parallel import ring
        f = self.forward
        inner, block = f._ring_inner(ctx)
        cd = ctx._compiler.device.compute_dtype
        cast = (lambda t: t.astype(cd)) if inner is not None \
            else (lambda t: t)
        return self._bwd_outer(
            xp, x, p, ctx, err,
            lambda q, k, v, o, lse, dctx: ring.ring_self_attention_bwd(
                q, k, v, o, lse, cast(dctx), f.seq_mesh,
                axis=f.seq_axis, causal=f.causal,
                batch_axis=f.seq_batch_axis, inner=inner, block=block,
                dot=ctx.dot, interpret=f._pallas_interpret(ctx)))

    def _bwd_flash(self, xp, x, p, ctx, err, mode):
        """Backward of the single-chip flash modes ("scan": block
        recomputation; "pallas": the kernels), by the forward's
        ``core_bwd``."""
        return self._bwd_outer(
            xp, x, p, ctx, err,
            functools.partial(self.forward.core_bwd, ctx, mode))

    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        x = ctx.get(f, "input")
        err = ctx.get(self, "err_output").reshape(x.shape)
        p = ctx.unit_params(f)
        mode = f._traced_mode(ctx, x.shape[1])
        if mode == "ring":
            dx, gw, gb, gwo, gbo = self._bwd_ring(jnp, x, p, ctx, err)
        elif mode in ("pallas", "scan"):
            dx, gw, gb, gwo, gbo = self._bwd_flash(
                jnp, x, p, ctx, err, mode)
        else:
            cache = tuple(ctx.get(f, "cache_" + n)
                          for n in ("q", "k", "v", "probs", "merged"))
            dx, gw, gb, gwo, gbo = self._bwd_core(
                jnp, x, p["weights"], p["weights_out"], cache, err,
                ctx.dot)
        if self.need_err_input:
            ctx.set(self, "err_input", dx.astype(ctx.act_dtype))
        self.update_weights_xla(ctx, gw, gb if f.include_bias else None)
        self.update_extra_xla(ctx, {
            "weights_out": gwo,
            "bias_out": gbo if f.include_bias else None})
