"""EVA attention (Zheng, Wang & Kong, "Efficient Attention via Control
Variates", ICLR 2023) as a pre-norm residual block over (B, S, d):
exact softmax over the query's own window, and over ONE learned summary
of each chunk of every earlier window, in one softmax.

Per head, with head size dh, window W, chunk C and query n in window
w = n // W:

    q, k, v = rope(n W_q), rope(n W_k), n W_v      n = rms(x; g)
    k^_c    = sum_{m in c} softmax_m(mu . k_m) k_m     (chunk c: C keys)
    v^_c    = sum_{m in c} softmax_m(phi . k_m) v_m
    L(n)    = {m : wW <= m <= n}                      (local keys)
    R(n)    = {c : (c + 1) C <= wW}        (every earlier window's chunks)
    o_n     = softmax over [q.k_m / sqrt(dh), m in L(n) |
                            q.k^_c / sqrt(dh), c in R(n)] of [v_m | v^_c]
    y       = x + merge(o) W_o

``mu`` (``pool_key``) and ``phi`` (``pool_value``) are (heads, dh)
vectors learned beside the projections; the summaries pool the ROTATED
keys. With C = 1 a summary is its own key and with W >= S there is no
earlier window: either way EVA is exact causal softmax attention.

The projections, rotation and output are :class:`GQAttention`'s (q/k
norm, gate and sliding window refused). The core runs in three parts
under ``veles.eva`` inside ``veles.core``, forward and backward:

* ``veles.eva.summary``: the pooled k^, v^ of every chunk, in float32
  statistics;
* ``veles.eva.local``: the causal K-loop kernels of
  ``parallel/pallas_attention.py`` on the windows folded into the rows,
  (B, H, S, dh) -> (B, H S / W, W, dh): a row a window;
* ``veles.eva.remote``: the queries against the S / C summaries, on the
  same kernels' ``prefix`` (W, W / C): query n sees the first
  (n // W) W / C summaries, tiles dividing W / C, no mask; a query of
  window 0 sees none (out 0, lse -inf, which the merge weighs 0).

The two parts merge by their lse (``flash.merge_partial``, the ring's
rule), and each part's backward kernel takes the MERGED out and lse and
one ``delta`` of the merged out: its share of the whole softmax's
gradient. No S x S or S x S / C score tensor exists off the kernels.
Off a TPU the kernels run in the Pallas interpreter. Of the unit only
its input, the merged context and the lse live from the forward to the
backward, which runs the norm, projections, rotation and summaries
again and pulls dq, dk, dv, dk^ and dv^ back through them to x, the
projections, mu and phi: at S = 16384 the kept q, k, v and their
norm's products were 0.9 GB a layer, more than the chip has beside the
four layers' weights (PERF.md, section 6).

Counters on the step's metric fetch, per layer (all heads and rows of a
training step): ``veles_eva_summaries_total`` (chunks pooled),
``veles_eva_local_pairs_total`` and ``veles_eva_remote_pairs_total``
(the query-key and query-summary pairs the definition has) and
``veles_eva_tile_pairs_total`` (pairs of the tiles the kernels' bounds
visit: the causal tiles' rounding in the local part; the remote part's
tiles divide its spans and visit exactly its pairs).
"""

import numpy

from veles import telemetry
from veles.memory import Array
from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.attention import _core_scope, _pow2_divisor
from veles.znicz_tpu.ops.gqa_attention import GQAttention
from veles.znicz_tpu.ops.vjp_units import GDVjp


def summarize(pool, k, v, chunk):
    """(k^, v^), (B, H, S / chunk, dh) in the type of ``k``: each chunk
    of ``chunk`` keys pooled by a softmax over its keys' scores against
    ``pool["pool_key"]`` (k^) and ``pool["pool_value"]`` (v^), one
    (H, dh) vector a head; the scores, weights and sums in float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, h, s, dh = k.shape
    kc = k.astype(f32).reshape(b, h, s // chunk, chunk, dh)
    vc = v.astype(f32).reshape(b, h, s // chunk, chunk, dh)

    def pooled(vector, values):
        scores = (kc * vector.astype(f32)[None, :, None, None]).sum(-1)
        weight = jax.nn.softmax(scores, axis=-1)
        return (weight[..., None] * values).sum(-2).astype(k.dtype)

    return pooled(pool["pool_key"], kc), pooled(pool["pool_value"], vc)


def local_pairs(s, window):
    """Query-key pairs of the local part, one (batch, head) row: each
    window's causal triangle (16,785,408 at S = 16384, W = 2048)."""
    return s // window * window * (window + 1) // 2


@forward_unit("eva_attention")
class EvaAttention(GQAttention):
    """``GQAttention``'s projections, rotation and output around EVA's
    core: ``local_window`` W and ``chunk`` C (the model's ``eva_window``
    and ``eva_chunk``)."""

    POOL = ("pool_key", "pool_value")

    def __init__(self, workflow, local_window=2048, chunk=16, **kwargs):
        name = kwargs.get("name") or type(self).__name__
        for key in ("window", "gate"):
            if kwargs.get(key):
                raise ValueError("%s: EVA attention has no %s" % (name, key))
        kv = kwargs.get("kv_heads")
        if kv and kv != kwargs.get("heads", 4):
            raise ValueError("%s: EVA attention pools a summary a head: "
                             "as many K/V heads as query heads" % name)
        kwargs.setdefault("qk_norm", False)
        if kwargs["qk_norm"]:
            raise ValueError("%s: EVA attention has no q/k norm" % name)
        self.local_window = int(local_window)
        self.chunk = int(chunk)
        if self.chunk < 1 or self.local_window % self.chunk:
            raise ValueError("%s: chunks of %r tokens tile a window of %r"
                             % (name, chunk, local_window))
        super().__init__(workflow, **kwargs)
        self.PARAMS = self.PARAMS + self.POOL
        for name in self.POOL:
            setattr(self, name, Array())

    def param_specs(self, ishape):
        specs = super().param_specs(ishape)
        scale = float(1.0 / numpy.sqrt(self.head_dim))
        specs.update({name: ((self.heads, self.head_dim), scale)
                      for name in self.POOL})
        return specs

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        s = self.input.shape[1]
        if s % self.window_of(s):
            raise ValueError("%s: windows of %d tokens tile a sequence of "
                             "%d" % (self.name, self.local_window, s))

    def window_of(self, s):
        """The window a row of ``s`` is cut into: W, or the whole row
        where W >= S."""
        return min(self.local_window, s)

    def remote(self, s):
        """Does a row of ``s`` have windows before its last?"""
        return s > self.window_of(s)

    # -- the core ------------------------------------------------------------

    def kernels(self, ctx, s):
        """{part: (forward, backward)} of the jitted kernels at a row
        of ``s``: the local part's causal pair at a row of a window, the
        remote part's prefix pair over the summaries, its key tile the
        largest power of two dividing a window's W / C summaries, up to
        512 (128 at W = 2048, C = 16: no tile is cut)."""
        from veles.znicz_tpu.parallel import pallas_attention as PA
        window = self.window_of(s)
        local = self._pallas_block(window)
        block_k = _pow2_divisor(window // self.chunk, 512)
        common = {"interpret": self._pallas_interpret(ctx)}
        parts = {"local": dict(common, causal=True, block_q=local,
                               block_k=local),
                 "remote": dict(common, causal=False, block_q=local,
                                block_k=block_k,
                                prefix=(window, window // self.chunk))}
        return {part: (PA.jitted(PA.flash_attention_fwd, **keys),
                       PA.jitted(PA.flash_attention_bwd, **keys))
                for part, keys in parts.items()}

    def folded(self, t):
        """(B, H, S, ...) -> (B, H S / W, W, ...): a window a row."""
        b, h, s = t.shape[:3]
        window = self.window_of(s)
        return t.reshape((b, h * (s // window), window) + t.shape[3:])

    def eva_fwd(self, ctx, q, k, v, kh, vh):
        """-> (merged out in the compute type, merged lse (B, H, S))."""
        import jax
        import jax.numpy as jnp
        from veles.znicz_tpu.parallel.flash import merge_partial
        b, h, s, _ = q.shape
        kernels = self.kernels(ctx, s)
        with jax.named_scope("veles.eva.local"):
            out, lse = kernels["local"][0](*map(self.folded, (q, k, v)))
            out, lse = out.reshape(q.shape), lse.reshape(b, h, s)
        if not self.remote(s):
            return out, lse
        with jax.named_scope("veles.eva.remote"):
            o_r, lse_r = kernels["remote"][0](q, kh, vh)
        out, lse = merge_partial(out.astype(jnp.float32), lse, o_r, lse_r)
        return out.astype(q.dtype), lse

    def eva_bwd(self, ctx, q, k, v, kh, vh, out, lse, dout):
        """-> (dq, dk, dv) of the local part and dq's remote share
        added, (dk^, dv^) of the remote part (None where W >= S)."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        b, h, s, _ = q.shape
        kernels = self.kernels(ctx, s)
        delta = (dout.astype(f32) * out.astype(f32)).sum(-1)
        with jax.named_scope("veles.eva.local"):
            grads = kernels["local"][1](
                *map(self.folded, (q, k, v, out, lse, dout)),
                delta=self.folded(delta))
            dq, dk, dv = (t.reshape(q.shape) for t in grads)
        if not self.remote(s):
            return (dq, dk, dv), None
        with jax.named_scope("veles.eva.remote"):
            dq_r, dkh, dvh = kernels["remote"][1](
                q, kh, vh, out, lse, dout, delta=delta)
        dq = (dq.astype(f32) + dq_r.astype(f32)).astype(dq.dtype)
        return (dq, dk, dv), (dkh, dvh)

    def parts(self, ctx):
        """The unit's parameters as its three stages take them: (the
        projections' and the norm's, mu and phi, W_o)."""
        p, _ = self.split_params(ctx)
        pool = {name: p[name] for name in self.POOL}
        pre = {k: t for k, t in p.items()
               if k != "weights_out" and k not in pool}
        return pre, pool, {"weights_out": p["weights_out"]}

    def front(self, ctx, pre, pool, x):
        """x -> (q, k, v, k^, v^): the norm, projections and rotation,
        and the summaries (None where a row has no earlier window).
        Nothing of it lives from the forward to the backward: the
        backward runs it again (:class:`GDEvaAttention`)."""
        import jax
        q, k, v = self.project(ctx, pre, x)
        if not self.remote(x.shape[1]):
            return q, k, v, None, None
        with _core_scope(), jax.named_scope("veles.eva"), \
                jax.named_scope("veles.eva.summary"):
            kh, vh = summarize(pool, k, v, self.chunk)
        return q, k, v, kh, vh

    def xla_run(self, ctx):
        import jax
        import jax.numpy as jnp
        x = ctx.get(self, "input")
        pre, pool, post = self.parts(ctx)

        def finish(post, merged):
            return self.finish(ctx, post, merged)

        q, k, v, kh, vh = self.front(ctx, pre, pool, x)
        with _core_scope(), jax.named_scope("veles.eva"):
            out, lse = self.eva_fwd(ctx, q, k, v, kh, vh)
        merged = self._merge(out)
        y = self.traced(ctx, finish, post, merged)
        ctx.set(self, "output",
                (x.astype(jnp.float32) + y).astype(self.stream_dtype(ctx)))
        if ctx.train:
            # the merged context (W_o's operand too) and the lse are
            # what lives to the backward, beside the input
            ctx.set(self, "core", (merged, lse))
            # rides the metric fetch: the host multiplies by the rows
            ctx.export("eva_tiles_" + self.name,
                       jnp.int32(self.tile_pairs(x.shape[1])))

    # -- counters ------------------------------------------------------------

    def defined_pairs(self, s):
        """(local, remote) pairs of one (batch, head) row."""
        from veles.znicz_tpu.parallel import pallas_attention as PA
        window = self.window_of(s)
        remote = 0 if not self.remote(s) else PA.prefix_pairs(
            s, (window, window // self.chunk))
        return local_pairs(s, window), remote

    def tile_pairs(self, s):
        """Pairs of the tiles the kernels' bounds visit, one (batch,
        head) row: the local part's causal tiles in each window, and the
        remote part's, which are its pairs."""
        from veles.znicz_tpu.parallel import pallas_attention as PA
        window = self.window_of(s)
        blk = self._pallas_block(window)
        return s // window * PA.visited_pairs(window, blk, blk, None) \
            + self.defined_pairs(s)[1]

    def metric_sinks(self):
        return [("eva_tiles_" + self.name, "step_tiles")]

    def metrics_published(self, fresh):
        """``XLAStep``'s hook, once a training step's sinks are filled."""
        if "step_tiles" not in fresh:
            return
        b, s, _ = self.input.shape
        rows = b * self.heads
        local, remote = self.defined_pairs(s)
        telemetry.counter(
            "veles_eva_summaries_total", "Chunk summaries (k^, v^) an EVA "
            "attention layer pooled, training steps", ("layer",)
        ).labels(self.name).inc(rows * (s // self.chunk if remote else 0))
        telemetry.counter(
            "veles_eva_local_pairs_total", "Query-key pairs of the local "
            "windows an EVA attention layer attended, training steps",
            ("layer",)).labels(self.name).inc(rows * local)
        telemetry.counter(
            "veles_eva_remote_pairs_total", "Query-summary pairs of the "
            "earlier windows an EVA attention layer attended, training "
            "steps", ("layer",)).labels(self.name).inc(rows * remote)
        telemetry.counter(
            "veles_eva_tile_pairs_total", "Pairs of the tiles an EVA "
            "attention layer's kernels visited, training steps",
            ("layer",)).labels(self.name).inc(rows * self.step_tiles)


@gradient_for(EvaAttention)
class GDEvaAttention(GDVjp):
    """The backward runs the forward's front again (norm, projections,
    rotation, summaries) behind an ``optimization_barrier`` on the
    cotangent it meets (``vjp_units.recomputed``'s rule), feeds both
    parts' kernels the merged out and lse, and pulls dq, dk, dv, dk^
    and dv^ back through the front to x, the projections, mu and phi:
    of the core only the merged context and the lse live from the
    forward to the backward."""

    EXTRA_PARAMS = (("weights_out", False), ("norm", True),
                    ("pool_key", False), ("pool_value", False))

    def xla_run(self, ctx):
        import functools
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        f = self.forward
        x = ctx.get(f, "input")
        err = ctx.get(self, "err_output").reshape(x.shape)
        grads, dmerged = self.pull(ctx, "finish", err.astype(f32))
        merged, lse = ctx.get(f, "core")
        pre, pool, _ = f.parts(ctx)
        x_in, pre, pool, merged, dmerged = jax.lax.optimization_barrier(
            (x, pre, pool, merged, dmerged))
        (q, k, v, kh, vh), front = jax.vjp(
            functools.partial(f.front, ctx), pre, pool, x_in)
        out, dout = f._split(merged), f._split(dmerged).astype(q.dtype)
        with _core_scope(), jax.named_scope("veles.eva"):
            (dq, dk, dv), remote = f.eva_bwd(ctx, q, k, v, kh, vh, out,
                                             lse, dout)
        dpre, dpool, dx = front((dq, dk, dv) + (remote or (None, None)))
        if self.need_err_input:
            ctx.set(self, "err_input",
                    (dx.astype(f32) + err).astype(f.stream_dtype(ctx)))
        self.apply_grads(ctx, dict(grads, **dpre, **dpool))
