"""No-drop top-k expert layer that is told which experts it holds, as
a pre-norm residual block over (B, S, d):

    n = rms(x; g)
    s = sigmoid(n W_r)                    W_r d x E, float32
    selected = top-k of (s + b)           b: E biases, a buffer
    p = s[selected] / (sum p + 1e-6) * scaling
    y = x + sum over selected AND held e of
            p_e * (silu(n W1_e) * (n W3_e)) W2_e

The router is ``E`` wide whatever is held, and the top-k is over all
``E``. The layer holds the experts ``[lo, hi)`` (``experts_held``;
default all) and computes their part of the sum; what the absent
experts would add is left out — on one chip's share of an
expert-parallel deployment that partial sum is the layer's result, and
nothing here stands in for the other chips or their exchange.

With ``shared_hidden`` > 0 the layer also has ONE SHARED expert of
that width, a SwiGLU every token passes whatever the router says,
``y = x + swiglu(n; W13_s, W2_s) + sum ...``: it is held whole on
every chip, outside the routed sum and its scaling.

No token is dropped and there is no capacity: the token-expert pairs
are sorted, held experts first and by expert, into a buffer of all
``T x k`` rows (the worst case: every pair on a held expert). The
``rows`` pairs that are real are a prefix of it, and the work follows
them, not the buffer: the three products run as grouped products
(``vjp_units.Products.grouped_dot``: ``jax.lax.ragged_dot``, which the
TPU compiler lowers to its own grouped-matmul kernel that stops at the
last group), and every row stage around them — the gather into the
buffer, the gating, the weighting, and each one's pullback — runs over
the chunks of ``CHUNK`` rows that hold a real pair
(:func:`over_prefix`: a loop of ``ceil(rows / chunk)`` trips).
Past the last chunk a buffer is not even written; the one reader of
such rows, the token side of ``combine``, takes a pair past ``rows``
as zero whatever its row holds. Where the held share is thin
(``sparse``: under an eighth of the rows are expected to be real) it
reads only the chunks that hold a real pair, as one-hot products in
the same loop; elsewhere it gathers each token's ``k`` rows, all
``T x k`` of them. The buffers keep their worst-case
shapes, so a layer that holds every expert runs every chunk and
nothing can drop. ``[W1 | W3]`` is one array, so the up-projections
are one grouped product. Gradients reach the router through ``p``
alone.

Scopes inside the unit's own: ``veles.route`` (router, top-k, sort,
dispatch, gating, weighting, combine), ``veles.experts`` (the grouped
products) and ``veles.shared`` (the shared expert, outside both).
Counters, advanced when the step's metrics are replayed on the host
(they ride the metric fetch the step makes anyway):
``veles_moe_pairs_total{layer}``, ``veles_moe_steps_total{layer}``,
``veles_moe_rows_touched_total{layer}`` (rows the row stages
processed: trips x chunk, read from the loop itself; over
``veles_moe_buffer_rows{layer}``, the gauge of ``T x k``, it says how
far the buffer is from the work, over the pairs what a chunk's
rounding costs), ``veles_moe_combine_rows_total{layer}`` (rows of
the buffer a step's ``combine`` read: the row stages' count where the
share is sparse, ``T x k`` elsewhere), ``veles_moe_dropped_pairs_total``
(:func:`misplaced_pairs`: held pairs whose row of the buffer lies
outside their expert's group; 0 while sort, group sizes and buffer
agree) and the gauge ``veles_moe_load_max_over_mean{layer}``.
"""

import functools
import math

from veles import telemetry
from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.swiglu import swiglu
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, recomputed, rms_norm)


#: rows a trip of :func:`over_prefix` handles (the largest divisor of
#: the buffer's rows it shares with them). Timed on a v5e on the
#: gating stage alone, 65,536 x 3072 -> 1536 in bf16 (PR 31): at a
#: quarter of the rows real 0.297 / 0.295 / 0.313 / 0.367 ms for
#: 1,024 / 2,048 / 4,096 / 8,192 (one pass over all rows 0.901), at
#: all rows real 1.073 / 1.005 / 0.972 / 0.955.
CHUNK = 2048


def unwritten(shape, dtype, after):
    """An array nothing has written, allocated once ``after`` exists.
    On the TPU an allocation, not a fill: a kernel that does nothing
    and takes ``after`` by reference. ``jax.lax.empty`` allocates too,
    but has no operand, and XLA's scheduler then allocates at the top
    of the program: every such buffer of every layer was live through
    the whole step (+1.6 GB in `lfm2_24b_a2b_s8k_train`, PR 31)."""
    import jax
    from jax.experimental import pallas as pl

    def allocation(after):
        return pl.pallas_call(
            lambda after_ref, out_ref: None,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(shape, dtype))(after)

    return jax.lax.platform_dependent(
        after, tpu=allocation,
        default=lambda after: jax.lax.empty(shape, dtype))


def prefix_loop(trip, rows, size, carry):
    """``i + 1, carry = trip(i, chunk, real, carry)`` for the chunks
    ``i`` of a ``size``-row buffer that intersect ``[0, rows)``: a loop
    of ``ceil(rows / chunk)`` trips, ``chunk`` the largest divisor of
    ``size`` shared with ``CHUNK``. ``real`` (chunk, 1) says which rows
    of the chunk lie inside the prefix: ``trip`` masks the tail of the
    last one. -> (carry, rows touched = trips x chunk). ``trip`` counts
    the index itself, between its work and its writes: the row stages
    then trace the same program, operation for operation, as when the
    loop was theirs alone (``tests/test_solar_open2.py`` hashes it)."""
    import jax
    import jax.numpy as jnp
    chunk = math.gcd(size, CHUNK)

    def body(state):
        i, carry = state
        real = (i * chunk + jnp.arange(chunk) < rows)[:, None]
        return trip(i, chunk, real, carry)

    trips, carry = jax.lax.while_loop(
        lambda state: state[0] * chunk < rows, body,
        (jnp.zeros((), rows.dtype), carry))
    return carry, trips * chunk


def over_prefix(fn, rows, reads, buffers):
    """The row chunks of ``buffers`` that intersect ``[0, rows)``, each
    replaced by ``fn(real, *chunks of reads, *chunks of buffers)``:
    :func:`prefix_loop`'s trips of slice, work, update in place. The
    other chunks are left as they are: those of an :func:`unwritten`
    buffer hold no defined value. -> (buffers, rows touched)."""
    import jax

    def trip(i, chunk, real, buffers):
        done = fn(real, *(
            jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
            for a in reads + buffers))
        return i + 1, tuple(
            jax.lax.dynamic_update_slice_in_dim(b, c, i * chunk, 0)
            for b, c in zip(buffers, done))

    return prefix_loop(trip, rows, buffers[0].shape[0], buffers)


def prefix_stage(fn):
    """``stage(rows, *operands) -> (result, rows touched)``: the
    row-wise ``fn(real, *chunks) -> chunk`` by :func:`over_prefix`,
    into an :func:`unwritten` buffer. Its pullback is the same loop
    over the chunks' pullbacks: of the cotangent, too, only the prefix
    is read, and each operand's cotangent is written over the operand
    (the residuals are the operands, and die there)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def stage(rows, *operands):
        like = jax.eval_shape(
            fn, jax.ShapeDtypeStruct((operands[0].shape[0], 1), bool),
            *operands)
        (out,), touched = over_prefix(
            lambda real, *chunks: (fn(real, *chunks[:-1]),), rows,
            operands, (unwritten(like.shape, like.dtype, operands[0]),))
        return out, touched

    def pull(real, g, *chunks):
        return jax.vjp(lambda *c: fn(real, *c), *chunks)[1](
            jnp.where(real, g, 0))

    stage.defvjp(
        lambda rows, *operands: (stage(rows, *operands),
                                 (rows, operands)),
        lambda saved, g: (None,) + over_prefix(
            pull, saved[0], (g[0],), saved[1])[0])
    return stage


@functools.lru_cache(maxsize=None)
def pair_moves(k, sparse):
    """(dispatch, combine) for ``k`` experts a token: the two moves
    between the (T, d) tokens and the (T x k, d) buffer of token-expert
    pairs sorted by ``order`` (``inv`` its inverse), of which the first
    ``rows`` are real. They are each other's transpose, and neither is
    left to jax: the transpose of a gather is a scatter-add, which the
    TPU runs row by row and, in bf16, sums in bf16. ``sparse``: under
    an eighth of the rows are expected to be real
    (``ExpertFFN.sparse``), and ``combine`` reads only the chunks that
    hold them, as one-hot products, where its gathers would read all
    ``T x k`` rows."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def dispatch(x, order, inv, rows):
        """Row r < ``rows`` of the buffer: the token of pair
        ``order[r]``, gathered by :func:`over_prefix`."""
        return over_prefix(
            lambda real, pairs, _: (jnp.where(real, x[pairs // k], 0),),
            rows, (order,),
            (unwritten((order.size,) + x.shape[1:], x.dtype, x),))[0][0]

    def gathered(buffer, order, inv, rows):
        # k gathers of T rows each: one gather of all T x k rows wants
        # a copy into (T, k, d) tiles before the sum (3.8 against 2.8
        # ms on a v5e at 65,536 rows of 2048, PR 28)
        where = inv.reshape(buffer.shape[0] // k, k).T
        return sum(jnp.where((where[j] < rows)[:, None],
                             buffer[where[j]], 0).astype(jnp.float32)
                   for j in range(k)).astype(buffer.dtype)

    def by_products(buffer, order, inv, rows):
        # a trip adds onehot(token of each row)^T @ its chunk: bf16
        # operands, exact ones, a float32 sum
        tokens = buffer.shape[0] // k

        def trip(i, chunk, real, total):
            pairs = jax.lax.dynamic_slice_in_dim(order, i * chunk, chunk)
            onehot = pairs[:, None] // k == jnp.arange(tokens)
            # masked BEFORE the product: a row past ``rows`` may hold
            # anything, and 0 * NaN would reach its token
            part = jnp.where(real, jax.lax.dynamic_slice_in_dim(
                buffer, i * chunk, chunk), 0)
            return i + 1, total + jax.lax.dot_general(
                onehot.astype(buffer.dtype), part,
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        total, _ = prefix_loop(
            trip, rows, buffer.shape[0],
            jnp.zeros((tokens,) + buffer.shape[1:], jnp.float32))
        return total.astype(buffer.dtype)

    @jax.custom_vjp
    def combine(buffer, order, inv, rows):
        """Token t: the sum of its k pairs' rows, added in float32;
        a pair past ``rows`` adds nothing, whatever its row holds."""
        return (by_products if sparse else gathered)(
            buffer, order, inv, rows)

    dispatch.defvjp(
        lambda x, *sorting: (dispatch(x, *sorting), sorting),
        lambda sorting, g: (combine(g, *sorting), None, None, None))
    combine.defvjp(
        lambda buffer, *sorting: (combine(buffer, *sorting), sorting),
        lambda sorting, g: (dispatch(g, *sorting), None, None, None))
    return dispatch, combine


def misplaced_pairs(local, held, inv, sizes):
    """Held token-expert pairs that the grouped products would NOT
    compute with their own expert: pair ``i`` of expert ``local[i]``
    sits at row ``inv[i]`` of the sorted buffer, and the products give
    expert ``e`` the rows ``[start_e, start_e + sizes[e])``. Counted
    from the pairs' own rows, not from ``sizes``: a sort that misfiled
    a pair, group sizes cut to a capacity or a buffer shorter than the
    pairs would all show here."""
    import jax.numpy as jnp
    start = jnp.cumsum(sizes) - sizes
    mine = jnp.clip(local, 0, sizes.size - 1)
    inside = (inv >= start[mine]) & (inv < start[mine] + sizes[mine])
    return jnp.sum(held & ~inside, dtype=jnp.int32)


@forward_unit("expert_ffn")
class ExpertFFN(VjpForward):
    PARAMS = ("weights", "weights13", "weights2", "norm", "expert_bias")
    BUFFERS = ("expert_bias",)
    HAS_AUX = True

    def __init__(self, workflow, experts=None, top_k=1, hidden=None,
                 experts_held=None, scaling=1.0, eps=1e-5,
                 bias_stddev=0.0, shared_hidden=0, **kwargs):
        """``scaling``: the model's routed scaling factor.
        ``bias_stddev``: the selection biases are drawn normal at this
        deviation when the unit initialises (0: they start at zero, as
        a fresh model's do); a snapshot or a test sets the buffer
        itself."""
        self.shared_hidden = int(shared_hidden or 0)
        if self.shared_hidden:
            self.PARAMS = type(self).PARAMS + ("shared13", "shared2")
        super().__init__(workflow, **kwargs)
        if not (experts and hidden):
            raise ValueError("expert_ffn needs experts and hidden")
        self.experts = int(experts)
        self.top_k = int(top_k)
        self.hidden = int(hidden)
        lo, hi = experts_held or (0, self.experts)
        if not 0 <= lo < hi <= self.experts or self.top_k > self.experts:
            raise ValueError("experts_held %r, top_k %d of %d experts"
                             % (experts_held, self.top_k, self.experts))
        self.held = (int(lo), int(hi))
        #: under an eighth of the pair buffer's rows are expected to be
        #: real (a token's top_k experts, the share of them held here):
        #: keeping the buffers for the backward costs more than making
        #: them again, and ``combine`` reads the real pairs' chunks alone
        self.sparse = 8 * (hi - lo) < self.experts
        self.scaling = float(scaling)
        self.eps = float(eps)
        self.expert_bias_stddev = float(bias_stddev)
        self._step_pairs = 0

    def param_specs(self, ishape):
        d, f, e = ishape[-1], self.hidden, self.experts
        n = self.held[1] - self.held[0]
        specs = {"weights": ((d, e), (d, e)),
                 "weights13": ((n, d, 2 * f), (d, f)),
                 "weights2": ((n, f, d), (f, d)),
                 "norm": ((d,), "ones"),
                 "expert_bias": ((e,), self.expert_bias_stddev or "zeros")}
        if self.shared_hidden:
            fs = self.shared_hidden
            specs.update(shared13=((d, 2 * fs), (d, fs)),
                         shared2=((fs, d), (fs, d)))
        return specs

    # -- the math --------------------------------------------------------

    def route(self, p, n):
        """-> (selected (T, k) expert ids, their weights (T, k))."""
        import jax
        import jax.numpy as jnp
        scores = jax.nn.sigmoid(jnp.matmul(
            n, p["weights"], precision=jax.lax.Precision.HIGHEST))
        _, selected = jax.lax.top_k(scores + p["expert_bias"], self.top_k)
        # the selected scores by a one-hot product, not a gather of
        # T x k scalars (and a scatter-add of as many in the backward)
        chosen = selected[:, :, None] == jnp.arange(self.experts)
        weight = jnp.where(chosen, scores[:, None, :], 0).sum(-1)
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
        return selected, weight * self.scaling

    def apply(self, ctx, p, x):
        import jax
        import jax.numpy as jnp
        mm = Products(ctx)
        f32 = jnp.float32
        d, k = x.shape[-1], self.top_k
        lo, hi = self.held
        with jax.named_scope("veles.route"):
            n = rms_norm(x, p["norm"], self.eps).reshape(-1, d)
            selected, weight = self.route(p, n)
            # pair t * k + j is token t's j-th expert; held pairs
            # first, by expert; `rows` of them are real
            local = selected.reshape(-1) - lo
            held = (local >= 0) & (local < hi - lo)
            order = jnp.argsort(jnp.where(held, local, hi - lo),
                                stable=True)
            sizes = jnp.sum(
                local[:, None] == jnp.arange(hi - lo)[None, :], axis=0,
                dtype=jnp.int32)
            rows = sizes.sum()
            inv = jnp.argsort(order)
            dispatch, combine = pair_moves(k, self.sparse)

        # Every row stage runs over the chunks of the buffers that hold
        # real pairs (``over_prefix``), as the grouped products stop at
        # the last group; past them a buffer holds no defined value,
        # and ``combine`` reads none of it.
        @prefix_stage
        def gate(real, h13):
            # masked BEFORE the activation: a row the product skipped
            # may hold anything, and 0 * NaN would reach the weights
            return swiglu(h13, keep=real).astype(mm.cd)

        @prefix_stage
        def weigh(real, out, weight):
            # masked BEFORE the weighting, for the same reason: the
            # weight's gradient is a sum over the row
            return (jnp.where(real, out, 0).astype(f32)
                    * weight[:, None]).astype(mm.act)

        # Checkpointed: the backward keeps the normalised tokens and
        # ``h13`` and makes the pair buffer and the activation again (a
        # gather, an elementwise pass) instead of keeping the (T x k)-row
        # buffers of every layer from the forward to the backward.
        @jax.checkpoint
        def up(tokens, w13):
            with jax.named_scope("veles.route"):
                xs = dispatch(tokens, order, inv, rows)
            with jax.named_scope("veles.experts"):
                return mm.grouped_dot(xs, w13, sizes)

        @jax.checkpoint
        def down(h13, w2):
            with jax.named_scope("veles.route"):
                act, _ = gate(rows, h13)
            with jax.named_scope("veles.experts"):
                return mm.grouped_dot(act, w2, sizes)

        def weighted(out, weight):
            # a weight past the real pairs is masked for its gradient's
            # sake: the weighting's pullback writes the prefix alone
            with jax.named_scope("veles.route"):
                out, touched = weigh(rows, out, jnp.where(
                    jnp.arange(order.size) < rows,
                    weight.reshape(-1)[order], 0))
                return combine(out, order, inv, rows).astype(f32), touched

        if self.sparse:
            # the (T x k)-row buffers are mostly air: the backward keeps
            # none of them and runs the held experts' part again
            @recomputed
            def routed(tokens, w13, w2, weight):
                return weighted(down(up(tokens, w13), w2), weight)[0]

            y = routed(n.astype(mm.cd), p["weights13"], p["weights2"],
                       weight)
            chunk = math.gcd(order.size, CHUNK)
            touched = (rows + chunk - 1) // chunk * chunk
        else:
            y, touched = weighted(
                down(up(n.astype(mm.cd), p["weights13"]), p["weights2"]),
                weight)
        with jax.named_scope("veles.route"):
            aux = {"pairs": rows, "max_load": sizes.max(),
                   "dropped": misplaced_pairs(local, held, inv, sizes),
                   "touched": touched}
        if self.shared_hidden:
            @jax.checkpoint     # as SwiGLUFFN: the backward gates again
            def shared_down(h13, w2):
                return mm.dot(swiglu(h13).astype(mm.cd), w2, f32)

            with jax.named_scope("veles.shared"):
                y = y + shared_down(mm.dot(n, p["shared13"]),
                                    p["shared2"])
        return x.astype(f32) + y.reshape(x.shape), aux

    # -- counters ----------------------------------------------------------

    def export_aux(self, ctx, aux):
        for key, value in aux.items():
            ctx.export("moe_%s_%s" % (key, self.name), value)

    def metric_sinks(self):
        return [("moe_%s_%s" % (key, self.name), "step_" + key)
                for key in ("pairs", "max_load", "dropped", "touched")]

    @property
    def step_pairs(self):
        return self._step_pairs

    @step_pairs.setter
    def step_pairs(self, pairs):
        self._step_pairs = pairs
        telemetry.counter(
            "veles_moe_pairs_total", "Token-expert pairs computed by "
            "the experts a layer holds", ("layer",)
        ).labels(self.name).inc(pairs)
        telemetry.counter(
            "veles_moe_steps_total", "Training steps an expert layer "
            "ran", ("layer",)).labels(self.name).inc()

    step_max_load = property(lambda self: None)

    @step_max_load.setter
    def step_max_load(self, busiest):
        mean = self._step_pairs / (self.held[1] - self.held[0])
        telemetry.gauge(
            "veles_moe_load_max_over_mean", "Last step: pairs of the "
            "busiest held expert over the mean of the held experts",
            ("layer",)).labels(self.name).set(
                busiest / mean if mean else 0.0)

    step_touched = property(lambda self: None)

    @step_touched.setter
    def step_touched(self, touched):
        telemetry.counter(
            "veles_moe_rows_touched_total", "Rows of the pair buffer an "
            "expert layer's row stages processed (chunks run x chunk)",
            ("layer",)).labels(self.name).inc(touched)
        rows = self.input.size // self.input.shape[-1] * self.top_k
        telemetry.counter(
            "veles_moe_combine_rows_total", "Rows of the pair buffer an "
            "expert layer's combine read (the row stages' chunks where "
            "the held share is sparse, all of them elsewhere)",
            ("layer",)).labels(self.name).inc(
                touched if self.sparse else rows)
        telemetry.gauge(
            "veles_moe_buffer_rows", "Rows of an expert layer's pair "
            "buffer: tokens a step x experts a token, the worst case",
            ("layer",)).labels(self.name).set(rows)

    step_dropped = property(lambda self: None)

    @step_dropped.setter
    def step_dropped(self, dropped):
        telemetry.counter(
            "veles_moe_dropped_pairs_total", "Held token-expert pairs "
            "an expert layer did not compute (must stay 0)"
        ).inc(dropped)


@gradient_for(ExpertFFN)
class GDExpertFFN(GDVjp):
    EXTRA_PARAMS = (("weights13", False), ("weights2", False),
                    ("norm", True), ("shared13", False),
                    ("shared2", False))
