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

No token is dropped and there is no capacity: the token-expert pairs
are sorted, held experts first and by expert, into a buffer of all
``T x k`` rows (the worst case: every pair on a held expert), and the
three products run over the rows of the held pairs as grouped products
(``vjp_units.Products.grouped_dot``: ``jax.lax.ragged_dot``, which the
TPU compiler lowers to its own grouped-matmul kernel that stops at the
last group). ``[W1 | W3]`` is one array, so the up-projections are one
grouped product. Gradients reach the router through ``p`` alone.

Scopes inside the unit's own: ``veles.route`` (router, top-k, sort,
dispatch, gating, weighting, combine) and ``veles.experts`` (the grouped
products). Counters, advanced when the step's metrics are replayed on
the host (they ride the metric fetch the step makes anyway):
``veles_moe_pairs_total{layer}``, ``veles_moe_steps_total{layer}``,
``veles_moe_dropped_pairs_total`` (:func:`misplaced_pairs`: held pairs
whose row of the buffer lies outside their expert's group; 0 while
sort, group sizes and buffer agree) and the gauge
``veles_moe_load_max_over_mean{layer}``.
"""

import functools

from veles import telemetry
from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.swiglu import swiglu
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, rms_norm)


@functools.lru_cache(maxsize=None)
def pair_moves(k):
    """(dispatch, combine) for ``k`` experts a token: the two moves
    between the (T, d) tokens and the (T x k, d) buffer of token-expert
    pairs sorted by ``order`` (``inv`` its inverse). They are each
    other's transpose, and each is written as a gather: left to jax,
    the transpose of a gather is a scatter-add, which the TPU runs row
    by row and, in bf16, sums in bf16."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def dispatch(x, order, inv):
        """Row r of the buffer: the token of pair ``order[r]``."""
        return x[order // k]

    @jax.custom_vjp
    def combine(rows, order, inv):
        """Token t: the sum of its k pairs' rows, added in float32 —
        k gathers of T rows each: one gather of all T x k rows wants a
        copy into (T, k, d) tiles before the sum (3.8 against 2.8 ms
        on a v5e at 65,536 rows of 2048, PR 28)."""
        where = inv.reshape(rows.shape[0] // k, k).T
        return sum(rows[where[j]].astype(jnp.float32)
                   for j in range(k)).astype(rows.dtype)

    dispatch.defvjp(
        lambda x, order, inv: (dispatch(x, order, inv), (order, inv)),
        lambda saved, g: (combine(g, *saved), None, None))
    combine.defvjp(
        lambda rows, order, inv: (combine(rows, order, inv),
                                  (order, inv)),
        lambda saved, g: (dispatch(g, *saved), None, None))
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
                 bias_stddev=0.0, **kwargs):
        """``scaling``: the model's routed scaling factor.
        ``bias_stddev``: the selection biases are drawn normal at this
        deviation when the unit initialises (0: they start at zero, as
        a fresh model's do); a snapshot or a test sets the buffer
        itself."""
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
        self.scaling = float(scaling)
        self.eps = float(eps)
        self.expert_bias_stddev = float(bias_stddev)
        self._step_pairs = 0

    def param_specs(self, ishape):
        d, f, e = ishape[-1], self.hidden, self.experts
        n = self.held[1] - self.held[0]
        return {"weights": ((d, e), (d, e)),
                "weights13": ((n, d, 2 * f), (d, f)),
                "weights2": ((n, f, d), (f, d)),
                "norm": ((d,), "ones"),
                "expert_bias": ((e,), self.expert_bias_stddev or "zeros")}

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
            real = (jnp.arange(order.size) < rows)[:, None]
            inv = jnp.argsort(order)
            dispatch, combine = pair_moves(k)

        # Checkpointed: the backward keeps the normalised tokens and
        # ``h13`` and makes the pair buffer and the activation again (a
        # gather, an elementwise pass) instead of keeping the (T x k)-row
        # buffers of every layer from the forward to the backward.
        @jax.checkpoint
        def up(tokens, w13):
            with jax.named_scope("veles.route"):
                xs = jnp.where(real, dispatch(tokens, order, inv), 0)
            with jax.named_scope("veles.experts"):
                return mm.grouped_dot(xs, w13, sizes)

        @jax.checkpoint
        def down(h13, w2):
            with jax.named_scope("veles.route"):
                # masked BEFORE the activation: a row the product
                # skipped may hold anything, and 0 * NaN would reach
                # the weights
                act = swiglu(h13, keep=real).astype(mm.cd)
            with jax.named_scope("veles.experts"):
                return mm.grouped_dot(act, w2, sizes)

        out = down(up(n.astype(mm.cd), p["weights13"]), p["weights2"])
        with jax.named_scope("veles.route"):
            # masked BEFORE the weighting, for the same reason: the
            # weight's gradient is a sum over the row
            out = jnp.where(real, out, 0).astype(f32) \
                * weight.reshape(-1)[order][:, None]
            y = combine(out.astype(mm.act), order, inv).astype(f32)
            aux = {"pairs": rows, "max_load": sizes.max(),
                   "dropped": misplaced_pairs(local, held, inv, sizes)}
        return x.astype(f32) + y.reshape(x.shape), aux

    # -- counters ----------------------------------------------------------

    def export_aux(self, ctx, aux):
        for key, value in aux.items():
            ctx.export("moe_%s_%s" % (key, self.name), value)

    def metric_sinks(self):
        return [("moe_%s_%s" % (key, self.name), "step_" + key)
                for key in ("pairs", "max_load", "dropped")]

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
                    ("norm", True))
