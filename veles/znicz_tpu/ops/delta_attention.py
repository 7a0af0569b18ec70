"""Gated delta-rule linear attention (Kimi Delta Attention), the
operator of a ``delta_attention`` layer, as a pre-norm residual block
over (B, S, d): ``heads`` heads, each carrying a ``dk x dv`` state
along the sequence (``dk = dv = head_dim``):

    n      = rms(x; g)
    q,k,v  = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))
                                            conv depthwise, causal
    q, k   = q / |q|_2 * dk^-1/2,  k / |k|_2          per head and token
    a_t    = -exp(A_log_h) * softplus((n_t W_f1) W_f2 + dt_bias)
                                            a log-decay <= 0 a channel
    b_t    = 2 * sigmoid(n_t W_b)           one a head, in (0, 2)
    S_t    = (I - b_t k_t k_t^T) diag(exp(a_t)) S_{t-1} + b_t k_t v_t^T
    o_t    = S_t^T q_t                      S float32, S_0 = 0
    y      = x + (rms_head(o_t; g_o) * sigmoid((n_t W_g1) W_g2 + c_g)) W_o

``weights`` holds ``[W_q | W_k | W_v]`` and ``conv`` their taps, so the
three projections are one product and one pass of taps
(``ShortConv``'s shifted-sum form). ``|t|_2`` is
``sqrt(sum t^2 + 1e-6)``.

The recurrence proper (:func:`delta_rule`) runs under
``veles.delta``, in float32, ``CHUNK`` tokens at a time. With
``G_t = a_1 + ... + a_t`` inside a chunk that starts from the state
``S_0``, and ``E_ts = exp(G_t - G_s)`` (a vector over the ``dk``
channels, taken for ``s <= t`` only, where it is at most 1):

    A_ts = sum_c k_t k_s E_ts  (s < t),   B_ts = sum_c q_t k_s E_ts (s <= t)
    (I + diag(b) A) [W_v | W_k] = diag(b) [V | exp(G) * K]
    U    = W_v - W_k S_0
    O    = (exp(G) * Q) S_0 + B U
    S_C  = diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T U

``A`` and ``B`` are made chunk by chunk (:func:`chunk_scores`), the
chunk cut into blocks of ``BLOCK`` tokens. With ``n(t)`` the first
token of ``t``'s block and ``r_t = G_{n(t) - 1}`` (0 in the chunk's
first block):

    s >= n(t):  E_ts = exp(G_t - G_s)                 pairwise
    s <  n(t):  E_ts = exp(G_t - r_t) * exp(r_t - G_s)
                A_ts = (k_t exp(G_t - r_t)) . (k_s exp(r_t - G_s))

Every ``a`` is <= 0, so ``G`` falls along the chunk and ``G_t <= r_t
<= G_s`` there: both factors lie in (0, 1], nothing overflows, and
where one underflows to 0 the pair's true decay is below float32's
range too. It is the same sum over the channels in the same float32,
taken as a product at ``Precision.HIGHEST`` (``B`` likewise, with
``q_t``); only pairs inside one block, with no boundary between them,
need the (w, w, dk) tensor of differences, masked before the
exponential. The product form with ONE reference for a whole chunk,
``(exp(G) k)(exp(-G) k)^T``, does overflow inside a chunk as soon as
``exp(A_log) = 16`` meets a softplus near 1: its second factor is not
bounded. Where ``BLOCK`` does not divide the chunk (a sequence that is
one chunk of an odd length) the chunk is one block.

The triangular system (the delta rule inside a chunk) is solved for
all chunks at once, it does not depend on the state:
:func:`product_solve`, the inverse of ``I + diag(b) A`` by forward
substitution over blocks that double (:func:`unit_lower_inverse`), then
one product with the right-hand side, all float32 products at
``Precision.HIGHEST``, under one ``jax.custom_vjp`` that keeps the
inverse and the solution: its backward is two products and no solve.
The inverse is ``C^3 log2 C`` multiply-adds a chunk, against ``C^2 (dk
+ dv)`` for a substitution: as much at ``CHUNK``, and on the matrix
unit; a sequence that is one long chunk (an odd length) pays the
difference. The three lines that depend on the state are the pass over
chunks, :func:`state_pass`: one algorithm, two implementations. Where
the step compiles for a TPU, ``dk`` and
``dv`` are whole 128-lane tiles and the chunks hold ``CHUNK`` tokens
(:func:`state_pass_kernels`: the shape and the platform decide, no
key) it is a pair of Pallas kernels under one ``jax.custom_vjp``
(``parallel/pallas_delta.py``): grid (heads, chunks), the float32
state in VMEM from a head's first chunk to its last, ``HEADS_AT_ONCE``
heads a program. Everywhere else - the CPU, heads of 8 or 16, a
sequence that is one odd chunk - it is a ``lax.scan`` over the chunks
that carries ``S`` (:func:`scan_pass`), the kernels' oracle, as
``parallel/flash.py`` is the attention kernels'.

The backward keeps ``q, k, v, a, b`` and runs all of this again
(:func:`~veles.znicz_tpu.ops.vjp_units.recomputed`), and inside that
the loop over head groups and the scores' keep a trip's inputs alone
(``jax.checkpoint`` on their bodies). The pass keeps every chunk's
ENTRY state - ``S / CHUNK`` states of ``heads x dk x dv`` float32 a
group, alive in that group's backward only: the kernels write them
beside the output when they run under differentiation (and none in a
forward nobody differentiates), the scan has them as its carries, its
body under ``jax.checkpoint`` too. With the kernels a step evaluates
the pass forward twice (the forward pass; the group's re-evaluation in
the backward, which keeps the states) and backward once; with the scan
a third forward runs inside the body's checkpoint.

Counters, on the step's metric fetch: ``veles_delta_tokens_total
{layer}``, ``veles_delta_steps_total{layer}``,
``veles_delta_pairwise_pairs_total{layer}`` (pairs of tokens whose
decay was taken pairwise, tokens x the block width in use: over tokens
x ``CHUNK`` the share of a chunk's square still made of differences,
1/4 at 64 / 16, 1 where a sequence fell back to one block),
``veles_delta_kernel_chunks_total{layer}`` (chunks whose state pass the
kernel pair ran: x ``CHUNK`` over the tokens 1 where the kernels run,
0 where the scan does); gauges of the last training step
``veles_delta_decay_mean{layer}`` (mean ``exp(a)``: which numeric
regime the chunks are in), ``veles_delta_beta_mean{layer}`` and
``veles_delta_state_rms{layer}`` (the final state's root mean square: a
recurrence that blows up under the negative eigenvalues ``b > 1``
allows shows here before the loss does).
"""

import functools
import math

import numpy

from veles import telemetry
from veles.znicz_tpu.nn_units import forward_unit, gradient_for
from veles.znicz_tpu.ops.vjp_units import (
    GDVjp, Products, VjpForward, recomputed, rms_norm)

#: tokens a chunk of :func:`delta_rule` holds
CHUNK = 64
#: tokens a block of a chunk holds: :func:`chunk_scores` takes the
#: decay of a pair of tokens pairwise inside a block alone
BLOCK = 16
#: chunks :func:`chunked_delta_rule` makes the scores of at a time:
#: four chunks' diagonal blocks are as many lanes as ONE chunk's
#: (C, C, dk) tensor was, so a trip holds what it held. A quarter of
#: the loop trips: a row of blocks is some forty small operations, and
#: at one chunk a trip a traced step held so many more device
#: operations than before the blocks that the host's memory ran out
#: reading the trace (PERF.md section 6, PR 35)
CHUNKS_AT_ONCE = 4
#: heads :func:`delta_rule` runs at a time. At 64 heads of 128 x 128
#: and S = 4096 the chunks' terms, the saved states and their
#: cotangents are 2.5 GB for all heads at once, in the middle of a
#: backward that still holds every earlier layer's residuals
#: (``benchmark/rehearse.py``, PR 34)
HEADS_AT_ONCE = 8


def chunk_of(s):
    """Tokens a chunk of a sequence of ``s`` tokens holds: ``CHUNK``,
    or the whole sequence where that does not divide it."""
    return CHUNK if s % CHUNK == 0 else s


def block_of(c):
    """Tokens a block of a chunk of ``c`` tokens holds: ``BLOCK``, or
    the whole chunk where that does not divide it."""
    return BLOCK if c % BLOCK == 0 else c


def pairwise_scores(q, k, g):
    """:func:`chunk_scores` of tokens that form one block: every
    pair's decay from the difference ``G_t - G_s``, a (w, w, dk)
    tensor."""
    import jax.numpy as jnp
    w = g.shape[-2]
    later = jnp.arange(w)[:, None] >= jnp.arange(w)[None, :]
    # masked BEFORE the exponential: above the diagonal the difference
    # is positive and as large as the block's whole decay
    decay = jnp.exp(jnp.where(
        later[:, :, None], g[..., :, None, :] - g[..., None, :, :],
        -jnp.inf))
    pairs = decay * k[..., None, :, :]
    a = (pairs * k[..., :, None, :]).sum(-1)
    b = (pairs * q[..., :, None, :]).sum(-1)
    return a, b


def chunk_scores(q, k, g):
    """(A, B) of one chunk, each (..., C, C) and zero above the
    diagonal: the decayed products of a token's k (A; the solve reads
    it below the diagonal alone) and q (B) with its own and every
    earlier token's k. ``q, k, g``: (..., C, dk), float32.

    The chunk is cut into blocks of :func:`block_of` tokens, and the
    scores are made a row of blocks at a time: the block on the
    diagonal pairwise (:func:`pairwise_scores`), everything before it
    as ONE float32 product of the block's tokens, grown from ``G`` at
    the last token before the block, with the earlier tokens decayed
    to it: both factors at most 1. A chunk of one block is the pairwise
    form of the whole chunk."""
    import jax
    import jax.numpy as jnp
    c = g.shape[-2]
    w = block_of(c)
    rows = []
    for start in range(0, c, w):
        q_own, k_own, g_own = (t[..., start:start + w, :]
                               for t in (q, k, g))
        parts = [pairwise_scores(q_own, k_own, g_own)]
        if start:
            # every token of the block lies after the reference, every
            # earlier one before it or on it: both differences are <= 0
            ref = g[..., start - 1:start, :]
            grown = jnp.exp(g_own - ref)
            earlier = k[..., :start, :] * jnp.exp(ref - g[..., :start, :])
            below = jnp.matmul(
                jnp.concatenate([grown * k_own, grown * q_own], -2),
                earlier.swapaxes(-1, -2),
                precision=jax.lax.Precision.HIGHEST)
            parts.insert(0, (below[..., :w, :], below[..., w:, :]))
        if start + w < c:
            above = jnp.zeros(g.shape[:-2] + (w, c - start - w), g.dtype)
            parts.append((above, above))
        rows.append([jnp.concatenate(side, -1) for side in zip(*parts)])
    return tuple(jnp.concatenate(side, -2) for side in zip(*rows))


def _product(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def unit_lower_inverse(l):
    """``(I + L)^-1`` for ``l`` (..., C, C) float32, of which the
    strictly lower triangle is ``L``: forward substitution by blocks
    that double, on whole C x C matrices. Where ``T_m`` inverts every
    diagonal block of ``m`` rows of ``I + L`` (zeros elsewhere), the
    inverse of two neighbouring blocks is

        [[T_1, 0], [-T_2 L_21 T_1, T_2]] = (I - P_m(T_m L)) T_m

    ``P_m`` keeping the blocks of ``m`` below the diagonal blocks of
    ``m`` inside each block of ``2 m``. So ``N_m = T_m L`` follows
    ``N_2m = (I - P_m(N_m)) N_m``, ONE batched product a level, from
    ``N_1 = L`` to the whole chunk, and ``T = I - N_C`` (``T (I + L) =
    I``). Every value made is a block of a partial inverse times ``L``:
    no power of ``L`` appears, so nothing grows past what the inverse
    itself holds. The identity rides inside the product, so a level's
    output is the new ``N`` and the one before it dies: XLA fuses a
    subtraction after the product into every later reader and then
    keeps every level's product alive (on a v5e, six 16 MB buffers a
    head group)."""
    import jax
    import jax.numpy as jnp
    c = l.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # below 2 m where row and column lie in one block of 2 m, at least
    # m where they lie in different blocks of m
    apart = row ^ col
    eye = (row == col).astype(l.dtype)
    n = jnp.where(row > col, l, 0.0)
    m = 1
    while m < c:
        n = _product(
            eye - jnp.where((apart >= m) & (apart < 2 * m), n, 0.0), n)
        m *= 2
    return eye - n


def _solve_forward(l, r):
    t = unit_lower_inverse(l)
    w = _product(t, r)
    return w, (t, w)


def _solve_backward(saved, dw):
    import jax
    import jax.numpy as jnp
    t, w = saved
    with jax.named_scope("veles.delta"):
        dr = _product(t.swapaxes(-1, -2), dw)
        return -jnp.tril(_product(dr, w.swapaxes(-1, -2)), -1), dr


def product_solve(l, r):
    """``W`` with ``(I + L) W = R`` for ``l`` (..., C, C), of which the
    strictly lower triangle is ``L``, and ``r`` (..., C, m), float32:
    ``T R`` with ``T`` = :func:`unit_lower_inverse`, float32 products
    at ``Precision.HIGHEST``. Under one ``jax.custom_vjp`` that keeps
    ``T`` and ``W``: ``dR = T^T dW``, ``dL = -strict_lower(dR W^T)``,
    two products and no solve."""
    return _product_solve()(l, r)


@functools.lru_cache(maxsize=None)
def _product_solve():
    import jax
    solve = jax.custom_vjp(lambda l, r: _solve_forward(l, r)[0])
    solve.defvjp(_solve_forward, _solve_backward)
    return solve


def delta_rule(q, k, v, a, beta, kernels=None):
    """The recurrence above for ``q, k, a`` (B, S, H, dk), ``v``
    (B, S, H, dv), ``beta`` (B, S, H), float32, from a zero state;
    -> (o (B, S, H, dv), the final state (B, H, dk, dv)). The heads do
    not meet: ``HEADS_AT_ONCE`` of them run at a time, one group after
    the other (a ``lax.map`` whose backward keeps a group's inputs
    alone), so the chunks' terms and the saved states of one group are
    alive at a time, not the layer's. ``kernels``: how
    :func:`state_pass` runs (:func:`state_pass_kernels`)."""
    import jax
    b, s, h, _ = q.shape
    groups = h // HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else 1
    if groups == 1:
        return chunked_delta_rule(q, k, v, a, beta, kernels)

    def split(t):       # (B, S, H, w) -> (G, B, S, H / G, w)
        return t.reshape(b, s, groups, h // groups, -1) \
            .transpose(2, 0, 1, 3, 4)

    o, state = jax.lax.map(
        jax.checkpoint(
            lambda x: chunked_delta_rule(*x[:4], x[4][..., 0], kernels),
            prevent_cse=False),
        tuple(split(t) for t in (q, k, v, a, beta[..., None])))
    return (o.transpose(1, 2, 0, 3, 4).reshape(b, s, h, -1),
            state.transpose(1, 0, 2, 3, 4).reshape((b, h)
                                                   + state.shape[3:]))


def chunked_delta_rule(q, k, v, a, beta, kernels=None):
    """:func:`delta_rule` for all the heads it is given, in chunks of
    ``CHUNK`` tokens (one chunk where that does not divide S)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = chunk_of(s)
    n = s // c

    def chunks(t):      # (B, S, H, w) -> (N, B, H, C, w)
        return t.reshape(b, n, c, h, -1).transpose(1, 0, 3, 2, 4)

    q, k, v, a = (chunks(t.astype(f32)) for t in (q, k, v, a))
    beta = chunks(beta.astype(f32)[..., None])
    g = jnp.cumsum(a, axis=-2)
    scores, reads = jax.lax.map(
        jax.checkpoint(lambda x: chunk_scores(*x), prevent_cse=False),
        (q, k, g), batch_size=CHUNKS_AT_ONCE)
    grown = jnp.exp(g)
    w = product_solve(
        beta * scores, beta * jnp.concatenate([v, grown * k], -1))
    last = g[..., -1:, :]
    o, state = state_pass(
        w[..., :dv], w[..., dv:], grown * q, reads, jnp.exp(last - g) * k,
        last, kernels)
    return o.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dv), state


def state_pass_kernels(platform, c, dk, dv):
    """How :func:`state_pass` runs for chunks of ``c`` tokens and heads
    of ``dk x dv`` in a step that compiles for ``platform``: "mosaic",
    the Pallas pair of ``parallel/pallas_delta.py``, on a TPU where the
    widths are whole 128-lane tiles and the chunk is ``CHUNK``; None,
    the ``lax.scan``, everywhere else."""
    from veles.backends import is_tpu
    if is_tpu(platform) and c == CHUNK and dk % 128 == 0 \
            and dv % 128 == 0:
        return "mosaic"
    return None


def state_pass(w_v, w_k, q_in, reads, k_out, last, kernels=None):
    """The pass over chunks that carries the state, from a zero state:
    chunk by chunk ``U = W_v - W_k S``, ``O = Q_in S + R U``, ``S <-
    exp(last) * S + K_out^T U``. Terms (N, B, H, C, .) float32,
    ``last`` (N, B, H, 1, dk) -> (O (N, B, H, C, dv), the final state
    (B, H, dk, dv)). One algorithm, two implementations: ``kernels``
    None the ``lax.scan`` (:func:`scan_pass`), "mosaic" the Pallas
    kernel pair, "interpret" the pair in Pallas's interpreter (the
    tests)."""
    if kernels is None:
        return scan_pass(w_v, w_k, q_in, reads, k_out, last)
    from veles.znicz_tpu.parallel import pallas_delta
    # a program takes a group's heads, or what of a group divides the
    # call's: a grid step costs as much as a head's chunk does
    return pallas_delta.state_pass(
        w_v, w_k, q_in, reads, k_out, last,
        rows=math.gcd(w_v.shape[1] * w_v.shape[2], HEADS_AT_ONCE),
        interpret=kernels == "interpret")


def scan_pass(w_v, w_k, q_in, reads, k_out, last):
    """:func:`state_pass` as a ``lax.scan`` over the chunks whose body
    is under ``jax.checkpoint``: the backward keeps the carries and
    makes a chunk's ``U`` again."""
    import jax
    import jax.numpy as jnp
    exact = jax.lax.Precision.HIGHEST

    @jax.checkpoint
    def step(state, x):
        w_v, w_k, q_in, read, k_out, keep = x
        u = w_v - jnp.matmul(w_k, state, precision=exact)
        o = jnp.matmul(q_in, state, precision=exact) \
            + jnp.matmul(read, u, precision=exact)
        state = keep * state + jnp.matmul(
            k_out.swapaxes(-1, -2), u, precision=exact)
        return state, o

    state, o = jax.lax.scan(
        step, jnp.zeros(w_k.shape[1:3] + (w_k.shape[-1], w_v.shape[-1]),
                        jnp.float32),
        (w_v, w_k, q_in, reads, k_out, jnp.exp(last).swapaxes(-1, -2)))
    return o, state


@forward_unit("delta_attention")
class DeltaAttention(VjpForward):
    PARAMS = ("weights", "conv", "weights_decay_in", "weights_decay_out",
              "weights_beta", "a_log", "dt_bias", "weights_gate_in",
              "weights_gate_out", "gate_bias", "weights_out", "norm",
              "norm_out")
    HAS_AUX = True

    def __init__(self, workflow, heads=4, head_dim=None, kernel=4,
                 gate_rank=None, eps=1e-5, **kwargs):
        super().__init__(workflow, **kwargs)
        self.heads = int(heads)
        self.head_dim = head_dim
        self.kernel = int(kernel)
        self.gate_rank = gate_rank
        self.eps = float(eps)

    @staticmethod
    def fill_a_log(gen, mem):
        """``A = exp(a_log)`` uniform in [1, 16)."""
        gen.fill_uniform(mem, 1.0, 16.0)
        numpy.log(mem, out=mem)

    @staticmethod
    def fill_dt_bias(gen, mem):
        """``softplus(dt_bias) = dt``, log-uniform in [1e-3, 1e-1)."""
        gen.fill_uniform(mem, numpy.log(1e-3), numpy.log(1e-1))
        numpy.exp(mem, out=mem)
        mem += numpy.log(-numpy.expm1(-mem))

    def param_specs(self, ishape):
        d = ishape[-1]
        dh = self.head_dim = int(self.head_dim or d // self.heads)
        wide = self.heads * dh
        rank = self.gate_rank = int(self.gate_rank or dh)
        return {"weights": ((d, 3 * wide), (d, wide)),
                "conv": ((3 * wide, self.kernel), (self.kernel, 1)),
                "weights_decay_in": ((d, rank), (d, rank)),
                "weights_decay_out": ((rank, wide), (rank, wide)),
                "weights_beta": ((d, self.heads), (d, self.heads)),
                "a_log": ((self.heads,), self.fill_a_log),
                "dt_bias": ((wide,), self.fill_dt_bias),
                "weights_gate_in": ((d, rank), (d, rank)),
                "weights_gate_out": ((rank, wide), (rank, wide)),
                "gate_bias": ((wide,), "zeros"),
                "weights_out": ((wide, d), (wide, d)),
                "norm": ((d,), "ones"),
                "norm_out": ((dh,), "ones")}

    def pass_kernels(self, platform):
        """:func:`state_pass_kernels` for this layer's shape in a step
        that compiles for ``platform``."""
        return state_pass_kernels(
            platform, chunk_of(self.input.shape[-2]), self.head_dim,
            self.head_dim)

    def apply(self, ctx, p, x):
        import jax
        import jax.numpy as jnp
        mm = Products(ctx)
        f32 = jnp.float32
        b, s, _ = x.shape
        h, dh, taps = self.heads, self.head_dim, self.kernel

        # The backward keeps the normed input, the two gates' low ranks
        # and the recurrence's output, and makes everything between
        # them again, the three projections too.
        @recomputed
        def mix(n, low, beta, w, conv, w_decay, a_log, dt_bias):
            padded = jnp.pad(mm.dot(n, w, f32),
                             ((0, 0), (taps - 1, 0), (0, 0)))
            qkv = jax.nn.silu(sum(conv[:, j] * padded[:, j:j + s]
                                  for j in range(taps)))
            q, k, v = (t.reshape(b, s, h, dh)
                       for t in jnp.split(qkv, 3, axis=-1))

            def unit(t):
                return t * jax.lax.rsqrt(
                    (t * t).sum(-1, keepdims=True) + 1e-6)

            a = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                (mm.dot(low, w_decay, f32) + dt_bias).reshape(b, s, h, dh))
            beta = 2.0 * jax.nn.sigmoid(beta)
            with jax.named_scope("veles.delta"):
                o, state = delta_rule(
                    unit(q) * dh ** -0.5, unit(k), v, a, beta,
                    self.pass_kernels(ctx._compiler.device.platform))
            state = jax.lax.stop_gradient(state)
            return o.astype(mm.act), {
                "decay": jnp.exp(jax.lax.stop_gradient(a)).mean(),
                "beta": jax.lax.stop_gradient(beta).mean(),
                "state_rms": jnp.sqrt((state * state).mean())}

        @recomputed
        def gated(o, low, w_gate, gate_bias, gain):
            gate = jax.nn.sigmoid(mm.dot(low, w_gate, f32) + gate_bias)
            o = rms_norm(o.reshape(b, s, h, dh), gain, self.eps)
            return (o.reshape(b, s, h * dh) * gate).astype(mm.cd)

        n = rms_norm(x, p["norm"], self.eps)
        o, aux = mix(
            n.astype(mm.cd), mm.dot(n, p["weights_decay_in"]),
            mm.dot(n, p["weights_beta"], f32), p["weights"], p["conv"],
            p["weights_decay_out"], p["a_log"], p["dt_bias"])
        out = mm.dot(
            gated(o, mm.dot(n, p["weights_gate_in"]),
                  p["weights_gate_out"], p["gate_bias"], p["norm_out"]),
            p["weights_out"], f32)
        return x.astype(f32) + out, aux

    # -- counters ----------------------------------------------------------

    #: the aux outputs of ``apply``, a gauge each
    AUX = ("decay", "beta", "state_rms")

    def export_aux(self, ctx, aux):
        for key, value in aux.items():
            ctx.export("delta_%s_%s" % (key, self.name), value)

    def metric_sinks(self):
        return [("delta_%s_%s" % (key, self.name), "step_" + key)
                for key in self.AUX]

    def set_gauge(self, name, what, value):
        telemetry.gauge(name, "Last step of a delta-rule layer: " + what,
                        ("layer",)).labels(self.name).set(value)

    def metrics_published(self, fresh):
        """``XLAStep``'s hook, once a training step's sinks are filled."""
        if "step_decay" not in fresh:
            return
        tokens = self.input.size // self.input.shape[-1]
        telemetry.counter(
            "veles_delta_tokens_total", "Tokens a delta-rule layer's "
            "recurrence ran over, training steps", ("layer",)
        ).labels(self.name).inc(tokens)
        telemetry.counter(
            "veles_delta_pairwise_pairs_total", "Pairs of tokens whose "
            "decay a delta-rule layer took pairwise (tokens x the block "
            "width in use), training steps", ("layer",)
        ).labels(self.name).inc(
            tokens * block_of(chunk_of(self.input.shape[-2])))
        telemetry.counter(
            "veles_delta_kernel_chunks_total", "Chunks whose state pass "
            "a delta-rule layer ran as the Pallas kernel pair (0: the "
            "lax.scan), training steps", ("layer",)
        ).labels(self.name).inc(
            tokens // chunk_of(self.input.shape[-2])
            if self.pass_kernels(self.device.platform) else 0)
        telemetry.counter(
            "veles_delta_steps_total", "Training steps a delta-rule "
            "layer ran", ("layer",)).labels(self.name).inc()
        self.set_gauge("veles_delta_decay_mean", "mean exp(a), the decay "
                       "a channel takes a token", self.step_decay)
        self.set_gauge("veles_delta_beta_mean", "mean b, the delta "
                       "rule's step in (0, 2)", self.step_beta)
        self.set_gauge("veles_delta_state_rms", "root mean square of the "
                       "final state", self.step_state_rms)


@gradient_for(DeltaAttention)
class GDDeltaAttention(GDVjp):
    EXTRA_PARAMS = (
        ("conv", True), ("weights_decay_in", False),
        ("weights_decay_out", False), ("weights_beta", False),
        ("a_log", True), ("dt_bias", True), ("weights_gate_in", False),
        ("weights_gate_out", False), ("gate_bias", True),
        ("weights_out", False), ("norm", True), ("norm_out", True))
