"""Hand-written Pallas TPU kernels for the gated delta rule's pass over
chunks — the three lines of ``ops/delta_attention.py`` that carry the
state from one chunk to the next:

    U    = W_v - W_k S
    O    = Q_in S + R U
    S'   = diag(exp(last)) S + K_out^T U

with ``W_v (C, dv)``, ``W_k, Q_in, K_out (C, dk)``, ``R (C, C)`` the
chunk's terms as ``chunked_delta_rule`` makes them (the solved system,
the grown queries, the decayed reads, the keys decayed to the chunk's
end), ``last (1, dk)`` the chunk's whole log-decay (<= 0) and ``S (dk,
dv)`` the head's float32 state, zero before the first chunk.

As a ``lax.scan`` (the second implementation, :func:`~veles.znicz_tpu.
ops.delta_attention.scan_pass`, and these kernels' oracle, as
``parallel/flash.py`` is the attention kernels') every trip is a dozen
device operations of half a microsecond of work each, 512 trips a layer
and evaluation at the Solar-Open2 cell's shape. Here the pass is ONE
kernel: the grid is (heads / ``rows``, chunks), the chunk axis
innermost and sequential, and the state stays in VMEM from a head's
first chunk to its last — the forward's in the revisited block of its
final-state output, the backward's cotangent in a scratch — as
``_dkvq_kernel`` keeps dq (``parallel/pallas_attention.py``). A program
takes one chunk of ``rows`` heads, straight-line (the heads do not
meet): a head's chunk is about as much work as a grid step costs, and
one layer's recurrence, forward + backward, read 70.0 / 68.2 / 70.8 /
67.1 ms at 1 / 2 / 4 / 8 heads a program on the v5e (79.1 with the
scan; PERF.md section 6, PR 37).

The state is held TRANSPOSED, ``(dv, dk)``: the chunk's decay is then a
``(1, dk)`` lane vector that scales it by a sublane broadcast (a per-row
scalar as a ``(dk, 1)`` column pads every value to a 128-lane row, the
layout ``pallas_attention`` has none of either), ``d last`` is a
reduction down the sublanes, and the products that meet the state or
its cotangent take it as their right-hand side, plain or contracted
over the lanes (``_NT``): no 128 x 128 tile is transposed but the one
of ``[dO; -dU]`` a chunk of the backward.

Everything is float32 and every product is asked at
``Precision.HIGHEST``, the precision the scan's products have, which
Mosaic gives (``contract_precision<fp32>``: a 128 x 128 product read
1.7e-7 of its largest value off float64 on the v5e, the figure XLA's
``HIGHEST`` reads; ``HIGH`` reads 1.7e-5 and the default 2.4e-3): the
same arithmetic, in another place.

:func:`state_pass` is the pair under one ``jax.custom_vjp``. Its plain
call — a forward nobody differentiates: the forward pass under
``vjp_units.recomputed``, a validation forward — runs the forward
kernel and writes no states. Under differentiation the forward kernel
also writes every chunk's ENTRY state, ``(chunks, heads, dv, dk)``
float32 (what the checkpointed scan kept as its carries), and the
backward kernel walks the chunks from the last to the first: it reads a
chunk's terms, its entry state and ``dO``, makes ``U`` again (one
product) and writes the six cotangents, carrying ``dS``. Its operations
run under ``jax.named_scope("veles.delta")`` like the forward's, so the
readers of that scope see both.

``interpret=True`` runs the same kernels in Pallas's interpreter (the
CPU tests); any widths go there. On the chip ``dk`` and ``dv`` are
multiples of 128 and ``C`` of 8 (``ops/delta_attention.py`` asks that
and ``C == CHUNK`` before it calls).
"""

import functools

from veles.znicz_tpu.parallel.pallas_attention import _NT


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at the scan's precision."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _fwd_kernel(wv_ref, wk_ref, q_ref, reads_ref, k_ref, last_ref,
                o_ref, state_ref, *entry_ref):
    """One chunk of the program's heads. ``state_ref`` is the
    final-state output's block, whose index does not move along the
    chunk axis: it is the heads' running state, transposed, in VMEM
    until the heads' last chunk has left it there. ``entry_ref`` (under
    differentiation only): this chunk's entry state."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state_ref[...] = jnp.zeros_like(state_ref)

    c = wk_ref.shape[1]
    for r in range(wk_ref.shape[0]):    # the heads do not meet
        state = state_ref[r]                        # (dv, dk)
        if entry_ref:
            entry_ref[0][r] = state
        # [W_k; Q_in] against the state as ONE product of 2C rows: the
        # state is latched once
        both = _dot(jnp.concatenate([wk_ref[r], q_ref[r]], 0), state,
                    _NT)                            # (2C, dv)
        u = wv_ref[r] - both[:c]
        o_ref[r] = both[c:] + _dot(reads_ref[r], u)
        state_ref[r] = jnp.exp(last_ref[r]) * state + _dot(u.T, k_ref[r])


def _bwd_kernel(wv_ref, wk_ref, q_ref, reads_ref, k_ref, last_ref,
                entry_ref, do_ref, dfinal_ref, dwv_ref, dwk_ref, dq_ref,
                dreads_ref, dk_ref, dlast_ref, dstate_ref):
    """One chunk of the program's heads, the chunks walked from the
    last to the first (the index maps reverse them). ``dstate_ref``, a
    scratch, is the cotangent of the state this chunk LEAVES,
    transposed: the final state's before the last chunk."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate_ref[...] = dfinal_ref[...]

    c = wk_ref.shape[1]
    for r in range(wk_ref.shape[0]):
        state, dstate = entry_ref[r], dstate_ref[r]     # (dv, dk)
        decay = jnp.exp(last_ref[r])                    # (1, dk)
        wk, do = wk_ref[r], do_ref[r]
        u = wv_ref[r] - _dot(wk, state, _NT)            # (C, dv)
        dreads_ref[r] = _dot(do, u, _NT)
        du = _dot(reads_ref[r].T, do) + _dot(k_ref[r], dstate, _NT)
        dwv_ref[r] = du
        dk_ref[r] = _dot(u, dstate)
        dlast_ref[r] = decay * (state * dstate).sum(0, keepdims=True)
        # [dO; -dU] serves twice, 2C rows a product: against the state
        # for [dQ_in; dW_k], and transposed against [Q_in; W_k] for the
        # state's own cotangent, dO^T Q_in - dU^T W_k
        both = jnp.concatenate([do, -du], 0)            # (2C, dv)
        grads = _dot(both, state)
        dq_ref[r] = grads[:c]
        dwk_ref[r] = grads[c:]
        dstate_ref[r] = decay * dstate \
            + _dot(both.T, jnp.concatenate([q_ref[r], wk], 0))


def _specs(n, rows, shapes, reverse=False):
    """A ``BlockSpec`` for each (N, heads, ., .) array of ``shapes``:
    one chunk of ``rows`` heads a program, the chunks in order or (the
    backward) from the last to the first."""
    from jax.experimental import pallas as pl

    def index(h, i):
        return (n - 1 - i if reverse else i, h, 0, 0)

    return [pl.BlockSpec((None, rows) + tuple(shape[2:]), index)
            for shape in shapes]


def _forward(w_v, w_k, q_in, reads, k_out, last, interpret, rows, keep):
    """(N, heads, C, .) terms and (N, heads, 1, dk) ``last`` -> (o
    (N, heads, C, dv), final state TRANSPOSED (heads, dv, dk), and with
    ``keep`` the entry states (N, heads, dv, dk))."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    n, heads, _, dv = w_v.shape
    dk = w_k.shape[-1]
    args = (w_v, w_k, q_in, reads, k_out, last)
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct(w_v.shape, f32),
                 jax.ShapeDtypeStruct((heads, dv, dk), f32)]
    out_specs = _specs(n, rows, [w_v.shape]) + [
        pl.BlockSpec((rows, dv, dk), lambda h, i: (h, 0, 0))]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((n, heads, dv, dk), f32))
        out_specs += _specs(n, rows, [out_shape[-1].shape])
    return pl.pallas_call(
        _fwd_kernel, grid=(heads // rows, n),
        in_specs=_specs(n, rows, [t.shape for t in args]),
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
    )(*args)


def _backward(terms, entry, do, dfinal_t, interpret, rows):
    """The six cotangents, shaped as ``terms``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, heads, _, dv = terms[0].shape
    dk = terms[1].shape[-1]
    shapes = [t.shape for t in terms]
    return pl.pallas_call(
        _bwd_kernel, grid=(heads // rows, n),
        in_specs=_specs(n, rows, shapes + [entry.shape, do.shape], True)
        + [pl.BlockSpec((rows, dv, dk), lambda h, i: (h, 0, 0))],
        out_specs=_specs(n, rows, shapes, True),
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
        scratch_shapes=[pltpu.VMEM((rows, dv, dk), jnp.float32)],
        interpret=interpret,
    )(*terms, entry, do, dfinal_t)


@functools.lru_cache(maxsize=None)
def _pair(interpret, rows):
    """The two kernels under one ``jax.custom_vjp``, over flat
    (N, heads, ., .) terms -> (o, the final state transposed)."""
    import jax

    @jax.custom_vjp
    def run(*terms):
        return tuple(_forward(*terms, interpret, rows, keep=False))

    def forward(*terms):
        o, final_t, entry = _forward(*terms, interpret, rows, keep=True)
        return (o, final_t), (terms, entry)

    def backward(saved, cotangents):
        terms, entry = saved
        with jax.named_scope("veles.delta"):
            return tuple(_backward(terms, entry, *cotangents, interpret,
                                   rows))

    run.defvjp(forward, backward)
    return run


def state_pass(w_v, w_k, q_in, reads, k_out, last, rows=1,
               interpret=False):
    """The pass over chunks for (N, B, H, C, .) float32 terms and
    ``last`` (N, B, H, 1, dk), from a zero state -> (o (N, B, H, C,
    dv), the final state (B, H, dk, dv)). ``rows``: heads a program, a
    divisor of B x H."""
    o, final_t = _pair(bool(interpret), rows)(*(
        t.reshape(t.shape[0], -1, *t.shape[3:])     # B x H heads
        for t in (w_v, w_k, q_in, reads, k_out, last)))
    return (o.reshape(w_v.shape),
            final_t.swapaxes(-1, -2).reshape(
                w_v.shape[1:3] + (w_k.shape[-1], w_v.shape[-1])))
