"""Ring attention — sequence/context parallelism over the 'seq' axis.

The long-context story (task brief: "ring attention or all-to-all
sequence/context parallelism for long sequences"). The single-chip
attention materialises the (B, H, S, S) score matrix; here the
sequence is SHARDED over a mesh axis and K/V blocks rotate around the
ring via ``lax.ppermute`` while each device keeps a running
flash-style online softmax for its local Q block — peak memory per
chip drops from O(S²) to O(S·S/n) and the K/V transfers ride ICI
neighbour links.

Forward keeps (out, logsumexp); backward re-computes block scores and
rotates (k, v, dk, dv) a full circle so gradients land back on their
home shard. Both are hand-written collectives (no autodiff), verified
against the dense oracle in tests.

Inner-block kernel (round 4 — composes the measured single-chip flash
wins with the ring): each ring step's local (S/n × S/n) attention
block can itself run flash-style instead of materialising the dense
block scores — ``inner="scan"`` uses the ``lax.scan`` blocked
formulation (``parallel/flash.py``), ``inner="pallas"`` the
hand-written Pallas TPU kernels (``parallel/pallas_attention.py``).
Per ring step a three-way branch on (source shard vs mine) picks
causal-kernel / full-kernel / skip-entirely — the skip recovers the
causal-ring optimisation the Pallas kernel's loop bound gives on a
single chip — and the normalized partials merge by logsumexp
(``_merge_partial``). ``inner=None`` keeps the original fused dense
block (the short-shard default).

Usage: wrap in ``shard_map`` with q/k/v sharded on the sequence dim —
:func:`ring_self_attention` does the plumbing given a mesh.
"""

import functools

import numpy


def _shard_map(**kw):
    """``jax.shard_map`` without the varying-axes check: the bodies
    it wraps here and in ``expert.py`` / ``pipeline.py`` are
    hand-written collectives whose replication the checker cannot
    infer."""
    import jax
    return functools.partial(jax.shard_map, check_vma=False, **kw)


def _local_attention_steps(q, k0, v0, axis_name, causal, n_dev):
    """Shared forward loop: returns (acc, m, l) after a full ring
    rotation. All arrays are per-device blocks (B, H, Sb, dh)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, sb, dh = q.shape
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    my = lax.axis_index(axis_name)
    qpos = my * sb + jnp.arange(sb)                 # global q rows
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(step, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (my - step) % n_dev
        kpos = src * sb + jnp.arange(sb)
        s = (q @ k_cur.transpose(0, 1, 3, 2)) * scale
        if causal:
            mask = (kpos[None, :] > qpos[:, None]) * \
                jnp.float32(-1e9)
            s = s + mask[None, None, :, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        coef = jnp.exp(m - m_new)
        l_new = l * coef + p.sum(axis=-1)
        acc_new = acc * coef[..., None] + p @ v_cur
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l_new, acc_new

    m0 = jnp.full((b, h, sb), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sb), jnp.float32)
    acc0 = jnp.zeros_like(q)
    carry = (k0, v0, m0, l0, acc0)
    for step in range(n_dev):   # static unroll: n_dev is mesh-sized
        carry = body(step, carry)
    _, _, m, l, acc = carry
    return acc, m, l


def ring_attention_fwd(q, k, v, axis_name, causal, n_dev):
    """Per-shard forward body (call under shard_map).

    Returns (out, lse) with out = softmax(qkᵀ)v over the GLOBAL
    sequence, lse = logsumexp of each row's scores."""
    import jax.numpy as jnp
    acc, m, l = _local_attention_steps(q, k, v, axis_name, causal,
                                       n_dev)
    out = acc / l[..., None]
    lse = m + jnp.log(l)
    return out, lse


# ---------------------------------------------------------------------------
# flash inner-block kernels: each ring step's local attention block
# runs the single-chip flash formulation (scan or Pallas) instead of
# the fused dense block

def _inner_kernels(inner, block, dot=None, interpret=None):
    """(fwd, bwd) block-attention kernels for one ring step.
    fwd(q, k, v, causal) -> (out, lse) with out NORMALIZED within the
    block; bwd(q, k, v, out, lse, dout, causal) -> (dq, dk, dv) where
    out/lse are the GLOBAL-row quantities (flash backward semantics).
    ``interpret`` reaches the Pallas kernels unchanged (see
    ``pallas_attention.flash_attention_fwd``)."""
    if inner == "pallas":
        from veles.znicz_tpu.parallel import pallas_attention as PA

        def fwd(q, k, v, causal):
            return PA.flash_attention_fwd(q, k, v, causal=causal,
                                          block_q=block, block_k=block,
                                          interpret=interpret)

        def bwd(q, k, v, out, lse, dout, causal, delta=None):
            return PA.flash_attention_bwd(q, k, v, out, lse, dout,
                                          causal=causal,
                                          block_q=block, block_k=block,
                                          interpret=interpret,
                                          delta=delta)
    elif inner == "scan":
        from veles.znicz_tpu.parallel import flash

        def fwd(q, k, v, causal):
            return flash.blocked_attention_fwd(q, k, v, causal=causal,
                                               block=block, dot=dot)

        def bwd(q, k, v, out, lse, dout, causal, delta=None):
            return flash.blocked_attention_bwd(q, k, v, out, lse, dout,
                                               causal=causal,
                                               block=block, dot=dot,
                                               delta=delta)
    else:
        raise ValueError("inner must be 'pallas' or 'scan', got %r"
                         % (inner,))
    return fwd, bwd


def _merge_partial(out, lse, o_b, lse_b):
    """logsumexp-merge of two NORMALIZED partial attentions. Guards
    the both-empty case (lse == lse_b == -inf -> coefficient 0, not
    nan)."""
    import jax.numpy as jnp
    new_lse = jnp.logaddexp(lse, lse_b)
    empty = jnp.isneginf(new_lse)
    c1 = jnp.where(empty, 0.0, jnp.exp(lse - new_lse))
    c2 = jnp.where(empty, 0.0, jnp.exp(lse_b - new_lse))
    return (out * c1[..., None]
            + o_b.astype(jnp.float32) * c2[..., None]), new_lse


def _ring_branches(causal, src, my, run_causal, run_full, run_skip):
    """The per-ring-step three-way dispatch: diagonal shard -> causal
    kernel, past shard -> full kernel, future shard -> skip (its
    contribution is fully masked). ``src``/``my`` are traced, so this
    is a runtime ``lax.cond`` per device — coarse-grained enough that
    the TPU conditional cost amortises over a whole block kernel."""
    from jax import lax
    if not causal:
        return run_full(None)
    return lax.cond(
        src == my, run_causal,
        lambda op: lax.cond(src < my, run_full, run_skip, op), None)


def ring_attention_fwd_flash(q, k0, v0, axis_name, causal, n_dev,
                             inner, block, dot=None, interpret=None):
    """Forward ring with a flash inner block; same contract as
    :func:`ring_attention_fwd`."""
    import jax.numpy as jnp
    from jax import lax

    b, h, sb, dh = q.shape
    kern_fwd, _ = _inner_kernels(inner, block, dot, interpret)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(step, carry):
        k_cur, v_cur, out, lse = carry
        src = (my - step) % n_dev
        o_b, lse_b = _ring_branches(
            causal, src, my,
            lambda _: kern_fwd(q, k_cur, v_cur, True),
            lambda _: kern_fwd(q, k_cur, v_cur, False),
            lambda _: (jnp.zeros_like(q),
                       jnp.full((b, h, sb), -jnp.inf, jnp.float32)))
        out, lse = _merge_partial(out, lse, o_b, lse_b)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, out, lse

    carry = (k0, v0, jnp.zeros((b, h, sb, dh), jnp.float32),
             jnp.full((b, h, sb), -jnp.inf, jnp.float32))
    for step in range(n_dev):   # static unroll: n_dev is mesh-sized
        carry = body(step, carry)
    _, _, out, lse = carry
    return out.astype(q.dtype), lse


def ring_attention_bwd_flash(q, k, v, out, lse, dout, axis_name,
                             causal, n_dev, inner, block, dot=None,
                             interpret=None):
    """Backward ring with a flash inner block; same contract as
    :func:`ring_attention_bwd` (dk/dv accumulate while riding the
    ring a full circle home)."""
    import jax.numpy as jnp
    from jax import lax

    _, kern_bwd = _inner_kernels(inner, block, dot, interpret)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    # delta is a property of (out, dout) alone — hoist the
    # rowsum(dout*out) out of the per-step kernel calls so the ring
    # does not re-read both tensors from HBM n_dev times
    delta = (dout.astype(jnp.float32)
             * out.astype(jnp.float32)).sum(axis=-1)

    def body(step, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        src = (my - step) % n_dev
        zeros = lambda _: (jnp.zeros_like(q), jnp.zeros_like(k_cur),
                           jnp.zeros_like(v_cur))
        dq_b, dk_b, dv_b = _ring_branches(
            causal, src, my,
            lambda _: kern_bwd(q, k_cur, v_cur, out, lse, dout, True,
                               delta),
            lambda _: kern_bwd(q, k_cur, v_cur, out, lse, dout, False,
                               delta),
            zeros)
        dq = dq + dq_b.astype(jnp.float32)
        dk_cur = dk_cur + dk_b.astype(jnp.float32)
        dv_cur = dv_cur + dv_b.astype(jnp.float32)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur, axis_name, perm)
        return k_nxt, v_nxt, dk_nxt, dv_nxt, dq

    carry = (k, v, jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32),
             jnp.zeros(q.shape, jnp.float32))
    for step in range(n_dev):
        carry = body(step, carry)
    _, _, dk, dv, dq = carry
    return (dq.astype(q.dtype), dk.astype(q.dtype),
            dv.astype(q.dtype))


def ring_attention_bwd(q, k, v, out, lse, dout, axis_name, causal,
                       n_dev):
    """Per-shard backward body: (dq, dk, dv), dk/dv returned on their
    home shards after a full ring circle."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, sb, dh = q.shape
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    my = lax.axis_index(axis_name)
    qpos = my * sb + jnp.arange(sb)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    delta = (dout * out).sum(axis=-1)               # (B,H,Sb)

    def body(step, carry):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        src = (my - step) % n_dev
        kpos = src * sb + jnp.arange(sb)
        s = (q @ k_cur.transpose(0, 1, 3, 2)) * scale
        if causal:
            mask = (kpos[None, :] > qpos[:, None]) * \
                jnp.float32(-1e9)
            s = s + mask[None, None, :, :]
        p = jnp.exp(s - lse[..., None])             # exact probs
        dp = dout @ v_cur.transpose(0, 1, 3, 2)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + ds @ k_cur
        dk_cur = dk_cur + ds.transpose(0, 1, 3, 2) @ q
        dv_cur = dv_cur + p.transpose(0, 1, 3, 2) @ dout
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = lax.ppermute(dv_cur, axis_name, perm)
        return k_nxt, v_nxt, dk_nxt, dv_nxt, dq

    carry = (k, v, jnp.zeros_like(k), jnp.zeros_like(v),
             jnp.zeros_like(q))
    for step in range(n_dev):
        carry = body(step, carry)
    _, _, dk, dv, dq = carry
    return dq, dk, dv


def ring_self_attention(q, k, v, mesh, axis="seq", causal=True,
                        batch_axis=None, inner=None, block=128,
                        dot=None, interpret=None):
    """Dense-equivalent attention with the sequence sharded over
    ``axis``. q/k/v: (B, H, S, dh) global arrays. Returns (out, lse)
    global arrays (out sharded like q). On a composed mesh,
    ``batch_axis`` additionally shards the batch dim (SP x DP) —
    attention is per-sample, so each data-group rings independently.
    ``inner``: None (fused dense block per ring step), "scan" or
    "pallas" — run each step's local block through the flash kernels
    (module docstring); ``block`` is the inner kernel's tile size and
    ``interpret`` the Pallas inner kernels' interpret flag."""
    from jax.sharding import PartitionSpec as P
    shard_map = _shard_map()

    n_dev = mesh.shape[axis]
    spec = P(batch_axis, None, axis, None)
    lspec = P(batch_axis, None, axis)

    if inner is None:
        body = functools.partial(ring_attention_fwd, axis_name=axis,
                                 causal=causal, n_dev=n_dev)
    else:
        body = functools.partial(ring_attention_fwd_flash,
                                 axis_name=axis, causal=causal,
                                 n_dev=n_dev, inner=inner,
                                 block=block, dot=dot,
                                 interpret=interpret)
    fn = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, lspec))
    return fn(q, k, v)


def ring_self_attention_bwd(q, k, v, out, lse, dout, mesh, axis="seq",
                            causal=True, batch_axis=None, inner=None,
                            block=128, dot=None, interpret=None):
    import functools as ft
    from jax.sharding import PartitionSpec as P
    shard_map = _shard_map()

    n_dev = mesh.shape[axis]
    spec = P(batch_axis, None, axis, None)
    lspec = P(batch_axis, None, axis)
    if inner is None:
        body = ft.partial(ring_attention_bwd, axis_name=axis,
                          causal=causal, n_dev=n_dev)
    else:
        body = ft.partial(ring_attention_bwd_flash, axis_name=axis,
                          causal=causal, n_dev=n_dev, inner=inner,
                          block=block, dot=dot, interpret=interpret)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, spec, lspec, spec),
        out_specs=(spec, spec, spec))
    return fn(q, k, v, out, lse, dout)
