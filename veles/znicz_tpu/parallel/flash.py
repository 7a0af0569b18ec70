"""Blocked (flash-style) attention — single-chip long-context path.

Complements ``parallel/ring.py``: the ring shards the sequence ACROSS
chips; this blocks it WITHIN one chip, so the (B, H, S, S) score
matrix is never materialised — peak memory drops to O(S·block) and
long sequences fit a single chip's HBM. The math is the same online
softmax the ring uses (running max/denominator across K/V blocks,
exact — not an approximation), with backward by block recomputation
from the saved logsumexp.

Written with ``lax.scan`` over K/V blocks: XLA keeps each block's
score tile in registers/VMEM and the MXU busy with (S × block)
matmuls, which is the same compute schedule a hand-written Pallas
flash kernel would pick — the scan IS the tiling loop. Probabilities
are cast to the matmul compute dtype (bf16 on TPU) before the PV /
dV / dK products: exp is evaluated in f32, but the materialised
(S × block) tile then costs half the HBM traffic. (A 2-level
q-block × k-block tiling with ``lax.cond`` skipping above-diagonal
tiles was tried and measured SLOWER on a v5e — 150k vs 201k tok/s on
the 57M LM: TPU conditionals break the scan's software pipelining and
the shorter q tiles underutilise the MXU. The single scan with
exp(-1e9) masking is the faster schedule at these shapes.) Verified
exactly against the dense formulation in tests.
"""

import numpy


def _hidden(kpos, qpos, window):
    """(S, block) bool: the keys a query under a ``window`` does not
    see — those after it and those ``window`` or more tokens before it
    (the kernels' band, ``parallel/pallas_attention.py``; here a mask
    alone: the scan visits every block)."""
    ahead = qpos[:, None] - kpos[None, :]
    return (ahead < 0) | (ahead >= window)


def blocked_attention_fwd(q, k, v, causal=True, block=128, dot=None,
                          window=None):
    """q/k/v: (B, H, S, dh) → (out, lse); exact softmax(qkᵀ)v with
    O(S·block) peak score memory. ``block`` must divide S. ``dot``:
    matmul implementation (``ctx.dot`` for bf16 MXU inputs).
    ``window``: a causal query sees itself and the ``window - 1``
    tokens before it."""
    import jax.numpy as jnp
    from jax import lax
    dot = dot or jnp.matmul

    b, h, s, dh = q.shape
    if s % block:
        raise ValueError("block %d does not divide sequence %d"
                         % (block, s))
    if window is not None and not causal:
        raise ValueError("a window is of a causal row")
    n = s // block
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    qpos = jnp.arange(s)
    kb = jnp.moveaxis(k.reshape(b, h, n, block, dh), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, n, block, dh), 2, 0)

    def body(carry, xs):
        m, l, acc = carry
        i, k_blk, v_blk = xs
        sc = dot(q, k_blk.transpose(0, 1, 3, 2)) * scale  # (B,H,S,blk)
        if window is not None:
            sc = jnp.where(_hidden(i * block + jnp.arange(block), qpos,
                                   window), jnp.float32(-1e9), sc)
        elif causal:
            kpos = i * block + jnp.arange(block)
            mask = (kpos[None, :] > qpos[:, None]) * jnp.float32(-1e9)
            sc = sc + mask[None, None, :, :]
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        coef = jnp.exp(m - m_new)
        l_new = l * coef + p.sum(axis=-1)
        # p in the compute dtype for the PV matmul: exp stays f32, the
        # materialised (S, block) tile costs half the HBM traffic
        acc_new = acc * coef[..., None] + dot(p.astype(q.dtype), v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n), kb, vb))
    out = (acc / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    return out, lse


def blocked_attention_bwd(q, k, v, out, lse, dout, causal=True,
                          block=128, dot=None, delta=None, window=None):
    """Backward by block recomputation from ``lse``; -> (dq, dk, dv),
    all exact (same formulas as the dense adjoint). The ds / p tiles
    are cast to the compute dtype before their three matmuls (same
    bandwidth argument as forward). ``delta``: optional precomputed
    ``rowsum(dout*out)`` (B, H, S) f32 — the ring's per-step inner
    backward hoists it across steps."""
    import jax.numpy as jnp
    from jax import lax
    dot = dot or jnp.matmul

    b, h, s, dh = q.shape
    if s % block:
        raise ValueError("block %d does not divide sequence %d"
                         % (block, s))
    if window is not None and not causal:
        raise ValueError("a window is of a causal row")
    n = s // block
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    qpos = jnp.arange(s)
    if delta is None:
        delta = (dout.astype(jnp.float32)
                 * out.astype(jnp.float32)).sum(axis=-1)  # (B,H,S)
    kb = jnp.moveaxis(k.reshape(b, h, n, block, dh), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, n, block, dh), 2, 0)

    def body(dq, xs):
        i, k_blk, v_blk = xs
        sc = dot(q, k_blk.transpose(0, 1, 3, 2)) * scale
        if window is not None:
            sc = jnp.where(_hidden(i * block + jnp.arange(block), qpos,
                                   window), jnp.float32(-1e9), sc)
        elif causal:
            kpos = i * block + jnp.arange(block)
            mask = (kpos[None, :] > qpos[:, None]) * jnp.float32(-1e9)
            sc = sc + mask[None, None, :, :]
        p = jnp.exp(sc - lse[..., None])                  # exact probs
        dp = dot(dout, v_blk.transpose(0, 1, 3, 2))
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        pc = p.astype(q.dtype)
        dq = dq + dot(ds, k_blk)
        dk_blk = dot(ds.transpose(0, 1, 3, 2), q)
        dv_blk = dot(pc.transpose(0, 1, 3, 2), dout)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, h, s, dh), jnp.float32)
    dq, (dks, dvs) = lax.scan(
        body, dq0, (jnp.arange(n), kb, vb))
    dq = dq.astype(q.dtype)
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, s, dh).astype(q.dtype)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, s, dh).astype(q.dtype)
    return dq, dk, dv
