"""Hand-written Pallas TPU flash-attention kernels — the attention
proper from S=256 up.

Real TPU kernels keeping the score tile and the softmax state in VMEM
(SURVEY.md §2.5, §7 stage 6). Where they win, and why:

* S>=1024 (an earlier builder's v5e readings, 57.5M LM, 2026-07-31,
  not in the driver's record; pallas vs scan tok/s): 174k vs 161k at
  S=1024, 156k vs 119k at S=2048, 111k vs 82k at S=4096, 85k vs 53k
  at S=8192 — the causal ``fori_loop`` bound SKIPS fully-masked K
  blocks entirely, halving the quadratic work, which the scan
  schedule cannot do (a lax.cond block-skip was measured SLOWER: TPU
  conditionals break scan pipelining; inside a Pallas kernel the loop
  bound is a plain scalar and costs nothing). The tile is free of
  attn_block (``MultiHeadAttention._pallas_block``, up to 512).
* S=512 and 256 (PERF.md section 6, PR 27: the 110M LM at 16,384
  tokens a step on a v5e): 151.3k vs the scan's 111.3k tok/s at
  S=512, 159.0k vs 152.8k at S=256. At S=512, batch 32 the scan's
  (B, H, S, block) score tile is 201 MB and every pass over it an HBM
  round trip. A sequence of one tile has its own kernels
  (``_tile_fwd_kernel`` / ``_tile_bwd_kernel``: no K loop, several
  (batch, head) rows a program, the tile transposed so that the
  per-query statistics are lane vectors); the general kernels read
  136.2k at S=512 (tile 512) and 138.6k at S=256, under the scan.
* S=128: the XLA scan (``parallel/flash.py``) wins, 167.8k vs 155.1k:
  the shorter S, the smaller the scan's tile and the better its one
  step fuses, while the kernels' cost a token does not fall with S.

``MultiHeadAttention`` therefore auto-selects: ``attn_impl=None``
uses the scan below ``PALLAS_AUTO_MIN_S`` (256) and these kernels at
or above it on a real TPU; ``attn_impl="scan"|"pallas"`` forces
either. Inputs ride in the compute dtype (bf16 on TPU): half the
VMEM — at S=8192 the difference between fitting and a scoped-vmem
OOM — and matched MXU input dtypes. Per-row lse/delta tensors are
shipped as (BH, 1, S) with the sequence on the LANE dim: a (BH, S, 1)
layout pads its trailing singleton to 128 lanes and explodes VMEM
(S·128·4 bytes per ref — the original S=8k backward compile failure).

Exact math (same online softmax as flash.py / ring.py; verified
against both in tests — interpret mode on CPU, real kernels on TPU):

* :func:`flash_attention_fwd`  — (B,H,S,dh) → (out, lse)
* :func:`flash_attention_bwd` — block-recomputation backward from the
  saved logsumexp. Default (round 5): ONE fused kernel computes
  dq/dk/dv in a single pass over the k-block grid (``_dkvq_kernel``;
  dq accumulates in a VMEM-resident revisited output ref — legal
  because the TPU Pallas grid is sequential), 5 block matmuls + 1 exp
  per causal pair vs the classic two-pass form's 7 + 2 (retained
  behind ``fused=False``); measured +38% at the 110M S=8k shapes.

Causal masking is paid only where it can matter (round 5): the
fori_loops split at the diagonal — blocks fully below it skip the
iota/where pass entirely, the diagonal remnant keeps it.

Consumed by ``MultiHeadAttention(attn_impl="pallas")``; backward is
wired through the explicit GD unit (znicz style), so no custom-VJP
registration is needed — autodiff never touches these.

VMEM budget: K and V ride whole per-(batch·head) rows in VMEM, so
S·dh·8 bytes must fit comfortably (≈16 MB/core) — S up to ~16k at
dh=64. Beyond that, block K/V from HBM with manual DMA (documented
escape hatch, not needed at current model scale).
"""

import functools

import numpy


@functools.lru_cache(maxsize=None)
def jitted(fn, **static):
    """``fn`` — :func:`flash_attention_fwd` or
    :func:`flash_attention_bwd` — under ``jax.jit`` with its keyword
    arguments bound, the SAME object for the same keywords: a step
    that calls the kernel once a layer then traces its body once, not
    once a layer. Tracing the 36 kernel calls of the 12-layer LM's
    step one by one was 11 of the 14 s its program took to trace on
    the v5e's host (PERF.md, PR 27), paid at every start, compile
    cache or not."""
    import jax
    return jax.jit(functools.partial(fn, **static))


def _on_tpu():
    """The library-level default behind ``interpret=None``: does jax's
    default device run Mosaic kernels? The workflow path does not ask
    this — it passes ``interpret`` from the platform its step compiles
    for (``MultiHeadAttention._pallas_interpret``). A failed device
    query raises; nothing falls back to the interpreter."""
    from veles import backends
    return backends.is_tpu(backends.default_platform())


def _device_vmem_bytes():
    """VMEM capacity (per TensorCore) of the TPU the kernel compiles
    for, from jax's own table — which raises for a device kind it
    does not know instead of assuming one."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.get_tpu_info().vmem_capacity_bytes


def _fused_bwd_vmem_limit(s, dh, block_q, block_k, itemsize,
                          device_vmem=None):
    """Scoped-VMEM grant for the fused dkvq kernel, derived from its
    RESIDENT footprint and clamped to the device's VMEM (16MB on the
    generations before v5, where the fused path can fail to fit while
    ``fused=False`` works).

    Resident per grid step: full q/do rows (storage dtype), the full
    f32 dq accumulator, lse/delta lanes, the k/v/dk/dv blocks and the
    (block_q, block_k) f32 score/prob temporaries. The 6x margin
    covers Mosaic's double buffering and spill slack (measured 16.75MB
    actual vs ~4.4MB resident at S=8k/dh=64/bf16 — a 3.8x ratio).
    Raises with the escape hatches when even that exceeds the device:
    ``fused=False`` (the two-kernel backward never holds dq resident)
    or a smaller ``pallas_tile``."""
    resident = (s * dh * (2 * itemsize + 4)    # q + do + f32 dq
                + 2 * 4 * s                    # lse + delta lanes
                + 4 * block_k * dh * itemsize  # k/v/dk/dv blocks
                + 4 * block_q * block_k * 4)   # score/prob temps
    need = 6 * resident
    vmem = device_vmem if device_vmem is not None \
        else _device_vmem_bytes()
    limit = min(max(need, 16 << 20), vmem)
    if need > vmem:
        raise ValueError(
            "fused attention backward needs ~%dMB scoped VMEM at "
            "S=%d, dh=%d, blocks (%d, %d) but the device has %dMB: "
            "use fused=False (the two-kernel backward) or a smaller "
            "pallas_tile"
            % (need >> 20, s, dh, block_q, block_k, vmem >> 20))
    return limit


def _split_loop(spans, make_body, init):
    """Chained ``fori_loop``s over ``spans`` = [(lo, hi, masked), ...]
    — the causal diagonal split shared by all four kernels (round 5):
    blocks strictly on the unmasked side of the diagonal skip the
    iota/where pass entirely (~2 of the ~10 VPU passes per block),
    only the diagonal remnant pays it. Loops over K blocks mask the
    TAIL span; loops over Q blocks (dkv/dkvq) mask the HEAD span."""
    import jax
    out = init
    for lo, hi, masked in spans:
        out = jax.lax.fori_loop(lo, hi, make_body(masked), out)
    return out


def _online_softmax_step(jnp, parts, carry, acc_dtype):
    """One K-block online-softmax update shared by the resident and
    the DMA-pipelined forward kernels: (m, l, acc) -> new carry.
    ``parts``: [(s, vb), ...] — the block's score tile(s), each with
    the V rows of ITS key columns (one pair for the resident kernel;
    the pipelined kernel splits a block by lane group, and softmax
    does not care in which order keys arrive).
    ``m``/``l`` always ride f32 (they feed the exact lse); the
    CARRIED ``acc`` rides ``acc_dtype`` — f32 by default, bf16 under
    the gated accumulation experiment (halves the live carry
    footprint; the numerics bound is pinned by
    tests/test_pallas_attention.py). The MXU itself always accumulates
    in f32 — Mosaic refuses anything else ("Expected matmul acc to be
    32-bit") — and the block product is cast to ``acc_dtype`` after."""
    m, l, acc = carry
    m_new = m
    for s, _ in parts:
        m_new = jnp.maximum(m_new, s.max(axis=-1, keepdims=True))
    coef = jnp.exp(m - m_new)
    l_new = l * coef
    pv = None
    for s, vb in parts:
        p = jnp.exp(s - m_new)
        l_new = l_new + p.sum(axis=-1, keepdims=True)
        # p in the storage dtype (bf16 on TPU) for the PV matmul — exp
        # stays f32, the MXU gets matched input dtypes
        part = jnp.dot(p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        pv = part if pv is None else pv + part
    acc_new = (acc * coef.astype(acc_dtype)) + pv.astype(acc_dtype)
    return m_new, l_new, acc_new


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                block_k, n_kb, causal, scale, acc_dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    qb = q_ref[0]                                   # (bq, dh)
    bq, dh = qb.shape
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def make_body(masked):
        def body(j, carry):
            kb = k_ref[0, pl.ds(j * block_k, block_k), :]
            vb = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                cols = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(cols > rows, jnp.float32(-1e9), s)
            return _online_softmax_step(jnp, [(s, vb)], carry,
                                        acc_dtype)
        return body

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dh), acc_dtype)
    if causal:
        # K blocks past this Q block's last row are all-masked — skip
        # them entirely; only the diagonal remnant needs the mask
        hi = pl.cdiv((qi + 1) * block_q, block_k)
        clear = (qi * block_q) // block_k
        spans = [(0, clear, False), (clear, hi, True)]
    else:
        spans = [(0, n_kb, False)]
    m, l, acc = _split_loop(spans, make_body, (m0, l0, acc0))
    o_ref[0] = (acc.astype(jnp.float32) / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                     # (bq, 1)


def _kv_lane_pack(dh):
    """How many consecutive K/V rows the pipelined forward packs into
    one HBM row: Mosaic only DMAs windows whose minor dim is a whole
    number of 128-lane tiles ("Slice shape along dimension 2 must be
    aligned to tiling (128), but is 64" at dh=64), so a head dim that
    divides 128 rides ``128 // dh`` rows side by side."""
    return 128 // dh if dh < 128 and 128 % dh == 0 else 1


def _fwd_kernel_pipe(q_ref, k_hbm, v_hbm, o_ref, lse_ref, *, block_q,
                     block_k, n_kb, causal, scale, acc_dtype,
                     kv_dtype, pack):
    """DMA-PIPELINED forward: K/V stay in HBM and each block of
    ``block_k`` keys is double-buffered into VMEM scratch — the j+1
    copy is in flight while block j computes, and resident VMEM drops
    from two full S·dh rows to four block tiles (the escape past the
    ~16k-token whole-row ceiling documented in the module header).

    K/V arrive LANE-PACKED as (BH, S/pack, pack·dh) — row r holds the
    original rows pack·r .. pack·r+pack-1 side by side (a free
    reshape; see ``_kv_lane_pack``) — so a block is a
    (block_k/pack, pack·dh) window and lane group g of it holds the
    keys j·block_k + pack·i + g. Each group is one score tile with its
    own column indices; the online softmax takes them together.

    The causal diagonal split is traded for an always-applied mask (a
    no-op on fully-unmasked blocks): chaining two fori_loops would
    force a second DMA warmup at the seam, costing more than the ~2
    VPU passes the split saves. The fully-masked tail blocks are still
    skipped — the loop bound ``hi`` is unchanged."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    qb = q_ref[0]                                   # (bq, dh)
    bq, dh = qb.shape
    rows_k = block_k // pack                        # packed rows/block
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, rows_k), 0)
    hi = pl.cdiv((qi + 1) * block_q, block_k) if causal else n_kb

    def run(kbuf, vbuf, ksem, vsem):
        def dma(slot, j):
            sl = pl.ds(j * rows_k, rows_k)
            return (pltpu.make_async_copy(k_hbm.at[bh, sl, :],
                                          kbuf.at[slot],
                                          ksem.at[slot]),
                    pltpu.make_async_copy(v_hbm.at[bh, sl, :],
                                          vbuf.at[slot],
                                          vsem.at[slot]))

        for d in dma(0, 0):        # warm up: hi >= 1 always (the
            d.start()              # diagonal block exists)

        def body(j, carry):
            slot = lax.rem(j, 2)

            @pl.when(j + 1 < hi)
            def _next():
                for d in dma(lax.rem(j + 1, 2), j + 1):
                    d.start()

            for d in dma(slot, j):
                d.wait()
            parts = []
            for g in range(pack):
                lanes = pl.ds(g * dh, dh)
                kb = kbuf[slot, :, lanes]
                s = jnp.dot(qb, kb.T,
                            preferred_element_type=jnp.float32) * scale
                if causal:
                    cols = j * block_k + g + pack * \
                        lax.broadcasted_iota(
                            jnp.int32, (block_q, rows_k), 1)
                    s = jnp.where(cols > rows, jnp.float32(-1e9), s)
                parts.append((s, vbuf[slot, :, lanes]))
            return _online_softmax_step(jnp, parts, carry, acc_dtype)

        m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, dh), acc_dtype)
        m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, acc0))
        o_ref[0] = (acc.astype(jnp.float32) / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)                 # (bq, 1)

    pl.run_scoped(
        run,
        kbuf=pltpu.VMEM((2, rows_k, pack * dh), kv_dtype),
        vbuf=pltpu.VMEM((2, rows_k, pack * dh), kv_dtype),
        ksem=pltpu.SemaphoreType.DMA((2,)),
        vsem=pltpu.SemaphoreType.DMA((2,)))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, *, block_q, block_k, n_kb, causal, scale):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    qb = q_ref[0]
    dob = do_ref[0]
    lse = lse_ref[0]                                # (bq, 1)
    delta = delta_ref[0]
    bq, dh = qb.shape
    rows = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def make_body(masked):
        def body(j, dq):
            kb = k_ref[0, pl.ds(j * block_k, block_k), :]
            vb = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                cols = j * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(cols > rows, jnp.float32(-1e9), s)
            p = jnp.exp(s - lse)
            dp = jnp.dot(dob, vb.T,
                         preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(kb.dtype)
            return dq + jnp.dot(ds, kb,
                                preferred_element_type=jnp.float32)
        return body

    if causal:
        # same split as the forward: mask only the diagonal remnant
        hi = pl.cdiv((qi + 1) * block_q, block_k)
        clear = (qi * block_q) // block_k
        spans = [(0, clear, False), (clear, hi, True)]
    else:
        spans = [(0, n_kb, False)]
    dq_ref[0] = _split_loop(
        spans, make_body,
        jnp.zeros((block_q, dh), jnp.float32)).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q, block_k, n_qb, causal,
                scale):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    kb = k_ref[0]                                   # (bk, dh)
    vb = v_ref[0]
    bk, dh = kb.shape
    cols = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def make_body(masked):
        def body(j, carry):
            dk, dv = carry
            qb = q_ref[0, pl.ds(j * block_q, block_q), :]
            dob = do_ref[0, pl.ds(j * block_q, block_q), :]
            # lse/delta ride as (1, 1, S) — sequence on the LANE dim;
            # a (1, S, 1) full block would pad its trailing singleton
            # to 128 lanes (S*128*4 bytes of VMEM each: the S=8k
            # compile OOM)
            lse = lse_ref[0, 0, pl.ds(j * block_q, block_q)][:, None]
            delta = delta_ref[0, 0,
                              pl.ds(j * block_q, block_q)][:, None]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                rows = j * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(cols > rows, jnp.float32(-1e9), s)
            p = jnp.exp(s - lse)
            dv = dv + jnp.dot(p.astype(dob.dtype).T, dob,
                              preferred_element_type=jnp.float32)
            dp = jnp.dot(dob, vb.T,
                         preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(qb.dtype)
            dk = dk + jnp.dot(ds.T, qb,
                              preferred_element_type=jnp.float32)
            return dk, dv
        return body

    dk0 = jnp.zeros((bk, dh), jnp.float32)
    dv0 = jnp.zeros((bk, dh), jnp.float32)
    if causal:
        # Q blocks strictly above this K block's first column see only
        # masked scores — start below them; only the diagonal remnant
        # [lo, clear) needs the mask
        lo = (ki * block_k) // block_q
        clear = pl.cdiv((ki + 1) * block_k - 1, block_q)
        spans = [(lo, clear, True), (clear, n_qb, False)]
    else:
        spans = [(0, n_qb, False)]
    dk, dv = _split_loop(spans, make_body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dkvq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dq_ref, *, block_q, block_k, n_qb,
                 causal, scale):
    """FUSED backward: one pass over the (q-block, k-block) pairs
    computes dk, dv AND dq — where the two-kernel form ran 7 block
    matmuls and 2 exp passes per pair (s and dp recomputed in each
    kernel), this runs 5 and 1.

    The trick is TPU Pallas' SEQUENTIAL grid: dq rides as a full
    (1, S, dh) f32 output ref whose block index is constant in the
    ki grid dim, so the buffer is revisited across k-blocks and
    accumulated in place (zeroed at ki == 0, flushed to HBM when the
    bh index advances) — the accumulation pattern a parallel-grid GPU
    kernel would need atomics for."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    kb = k_ref[0]                                   # (bk, dh)
    vb = v_ref[0]
    bk, dh = kb.shape
    cols = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(ki == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def make_body(masked):
        def body(j, carry):
            dk, dv = carry
            qb = q_ref[0, pl.ds(j * block_q, block_q), :]
            dob = do_ref[0, pl.ds(j * block_q, block_q), :]
            lse = lse_ref[0, 0, pl.ds(j * block_q, block_q)][:, None]
            delta = delta_ref[0, 0,
                              pl.ds(j * block_q, block_q)][:, None]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale
            if masked:
                rows = j * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(cols > rows, jnp.float32(-1e9), s)
            p = jnp.exp(s - lse)
            dv = dv + jnp.dot(p.astype(dob.dtype).T, dob,
                              preferred_element_type=jnp.float32)
            dp = jnp.dot(dob, vb.T,
                         preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(qb.dtype)
            dk = dk + jnp.dot(ds.T, qb,
                              preferred_element_type=jnp.float32)
            sl = pl.ds(j * block_q, block_q)
            dq_ref[0, sl, :] = dq_ref[0, sl, :] + jnp.dot(
                ds, kb, preferred_element_type=jnp.float32)
            return dk, dv
        return body

    dk0 = jnp.zeros((bk, dh), jnp.float32)
    dv0 = jnp.zeros((bk, dh), jnp.float32)
    if causal:
        # Q blocks strictly above this K block's first column see only
        # masked scores — start below them; only the diagonal remnant
        # [lo, clear) needs the mask
        lo = (ki * block_k) // block_q
        clear = pl.cdiv((ki + 1) * block_k - 1, block_q)
        spans = [(lo, clear, True), (clear, n_qb, False)]
    else:
        spans = [(0, n_qb, False)]
    dk, dv = _split_loop(spans, make_body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


#: the short-sequence kernels (``_tile_fwd_kernel`` /
#: ``_tile_bwd_kernel``) take a sequence whose one tile is the whole
#: (S, S) square, up to this S (the largest tile the general kernels
#: use; 1024 blows scoped VMEM) ...
TILE_MAX_S = 512
#: ... and this many (batch, head) rows a program, where the number
#: of rows allows
TILE_ROWS = 4


def _tile_rows(bh):
    """Rows a program of the short-sequence kernels: the largest
    power-of-two divisor of ``bh`` (= batch x heads as the kernel sees
    them: per shard under ``parallel.kernel_per_shard``) up to
    ``TILE_ROWS``."""
    rows = 1
    while rows * 2 <= TILE_ROWS and bh % (rows * 2) == 0:
        rows *= 2
    return rows


def _tile_params(rows, s, dh, itemsize, interpret):
    """``pallas_call`` keywords granting the short-sequence kernels
    their scoped VMEM: per row the double-buffered operands (at most
    four in, three out) and five (S, S) f32-sized temporaries, with a
    2x margin for Mosaic's own slack; never under the 16MB default,
    clamped to the device."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    per_row = 2 * 7 * s * dh * itemsize + 5 * s * s * 4
    limit = min(max(2 * rows * per_row, 16 << 20), _device_vmem_bytes())
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=limit)}


def _tile_mask(jnp, s):
    """(s, s) bool over (key, query): True where the key comes after
    the query."""
    from jax import lax
    return lax.broadcasted_iota(jnp.int32, (s, s), 0) \
        > lax.broadcasted_iota(jnp.int32, (s, s), 1)


def _tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, rows,
                     causal, scale):
    """SHORT-SEQUENCE forward: the whole (S, S) score square of a
    (batch, head) row is ONE tile, so there is no K loop and no
    running state — plain softmax in VMEM — and a program takes
    ``rows`` rows, straight-line, to share its fixed cost (~0.7 us a
    program, a third of a one-row program at S=512).

    The tile is held TRANSPOSED, keys on sublanes and queries on
    lanes: the softmax's max and sum then run down the sublanes
    (elementwise over vregs, no cross-lane reduction), the per-query
    statistics are lane vectors that divide the (dh, S) context
    without a relayout, and lse leaves lane-dense as (1, S) — a
    (S, 1) block pads every value to a 128-lane row, which cost more
    to write and to repack than the softmax (PERF.md, PR 27). The
    price is two small transposes, v (S, dh) and the context."""
    import jax.numpy as jnp

    if causal:
        after = _tile_mask(jnp, q_ref.shape[1])
    for r in range(rows):
        v = v_ref[r]
        st = jnp.dot(k_ref[r], q_ref[r].T,
                     preferred_element_type=jnp.float32) * scale
        if causal:
            st = jnp.where(after, jnp.float32(-1e9), st)
        m = st.max(axis=0, keepdims=True)           # (1, s)
        pt = jnp.exp(st - m)
        l = pt.sum(axis=0, keepdims=True)
        ctx_t = jnp.dot(v.T, pt.astype(v.dtype),
                        preferred_element_type=jnp.float32)
        o_ref[r] = (ctx_t / l).T.astype(o_ref.dtype)
        lse_ref[r] = m + jnp.log(l)


def _tile_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, *, rows, causal, scale):
    """SHORT-SEQUENCE backward, the pair of ``_tile_fwd_kernel`` and
    transposed like it (lse and delta arrive as the (1, S) lane
    vectors they are stored as; dv and dk are plain products of the
    transposed tile, only dq transposes it back). One tile a row
    means dq needs no accumulator across programs, so all three
    gradients leave in the storage dtype: no f32 dq row and no
    convert pass after the kernel. The same 5 products + 1 exp as
    ``_dkvq_kernel``."""
    import jax.numpy as jnp

    if causal:
        after = _tile_mask(jnp, q_ref.shape[1])
    for r in range(rows):
        q, k, v, do = q_ref[r], k_ref[r], v_ref[r], do_ref[r]
        st = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
        if causal:
            st = jnp.where(after, jnp.float32(-1e9), st)
        pt = jnp.exp(st - lse_ref[r])
        dv_ref[r] = jnp.dot(
            pt.astype(do.dtype), do,
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dpt = jnp.dot(v, do.T, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[r]) * scale).astype(q.dtype)
        dk_ref[r] = jnp.dot(
            dst, q,
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)
        dq_ref[r] = jnp.dot(
            dst.T, k,
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _tile_specs(rows, s, dh):
    """Specs of the short-sequence kernels, ``rows`` whole (batch,
    head) rows a program: (rows, S, dh) tensors and (rows, 1, S)
    per-row scalars, the sequence on the lane dim."""
    from jax.experimental import pallas as pl
    return (pl.BlockSpec((rows, s, dh), lambda i: (i, 0, 0)),
            pl.BlockSpec((rows, 1, s), lambda i: (i, 0, 0)))


def _specs(block_rows, s, dh):
    """Row-blocked / full-rows specs for (BH, S, dh) tensors plus the
    matching specs for (BH, S, 1) per-row scalars (lse, delta) — the
    trailing singleton keeps the sublane/lane tiling rule satisfied
    (block dim == array dim counts as legal)."""
    from jax.experimental import pallas as pl
    blocked = pl.BlockSpec((1, block_rows, dh),
                           lambda bh, i: (bh, i, 0))
    full = pl.BlockSpec((1, s, dh), lambda bh, i: (bh, 0, 0))
    vec = pl.BlockSpec((1, block_rows, 1), lambda bh, i: (bh, i, 0))
    # per-row scalars as (BH, 1, S): sequence on the lane dim, so the
    # full-rows variant costs S*4 bytes, not S*128*4 (see _dkv_kernel)
    full_vec = pl.BlockSpec((1, 1, s), lambda bh, i: (bh, 0, 0))
    return blocked, full, vec, full_vec


def flash_attention_fwd(q, k, v, causal=True, block_q=128,
                        block_k=128, interpret=None, pipeline=False,
                        acc_dtype=None):
    """q/k/v: (B, H, S, dh) → (out, lse); exact. Blocks must divide
    S. ``interpret``: False = the real Mosaic kernel, True = the
    Pallas interpreter (how the CPU tests run the same code); None
    asks jax's default device (:func:`_on_tpu`).

    ``pipeline=True`` keeps K/V in HBM and double-buffers each block
    into VMEM scratch (``_fwd_kernel_pipe``): the next block's DMA
    overlaps the current block's matmuls, and the kernel's resident
    VMEM no longer scales with S — the long-context escape hatch past
    the whole-row ceiling. ``acc_dtype`` (default f32) sets the
    running-context accumulator dtype; ``jnp.bfloat16`` is the gated
    accumulation experiment — lse/softmax statistics stay f32 either
    way, so only the PV accumulation chain narrows (error bound
    pinned by the numerics test). Both are experiments of the general
    kernel; without them a sequence that is one tile (blocks == S <=
    ``TILE_MAX_S``) runs the short-sequence ``_tile_fwd_kernel``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, s, dh = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError("blocks (%d, %d) do not divide sequence %d"
                         % (block_q, block_k, s))
    if interpret is None:
        interpret = not _on_tpu()
    if acc_dtype is None:
        acc_dtype = jnp.float32
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    qf = q.reshape(b * h, s, dh)
    kv_shape = (b * h, s, dh)
    if block_q == block_k == s <= TILE_MAX_S and not pipeline \
            and acc_dtype == jnp.float32:
        rows = _tile_rows(b * h)
        tensor, lanes = _tile_specs(rows, s, dh)
        out, lse = pl.pallas_call(
            functools.partial(_tile_fwd_kernel, rows=rows,
                              causal=causal, scale=scale),
            grid=(b * h // rows,),
            in_specs=[tensor, tensor, tensor],
            out_specs=[tensor, lanes],
            out_shape=[jax.ShapeDtypeStruct(kv_shape, q.dtype),
                       jax.ShapeDtypeStruct((b * h, 1, s),
                                            jnp.float32)],
            interpret=interpret,
            **_tile_params(rows, s, dh, q.dtype.itemsize, interpret),
        )(qf, k.reshape(kv_shape), v.reshape(kv_shape))
        return (out.reshape(b, h, s, dh), lse.reshape(b, h, s))
    blocked, full, vec, _ = _specs(block_q, s, dh)
    if pipeline:
        pack = _kv_lane_pack(dh)
        if block_k % pack:
            raise ValueError(
                "pipelined forward packs %d K/V rows per 128-lane row "
                "at dh=%d; block_k %d is not a multiple"
                % (pack, dh, block_k))
        kernel = functools.partial(
            _fwd_kernel_pipe, block_q=block_q, block_k=block_k,
            n_kb=s // block_k, causal=causal, scale=scale,
            acc_dtype=acc_dtype, kv_dtype=k.dtype, pack=pack)
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
        kv_shape = (b * h, s // pack, pack * dh)
    else:
        kernel = functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k,
            n_kb=s // block_k, causal=causal, scale=scale,
            acc_dtype=acc_dtype)
        kv_spec = full
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q),
        in_specs=[blocked, kv_spec, kv_spec],
        out_specs=[blocked, vec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)],
        interpret=interpret,
    )(qf, k.reshape(kv_shape), v.reshape(kv_shape))
    return (out.reshape(b, h, s, dh), lse.reshape(b, h, s))


def flash_attention_bwd(q, k, v, out, lse, dout, causal=True,
                        block_q=128, block_k=128, interpret=None,
                        delta=None, fused=True):
    """Block-recomputation backward → (dq, dk, dv), exact. ``delta``:
    optional precomputed ``rowsum(dout*out)`` (B, H, S) f32 — callers
    that invoke this kernel repeatedly on the same out/dout (the ring's
    per-step inner backward) hoist it to avoid re-reading both tensors
    from HBM every call.

    ``fused=True`` (default) runs the single-pass dk/dv/dq kernel
    (``_dkvq_kernel`` — dq accumulated in a revisited output ref
    across the sequential k-block grid): 5 block matmuls + 1 exp per
    pair instead of the two-kernel form's 7 + 2, measured +38% (10.5 -> 7.65 ms) on the
    whole backward at the 110M S=8k shapes. ``fused=False`` keeps the
    classic dq-kernel + dkv-kernel pair (the reference formulation,
    retained for A/B and as the fallback if a Pallas/Mosaic change
    ever breaks output-ref revisiting). A sequence that is one tile
    (blocks == S <= ``TILE_MAX_S``) runs the short-sequence
    ``_tile_bwd_kernel`` in place of the fused one."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, s, dh = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError("blocks (%d, %d) do not divide sequence %d"
                         % (block_q, block_k, s))
    if interpret is None:
        interpret = not _on_tpu()
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    flat = (b * h, s, dh)
    qf, kf, vf, dof = (t.reshape(flat) for t in (q, k, v, dout))
    lsef = lse.reshape(b * h, s, 1)
    lse_lanes = lse.reshape(b * h, 1, s)
    if delta is None:
        delta_rows = (dout.astype(jnp.float32)
                      * out.astype(jnp.float32)).sum(axis=-1)
    else:
        delta_rows = delta
    delta_rows = delta_rows.astype(jnp.float32)
    delta = delta_rows.reshape(b * h, s, 1)
    delta_lanes = delta_rows.reshape(b * h, 1, s)
    shape = (b, h, s, dh)
    if fused and block_q == block_k == s <= TILE_MAX_S:
        rows = _tile_rows(b * h)
        tensor, lanes = _tile_specs(rows, s, dh)
        grad = jax.ShapeDtypeStruct(flat, q.dtype)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_tile_bwd_kernel, rows=rows,
                              causal=causal, scale=scale),
            grid=(b * h // rows,),
            in_specs=[tensor, tensor, tensor, tensor, lanes, lanes],
            out_specs=[tensor, tensor, tensor],
            out_shape=[grad, grad, grad],
            interpret=interpret,
            **_tile_params(rows, s, dh, q.dtype.itemsize, interpret),
        )(qf, kf, vf, dof, lse_lanes, delta_lanes)
        return (dq.reshape(shape), dk.reshape(shape),
                dv.reshape(shape))
    qblocked, qfull, qvec, qfull_vec = _specs(block_q, s, dh)
    kblocked, _, _, _ = _specs(block_k, s, dh)

    if fused:
        dkvq = functools.partial(_dkvq_kernel, block_q=block_q,
                                 block_k=block_k,
                                 n_qb=s // block_q,
                                 causal=causal, scale=scale)
        # dq: full-row f32 accumulator, block index CONSTANT in ki so
        # the sequential grid revisits (and keeps) it in VMEM
        dq_full_f32 = pl.BlockSpec((1, s, dh), lambda bh, i: (bh, 0, 0))
        # the resident q/do/dq rows push past the default 16MB scoped-
        # vmem budget at S=8k inside a larger program (measured
        # 16.75MB) — grant the kernel what its footprint needs,
        # clamped to the device generation's actual VMEM
        params = {}
        if not interpret:
            from jax.experimental.pallas import tpu as pltpu
            params["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=_fused_bwd_vmem_limit(
                    s, dh, block_q, block_k, q.dtype.itemsize))
        dk, dv, dq = pl.pallas_call(
            dkvq,
            grid=(b * h, s // block_k),
            in_specs=[qfull, kblocked, kblocked, qfull, qfull_vec,
                      qfull_vec],
            out_specs=[kblocked, kblocked, dq_full_f32],
            out_shape=[jax.ShapeDtypeStruct(flat, q.dtype),
                       jax.ShapeDtypeStruct(flat, q.dtype),
                       jax.ShapeDtypeStruct(flat, jnp.float32)],
            interpret=interpret,
            **params,
        )(qf, kf, vf, dof, lse_lanes, delta_lanes)
        return (dq.astype(q.dtype).reshape(shape),
                dk.reshape(shape), dv.reshape(shape))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q,
                          block_k=block_k, n_kb=s // block_k,
                          causal=causal, scale=scale),
        grid=(b * h, s // block_q),
        in_specs=[qblocked, qfull, qfull, qblocked, qvec, qvec],
        out_specs=qblocked,
        out_shape=jax.ShapeDtypeStruct(flat, q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q,
                          block_k=block_k, n_qb=s // block_q,
                          causal=causal, scale=scale),
        grid=(b * h, s // block_k),
        in_specs=[qfull, kblocked, kblocked, qfull, qfull_vec,
                  qfull_vec],
        out_specs=[kblocked, kblocked],
        out_shape=[jax.ShapeDtypeStruct(flat, q.dtype),
                   jax.ShapeDtypeStruct(flat, q.dtype)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse_lanes, delta_lanes)

    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))
