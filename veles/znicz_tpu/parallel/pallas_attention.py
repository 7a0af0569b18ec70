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
  These K-loop kernels hold the score tile TRANSPOSED, keys on
  sublanes and queries on lanes, as the one-tile kernels below do
  (PERF.md section 6, PR 29: a layer call at B=4, H=12, S=8192,
  dh=64 alone on a v5e, tile 512, 8.33 -> 6.77 ms forward, 16.06 ->
  13.08 ms fused backward; 6.08 / 12.73 at tile 1024): every
  per-query statistic is a lane vector, no (tile, tile) transpose
  and no lane-to-column relayout is left in a pair loop.
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
shipped as (BH, 1, S) with the sequence on the LANE dim, out of the
forward and into the backward alike: a (BH, S, 1) layout pads its
trailing singleton to 128 lanes and explodes VMEM (S·128·4 bytes per
ref — the original S=8k backward compile failure) and HBM (201 MB a
layer call at S=8k, B=4, which the forward wrote and XLA repacked
until PR 29). Only the default-off pipelined forward and two-kernel
backward still use that layout.

Exact math (same online softmax as flash.py / ring.py; verified
against both in tests — interpret mode on CPU, real kernels on TPU):

* :func:`flash_attention_fwd`  — (B,H,S,dh) → (out, lse)
* :func:`flash_attention_bwd` — block-recomputation backward from the
  saved logsumexp. Default (round 5): ONE fused kernel computes
  dq/dk/dv in a single pass over the k-block grid (``_dkvq_kernel``;
  dq accumulates, transposed and lane-dense, in a VMEM-resident
  revisited output ref — legal because the TPU Pallas grid is
  sequential), 5 block matmuls + 1 exp per causal pair vs the classic
  two-pass form's 7 + 2 (retained behind ``fused=False``); measured
  +38% at the 110M S=8k shapes.

What bounds them at head size 64 (PR 29, a trivial Pallas kernel of
chained bf16 products on the v5e): every product has 64 as its
contraction or its output width, which half-fills the 128x128 MXU —
(512x64)·(512x64)^T reads 68 TFLOP/s, (512x512)·(512x64) 71,
(64x512)·(512x512) 113, a full-depth (512x512)·(512x512) 154. The
forward uses the first and the third form, the backward 2 + 2 + 1:
timed one form at a time that is 0.79 and 2.2 us a 512 pair, and in
a step the kernels run a pair in 0.99 and 1.92 us — the backward,
interleaving its five products, is at their speed.

Causal masking is paid only where it can matter (round 5): the
fori_loops split at the diagonal — blocks fully below it skip the
iota/where pass entirely, the diagonal remnant keeps it.

Consumed by ``MultiHeadAttention(attn_impl="pallas")``; backward is
wired through the explicit GD unit (znicz style), so no custom-VJP
registration is needed — autodiff never touches these.

VMEM budget: K and V ride whole per-(batch·head) rows in VMEM — the
forward's K row (lane-padded) and transposed V row are S·dh·6 bytes
at dh=64, double-buffered inside the 16 MB default up to S=16k (it
compiles there for a described v5e, dh=64 and 128; the parent's
failed from S=12k) — and the fused backward q, do and the f32 dq row,
granted 1.5x their footprint of the v5e's 128 MB
(``_fused_bwd_vmem_limit``: 27 MB at S=8k, tile 512). Beyond that,
block K/V from HBM with manual DMA (``pipeline=True``: documented
escape hatch, not needed at current model scale).
On the chip a tile is a multiple of 128 lanes, or the whole S: the
lane-dense lse and dq blocks want it (the backward always did).
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

    Resident per grid step, as VMEM holds it — every operand of the
    call twice (Pallas double-buffers a block even when its index
    never moves), a minor dim under 128 lanes padded to 128, a (1, S)
    row to 8 sublanes: the full q/do rows (storage dtype), the full
    TRANSPOSED f32 dq accumulator (dh, S), the lse/delta lanes, the
    k/v/dk/dv blocks; once, four (block_k, block_q) f32 score/prob
    temporaries. The 1.5x margin is Mosaic's slack over that: what
    the v5e compiler really needs is 0.54-0.93 of the footprint
    (PR 29, found by lowering the grant until the compile fails:
    15.6MB of 18MB at S=8k/dh=64/bf16/tile 512, 22.4 of 31 at tile
    1024, 12.6 of 13.5 at tile 128, 28.0 of 31 at S=16k, 16.1 of 22
    at dh=128; the (queries, keys) kernel before it needed 20.5MB at
    tile 512, 16.8 at tile 128).
    Raises with the escape hatches when even that exceeds the device:
    ``fused=False`` (the two-kernel backward never holds dq resident)
    or a smaller ``pallas_tile``."""
    lanes = max(dh, 128)
    resident = (2 * (2 * s * lanes * itemsize       # q + do rows
                     + dh * s * 4                   # f32 dq_t
                     + 2 * 8 * s * 4                # lse + delta
                     + 4 * block_k * lanes * itemsize)  # k/v/dk/dv
                + 4 * block_q * block_k * 4)        # score/prob temps
    need = resident * 3 // 2
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


#: contract the LAST dim of both operands, ``a @ b.T`` without the
#: transpose as an operation of its own (the MXU takes its latched
#: operand either way round)
_NT = (((1,), (1,)), ((), ()))


def _after(jnp, k0, q0, block_k, block_q):
    """(block_k, block_q) bool over (key, query) of the tile whose
    first key is ``k0`` and first query ``q0``: True where the key
    comes after the query — what the causal mask hides."""
    from jax import lax
    shape = (block_k, block_q)
    return k0 + lax.broadcasted_iota(jnp.int32, shape, 0) \
        > q0 + lax.broadcasted_iota(jnp.int32, shape, 1)


def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, *, block_q,
                block_k, n_kb, causal, scale, acc_dtype):
    """K-LOOP forward, one q block a program, the K row and the
    TRANSPOSED V row (dh, S) resident. The score tile is held
    transposed, (keys on sublanes, queries on lanes), as the one-tile
    kernels hold it: the running max, sum and rescale factor are
    (1, block_q) lane vectors, ``max`` / ``sum`` run down the sublanes
    (elementwise over vregs), ``st - m`` and ``acc_t * coef`` are
    sublane broadcasts, and the carry is the (dh, block_q) context plus
    two lane vectors — inside the register file, where the (queries,
    keys) form carried three (block_q, .) columns of one used lane in
    128 (PERF.md section 6, PR 29). One (dh, block_q) transpose ends
    the program; lse leaves lane-dense, a (1, block_q) block of
    (BH, 1, S)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    qb = q_ref[0]                                   # (bq, dh)
    dh = qb.shape[1]

    def make_body(masked):
        def body(j, carry):
            m, l, acc_t = carry
            k0 = pl.multiple_of(j * block_k, block_k)
            kb = k_ref[0, pl.ds(k0, block_k), :]    # (bk, dh)
            vt = vt_ref[0, :, pl.ds(k0, block_k)]   # (dh, bk)
            st = lax.dot_general(
                kb, qb, _NT,
                preferred_element_type=jnp.float32) * scale
            if masked:
                st = jnp.where(
                    _after(jnp, k0, qi * block_q, block_k, block_q),
                    jnp.float32(-1e9), st)
            m_new = jnp.maximum(m, st.max(axis=0, keepdims=True))
            coef = jnp.exp(m - m_new)               # (1, bq)
            pt = jnp.exp(st - m_new)
            l_new = l * coef + pt.sum(axis=0, keepdims=True)
            # pt in the storage dtype (bf16 on TPU) for the product —
            # exp stays f32, the MXU gets matched input dtypes and
            # accumulates in f32 whatever ``acc_dtype`` carries
            pv_t = jnp.dot(vt, pt.astype(vt.dtype),
                           preferred_element_type=jnp.float32)
            acc_new = acc_t * coef.astype(acc_dtype) \
                + pv_t.astype(acc_dtype)
            return m_new, l_new, acc_new
        return body

    m0 = jnp.full((1, block_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, block_q), jnp.float32)
    acc0 = jnp.zeros((dh, block_q), acc_dtype)
    if causal:
        # K blocks past this Q block's last row are all-masked — skip
        # them entirely; only the diagonal remnant needs the mask
        hi = pl.cdiv((qi + 1) * block_q, block_k)
        clear = (qi * block_q) // block_k
        spans = [(0, clear, False), (clear, hi, True)]
    else:
        spans = [(0, n_kb, False)]
    m, l, acc_t = _split_loop(spans, make_body, (m0, l0, acc0))
    o_ref[0] = (acc_t.astype(jnp.float32) / l).T.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                     # (1, bq)


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
                 dk_ref, dv_ref, dqt_ref, *, block_q, block_k, n_qb,
                 causal, scale):
    """FUSED backward: one pass over the (q-block, k-block) pairs
    computes dk, dv AND dq — where the two-kernel form ran 7 block
    matmuls and 2 exp passes per pair (s and dp recomputed in each
    kernel), this runs 5 and 1.

    The trick is TPU Pallas' SEQUENTIAL grid: dq rides as a full-row
    f32 output ref whose block index is constant in the ki grid dim,
    so the buffer is revisited across k-blocks and accumulated in
    place (zeroed at ki == 0, flushed to HBM when the bh index
    advances) — the accumulation pattern a parallel-grid GPU kernel
    would need atomics for.

    The tile is held TRANSPOSED like the forward's, (keys, queries):
    lse and delta are used as the (1, block_q) lane vectors they are
    stored as, dv and dk are plain products of the transposed tile
    (``pt @ do``, ``dst @ q``), and dq accumulates transposed too,
    ``dq_t[:, q block] += k_blk.T @ dst`` with ``k_blk.T`` made once a
    program: the resident accumulator is (dh, S), lane-dense. No
    (block, block) transpose and no lane-to-column relayout is left
    in the pair loop."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    kb = k_ref[0]                                   # (bk, dh)
    vb = v_ref[0]
    bk, dh = kb.shape
    kb_t = kb.T                                     # (dh, bk)

    @pl.when(ki == 0)
    def _init():
        dqt_ref[0] = jnp.zeros_like(dqt_ref[0])

    def make_body(masked):
        def body(j, carry):
            dk, dv = carry
            q0 = pl.multiple_of(j * block_q, block_q)
            rows = pl.ds(q0, block_q)
            qb = q_ref[0, rows, :]                  # (bq, dh)
            dob = do_ref[0, rows, :]
            st = lax.dot_general(
                kb, qb, _NT,
                preferred_element_type=jnp.float32) * scale
            if masked:
                st = jnp.where(
                    _after(jnp, ki * block_k, q0, block_k, block_q),
                    jnp.float32(-1e9), st)
            pt = jnp.exp(st - lse_ref[0, :, rows])  # lse: (1, bq)
            dv = dv + jnp.dot(pt.astype(dob.dtype), dob,
                              preferred_element_type=jnp.float32)
            dpt = lax.dot_general(
                vb, dob, _NT, preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta_ref[0, :, rows])
                   * scale).astype(qb.dtype)
            dk = dk + jnp.dot(dst, qb,
                              preferred_element_type=jnp.float32)
            dqt_ref[0, :, rows] = dqt_ref[0, :, rows] + jnp.dot(
                kb_t, dst, preferred_element_type=jnp.float32)
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
#: (S, S) square, up to this S (a 1024 square, several rows a
#: program, blows scoped VMEM) ...
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
    """q/k/v: (B, H, S, dh) → (out (B, H, S, dh), lse (B, H, S)
    f32); exact. Blocks must divide S. ``interpret``: False = the
    real Mosaic kernel, True = the Pallas interpreter (how the CPU
    tests run the same code); None asks jax's default device
    (:func:`_on_tpu`). The K-loop kernel takes V as (BH, dh, S) — one
    XLA transpose a call — and writes lse lane-dense as (BH, 1, S),
    the layout the backward reads.

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
        v_spec, vf = kv_spec, v.reshape(kv_shape)
        lse_spec, lse_shape = vec, (b * h, s, 1)
    else:
        kernel = functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k,
            n_kb=s // block_k, causal=causal, scale=scale,
            acc_dtype=acc_dtype)
        kv_spec = full
        # V as (BH, dh, S): one O(S*dh) XLA transpose a call, so that
        # the kernel's P.V product needs none a pair and V's resident
        # row is lane-dense; lse leaves as (BH, 1, S) blocks, the
        # layout the backward reads
        v_spec = pl.BlockSpec((1, dh, s), lambda bh, i: (bh, 0, 0))
        vf = v.reshape(kv_shape).swapaxes(1, 2)
        lse_spec = pl.BlockSpec((1, 1, block_q),
                                lambda bh, i: (bh, 0, i))
        lse_shape = (b * h, 1, s)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q),
        in_specs=[blocked, kv_spec, v_spec],
        out_specs=[blocked, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
                   jax.ShapeDtypeStruct(lse_shape, jnp.float32)],
        interpret=interpret,
    )(qf, k.reshape(kv_shape), vf)
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
    (``_dkvq_kernel`` — dq accumulated transposed, (BH, dh, S) f32,
    in a revisited output ref across the sequential k-block grid,
    transposed back and converted in one XLA pass here): 5 block
    matmuls + 1 exp per pair instead of the two-kernel form's 7 + 2,
    measured +38% on the whole backward at the 110M S=8k shapes by an
    earlier builder; 13.08 ms a layer call of B=4 at tile 512, 12.73
    at 1024 (PR 29). ``fused=False`` keeps the
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
        # dq: full-row f32 accumulator, TRANSPOSED (dh, S) so that it
        # is lane-dense, block index CONSTANT in ki so the sequential
        # grid revisits (and keeps) it in VMEM
        dqt_full_f32 = pl.BlockSpec((1, dh, s),
                                    lambda bh, i: (bh, 0, 0))
        # the resident q/do/dq rows reach the default 16MB scoped-
        # vmem budget at S=8k (15.6MB at tile 512, 22.4 at 1024) —
        # grant the kernel what its footprint needs, clamped to the
        # device generation's actual VMEM
        params = {}
        if not interpret:
            from jax.experimental.pallas import tpu as pltpu
            params["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=_fused_bwd_vmem_limit(
                    s, dh, block_q, block_k, q.dtype.itemsize))
        dk, dv, dq_t = pl.pallas_call(
            dkvq,
            grid=(b * h, s // block_k),
            in_specs=[qfull, kblocked, kblocked, qfull, qfull_vec,
                      qfull_vec],
            out_specs=[kblocked, kblocked, dqt_full_f32],
            out_shape=[jax.ShapeDtypeStruct(flat, q.dtype),
                       jax.ShapeDtypeStruct(flat, q.dtype),
                       jax.ShapeDtypeStruct((b * h, dh, s),
                                            jnp.float32)],
            interpret=interpret,
            **params,
        )(qf, kf, vf, dof, lse_lanes, delta_lanes)
        # transpose and convert in one XLA pass
        return (dq_t.swapaxes(1, 2).astype(q.dtype).reshape(shape),
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
