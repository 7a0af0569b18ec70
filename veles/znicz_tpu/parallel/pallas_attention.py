"""Hand-written Pallas TPU flash-attention kernels — the attention
proper from S=256 up.

Real TPU kernels keeping the score tile and the softmax state in VMEM
(SURVEY.md §2.5, §7 stage 6). Four kernel bodies, two pairs, and the
SHAPE alone says which pair runs (``flash_attention_fwd`` /
``flash_attention_bwd`` have no argument that selects a kernel):

* a sequence that is ONE tile, ``block_q == block_k == S <=
  TILE_MAX_S`` (512) — what ``MultiHeadAttention._pallas_block`` picks
  at S=512 and S=256: ``_tile_fwd_kernel`` / ``_tile_bwd_kernel``, no
  K loop, several (batch, head) rows a program;
* every other shape — any S above 512, or a tile smaller than S:
  ``_fwd_kernel`` / ``_dkvq_kernel``, a loop over the K (forward) or
  Q (backward) tiles of a row, the row resident in VMEM.

Both pairs hold the score tile TRANSPOSED, keys on sublanes and
queries on lanes: every per-query statistic is a lane vector, no
(tile, tile) transpose and no lane-to-column relayout is left in a
pair loop, and per-row scalars (lse, delta) have ONE layout in this
module, (BH, 1, S) with the sequence on the LANE dim, out of the
forward and into the backward alike. A (BH, S, 1) layout pads its
trailing singleton to 128 lanes and explodes VMEM (S·128·4 bytes per
ref — the original S=8k backward compile failure) and HBM (201 MB a
layer call at S=8k, B=4, which the forward wrote and XLA repacked
until PR 29).

Where they win, and why:

* S>=1024 (an earlier builder's v5e readings, 57.5M LM, 2026-07-31,
  not in the driver's record; pallas vs scan tok/s): 174k vs 161k at
  S=1024, 156k vs 119k at S=2048, 111k vs 82k at S=4096, 85k vs 53k
  at S=8192 — the causal ``fori_loop`` bound SKIPS fully-masked K
  blocks entirely, halving the quadratic work, which the scan
  schedule cannot do (a lax.cond block-skip was measured SLOWER: TPU
  conditionals break scan pipelining; inside a Pallas kernel the loop
  bound is a plain scalar and costs nothing). The tile is free of
  attn_block (``MultiHeadAttention._pallas_block``, up to 512).
  PERF.md section 6, PR 29: a layer call at B=4, H=12, S=8192, dh=64
  alone on a v5e, tile 512, 6.77 ms forward, 13.08 ms fused backward
  (8.33 / 16.06 with the tile held (queries, keys); 6.08 / 12.73 at
  tile 1024).
* S=512 and 256 (PERF.md section 6, PR 27: the 110M LM at 16,384
  tokens a step on a v5e): 151.3k vs the scan's 111.3k tok/s at
  S=512, 159.0k vs 152.8k at S=256. At S=512, batch 32 the scan's
  (B, H, S, block) score tile is 201 MB and every pass over it an HBM
  round trip. The K-loop kernels read 136.2k at S=512 (tile 512) and
  138.6k at S=256, under the scan: a row of one tile wants its own
  kernels.
* S=128: the XLA scan (``parallel/flash.py``) wins, 167.8k vs 155.1k:
  the shorter S, the smaller the scan's tile and the better its one
  step fuses, while the kernels' cost a token does not fall with S.

``MultiHeadAttention`` therefore auto-selects: ``attn_impl=None``
uses the scan below ``PALLAS_AUTO_MIN_S`` (256) and these kernels at
or above it on a real TPU; ``attn_impl="scan"|"pallas"`` forces
either. Inputs ride in the compute dtype (bf16 on TPU): half the
VMEM — at S=8192 the difference between fitting and a scoped-vmem
OOM — and matched MXU input dtypes.

Exact math (same online softmax as flash.py / ring.py; verified
against both in tests — interpret mode on CPU, real kernels on TPU):

* :func:`flash_attention_fwd`  — (B,H,S,dh) → (out, lse)
* :func:`flash_attention_bwd` — block-recomputation backward from the
  saved logsumexp: ONE kernel computes dq/dk/dv in a single pass (the
  K-loop ``_dkvq_kernel`` over the k-block grid, dq accumulating,
  transposed and lane-dense, in a VMEM-resident revisited output ref —
  legal because the TPU Pallas grid is sequential), 5 block matmuls +
  1 exp per causal pair.

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

A ``window`` W (static; a sliding-window layer: a query sees itself
and the W - 1 tokens before it) makes the triangle a BAND, by the same
two rules (PR 38). The K-loop pair's loops get a second bound — the
forward's loop over K tiles a LOWER one, ``max(q0 - W + 1, 0) //
block_k`` for the Q block that starts at ``q0`` (tiles wholly older
than the window are skipped as tiles wholly in the future are), the
fused backward's loop over Q tiles an UPPER one, ``cdiv(k0 + block_k +
W - 1, block_q)`` — as scalars of the ``fori_loop``s, and the loops
split in three: the tiles the band's OLD edge cuts (masked with both
terms: under a window shorter than a tile the diagonal runs through
them too), the tiles wholly inside (no mask pass), the tiles the
diagonal cuts (the causal term alone, as without a window)
(:func:`_band_spans_fwd`, :func:`_band_spans_bwd`). The one-tile pair
gets one more term in ``_tile_mask``. A window >= S hides nothing a
causal row shows and runs the kernels of ``window=None``, whose
programs are the ones they were; no argument selects a kernel, shape
and window do. Counts (:func:`band_pairs`, :func:`visited_pairs`): at
S = 8192, W = 512 a head's band holds 4,063,488 query-key pairs where
the triangle holds 33,558,528 (8.3 times: what a masked full kernel
would do for three layers of 72 heads); at tile 512 the bounds visit 31
tiles a row, 8,126,464 pairs, 2.0 times the band — each Q block its
diagonal tile and the one before it, both masked, none plain: what the
tile's rounding costs is the tile choice's to win back (a 256 tile
visits 1.5 times the band, a 128 tile 1.25).

A ``prefix`` (span_q, span_k) (static; EVA's remote part) gives
the K-loop pair keys of a length of their own, S_k = S / span_q x
span_k, and has query ``t`` see the first ``(t // span_q) x span_k`` of
them: the summaries of every whole span before its own. The forward's
K loop runs to that bound for its Q block, the backward's Q loop from
the first Q tile of the span after its K tile's
(:func:`_prefix_tiles`, :func:`_prefix_first_q`); the blocks divide the
spans, so no tile is cut and none is masked. A block of the first span
sees no key and writes out 0, lse -inf. At S = 16384, spans (2048,
128) a head's row holds 7,340,032 such pairs (:func:`prefix_pairs`).

Consumed by ``MultiHeadAttention(attn_impl="pallas")``; backward is
wired through the explicit GD unit (znicz style), so no custom-VJP
registration is needed — autodiff never touches these.

VMEM budget of the K-loop pair: K and V ride whole per-(batch·head)
rows in VMEM — the forward's K row (lane-padded) and transposed V row
are S·dh·6 bytes at dh=64, double-buffered inside the 16 MB default up
to S=16k (it compiles there for a described v5e, dh=64 and 128) — and
the fused backward q, do and the f32 dq row, granted 1.5x their
footprint of the v5e's 128 MB (``_fused_bwd_vmem_limit``: 27 MB at
S=8k, tile 512). Past S=16k one chip has no path yet: the first thing
to try is such a grant for the resident forward (ROADMAP R6f).
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
    generations before v5, where a long row may not fit at all).

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
    Raises when even that exceeds the device, naming what is left: a
    smaller ``pallas_tile``."""
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
            "use a smaller pallas_tile"
            % (need >> 20, s, dh, block_q, block_k, vmem >> 20))
    return limit


def _split_loop(spans, make_body, init):
    """Chained ``fori_loop``s over ``spans`` = [(lo, hi, masked), ...]
    — the causal diagonal split shared by both K-loop kernels (round
    5): blocks strictly on the unmasked side of the diagonal skip the
    iota/where pass entirely (~2 of the ~10 VPU passes per block),
    only the diagonal remnant pays it. The forward's loop over K
    blocks masks the TAIL span; the backward's loop over Q blocks
    masks the HEAD span."""
    import jax
    out = init
    for lo, hi, masked in spans:
        out = jax.lax.fori_loop(lo, hi, make_body(masked), out)
    return out


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


def _hidden(jnp, k0, q0, block_k, block_q, masked, window):
    """What a masked tile hides, (block_k, block_q) bool over (key,
    query): ``masked`` "causal" the keys after the query, "band" those
    and the keys ``window`` or more tokens before it (a query sees
    itself and the ``window - 1`` tokens before it)."""
    if masked != "band":
        return _after(jnp, k0, q0, block_k, block_q)
    from jax import lax
    shape = (block_k, block_q)
    ahead = q0 - k0 + lax.broadcasted_iota(jnp.int32, shape, 1) \
        - lax.broadcasted_iota(jnp.int32, shape, 0)
    return (ahead < 0) | (ahead >= window)


def _least_most(index):
    """(max, min) for a bound computed from ``index``: Python's for an
    int (the counters' rule), jax's for a kernel's program id."""
    if isinstance(index, int):
        return max, min
    import jax.numpy as jnp
    return jnp.maximum, jnp.minimum


def _band_spans_fwd(qi, block_q, block_k, window):
    """The K tiles the forward's Q block ``qi`` visits under a band,
    as :func:`_split_loop` spans. ``qi`` is a Python int (the counters'
    rule) or the kernel's program id (its loop bounds): the tiles from
    the one holding the oldest key the block's FIRST query sees to the
    diagonal's; masked where an edge of the band cuts them — the old
    edge below the first tile whose keys every query of the block
    still sees (both terms: a window under a tile meets the diagonal
    there too), the diagonal as without a window — and plain between."""
    q0 = qi * block_q
    hi = (q0 + block_q + block_k - 1) // block_k
    clear = q0 // block_k
    least, most = _least_most(qi)
    lo = least(q0 - window + 1, 0) // block_k
    inside = most(least((least(q0 + block_q - window, 0) + block_k - 1)
                        // block_k, lo), hi)
    return [(lo, inside, "band"), (inside, least(clear, inside), False),
            (least(clear, inside), hi, "causal")]


def _band_spans_bwd(ki, block_q, block_k, window, n_qb):
    """The Q tiles the backward's K block ``ki`` visits under a band:
    from the diagonal's to the one holding the last query that sees the
    block's LAST key; the diagonal remnant masked as without a window,
    the tiles past the last one whose queries all see the block's first
    key masked with both terms, plain between."""
    k0 = ki * block_k
    lo = k0 // block_q
    clear = (k0 + block_k - 1 + block_q - 1) // block_q
    least, most = _least_most(ki)
    hi = most((k0 + block_k + window - 1 + block_q - 1) // block_q, n_qb)
    inside = most(least((k0 + window) // block_q, lo), hi)
    return [(lo, most(clear, inside), "causal"),
            (most(clear, inside), inside, False), (inside, hi, "band")]


def visited_pairs(s, block_q, block_k, window):
    """Query-key pairs of the tiles the K-loop kernels' loop bounds
    visit for ONE (batch, head) row of length ``s``: the static count
    behind ``veles_window_tile_pairs_total``. Over :func:`band_pairs`
    it is what the tile's rounding costs (2.0 at tile 512, window 512,
    S = 8192; 1.06 for the causal triangle there)."""
    block_q, block_k = min(block_q, s), min(block_k, s)
    if window is None or window >= s:
        tiles = sum(-(-(qi + 1) * block_q // block_k)
                    for qi in range(s // block_q))
    else:
        tiles = 0
        for qi in range(s // block_q):
            spans = _band_spans_fwd(qi, block_q, block_k, window)
            tiles += spans[-1][1] - spans[0][0]
    return tiles * block_q * block_k


def _prefix_tiles(qi, block_q, block_k, prefix):
    """K tiles the forward's Q block ``qi`` visits under a ``prefix``
    (span_q, span_k): the keys of every WHOLE span of ``span_q`` queries
    before the block's, ``span_k`` keys a span."""
    span_q, span_k = prefix
    return (qi * block_q) // span_q * (span_k // block_k)


def _prefix_first_q(ki, block_q, block_k, prefix):
    """The first Q tile that sees the backward's K block ``ki`` under a
    ``prefix``: the first of the span after the one its keys sum up."""
    span_q, span_k = prefix
    return ((ki * block_k) // span_k + 1) * span_q // block_q


def prefix_pairs(s, prefix):
    """Query-key pairs a row of ``s`` queries attends under a
    ``prefix`` (span_q, span_k): query ``t`` sees ``(t // span_q) x
    span_k`` keys (7,340,032 at S = 16384, spans (2048, 128)). The
    kernels' tiles divide the spans, so their bounds visit exactly
    these pairs and mask none."""
    span_q, span_k = prefix
    spans = s // span_q
    return span_q * span_k * spans * (spans - 1) // 2


def band_pairs(s, window=None):
    """Query-key pairs a causal row of length ``s`` attends: query
    ``t`` sees ``min(t + 1, window)`` keys (4,063,488 at S = 8192,
    window 512, where the triangle holds 33,558,528)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, *, block_q,
                block_k, n_kb, causal, scale, window=None, prefix=None):
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
                    _hidden(jnp, k0, qi * block_q, block_k, block_q,
                            masked, window),
                    jnp.float32(-1e9), st)
            m_new = jnp.maximum(m, st.max(axis=0, keepdims=True))
            coef = jnp.exp(m - m_new)               # (1, bq)
            pt = jnp.exp(st - m_new)
            l_new = l * coef + pt.sum(axis=0, keepdims=True)
            # pt in the storage dtype (bf16 on TPU) for the product —
            # exp stays f32, the MXU gets matched input dtypes and
            # accumulates in f32, as the carried context does
            pv_t = jnp.dot(vt, pt.astype(vt.dtype),
                           preferred_element_type=jnp.float32)
            return m_new, l_new, acc_t * coef + pv_t
        return body

    m0 = jnp.full((1, block_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, block_q), jnp.float32)
    acc0 = jnp.zeros((dh, block_q), jnp.float32)
    if prefix is not None:
        # the keys of the spans before this block's, no tile cut
        spans = [(0, _prefix_tiles(qi, block_q, block_k, prefix), False)]
    elif window is not None:
        # ... and K blocks wholly older than the window likewise: the
        # mask is paid where an edge of the band cuts a tile
        spans = _band_spans_fwd(qi, block_q, block_k, window)
    elif causal:
        # K blocks past this Q block's last row are all-masked — skip
        # them entirely; only the diagonal remnant needs the mask
        hi = pl.cdiv((qi + 1) * block_q, block_k)
        clear = (qi * block_q) // block_k
        spans = [(0, clear, False), (clear, hi, True)]
    else:
        spans = [(0, n_kb, False)]
    m, l, acc_t = _split_loop(spans, make_body, (m0, l0, acc0))
    if prefix is not None:
        # a block of the first span sees no key: out 0, lse -inf
        l_out = jnp.where(l > 0, l, 1.0)
    else:
        l_out = l
    o_ref[0] = (acc_t / l_out).T.astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                     # (1, bq)


def _dkvq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dqt_ref, *, block_q, block_k, n_qb,
                 causal, scale, window=None, prefix=None):
    """FUSED backward: one pass over the (q-block, k-block) pairs
    computes dk, dv AND dq, 5 block matmuls and 1 exp pass per pair
    (a dq kernel beside a dk/dv kernel would recompute s and dp: 7
    and 2).

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
                    _hidden(jnp, ki * block_k, q0, block_k, block_q,
                            masked, window),
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
    if prefix is not None:
        # from the first Q block of the span after this K block's
        spans = [(_prefix_first_q(ki, block_q, block_k, prefix), n_qb,
                  False)]
    elif window is not None:
        # ... and stop at the last Q block that still sees this K block
        spans = _band_spans_bwd(ki, block_q, block_k, window, n_qb)
    elif causal:
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


def _tile_mask(jnp, s, window=None):
    """(s, s) bool over (key, query): True where the key comes after
    the query or, under a ``window``, that many tokens or more before
    it."""
    from jax import lax
    key = lax.broadcasted_iota(jnp.int32, (s, s), 0)
    query = lax.broadcasted_iota(jnp.int32, (s, s), 1)
    if window is None:
        return key > query
    return (key > query) | (query - key >= window)


def _tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, rows,
                     causal, scale, window=None):
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
        after = _tile_mask(jnp, q_ref.shape[1], window)
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
                     dq_ref, dk_ref, dv_ref, *, rows, causal, scale,
                     window=None):
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
        after = _tile_mask(jnp, q_ref.shape[1], window)
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
    """Specs of the K-loop kernels: a row-blocked and a full-rows spec
    for (BH, S, dh) tensors, and the full-rows spec for the (BH, 1, S)
    per-row scalars (lse, delta) — the sequence on the lane dim, the
    module's one layout for them: S*4 bytes a row, where (BH, S, 1)
    pads every value to a 128-lane row."""
    from jax.experimental import pallas as pl
    blocked = pl.BlockSpec((1, block_rows, dh),
                           lambda bh, i: (bh, i, 0))
    full = pl.BlockSpec((1, s, dh), lambda bh, i: (bh, 0, 0))
    full_lanes = pl.BlockSpec((1, 1, s), lambda bh, i: (bh, 0, 0))
    return blocked, full, full_lanes


def _band(window, causal, s):
    """``window`` as the kernels take it: None where it hides nothing
    a causal row of length ``s`` would show (a window >= S is plain
    causal, and runs today's kernels)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("a window (%r) is of a causal row and at "
                         "least 1" % (window,))
    return None if window >= s else int(window)


def _keys(q, k, block_q, block_k, causal, window, prefix):
    """(S, keys, block_q, block_k, window as the kernels take it) of a
    call: the blocks clipped to the lengths and checked to divide them;
    a ``prefix`` checked against its spans, of a row that is neither
    causal nor windowed."""
    s, sk = q.shape[2], k.shape[2]
    block_q, block_k = min(block_q, s), min(block_k, sk)
    if s % block_q or sk % block_k:
        raise ValueError("blocks (%d, %d) do not divide sequences "
                         "(%d, %d)" % (block_q, block_k, s, sk))
    if prefix is None:
        if sk != s:
            raise ValueError("%d keys for %d queries need a prefix"
                             % (sk, s))
        return s, sk, block_q, block_k, _band(window, causal, s)
    span_q, span_k = prefix
    if causal or window is not None:
        raise ValueError("a prefix row is not causal and has no window")
    if span_q % block_q or span_k % block_k or s % span_q \
            or sk != s // span_q * span_k:
        raise ValueError(
            "prefix spans (%d, %d) over sequences (%d, %d) in blocks "
            "(%d, %d): the blocks divide the spans, the spans the "
            "sequences" % (span_q, span_k, s, sk, block_q, block_k))
    return s, sk, block_q, block_k, None


def flash_attention_fwd(q, k, v, causal=True, block_q=128,
                        block_k=128, interpret=None, window=None,
                        prefix=None):
    """q (B, H, S, dh), k/v (B, H, S_k, dh) → (out (B, H, S, dh), lse
    (B, H, S) f32); exact softmax over the keys a query sees: the
    causal prefix of its own position (``causal``), a band under a
    ``window``, all S_k keys (neither), or, with ``prefix`` = (span_q,
    span_k), the first ``(t // span_q) x span_k`` keys for query ``t``
    (the summaries of every whole span before its own: EVA's remote
    part, ``ops/eva_attention.py``). Without a prefix S_k = S; with one
    S_k = S / span_q x span_k, the blocks divide the spans, so no tile
    is cut, and a query of the first span sees no key: out 0, lse -inf.
    Blocks must divide the lengths. ``interpret``: False = the real
    Mosaic kernel, True = the Pallas interpreter (how the CPU tests run
    the same code); None asks jax's default device (:func:`_on_tpu`).
    The shape alone picks the kernel: a sequence that is one tile
    (blocks == S <= ``TILE_MAX_S``, no prefix) runs
    ``_tile_fwd_kernel``, any other the K-loop ``_fwd_kernel``, which
    takes V as (BH, dh, S_k) — one XLA transpose a call. Both write lse
    lane-dense as (BH, 1, S), the layout the backward reads."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, _, dh = q.shape
    s, sk, block_q, block_k, window = _keys(
        q, k, block_q, block_k, causal, window, prefix)
    if interpret is None:
        interpret = not _on_tpu()
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    flat = (b * h, s, dh)
    qf = q.reshape(flat)
    kf, vf = (t.reshape(b * h, sk, dh) for t in (k, v))
    out_shape = [jax.ShapeDtypeStruct(flat, q.dtype),
                 jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32)]
    if prefix is None and block_q == block_k == s <= TILE_MAX_S:
        rows = _tile_rows(b * h)
        tensor, lanes = _tile_specs(rows, s, dh)
        out, lse = pl.pallas_call(
            functools.partial(_tile_fwd_kernel, rows=rows,
                              causal=causal, scale=scale,
                              window=window),
            grid=(b * h // rows,),
            in_specs=[tensor, tensor, tensor],
            out_specs=[tensor, lanes],
            out_shape=out_shape,
            interpret=interpret,
            **_tile_params(rows, s, dh, q.dtype.itemsize, interpret),
        )(qf, kf, vf)
        return (out.reshape(b, h, s, dh), lse.reshape(b, h, s))
    blocked, _, _ = _specs(block_q, s, dh)
    _, full, _ = _specs(block_k, sk, dh)
    # V as (BH, dh, S): one O(S*dh) XLA transpose a call, so that the
    # kernel's P.V product needs none a pair and V's resident row is
    # lane-dense; lse leaves as (1, block_q) blocks of (BH, 1, S)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q,
                          block_k=block_k, n_kb=sk // block_k,
                          causal=causal, scale=scale,
                          window=window, prefix=prefix),
        grid=(b * h, s // block_q),
        in_specs=[blocked, full,
                  pl.BlockSpec((1, dh, sk), lambda bh, i: (bh, 0, 0))],
        out_specs=[blocked,
                   pl.BlockSpec((1, 1, block_q),
                                lambda bh, i: (bh, 0, i))],
        out_shape=out_shape,
        interpret=interpret,
    )(qf, kf, vf.swapaxes(1, 2))
    return (out.reshape(b, h, s, dh), lse.reshape(b, h, s))


def flash_attention_bwd(q, k, v, out, lse, dout, causal=True,
                        block_q=128, block_k=128, interpret=None,
                        delta=None, window=None, prefix=None):
    """Block-recomputation backward → (dq, dk, dv), exact, of
    :func:`flash_attention_fwd` with the same ``causal``, ``window``
    and ``prefix``. ``delta``: optional precomputed ``rowsum(dout*out)``
    (B, H, S) f32 — callers that invoke this kernel repeatedly on the
    same out/dout (the ring's per-step inner backward) hoist it to
    avoid re-reading both tensors from HBM every call. ``out`` and
    ``lse`` may be those of a softmax over MORE keys than this call's
    (the ring's and EVA's merge): each part's gradient is then its
    share of the whole's.

    The shape alone picks the kernel, as in the forward: a sequence
    that is one tile runs ``_tile_bwd_kernel``, any other the fused
    K-loop ``_dkvq_kernel`` — one pass for dk, dv and dq, dq
    accumulated transposed, (BH, dh, S) f32, in a revisited output ref
    across the sequential k-block grid, transposed back and converted
    in one XLA pass here: 13.08 ms a layer call of B=4, H=12, S=8192,
    dh=64 at tile 512, 12.73 at 1024 (PERF.md section 6, PR 29)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b, h, _, dh = q.shape
    s, sk, block_q, block_k, window = _keys(
        q, k, block_q, block_k, causal, window, prefix)
    if interpret is None:
        interpret = not _on_tpu()
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    flat = (b * h, s, dh)
    qf, kf, vf, dof = (t.reshape((b * h,) + t.shape[2:])
                       for t in (q, k, v, dout))
    lse_lanes = lse.reshape(b * h, 1, s)
    if delta is None:
        delta = (dout.astype(jnp.float32)
                 * out.astype(jnp.float32)).sum(axis=-1)
    delta_lanes = delta.astype(jnp.float32).reshape(b * h, 1, s)
    shape = (b, h, s, dh)
    grad = jax.ShapeDtypeStruct(flat, q.dtype)
    if prefix is None and block_q == block_k == s <= TILE_MAX_S:
        rows = _tile_rows(b * h)
        tensor, lanes = _tile_specs(rows, s, dh)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_tile_bwd_kernel, rows=rows,
                              causal=causal, scale=scale,
                              window=window),
            grid=(b * h // rows,),
            in_specs=[tensor, tensor, tensor, tensor, lanes, lanes],
            out_specs=[tensor, tensor, tensor],
            out_shape=[grad, grad, grad],
            interpret=interpret,
            **_tile_params(rows, s, dh, q.dtype.itemsize, interpret),
        )(qf, kf, vf, dof, lse_lanes, delta_lanes)
        return (dq.reshape(shape), dk.reshape(shape),
                dv.reshape(shape))
    _, qfull, qfull_lanes = _specs(block_q, s, dh)
    kblocked, _, _ = _specs(block_k, sk, dh)
    kgrad = jax.ShapeDtypeStruct((b * h, sk, dh), q.dtype)
    # dq: full-row f32 accumulator, TRANSPOSED (dh, S) so that it is
    # lane-dense, block index CONSTANT in ki so the sequential grid
    # revisits (and keeps) it in VMEM
    dqt_full_f32 = pl.BlockSpec((1, dh, s), lambda bh, i: (bh, 0, 0))
    # the resident q/do/dq rows reach the default 16MB scoped-vmem
    # budget at S=8k (15.6MB at tile 512, 22.4 at 1024) — grant the
    # kernel what its footprint needs, clamped to the device
    # generation's actual VMEM
    params = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_fused_bwd_vmem_limit(
                s, dh, block_q, block_k, q.dtype.itemsize))
    dk, dv, dq_t = pl.pallas_call(
        functools.partial(_dkvq_kernel, block_q=block_q,
                          block_k=block_k, n_qb=s // block_q,
                          causal=causal, scale=scale,
                          window=window, prefix=prefix),
        grid=(b * h, sk // block_k),
        in_specs=[qfull, kblocked, kblocked, qfull, qfull_lanes,
                  qfull_lanes],
        out_specs=[kblocked, kblocked, dqt_full_f32],
        out_shape=[kgrad, kgrad,
                   jax.ShapeDtypeStruct((b * h, dh, s), jnp.float32)],
        interpret=interpret,
        **params,
    )(qf, kf, vf, dof, lse_lanes, delta_lanes)
    # transpose and convert in one XLA pass
    kshape = (b, h, sk, dh)
    return (dq_t.swapaxes(1, 2).astype(q.dtype).reshape(shape),
            dk.reshape(kshape), dv.reshape(kshape))
