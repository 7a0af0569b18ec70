"""Hand-fused Pallas bias-gradient kernel — the convert+reduce
escape hatch.

The bias gradient of every GD unit is an activation-derivative mask on
the (possibly bf16) error flow followed by an f32-accumulating
reduction over the batch·space rows:

    grad_b[k] = Σ_n  (err ∘ act'(y))[n, k]          (f32 accumulate)

In-program, XLA lowers that to a ``convert_reduce`` loop fusion: next
to the wgrad/err-input conv consumers it duplicates the masked-convert
producer into the bias-reduce fusion instead of reusing the conv's
operand (exemplars in ``docs/convert_reduce_fusion_hlo.txt``). An
earlier builder's trace read that fusion as far slower than the same
computation isolated, and found four semantically equivalent XLA-level
rewrites slower still; none of that is in the driver's record or has
been re-measured (``docs/repro_convert_reduce.py`` times the variants
when run on a TPU). This kernel takes the reduction out of XLA's hands
entirely: it IS the masked reduction, block-tiled, with the mask
recomputed from err/y inside the kernel — the surrounding program
keeps its dz for the conv consumers and XLA no longer sees a bias
reduce to fuse.

Design (same conventions as ``parallel/pallas_attention.py``):

* grid = sequential row blocks; the (1, K) f32 accumulator rides as a
  revisited output ref (block index constant in the grid dim — legal
  because the TPU Pallas grid is sequential), zeroed at step 0;
* the activation derivative is THE shared formula table
  (``ops/activations.py`` — one copy of the math repo-wide), computed
  in f32 inside the kernel so the accumulation chain never narrows;
* the tile is FIXED — 512 rows × 1024 channels (smaller only when
  the whole input is smaller) — with a ceil-div grid and an
  in-kernel row mask on the boundary block: never a divisor hunt,
  which would degenerate to tiny blocks (and an enormous sequential
  grid) for row counts with few factors of two, and never an untiled
  K, which would blow VMEM for vocab-wide dense layers. Rows run as
  the INNER grid axis so each K-block's accumulator stays resident
  across its whole row sweep.

Exactness is pinned by ``tests/test_pallas_grads.py`` against the
reference ``dz.sum(axis=0)`` math at the existing gd tolerances.
Consumed via ``GradientDescentBase.bias_grad_xla`` behind the
``fused_bias_grad`` escape hatch (None = auto: on TPU when
$VELES_FUSED_BIAS_GRAD=1 — opt-in until a paired chip run decides it;
True/False force).
"""

import functools

from veles.znicz_tpu.ops import activations as A
from veles.znicz_tpu.parallel.pallas_attention import _on_tpu


def _pow2_ceil(n):
    """Smallest power of two >= ``n`` (sublane-friendly tile bound)."""
    b = 1
    while b < n:
        b *= 2
    return b


def _row_mask(dz, i, n_rows):
    """Zero the tail rows of the LAST block when ``block_n`` does not
    divide the row count — boundary blocks read unspecified padding,
    and a select keeps it out of the accumulation."""
    import jax.numpy as jnp
    from jax import lax
    rows = i * dz.shape[0] + lax.broadcasted_iota(
        jnp.int32, dz.shape, 0)
    return jnp.where(rows < n_rows, dz, 0.0)


def _bias_grad_kernel(err_ref, y_ref, out_ref, *, activation, n_rows):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(1)          # row-block axis (innermost)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # mask + convert INSIDE the kernel, f32 end to end: this is the
    # producer XLA used to duplicate into its pathological fusion
    e = err_ref[...].astype(jnp.float32)
    d = A.ACTIVATIONS[activation][1](jnp, y_ref[...].astype(jnp.float32))
    dz = e if isinstance(d, float) else e * d
    dz = _row_mask(dz, i, n_rows)
    out_ref[...] = out_ref[...] + dz.sum(axis=0, keepdims=True)


def _sum_rows_kernel(err_ref, out_ref, *, n_rows):
    """Identity-derivative fast path (linear/softmax): no ``y`` read,
    half the HBM traffic of the masked form."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(1)          # row-block axis (innermost)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    e = _row_mask(err_ref[...].astype(jnp.float32), i, n_rows)
    out_ref[...] = out_ref[...] + e.sum(axis=0, keepdims=True)


def bias_grad(err, y, activation, block_n=None, block_k=None,
              interpret=None):
    """``Σ_n (err ∘ act'(y))[n, k]`` over 2-D ``(N, K)`` inputs as ONE
    block-tiled Pallas kernel; -> (K,) float32. ``err`` and ``y`` may
    ride any float dtype (bf16 on TPU); the mask and the accumulation
    run in f32. ``activation`` names an ``ACTIVATIONS`` entry (linear
    and softmax derivatives are the identity — the kernel is then the
    pure f32-accumulating reduction). ``interpret`` as in
    ``pallas_attention.flash_attention_fwd``: False = the Mosaic
    kernel, True = the interpreter, None = ask jax's default device."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if activation not in A.ACTIVATIONS:
        raise KeyError("unknown activation %r" % (activation,))
    n, k = err.shape
    if y.shape != err.shape:
        raise ValueError("err %s and y %s must agree"
                         % (err.shape, y.shape))
    if block_n is None:
        # FIXED tile, never a divisor hunt: the auto-on TPU path must
        # not degenerate to tiny blocks when n has few factors of 2
        # (n = 100·27·27 = 72900 -> pow2 divisor 4 -> an 18k-step
        # grid slower than the matvec this kernel replaces); the
        # ceil-div grid's boundary block is masked in-kernel instead
        block_n = min(512, _pow2_ceil(n))
    elif n % block_n:
        raise ValueError("block_n %d does not divide rows %d"
                         % (block_n, n))
    if block_k is None:
        # channels tile too: a vocab-wide dense layer (K = tens of
        # thousands) at 512 rows would otherwise claim tens of MB of
        # VMEM per grid step and fail Mosaic lowering on the auto
        # path — 512x1024 holds every tile at <=4 MB even in f32.
        # K-boundary garbage columns land only in dropped out-of-
        # bounds output columns, so only the ROW boundary needs the
        # in-kernel mask
        block_k = min(1024, _pow2_ceil(k))
    elif k % block_k:
        raise ValueError("block_k %d does not divide channels %d"
                         % (block_k, k))
    if interpret is None:
        interpret = not _on_tpu()
    # grid = (K blocks, row blocks): rows INNERMOST, so each K-block's
    # accumulator is revisited across its whole row sweep
    blocked = pl.BlockSpec((block_n, block_k), lambda kb, ib: (ib, kb))
    # the accumulator: row index CONSTANT in the grid dim, so the
    # sequential grid revisits (and keeps) it in VMEM across blocks
    acc = pl.BlockSpec((1, block_k), lambda kb, ib: (0, kb))
    identity = A.ACTIVATIONS[activation][1] is A.dlinear
    kernel = functools.partial(
        _sum_rows_kernel if identity else functools.partial(
            _bias_grad_kernel, activation=activation), n_rows=n)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(k, block_k), pl.cdiv(n, block_n)),
        in_specs=[blocked] if identity else [blocked, blocked],
        out_specs=acc,
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        interpret=interpret,
    )(*((err,) if identity else (err, y)))
    return out[0]
