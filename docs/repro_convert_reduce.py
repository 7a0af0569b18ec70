"""Minimal repro: XLA:TPU convert+reduce fusion in the bias gradient.

Context: in the AlexNet training step the conv1/conv2 bias-gradient — a
relu-derivative mask on the bf16 error flow followed by an
f32-accumulating reduction over batch*space — lowers to a
`convert_reduce` loop fusion. An earlier builder's in-program trace on
a v5e read that fusion at ~11 GB/s effective HBM bandwidth (19.5 +
11.1 ms of a 284 ms step) and found four semantically equivalent
XLA-level rewrites slower end to end, so the production code keeps the
cleanest form and this file is the standalone reproducer for an
upstream XLA escalation. Those figures are that builder's, not the
driver's record; nothing here has been re-measured on the machine
builders use now.

Run on a TPU: ``python docs/repro_convert_reduce.py``. It times the
isolated bias-grad computation at the AlexNet conv1/conv2 shapes in
four variants and prints effective bandwidth for each, then dumps the
optimized HLO of the pathological one to
``/tmp/convert_reduce_repro_hlo.txt``. Timing: data-dependent
chaining (independent identical dispatches get CSE'd), scalar readback
as the sync point, and a two-rep-count difference to cancel the
constant dispatch overhead.

Variant definitions:

* `mask_matvec`  — dz = err * (y > 0); ones @ dz (f32 accumulate):
  the production form; in-graph it fuses mask+convert+reduce.
* `mask_sum`     — dz.sum(axis=0) instead of the matvec.
* `pre_masked`   — the matvec on an ALREADY-masked f32 dz (isolates
  the reduction from the convert+mask producer).
* `f32_reduce`   — plain f32 sum at the same element count (the
  bandwidth baseline XLA should be hitting).
* `ctx` / `ctx_nobias` — dz additionally feeding a wgrad-style
  contraction (the real program's consumer structure); the bias
  reduce's MARGINAL cost is ctx − ctx_nobias. The round-4 in-program
  trace showed the pathology only materializes in this multi-consumer
  context (XLA duplicates the mask+convert producer into the reduce
  fusion instead of reusing the conv's operand), so the isolated
  variants above are the control group: if they run at roofline while
  the marginal in-context cost is ~milliseconds, the fusion-duplication
  decision — not the reduce codegen itself — is the bug.
* `kernel` — the SHIPPED fix (ISSUE 14): the hand-fused Pallas
  bias-grad kernel (``veles/znicz_tpu/ops/pallas_grads.py``) doing
  mask + convert + f32 block-reduce in one sequential-grid pass. It
  is wired into ``gd.py``/``gd_conv.py`` behind the
  ``fused_bias_grad`` escape hatch (on real TPUs when
  $VELES_FUSED_BIAS_GRAD=1; opt-in until the device window below
  fills the table), so the
  training program no longer CONTAINS a bias reduce for XLA's fusion
  pass to duplicate the producer into — the decision this file
  documents is sidestepped, not re-litigated.
* `ctx_kernel` — the kernel inside the multi-consumer context (dz
  still feeds the wgrad contraction): ``ctx_kernel − ctx_nobias`` is
  the shipped form's marginal bias-reduce cost, the number to hold
  against the pathological ``ctx − ctx_nobias``.

PALLAS-KERNEL OUTCOME (ISSUE 14): exactness is pinned on CPU
interpret mode (``tests/test_pallas_grads.py``, atol at the existing
gd bounds) and, compiled for the chip, by ``chip_smoke.py``'s kernel
phase; ``bench.py`` tracks ``bias_grad_step_seconds``. The in-program
step delta on a v5e is NOT MEASURED: this script times `kernel` /
`ctx_kernel` alongside the original variants, so one run on a TPU
fills the table, and the comparison to make is ``ctx_kernel −
ctx_nobias`` against ``ctx − ctx_nobias``.
"""

import sys
import time

sys.path.insert(0, "/root/repo")


def bench_variants(b, oy, ox, k, label):
    import jax
    import jax.numpy as jnp
    import numpy
    from jax import lax

    gen = numpy.random.Generator(numpy.random.PCG64(11))
    n = b * oy * ox
    err = jnp.asarray(gen.standard_normal((n, k), numpy.float32),
                      jnp.bfloat16)
    y = jnp.asarray(gen.standard_normal((n, k), numpy.float32),
                    jnp.bfloat16)

    def mask_matvec(e, yy):
        dz = e * (yy > 0).astype(e.dtype)
        ones = jnp.ones((1, n), e.dtype)
        return lax.dot_general(ones, dz, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)[0]

    def mask_sum(e, yy):
        dz = e * (yy > 0).astype(e.dtype)
        return dz.sum(axis=0, dtype=jnp.float32)

    def pre_masked(e, yy):
        dz = e.astype(jnp.float32) * (yy.astype(jnp.float32) > 0)
        dz = lax.optimization_barrier(dz)
        ones = jnp.ones((1, n), jnp.float32)
        return lax.dot_general(ones, dz, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)[0]

    def f32_reduce(e, yy):
        return e.astype(jnp.float32).sum(axis=0)

    # the real program's consumer structure: dz feeds a wgrad-style
    # contraction AND the bias reduce (x stands in for the im2col'd
    # input patches; a dot probes the same producer-duplication
    # fusion decision the conv triggers in the round-4 trace)
    c_in = 128
    x_in = jnp.asarray(gen.standard_normal((n, c_in), numpy.float32),
                       jnp.bfloat16)

    def ctx_full(e, yy):
        dz = e * (yy > 0).astype(e.dtype)
        gw = lax.dot_general(x_in, dz, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ones = jnp.ones((1, n), e.dtype)
        gb = lax.dot_general(ones, dz, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)[0]
        return jnp.concatenate([gw.sum(axis=0) * 1e-3, gb])

    def ctx_nobias(e, yy):
        dz = e * (yy > 0).astype(e.dtype)
        gw = lax.dot_general(x_in, dz, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return gw.sum(axis=0) * 1e-3

    # the shipped Pallas kernel (ops/pallas_grads.py): real kernel on
    # TPU — do not run this variant through a CPU interpret session,
    # it would time the emulator
    from veles.znicz_tpu.ops import pallas_grads as PG

    def kernel(e, yy):
        return PG.bias_grad(e, yy, "strict_relu")

    def ctx_kernel(e, yy):
        dz = e * (yy > 0).astype(e.dtype)
        gw = lax.dot_general(x_in, dz, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        gb = PG.bias_grad(e, yy, "strict_relu")
        return jnp.concatenate([gw.sum(axis=0) * 1e-3, gb])

    def timed(fn, feed, reps_hi=120, reps_lo=12):
        """Unrolled data-dependent chaining: BOTH err and y perturb
        each rep (a constant y lets the mask hoist out of the loop and
        over-reads the bandwidth), rep-count difference cancels the
        constant dispatch overhead."""
        def chain(reps):
            @jax.jit
            def run(e, yy):
                acc = jnp.float32(0)
                for _ in range(reps):
                    g = fn(e, yy)
                    acc = acc + g.sum()
                    bump = g[None, :k].astype(e.dtype) * 1e-6
                    e = e + bump
                    yy = yy + bump
                return acc
            float(run(feed, y))
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                float(run(feed, y))
                best = min(best, time.perf_counter() - t0)
            return best
        return (chain(reps_hi) - chain(reps_lo)) \
            / (reps_hi - reps_lo)

    bytes_read = 2 * n * k * 2          # err + y, bf16
    print("%s  (B=%d %dx%d K=%d; %d MB read/step)"
          % (label, b, oy, ox, k, bytes_read >> 20))
    times = {}
    variants = [("mask_matvec", mask_matvec),
                ("mask_sum", mask_sum),
                ("pre_masked", pre_masked),
                ("f32_reduce", f32_reduce),
                ("ctx_full", ctx_full),
                ("ctx_nobias", ctx_nobias)]
    if PG._on_tpu():
        # interpret mode would take HOURS at these shapes and time
        # the emulator, not the kernel — the comment above made the
        # rule, this guard enforces it
        variants += [("kernel", kernel), ("ctx_kernel", ctx_kernel)]
    else:
        print("  (kernel/ctx_kernel skipped: no TPU — interpret mode "
              "would time the Pallas emulator, not the kernel)")
    for name, fn in variants:
        try:
            t = timed(fn, err)
        except Exception as exc:
            print("  %-12s FAILED: %s" % (name, str(exc)[:140]))
            continue
        times[name] = t
        print("  %-12s %7.3f ms   %7.1f GB/s effective"
              % (name, t * 1e3, bytes_read / t / 1e9), flush=True)
    if "ctx_full" in times and "ctx_nobias" in times:
        marginal = times["ctx_full"] - times["ctx_nobias"]
        print("  in-context marginal bias-reduce cost: %.3f ms "
              "(isolated form: %.3f ms)"
              % (marginal * 1e3, times.get("mask_matvec", 0) * 1e3))
    if "ctx_kernel" in times and "ctx_nobias" in times:
        print("  SHIPPED-KERNEL in-context marginal cost: %.3f ms "
              "(ops/pallas_grads.py; hold against the pathological "
              "marginal above)"
              % ((times["ctx_kernel"] - times["ctx_nobias"]) * 1e3))
    return mask_matvec, err, y


def main():
    import jax

    mask_matvec, err, y = bench_variants(128, 55, 55, 96,
                                         "conv1-shape")
    bench_variants(128, 27, 27, 256, "conv2-shape")
    hlo = jax.jit(mask_matvec).lower(err, y).compile().as_text()
    path = "/tmp/convert_reduce_repro_hlo.txt"
    with open(path, "w") as f:
        f.write(hlo)
    print("optimized HLO of the ISOLATED (fast) form ->", path)
    print("the in-program (pathological) fusions are committed at "
          "docs/convert_reduce_fusion_hlo.txt")


if __name__ == "__main__":
    main()
