"""Pallas flash-attention kernels: exact parity with the scan-flash
and dense formulations (interpret mode on CPU; the same kernels run
natively on TPU), and the attention unit's pallas path against the
dense numpy oracle."""

import numpy
import pytest

import veles.prng as prng
from veles.memory import Array
from veles.znicz_tpu.ops.attention import MultiHeadAttention
from veles.znicz_tpu.parallel import flash, pallas_attention as PA

from tests.test_conv_stack import build, xla_forward, xla_backward


CASES = [
    dict(causal=True, s=64, block=32),
    dict(causal=False, s=64, block=32),
    dict(causal=True, s=128, block=64),
    dict(causal=True, s=64, block=64),   # single block
]


def _qkv(s, b=2, h=2, dh=8, seed=909):
    prng.seed_all(seed)
    gen = prng.get("pa")
    shape = (b, h, s, dh)
    return tuple(gen.normal(0, 1.0, shape).astype(numpy.float32)
                 for _ in range(3))


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(c))
def test_pallas_fwd_matches_scan_flash(case):
    q, k, v = _qkv(case["s"])
    out_ref, lse_ref = flash.blocked_attention_fwd(
        q, k, v, causal=case["causal"], block=case["block"])
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=case["causal"], block_q=case["block"],
        block_k=case["block"], interpret=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(out_ref),
                          atol=2e-5), \
        numpy.abs(numpy.asarray(out) - numpy.asarray(out_ref)).max()
    assert numpy.allclose(numpy.asarray(lse), numpy.asarray(lse_ref),
                          atol=2e-5)


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(c))
def test_pallas_bwd_matches_scan_flash(case):
    q, k, v = _qkv(case["s"])
    prng.seed_all(910)
    dout = prng.get("pa2").normal(0, 1.0, q.shape).astype(
        numpy.float32)
    out, lse = flash.blocked_attention_fwd(
        q, k, v, causal=case["causal"], block=case["block"])
    refs = flash.blocked_attention_bwd(
        q, k, v, out, lse, dout, causal=case["causal"],
        block=case["block"])
    got = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=case["causal"],
        block_q=case["block"], block_k=case["block"], interpret=True)
    for name, r, g in zip(("dq", "dk", "dv"), refs, got):
        assert numpy.allclose(numpy.asarray(g), numpy.asarray(r),
                              atol=2e-4), \
            (name,
             numpy.abs(numpy.asarray(g) - numpy.asarray(r)).max())


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(c))
def test_pallas_bwd_fused_matches_two_kernel(case):
    """The single-pass dk/dv/dq kernel (dq accumulated in a revisited
    output ref across the sequential k-block grid — round 5, measured
    +38% on the backward at S=8k) must agree leaf-for-leaf with the
    retained two-kernel formulation."""
    q, k, v = _qkv(case["s"])
    prng.seed_all(911)
    dout = prng.get("pa3").normal(0, 1.0, q.shape).astype(
        numpy.float32)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=case["causal"], block_q=case["block"],
        block_k=case["block"], interpret=True)
    two = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=case["causal"],
        block_q=case["block"], block_k=case["block"], interpret=True,
        fused=False)
    one = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=case["causal"],
        block_q=case["block"], block_k=case["block"], interpret=True,
        fused=True)
    for name, a, b in zip(("dq", "dk", "dv"), two, one):
        assert numpy.allclose(numpy.asarray(a), numpy.asarray(b),
                              atol=2e-5), \
            (name,
             numpy.abs(numpy.asarray(a) - numpy.asarray(b)).max())


@pytest.mark.parametrize("bq,bk", [(32, 16), (16, 32)],
                         ids=["bq>bk", "bq<bk"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
def test_pallas_unequal_blocks(bq, bk, causal):
    """UNEQUAL block_q/block_k exercise the hand-derived diagonal
    split boundaries (round 5: the floor/ceil clear points differ
    from the trivial qi/ki±1 values only here) — fwd vs the scan
    flash, and BOTH backward forms vs the scan backward."""
    s = 64
    q, k, v = _qkv(s)
    prng.seed_all(912)
    dout = prng.get("pa4").normal(0, 1.0, q.shape).astype(
        numpy.float32)
    out_ref, lse_ref = flash.blocked_attention_fwd(
        q, k, v, causal=causal, block=16)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=causal, block_q=bq, block_k=bk,
        interpret=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(out_ref),
                          atol=2e-5)
    assert numpy.allclose(numpy.asarray(lse), numpy.asarray(lse_ref),
                          atol=2e-5)
    refs = flash.blocked_attention_bwd(
        q, k, v, out_ref, lse_ref, dout, causal=causal, block=16)
    for fused in (False, True):
        got = PA.flash_attention_bwd(
            q, k, v, out, lse, dout, causal=causal, block_q=bq,
            block_k=bk, interpret=True, fused=fused)
        for name, r, g in zip(("dq", "dk", "dv"), refs, got):
            assert numpy.allclose(numpy.asarray(g), numpy.asarray(r),
                                  atol=2e-4), \
                (fused, name,
                 numpy.abs(numpy.asarray(g) - numpy.asarray(r)).max())


@pytest.mark.parametrize("case", CASES, ids=lambda c: str(c))
def test_pallas_fwd_pipelined_matches_resident(case):
    """The DMA-pipelined forward (K/V in HBM, double-buffered block
    scratch) is a pure data-movement change: out and lse must match
    the resident-rows kernel to float tolerance."""
    q, k, v = _qkv(case["s"])
    out_ref, lse_ref = PA.flash_attention_fwd(
        q, k, v, causal=case["causal"], block_q=case["block"],
        block_k=case["block"], interpret=True)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=case["causal"], block_q=case["block"],
        block_k=case["block"], interpret=True, pipeline=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(out_ref),
                          atol=2e-5), \
        numpy.abs(numpy.asarray(out) - numpy.asarray(out_ref)).max()
    assert numpy.allclose(numpy.asarray(lse), numpy.asarray(lse_ref),
                          atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(32, 16), (16, 32)],
                         ids=["bq>bk", "bq<bk"])
def test_pallas_fwd_pipelined_unequal_blocks(bq, bk):
    """Unequal tiles stress the pipelined loop's causal bound (hi =
    cdiv over block_k while the DMA window is block_k-sized)."""
    q, k, v = _qkv(64)
    out_ref, lse_ref = flash.blocked_attention_fwd(
        q, k, v, causal=True, block=16)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=True, block_q=bq, block_k=bk,
        interpret=True, pipeline=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(out_ref),
                          atol=2e-5)
    assert numpy.allclose(numpy.asarray(lse), numpy.asarray(lse_ref),
                          atol=2e-5)


def test_pallas_fwd_bf16_accumulate_numerics_gate():
    """THE gate for the bf16-accumulation experiment: against the f32-
    accumulated reference the output error must stay within the bf16
    input-rounding regime (~2^-8 relative on O(1) softmax-weighted
    averages), and the lse — whose statistics deliberately stay f32 —
    must remain exact. If a kernel change ever narrows the softmax
    chain too, this is the test that fires."""
    import jax.numpy as jnp
    q, k, v = _qkv(128, b=2, h=2, dh=16)
    for causal in (True, False):
        ref, lse_ref = PA.flash_attention_fwd(
            q, k, v, causal=causal, block_q=32, block_k=32,
            interpret=True)
        out, lse = PA.flash_attention_fwd(
            q, k, v, causal=causal, block_q=32, block_k=32,
            interpret=True, acc_dtype=jnp.bfloat16)
        err = numpy.abs(numpy.asarray(out) - numpy.asarray(ref)).max()
        assert err < 1.5e-2, err          # bf16 accumulation regime
        assert err > 0.0                  # the variant really ran
        assert numpy.allclose(numpy.asarray(lse),
                              numpy.asarray(lse_ref), atol=2e-5)


def test_attention_unit_pipelined_path():
    """attn_pipeline=True through the unit: forward matches the dense
    numpy oracle and the backward (which reads the cached out/lse —
    layout unchanged by the pipelined forward) still agrees."""
    wf, feed, fwd, gd, x, err, comp = build(
        MultiHeadAttention, input_shape=(2, 32, 16), gd_kwargs={},
        heads=2, attn_impl="pallas", attn_block_size=16,
        attn_pipeline=True)
    golden = numpy.array(fwd.output.mem)
    params0 = comp.gather_params()
    state0 = comp.gather_state()
    y = xla_forward(comp, feed, fwd, params0, x)
    assert numpy.allclose(numpy.asarray(y), golden, atol=3e-5)
    gd.numpy_run()
    ei_np = numpy.array(gd.err_input.mem)
    ei_x, _ = xla_backward(comp, feed, fwd, gd, params0, state0,
                           x, err)
    assert numpy.allclose(ei_np, numpy.asarray(ei_x), atol=3e-4)


def test_attention_unit_bf16_acc_path():
    """attn_acc='bf16' through the unit, forward AND backward: the
    experimental arm's gradients must stay within the bf16-acc
    numerics regime of the dense numpy oracle — a forward-only gate
    would let a backward-side regression ship on exactly the A/B run
    the knob exists for (the backward consumes the bf16-accumulated
    out/lse via delta = rowsum(dout*out))."""
    wf, feed, fwd, gd, x, err, comp = build(
        MultiHeadAttention, input_shape=(2, 32, 16), gd_kwargs={},
        heads=2, attn_impl="pallas", attn_block_size=16,
        attn_acc="bf16")
    golden = numpy.array(fwd.output.mem)
    params0 = comp.gather_params()
    state0 = comp.gather_state()
    y = xla_forward(comp, feed, fwd, params0, x)
    assert numpy.allclose(numpy.asarray(y), golden, atol=2e-2)
    gd.numpy_run()
    ei_np = numpy.array(gd.err_input.mem)
    ei_x, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                                 x, err)
    assert numpy.allclose(ei_np, numpy.asarray(ei_x), atol=2e-2), \
        numpy.abs(ei_np - numpy.asarray(ei_x)).max()
    for pname in fwd.PARAMS:
        w1_np = getattr(fwd, pname).map_read().mem
        w1_x = numpy.asarray(params1[fwd.name][pname])
        assert numpy.allclose(w1_np, w1_x, atol=3e-2), pname


def test_attention_unit_rejects_bad_attn_acc():
    from veles.workflow import Workflow
    wf = Workflow(None, name="wf-acc")
    with pytest.raises(ValueError):
        MultiHeadAttention(wf, heads=2, attn_acc="fp64")


def test_attention_unit_rejects_inert_fwd_experiments():
    """attn_pipeline/attn_acc='bf16' on a dispatch that resolves to
    any non-pallas mode (dense/scan/ring) must raise loudly (like
    transformer_lm's stacked guard), never run the other kernel with
    a silently inert knob — the worst failure mode for an A/B."""
    from veles.workflow import Workflow
    wf = Workflow(None, name="wf-inert")
    for kwargs in ({"attn_pipeline": True}, {"attn_acc": "bf16"}):
        dense = MultiHeadAttention(wf, heads=2, **kwargs)
        with pytest.raises(ValueError, match="pallas"):
            dense._traced_mode(None, 32)
        scan = MultiHeadAttention(wf, heads=2, attn_impl="scan",
                                  attn_block_size=16, **kwargs)
        with pytest.raises(ValueError, match="pallas"):
            scan._traced_mode(None, 32)
        ring = MultiHeadAttention(wf, heads=2, **kwargs)
        ring.seq_mesh = object()
        with pytest.raises(ValueError, match="pallas"):
            ring._traced_mode(None, 32)
        # attn_acc='f32' is the explicit default, not an experiment
        MultiHeadAttention(wf, heads=2,
                           attn_acc="f32")._traced_mode(None, 32)


def test_attention_unit_pallas_path():
    """The unit with attn_impl='pallas': traced forward and backward
    must match the dense numpy oracle (different formulation, same
    math)."""
    wf, feed, fwd, gd, x, err, comp = build(
        MultiHeadAttention, input_shape=(2, 32, 16), gd_kwargs={},
        heads=2, attn_impl="pallas", attn_block_size=16)
    golden = numpy.array(fwd.output.mem)          # dense numpy oracle
    params0 = comp.gather_params()
    state0 = comp.gather_state()
    y = xla_forward(comp, feed, fwd, params0, x)
    assert numpy.allclose(numpy.asarray(y), golden, atol=3e-5)
    gd.numpy_run()                                # dense oracle bwd
    ei_np = numpy.array(gd.err_input.mem)
    ei_x, params1 = xla_backward(comp, feed, fwd, gd, params0, state0,
                                 x, err)
    assert numpy.allclose(ei_np, numpy.asarray(ei_x), atol=3e-4), \
        numpy.abs(ei_np - numpy.asarray(ei_x)).max()
    for pname in fwd.PARAMS:
        w1_np = getattr(fwd, pname).map_read().mem
        w1_x = numpy.asarray(params1[fwd.name][pname])
        assert numpy.allclose(w1_np, w1_x, atol=5e-4), pname


def test_lm_trains_with_pallas_attention():
    """Config-only switch: the LM sample converges with the Pallas
    kernels exactly like the scan path."""
    from veles.config import root
    prng.seed_all(4242)
    from veles.znicz_tpu.models import transformer_lm
    saved_loader = root.lm.loader.to_dict()
    saved_model = root.lm.model.to_dict()
    saved_epochs = root.lm.decision.get("max_epochs")
    root.lm.loader.update({"minibatch_size": 32, "n_train": 256,
                           "n_valid": 64, "seq_len": 16, "vocab": 8,
                           "max_period": 4})
    root.lm.model.update({"dim": 32, "heads": 2, "layers": 1,
                          "ffn_hidden": 64, "attn_block": 16,
                          "attn_impl": "pallas", "moe_experts": 0,
                          "stacked": False})
    root.lm.decision.max_epochs = 5
    root.lm.parallel.update({"seq": 1, "model": 1, "data": 1,
                             "expert": 1, "pipe": 1})
    try:
        wf = transformer_lm.create_workflow(name="PallasLM")
        wf.initialize(device="xla")
        wf.run()
    finally:
        root.lm.loader.update(saved_loader)
        root.lm.model.update(saved_model)
        root.lm.decision.max_epochs = saved_epochs
    hist = [h["validation"]["metric"] for h in wf.decision.history]
    assert hist[-1] < hist[0], hist


# -- the auto rule: which S takes the kernels, at which tile (PR 27) ----


def _platform_ctx(platform):
    """What ``_pallas_interpret`` reads of a trace context: the
    platform of the device the step compiles for."""
    from types import SimpleNamespace
    return SimpleNamespace(_compiler=SimpleNamespace(
        device=SimpleNamespace(platform=platform)))


def _auto_unit(**kwargs):
    from veles.workflow import Workflow
    return MultiHeadAttention(Workflow(None, name="wf-auto"), heads=2,
                              attn_block_size=256, **kwargs)


@pytest.mark.parametrize("s,mode", [
    (8192, "pallas"), (1024, "pallas"),
    (1032, "pallas"),   # from 1024 up any tile goes, as before PR 27
    (768, "pallas"),    # tile 256
    (512, "pallas"),    # one 512 tile a row: the S=512 cells
    (256, "pallas"),    # the bound itself
    (255, "scan"), (128, "scan"),
    (640, "scan"), (384, "scan"),   # over the bound, but only a 128
                                    # tile divides
])
def test_auto_rule_takes_the_kernels_from_s256_on_a_tpu(s, mode):
    unit = _auto_unit()
    assert unit._traced_mode(_platform_ctx("tpu"), s) == mode
    # ... and never off the TPU (the interpreter is no fast path),
    # nor against an explicit choice
    assert unit._traced_mode(_platform_ctx("cpu"), s) == "scan"
    assert _auto_unit(attn_impl="scan")._traced_mode(
        _platform_ctx("tpu"), s) == "scan"
    assert _auto_unit(attn_impl="pallas")._traced_mode(
        _platform_ctx("cpu"), s) == "pallas"


@pytest.mark.parametrize("s,tile", [
    (128, 128), (256, 256), (512, 512), (768, 256),
    (1024, 512), (2048, 512), (4096, 512), (8192, 512)])
def test_pallas_block_is_the_measured_table(s, tile):
    assert _auto_unit()._pallas_block(s) == tile
    assert _auto_unit(pallas_tile=128)._pallas_block(s) == 128


@pytest.mark.parametrize("s_loc,inner", [
    (2048, "pallas"), (512, "pallas"), (256, "pallas"), (128, "scan")])
def test_ring_inner_follows_the_auto_rule(s_loc, inner):
    from types import SimpleNamespace
    unit = _auto_unit()
    unit.seq_mesh, unit.seq_axis = SimpleNamespace(shape={"seq": 4}), "seq"
    unit.input = SimpleNamespace(shape=(2, 4 * s_loc, 16))
    got, block = unit._ring_inner(_platform_ctx("tpu"))
    assert got == inner
    assert block == (min(s_loc, 512) if inner == "pallas" else 128)


# -- the short-sequence (one-tile) kernels -------------------------------


@pytest.mark.parametrize("bh,rows", [(384, 4), (6, 2), (3, 1), (8, 4)])
def test_tile_rows_divide_the_batch_of_heads(bh, rows):
    assert PA._tile_rows(bh) == rows


def _dense_core(q, k, v, dout, causal):
    from veles.znicz_tpu.ops.attention import (
        dense_attention_core_bwd, dense_attention_core_fwd)
    scale = numpy.float32(1.0 / numpy.sqrt(q.shape[-1]))
    probs, out = dense_attention_core_fwd(numpy, q, k, v, causal, scale)
    return out, dense_attention_core_bwd(numpy, q, k, v, probs, dout,
                                         scale)


@pytest.mark.parametrize("b,h,s,dh,causal", [
    (1, 4, 512, 64, True),      # the benchmark's shape, 4 rows a program
    (1, 3, 512, 64, True),      # a row count only 1 divides
    (2, 2, 128, 8, False),
], ids=str)
def test_tile_kernels_match_dense_core(b, h, s, dh, causal):
    """S=512, head 64, tile 512 — what ``_pallas_block`` picks for the
    S=512 cells — forward and fused backward against the dense float32
    core, at this file's bounds."""
    prng.seed_all(913)
    gen = prng.get("pa5")
    q, k, v, dout = (gen.normal(0, 1.0, (b, h, s, dh)).astype(
        numpy.float32) for _ in range(4))
    out_ref, grads_ref = _dense_core(q, k, v, dout, causal)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=causal, block_q=512, block_k=512,
        interpret=True)
    assert lse.shape == (b, h, s)
    assert numpy.allclose(numpy.asarray(out), out_ref, atol=2e-5), \
        numpy.abs(numpy.asarray(out) - out_ref).max()
    got = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=512,
        block_k=512, interpret=True)
    for name, r, g in zip(("dq", "dk", "dv"), grads_ref, got):
        assert numpy.allclose(numpy.asarray(g), r, atol=2e-4), \
            (name, numpy.abs(numpy.asarray(g) - r).max())


def _dense_lse(q, k, causal):
    """log-sum-exp of the scaled (masked) scores, dense float64."""
    scale = 1.0 / numpy.sqrt(q.shape[-1])
    s = numpy.einsum("bhqd,bhkd->bhqk", q.astype(numpy.float64),
                     k.astype(numpy.float64)) * scale
    if causal:
        n = q.shape[2]
        s = numpy.where(numpy.arange(n)[None, :]
                        > numpy.arange(n)[:, None], -numpy.inf, s)
    top = s.max(axis=-1, keepdims=True)
    return (top + numpy.log(numpy.exp(s - top).sum(
        axis=-1, keepdims=True)))[..., 0]


@pytest.mark.parametrize("b,h,s,bq,bk,causal,hoist_delta", [
    (1, 3, 512, 128, 128, True, False),     # BH odd, 4 tiles a row
    (1, 2, 512, 128, 128, False, True),
    (1, 2, 1024, 256, 256, True, True),
    (1, 1, 1024, 256, 256, False, False),
    (1, 2, 512, 256, 128, True, False),     # block_q > block_k
    (1, 2, 512, 128, 256, True, True),      # block_q < block_k
    (1, 1, 512, 256, 128, False, True),
    (1, 1, 512, 128, 256, False, False),
], ids=str)
def test_kloop_kernels_match_dense_core(b, h, s, bq, bk, causal,
                                        hoist_delta):
    """The K-loop kernels (several tiles a row: what every S above
    512 runs, S=8192 at tile 512 on the chip) at head size 64 against
    the dense float32 core: out, the lane-dense lse, the fused
    backward — with ``delta`` hoisted by the caller, as the ring
    does, and not — and the fused backward against ``fused=False``."""
    prng.seed_all(915)
    gen = prng.get("pa7")
    q, k, v, dout = (gen.normal(0, 1.0, (b, h, s, 64)).astype(
        numpy.float32) for _ in range(4))
    out_ref, grads_ref = _dense_core(q, k, v, dout, causal)
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=causal, block_q=bq, block_k=bk,
        interpret=True)
    assert lse.shape == (b, h, s) and lse.dtype == numpy.float32
    assert out.shape == (b, h, s, 64)
    assert numpy.allclose(numpy.asarray(lse), _dense_lse(q, k, causal),
                          atol=2e-5)
    assert numpy.allclose(numpy.asarray(out), out_ref, atol=2e-5), \
        numpy.abs(numpy.asarray(out) - out_ref).max()
    delta = (dout * out_ref).sum(axis=-1) if hoist_delta else None
    got = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=bq,
        block_k=bk, interpret=True, delta=delta)
    two = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=bq,
        block_k=bk, interpret=True, delta=delta, fused=False)
    for name, r, g, t in zip(("dq", "dk", "dv"), grads_ref, got, two):
        assert g.shape == (b, h, s, 64)
        assert numpy.allclose(numpy.asarray(g), r, atol=2e-4), \
            (name, numpy.abs(numpy.asarray(g) - r).max())
        assert numpy.allclose(numpy.asarray(g), numpy.asarray(t),
                              atol=2e-5), name


#: what the v5e compiler needs for the fused backward, MB of scoped
#: VMEM, found by lowering the grant until the compile fails (PR 29;
#: s, dh, block_q, block_k -> need)
_FUSED_BWD_NEED_MB = [
    ((8192, 64, 512, 512), 15.7),       # the S=8192 cells
    ((8192, 64, 256, 256), 13.6),
    ((8192, 64, 128, 128), 12.7),
    ((16384, 64, 512, 512), 28.0),
    ((8192, 128, 512, 512), 16.2),
    ((8192, 64, 1024, 1024), 22.4),
]


@pytest.mark.parametrize("shape,need_mb", _FUSED_BWD_NEED_MB, ids=str)
def test_fused_bwd_vmem_limit_covers_the_transposed_resident_set(
        shape, need_mb):
    """The grant follows the kernel's resident set — q and do rows,
    the (dh, S) float32 dq accumulator, lse/delta lanes, double
    buffered — and covers what the compiler was seen to need without
    claiming three times that."""
    grant = PA._fused_bwd_vmem_limit(*shape, 2, device_vmem=128 << 20)
    assert need_mb * 2 ** 20 < grant < 3 * need_mb * 2 ** 20
    # a device that cannot hold it is refused, the escape hatch named
    with pytest.raises(ValueError, match="fused=False"):
        PA._fused_bwd_vmem_limit(*shape, 2, device_vmem=8 << 20)


def test_tile_kernels_equal_the_general_kernels(monkeypatch):
    """One tile a row: the short-sequence kernels and the general
    ones (taken here by switching the short ones off) are the same
    arithmetic."""
    q, k, v = _qkv(64)
    prng.seed_all(914)
    dout = prng.get("pa6").normal(0, 1.0, q.shape).astype(
        numpy.float32)

    def both():
        out, lse = PA.flash_attention_fwd(
            q, k, v, causal=True, block_q=64, block_k=64,
            interpret=True)
        return (out, lse) + PA.flash_attention_bwd(
            q, k, v, out, lse, dout, causal=True, block_q=64,
            block_k=64, interpret=True)

    short = both()
    monkeypatch.setattr(PA, "TILE_MAX_S", 0)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), short,
                          both()):
        assert numpy.allclose(numpy.asarray(a), numpy.asarray(b),
                              atol=2e-6), name
