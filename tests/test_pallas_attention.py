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


@pytest.mark.parametrize("bq,bk", [(32, 16), (16, 32)],
                         ids=["bq>bk", "bq<bk"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
def test_pallas_unequal_blocks(bq, bk, causal):
    """UNEQUAL block_q/block_k exercise the hand-derived diagonal
    split boundaries (round 5: the floor/ceil clear points differ
    from the trivial qi/ki±1 values only here) — forward and fused
    backward vs the scan flash."""
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
    got = PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=bq,
        block_k=bk, interpret=True)
    for name, r, g in zip(("dq", "dk", "dv"), refs, got):
        assert numpy.allclose(numpy.asarray(g), numpy.asarray(r),
                              atol=2e-4), \
            (name,
             numpy.abs(numpy.asarray(g) - numpy.asarray(r)).max())


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


#: |kernel - dense float32 core| by operand type. float32: this file's
#: bounds. bfloat16 — what every cell feeds the kernels, and what only
#: ``chip_smoke.py`` checked until PR 30: the reference is the dense
#: float32 core of the float32 values of the SAME bf16 operands, and
#: each bound is at most 3x the interpreter's own worst reading over
#: the eleven bf16 cases below (out 7.3e-3, lse 5.6e-7, dq 1.44e-2, dk
#: 1.35e-2, dv 1.28e-2; the v5e's kernels read 7.3e-3, 6.7e-6, 5.8e-3,
#: 1.0e-2, 2.0e-2 at S=8192: PERF.md section 6, PR 29).
_BOUNDS = {
    "float32": dict(out=2e-5, lse=2e-5, dq=2e-4, dk=2e-4, dv=2e-4),
    "bfloat16": dict(out=1.5e-2, lse=1.5e-6, dq=3e-2, dk=3e-2, dv=3e-2),
}


def _check_kernels(stream, seed, shape, dtype, block_q, block_k, causal,
                   hoist_delta=False):
    """Forward and backward of :mod:`pallas_attention` at ``shape`` =
    (b, h, s, dh) on random operands of ``dtype`` against the dense
    float32 core of the same values, at ``_BOUNDS[dtype]``."""
    import jax.numpy as jnp
    prng.seed_all(seed)
    gen = prng.get(stream)
    ops = [jnp.asarray(gen.normal(0, 1.0, shape).astype(numpy.float32),
                       dtype) for _ in range(4)]
    q32, k32, v32, dout32 = (numpy.asarray(t.astype(jnp.float32))
                             for t in ops)
    q, k, v, dout = ops
    out_ref, grads_ref = _dense_core(q32, k32, v32, dout32, causal)
    want = dict(zip(("dq", "dk", "dv"), grads_ref), out=out_ref,
                lse=_dense_lse(q32, k32, causal))
    out, lse = PA.flash_attention_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=True)
    assert lse.shape == shape[:3] and lse.dtype == numpy.float32
    delta = (dout32 * out_ref).sum(axis=-1) if hoist_delta else None
    got = dict(zip(("dq", "dk", "dv"), PA.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=block_q,
        block_k=block_k, interpret=True, delta=delta)), out=out)
    for name, g in got.items():
        assert g.shape == shape and g.dtype == q.dtype, name
    got["lse"] = lse
    errs = {name: float(numpy.abs(numpy.asarray(
        g.astype(jnp.float32)) - want[name]).max())
        for name, g in got.items()}
    assert all(errs[name] <= bound
               for name, bound in _BOUNDS[dtype].items()), errs


_TILE_SHAPES = [
    (1, 4, 512, 64, True),      # the benchmark's shape, 4 rows a program
    (1, 3, 512, 64, True),      # a row count only 1 divides
    (2, 2, 128, 8, False),
]


@pytest.mark.parametrize(
    "b,h,s,dh,causal,dtype",
    [c + ("float32",) for c in _TILE_SHAPES]
    + [c + ("bfloat16",) for c in _TILE_SHAPES]
    + [(1, 2, 256, 128, True, "float32")],      # the other head size
    ids=str)
def test_tile_kernels_match_dense_core(b, h, s, dh, causal, dtype):
    """S=512, head 64, tile 512 — what ``_pallas_block`` picks for the
    S=512 cells — forward and fused backward against the dense float32
    core, in float32 at this file's bounds and in the cells' bf16."""
    _check_kernels("pa5", 913, (b, h, s, dh), dtype, 512, 512, causal)


_KLOOP_SHAPES = [
    (1, 3, 512, 64, 128, 128, True, False),     # BH odd, 4 tiles a row
    (1, 2, 512, 64, 128, 128, False, True),
    (1, 2, 1024, 64, 256, 256, True, True),
    (1, 1, 1024, 64, 256, 256, False, False),
    (1, 2, 512, 64, 256, 128, True, False),     # block_q > block_k
    (1, 2, 512, 64, 128, 256, True, True),      # block_q < block_k
    (1, 1, 512, 64, 256, 128, False, True),
    (1, 1, 512, 64, 128, 256, False, False),
]


@pytest.mark.parametrize(
    "b,h,s,dh,bq,bk,causal,hoist_delta,dtype",
    [c + ("float32",) for c in _KLOOP_SHAPES]
    + [c + ("bfloat16",) for c in _KLOOP_SHAPES]
    + [(1, 1, 512, 128, 256, 128, True, False, "float32")],
    ids=str)
def test_kloop_kernels_match_dense_core(b, h, s, dh, bq, bk, causal,
                                        hoist_delta, dtype):
    """The K-loop kernels (several tiles a row: what every S above
    512 runs, S=8192 at tile 512 on the chip) at head size 64 — and
    once at 128, the other size they compile for — against the dense
    float32 core: out, the lane-dense lse, the fused backward — with
    ``delta`` hoisted by the caller, as the ring does, and not — in
    float32 and in the cells' bf16."""
    _check_kernels("pa7", 915, (b, h, s, dh), dtype, bq, bk, causal,
                   hoist_delta)


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


@pytest.mark.parametrize("s,device_mb,want_mb", [
    (512, 128, 16),         # a small shape keeps the 16 MB default
    (16384, 128, 39.75),    # 1.5x the resident set (20.25 at S=8192)
    (512, 8, 8),            # a device that has less than the default
    (8192, 16, None),       # a v2/v3-sized VMEM cannot hold the rows
], ids=["floor", "monotone_in_s", "clamped_to_device", "refused"])
def test_fused_bwd_vmem_limit_tracks_footprint(s, device_mb, want_mb):
    """The grant is the 16 MB default at least, the footprint's 1.5x
    above it, never more than the device has, and a device that
    cannot hold the footprint is refused loudly (dh 64, tile 128,
    bf16)."""
    def grant(s):
        return PA._fused_bwd_vmem_limit(s, 64, 128, 128, 2,
                                        device_vmem=device_mb << 20)
    if want_mb is None:
        with pytest.raises(ValueError, match="smaller pallas_tile"):
            grant(s)
        return
    assert grant(s) == want_mb * 2 ** 20
    assert grant(s // 2) <= grant(s) <= device_mb << 20


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
