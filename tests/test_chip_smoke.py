"""What can be checked about the chip path WITHOUT a chip: where the
compile cache lands, that every shipped Pallas variant lowers for the
TPU at the shapes ``chip_smoke.py`` runs, that the smoke refuses a
machine with no TPU, and that its phase command lines run (the same
table at toy widths, on the CPU)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from veles import backends
from veles.znicz_tpu.parallel import pallas_attention as PA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Every ``jax.config.update`` call made while the fixture is
    live, recorded instead of applied."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path,
                                               config_updates):
    """With $JAX_COMPILATION_CACHE_DIR set the helper sets nothing in
    code: jax reads the variable itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backends.enable_compile_cache() == str(tmp_path)
    assert config_updates == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path,
                                                config_updates):
    """Unset, the cache is <checkout>/.jax_compile_cache whatever the
    working directory — computed from the package's own path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_compile_cache")
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        assert backends.enable_compile_cache() == want
    assert [value for name, value in config_updates
            if name.endswith("_dir")] == [want, want]


def test_every_pallas_variant_lowers_for_tpu(monkeypatch):
    """Each variant of the smoke's table, ``interpret=False``, at the
    full train_long shapes in bf16, lowers to a Mosaic custom call for
    the TPU platform. Lowering is not compiling (VMEM limits and tile
    alignment are the chip run's job), but it is where a kernel that
    asks the MXU for a bf16 accumulator is caught — and it needs no
    chip."""
    # the fused backward sizes its VMEM grant from the device it
    # compiles for; there is none here, so name the v5e's 128 MiB
    monkeypatch.setattr(PA, "_device_vmem_bytes", lambda: 128 << 20)
    variants = chip_smoke.pallas_variants("full", False, jnp.bfloat16)
    assert len(variants) == 6
    for name, (fn, specs) in variants.items():
        text = fn.trace(*specs).lower(
            lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text, name


#: run in a child: loading libtpu's compiler is kept out of the test
#: process. Prints NO_TOPOLOGY when libtpu cannot describe a v5e here.
_COMPILE_FOR_V5E = """
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import chip_smoke
from veles.znicz_tpu.parallel import pallas_attention as PA
try:
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
except Exception as exc:
    print("NO_TOPOLOGY %s" % exc)
    sys.exit(0)
PA._device_vmem_bytes = lambda: 128 << 20
on_chip = SingleDeviceSharding(topo.devices[0])
for name, (fn, specs) in chip_smoke.pallas_variants(
        "full", False, jnp.bfloat16).items():
    fn.lower(*(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_chip)
               for s in specs)).compile()
    print("COMPILED %s" % name, flush=True)
# the expert layer's row stages (ops/expert_ffn.py) at the LFM2 cell's
# gating shapes: a loop over chunks into a buffer that is allocated,
# not filled
from veles.znicz_tpu.ops import expert_ffn
from veles.znicz_tpu.ops.swiglu import swiglu
gate = expert_ffn.prefix_stage(
    lambda real, h13: swiglu(h13, keep=real).astype(jnp.bfloat16))
text = jax.jit(lambda h13, rows: gate(rows, h13)[0]).lower(
    jax.ShapeDtypeStruct((65536, 3072), jnp.bfloat16, sharding=on_chip),
    jax.ShapeDtypeStruct((), jnp.int32, sharding=on_chip)
).compile().as_text()
assert " while(" in text and text.count("tpu_custom_call") == 1, text
assert "bf16[65536,1536]{1,0:T(8,128)(2,1)} broadcast(" not in text, text
print("COMPILED expert row stage", flush=True)
# the thin share's combine (PR 40) at the Laguna cell's shape: one loop
# of one-hot products on the MXU, no gather
rows = 8192 * 10
text = jax.jit(expert_ffn.pair_moves(10, True)[1]).lower(
    jax.ShapeDtypeStruct((rows, 3072), jnp.bfloat16, sharding=on_chip),
    *(jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=on_chip),) * 2,
    jax.ShapeDtypeStruct((), jnp.int32, sharding=on_chip)
).compile().as_text()
assert text.count(" while(") == 1 and " gather(" not in text, text
assert " convolution(" in text, text
print("COMPILED thin-share combine", flush=True)
# the delta rule's pass over chunks (parallel/pallas_delta.py) at the
# Solar-Open2 cell's shape, a group of 8 heads of 128 x 128 over 64
# chunks of 64: the plain forward, and the forward that keeps the
# entry states with the backward
from veles.znicz_tpu.parallel import pallas_delta
terms = [jax.ShapeDtypeStruct((64, 1, 8, rows, cols), jnp.float32,
                              sharding=on_chip)
         for rows, cols in ((64, 128),) * 3 + ((64, 64), (64, 128),
                                               (1, 128))]
def state_pass(*terms):
    def total(*terms):
        o, state = pallas_delta.state_pass(*terms, rows=8)
        return (o * o).sum() + (state * state).sum()
    return (pallas_delta.state_pass(*terms, rows=8),
            jax.grad(total, argnums=tuple(range(6)))(*terms))
text = jax.jit(state_pass).lower(*terms).compile().as_text()
assert text.count('custom_call_target="tpu_custom_call"') == 3, text
print("COMPILED delta state pass", flush=True)
"""


@pytest.mark.slow
def test_every_pallas_variant_compiles_for_v5e(tmp_path):
    """The step past lowering, still without a chip: libtpu is
    installed, so a compile-only v5e topology runs the real TPU
    compiler — Mosaic's VMEM limits and (8,128) tiling rules included
    (this is where a 64-lane DMA window is refused) — over the same
    table at the same shapes."""
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_FOR_V5E], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 TPU_WORKER_HOSTNAMES="localhost",
                 # TPU executables are ~100 MB each: not in the repo's
                 # cache, which the chip tool copies
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=600)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip("libtpu gives no compile-only v5e topology here: "
                    + proc.stdout.strip()[:200])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.count("COMPILED ") == 9, proc.stdout


def test_smoke_refuses_a_machine_without_a_tpu():
    """``python chip_smoke.py`` with no chip: non-zero exit, the
    missing TPU named, no result line — and the parent process never
    imported jax."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "rc = chip_smoke.main([])\n"
         "print('PARENT_IMPORTED_JAX=%s' % ('jax' in sys.modules))\n"
         "sys.exit(rc)"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "PARENT_IMPORTED_JAX=False" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert "tpu" in proc.stderr.lower()
    assert "chip_smoke FAILED" in proc.stderr


def test_smoke_phases_run_at_tiny_widths_on_the_cpu():
    """The smoke's own phase functions — the same command lines, from
    the same table, at the "tiny" entry with ``-d cpu``: both trainer
    runs, the kernels child (interpreted) and the server with its
    requests. A typo in a line is found here, not on chip time."""
    facts = chip_smoke.run_single_chip(
        "tiny", "cpu", budget_s=600, env={"JAX_PLATFORMS": "cpu"})
    assert facts["platform"] == "cpu"


@pytest.mark.slow
def test_smoke_four_chip_legs_run_at_tiny_widths_on_the_cpu():
    """The ``--chips 4`` legs (DP=4, DP2xTP2, ring SP=4) at the tiny
    entry on four virtual CPU devices."""
    facts = chip_smoke.run_four_chips(
        "tiny", "cpu", budget_s=900, env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert facts["count"] == 4
