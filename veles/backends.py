"""Device backends.

Re-design of ``veles/backends.py`` [U] (SURVEY.md §2.1 "Device
backends"). The reference enumerated OpenCL/CUDA devices and kept a
per-device tuned BLOCK_SIZE database for its hand-written kernels. On
TPU, XLA owns tiling/autotuning, so a Device here is much thinner:

* :class:`NumpyDevice` — the oracle backend; all ``numpy_run`` paths.
* :class:`XLADevice` — wraps the jax device set of ONE platform, owns
  the default :class:`jax.sharding.Mesh` and the precision policy
  (bfloat16 matmuls on the MXU, float32 params).

Device selection mirrors ``velescli -d``: ``"numpy"`` forces the oracle,
``"xla"`` is jax's default platform (what ``JAX_PLATFORMS`` says — the
CPU in the test suite), ``"tpu"`` / ``"cpu"`` name a platform and raise
when jax cannot reach it.

Two definitions the rest of the package imports from here instead of
restating: :func:`is_tpu` (THE platform predicate — which devices run
the Mosaic kernels and take the bf16 policy) and
:func:`enable_compile_cache` (where jax's persistent compilation cache
lives — the analogue of the reference's on-disk kernel cache).
"""

import os

import numpy

from veles.config import root
from veles.logger import Logger


def is_tpu(platform):
    """True when ``platform`` (a ``jax.Device.platform`` string) is a
    TPU: real Pallas kernels instead of interpret mode, bf16 compute
    policy, kernel auto-selection."""
    return platform == "tpu"


#: the checkout's own cache directory (gitignored), computed from the
#: package path so every working directory resolves the same one — the
#: directory is part of jax's cache key, so a path that moves never hits
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def enable_compile_cache():
    """Turn on jax's persistent compilation cache for this process;
    -> the directory in use. ``$JAX_COMPILATION_CACHE_DIR`` places it
    from outside — jax reads that variable itself, so nothing is set
    in code then; otherwise ``<checkout>/.jax_compile_cache``, with
    EVERY program cached: under jax's default one-second floor a
    program that compiles in about a second is cached by one run and
    not by the next, so a second run of the same command still grew
    the directory. With the variable set that floor is jax's own, and
    whoever places the cache from outside can lower it the same way
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0``). Every process
    that compiles (trainer, serving engine, bench, tests) calls this
    ONE helper, so they all share one cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return _REPO_CACHE_DIR


def default_platform():
    """``platform`` of jax's default device. A failed device query
    raises — nothing on the jit paths continues on a guess."""
    import jax
    return jax.devices()[0].platform


def device_report(devices=None):
    """The device facts every result names (``--result-file``, the
    serve CLI's first line, ``chip_smoke.py``): platform, device_kind
    and count as jax reports them for ``devices`` (default: jax's
    default platform), plus the jax and libtpu versions."""
    import importlib.metadata
    import jax
    if devices is None:
        devices = jax.devices()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__, "libtpu": libtpu}


def force_virtual_cpu_devices(n):
    """Pin THIS process to jax's CPU platform split into ``n`` virtual
    devices — how multi-device logic runs without chips (the test
    suite's mesh, ``__graft_entry__.dryrun_multichip``). Call before
    any jax backend initializes. The environment is only read when
    jax is first imported, so the live config is flipped too for a
    process that imported it earlier."""
    import re
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=%d"
        % n).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


class Device(Logger):
    backend_name = "abstract"

    #: True when jax is the execution engine.
    is_xla = False

    def __init__(self):
        self.name = type(self).__name__

    @property
    def exists(self):
        return True

    def __repr__(self):
        return "<%s>" % self.backend_name


class NumpyDevice(Device):
    """Pure-numpy oracle backend (reference ``NumpyDevice`` [U])."""

    backend_name = "numpy"

    def __init__(self, dtype=numpy.float32):
        super().__init__()
        self.dtype = numpy.dtype(dtype)


class XLADevice(Device):
    """JAX/XLA execution on the devices of ONE jax platform:
    ``platform`` when given (``jax.devices(platform)`` raises when
    that platform is not reachable — an explicit ``tpu`` never lands
    on the CPU), else jax's default platform.

    The whole forward/backward/update cycle compiles into one program
    (SURVEY.md §7 design stance) so, unlike the reference's per-kernel
    device state, this object mostly carries policy: dtypes, the mesh,
    and donation settings.
    """

    backend_name = "xla"
    is_xla = True

    def __init__(self, platform=None, mesh=None,
                 compute_dtype=None, param_dtype=None):
        super().__init__()
        import jax
        self._jax = jax
        if platform:
            devices = jax.devices(platform)
        else:
            devices = jax.devices()
        self.jax_devices = devices
        self.platform = devices[0].platform
        self.mesh = mesh  # set up lazily / by veles.parallel
        # bfloat16 matmuls feed the MXU at full rate; params stay f32.
        # Overridable from config (root.common.engine.compute_dtype =
        # "float32"/"bfloat16"): measured on v5e, bf16 wins big on the
        # conv stack (AlexNet +21%) but costs ~4% on the transformer
        # LM (cast traffic around the matmuls) — workloads differ.
        import jax.numpy as jnp

        def policy_dtype(cfg_key, allowed):
            """Config-overridable dtype with the TPU-first default:
            bf16 on a TPU, f32 elsewhere (keeps the CPU parity suite
            exact)."""
            cfg_dt = root.common.engine.get(cfg_key)
            if cfg_dt:
                if cfg_dt not in allowed:
                    raise ValueError(
                        "root.common.engine.%s must be one of %s, "
                        "got %r" % (cfg_key, allowed, cfg_dt))
                return getattr(jnp, cfg_dt)
            return (jnp.bfloat16 if is_tpu(self.platform)
                    else jnp.float32)

        self.compute_dtype = compute_dtype or policy_dtype(
            "compute_dtype", ("float32", "bfloat16", "float16"))
        self.param_dtype = param_dtype or jnp.float32
        # Mixed-precision ACTIVATION policy (root.common.engine.amp =
        # "bfloat16"/"float32"): tensors flowing BETWEEN units (outputs
        # and err flows) are stored in this dtype; master weights and
        # solver state stay in param_dtype (f32), loss/softmax/stat
        # reductions compute in f32. On a v5e the f32 activation flow
        # was the single largest cost of the AlexNet step (LRN, pooling
        # scatter and bias-sum fusions are HBM-bandwidth-bound); bf16
        # halves it.
        self.act_dtype = policy_dtype("amp", ("float32", "bfloat16"))
        enable_compile_cache()

    @property
    def device_count(self):
        return len(self.jax_devices)

    def __repr__(self):
        return "<xla:%s x%d>" % (self.platform, self.device_count)


def get_device(spec=None) -> Device:
    """Build a Device from a CLI-ish spec.

    ``None`` → config default (``root.common.engine.backend``);
    ``"numpy"`` → oracle; ``"xla"`` → default jax platform;
    ``"tpu"``/``"cpu"`` → that jax platform.
    """
    if isinstance(spec, Device):
        return spec
    spec = spec or root.common.engine.backend
    if spec == "numpy":
        return NumpyDevice()
    if spec in ("xla", None):
        return XLADevice()
    return XLADevice(platform=spec)
