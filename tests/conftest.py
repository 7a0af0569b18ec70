"""Test harness setup.

The suite runs on jax's CPU platform split into 8 virtual devices, set
up BEFORE anything initializes a jax backend (SURVEY.md §4 "TPU build
translation": multi-device logic is tested with
``--xla_force_host_platform_device_count=8``, the honest analogue of
the reference's fake-transport distributed tests). The chip is
exercised by ``chip_smoke.py``, never from here.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veles import backends  # noqa: E402

# Forced, not defaulted: whatever platform the outer environment names,
# these tests compare against CPU-exact references.
backends.force_virtual_cpu_devices(8)
# Persistent compile cache, shared with every other entry point: a
# large share of the tier-1 budget is recompiling the same programs.
backends.enable_compile_cache()

import numpy  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return numpy.random.Generator(numpy.random.PCG64(1234))


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Every test runs under a FRESH scoped telemetry registry
    (veles/telemetry.py): instruments created by one test can never
    leak counts into another or into tier-1 flakiness. LazyChild
    handles on long-lived units re-resolve automatically when the
    registry generation changes. The span tracer is reset too, in
    case a test enabled it and failed before stopping."""
    from veles import telemetry
    with telemetry.scoped():
        yield
    telemetry.tracer.stop()
    telemetry.tracer.clear()


@pytest.fixture(autouse=True)
def _model_health_isolation():
    """Each test gets a fresh model-health monitor
    (veles/model_health.py): layer stats, the loss EWMA and the
    divergence verdict one test's training run produces can never
    leak into another's /debug/model or SLO evaluation."""
    from veles import model_health
    with model_health.scoped():
        yield


@pytest.fixture(autouse=True, scope="module")
def _mnist_config_isolation():
    """Workflow builders (``make_wf`` in tests/test_service.py and its
    copies) set ``root.mnist`` for their small runs and do not restore
    it; which file a worker runs next is a matter of timing, so the
    leak must end with the FILE that made it (test_mnist_functional
    trains on whatever sizes it finds: 500 samples read 0.46 where
    6000 read 0.09)."""
    from veles.config import root
    # the sample's module-level defaults must be in root BEFORE the
    # snapshot, or a never-touched key restores as an explicit None
    from veles.znicz_tpu.models import mnist  # noqa: F401
    saved_loader = {k: root.mnist.loader.get(k)
                    for k in ("minibatch_size", "n_train", "n_valid")}
    saved_epochs = root.mnist.decision.get("max_epochs")
    yield
    root.mnist.loader.update(saved_loader)
    root.mnist.decision.max_epochs = saved_epochs


@pytest.fixture(autouse=True)
def _tenant_table_isolation():
    """The per-tenant QoS table (veles/serving/tenants.py) is
    process-global by design; a test that installs one must never
    leave quotas/weights behind for the next test's frontends."""
    yield
    from veles.serving import tenants
    tenants.set_table(None)


@pytest.fixture(autouse=True)
def _health_isolation():
    """Each test gets a fresh health monitor (veles/health.py): the
    readiness checks and SLO alert state one test registers (web
    status, serving frontends, masters) can never leak into another.
    The monitor is closed on exit so no sampler thread outlives its
    test."""
    from veles import health
    with health.scoped():
        yield
