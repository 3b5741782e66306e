"""Test harness: force an 8-device virtual CPU platform.

Mirrors how the reference smoke-tests its MPI pipeline on one box with
``--ci 1`` (FedAvgEnsAggregatorSoftCluster.py:259-264): the pjit/collective
paths run against XLA's host-platform device simulation so multi-chip sharding
is exercised without TPU hardware.

The tests never take a chip: the platform is pinned to CPU here, in the
process, whatever the environment says, and XLA_FLAGS is appended before
the first backend initialisation. What runs on the TPU is chip_smoke.py.
"""

import os

_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) >= 8, jax.devices()


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Full-suite runs accumulate hundreds of compiled executables across
    modules; XLA:CPU has been observed to segfault inside backend_compile
    late in the run (reproducibly at the same test in-suite, never when the
    module runs alone). Dropping compiled programs between modules keeps the
    compiler's heap small; per-module recompiles are the price."""
    yield
    import jax
    jax.clear_caches()


# The threaded suites run under the lock-order recorder
# (analysis/lockorder.py): every repo-created lock is instrumented, a
# same-thread re-acquisition of a non-reentrant Lock (the PR 9 tap
# re-entrancy deadlock) raises instead of hanging, and at module teardown
# the accumulated acquisition graph must be ACYCLIC — a cycle is a latent
# deadlock two threads can hit even if this run didn't.
_LOCKORDER_MODULES = ("test_live_ops", "test_resilience", "test_prefetch")


@pytest.fixture(autouse=True, scope="module")
def _lock_order_recorder(request):
    name = request.module.__name__.rsplit(".", 1)[-1]
    if name not in _LOCKORDER_MODULES:
        yield None
        return
    from feddrift_tpu.analysis.lockorder import LockOrderRecorder
    rec = LockOrderRecorder()
    rec.install()
    try:
        yield rec
    finally:
        rec.uninstall()
    rec.check()     # raises LockOrderViolation on any recorded cycle
