"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's QA pattern (SURVEY.md §4): local-mode Spark
(``local[N]``) gave N executors in one process so the full distributed path
ran on a laptop; here ``--xla_force_host_platform_device_count=8`` gives 8
XLA CPU devices so every mesh/collective/async path runs without TPU
hardware. Must be set before JAX initializes a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU wherever they start
# the persistent compile cache stays off under test, in this process and
# in the children some tests start: entry points that turn it on
# (distkeras_tpu.utils.compile_cache.enable) then only name a directory
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the env var alone loses to a jax_platforms *config* value set before
# this file ran; force the config back to cpu before any backend
# initializes
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Suites that exercise the cross-thread serving surfaces run under the
# dynamic lock-order detector (distkeras_tpu.analysis.lockorder): every
# threading.Lock/RLock allocated from package or test code during the
# test reports its acquisition order, and a cycle in the global graph —
# a lock-order inversion, i.e. a deadlock awaiting its interleaving —
# fails the test even though no deadlock happened. Off everywhere else:
# nothing is installed, threading is untouched, overhead is zero.
_LOCKORDER_SUITES = {"test_serving", "test_router", "test_telemetry"}


@pytest.fixture(autouse=True)
def _lock_order_guard(request):
    name = request.module.__name__.rpartition(".")[2]
    if name not in _LOCKORDER_SUITES:
        yield
        return
    from distkeras_tpu.analysis.lockorder import LockOrderDetector

    det = LockOrderDetector()
    det.install()
    try:
        yield det
    finally:
        det.uninstall()
    # only reached when the test body didn't raise: report inversions
    # without masking a genuine test failure
    det.assert_no_cycles()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    from distkeras_tpu.parallel.mesh import make_mesh

    return make_mesh({"dp": 8})
