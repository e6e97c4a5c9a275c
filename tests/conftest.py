"""Test configuration: JAX on a virtual 8-device CPU mesh.

The suite proves bytes, counts and control flow, never a device time, so it
runs on the CPU backend with eight virtual devices (the mesh tests need
them). Both variables are set here, before any test imports jax, and are
inherited by the subprocesses tests spawn. The chip is exercised by
``chip_smoke.py``, not by pytest.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Make the repo root importable regardless of pytest invocation directory.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_device_breaker():
    """The wedge circuit breaker (ops/breaker.py) is process-global on
    purpose — but a test that trips it must not route every later test's
    dispatches to the host engine. Reset after each test, lazily (never
    import the ops stack for tests that don't touch it)."""
    yield
    mod = sys.modules.get("fgumi_tpu.ops.breaker")
    if mod is not None:
        mod.BREAKER.reset()


@pytest.fixture(autouse=True)
def _reset_resource_governor():
    """Same discipline for the resource governor (utils/governor.py): a
    test that drives it into a pressure state or injects samplers must not
    leak that into later tests' budget waits. Lazy — only when imported."""
    yield
    mod = sys.modules.get("fgumi_tpu.utils.governor")
    if mod is not None:
        mod.GOVERNOR.reset_for_tests()


@pytest.fixture(autouse=True)
def _reset_mesh_snapshot():
    """publish_mesh (parallel/mesh.py) records the active mesh in a
    process-global snapshot the run report and flight dumps read; any test
    whose CLI run builds a mesh (--devices auto sees the 8 virtual
    devices) must not leak it into later report-shape tests. Lazy."""
    yield
    mod = sys.modules.get("fgumi_tpu.parallel.mesh")
    if mod is not None:
        mod.LAST_MESH_SNAPSHOT = None


@pytest.fixture(autouse=True)
def _reset_audit_sentinel():
    """The silent-corruption sentinel (ops/sentinel.py) is process-global
    like the breaker: a test that injects a divergence must not leave its
    counters (or queued audits holding staging buffers) for later tests'
    run-report shapes. Lazy — only when imported."""
    yield
    mod = sys.modules.get("fgumi_tpu.ops.sentinel")
    if mod is not None:
        mod.SENTINEL.drain(timeout=10)
        mod.SENTINEL.reset()


@pytest.fixture(autouse=True)
def _reset_flight_recorder():
    """The flight recorder (observe/flight.py) is process-global and
    dedupes dumps per reason — a test that triggers a dump must not
    swallow the next test's. Reset the explicit dump-dir override and the
    dedupe state after each test; lazy like the fixtures above."""
    yield
    mod = sys.modules.get("fgumi_tpu.observe.flight")
    if mod is not None:
        mod.FLIGHT.reset()


@pytest.fixture(autouse=True)
def _reset_deployment_profile():
    """Profile application (tune/profile.py) is process-once on purpose —
    but a test that applies one must not make every later test's run
    report carry a `profile` section (or leave seeded router priors
    behind). Lazy: only when the tune module (and the router it seeds)
    was actually touched."""
    yield
    mod = sys.modules.get("fgumi_tpu.tune.profile")
    if mod is not None and mod.applied_info() is not None:
        mod.reset_applied_for_tests()
        router = sys.modules.get("fgumi_tpu.ops.router")
        if router is not None:
            router.ROUTER.reset()
