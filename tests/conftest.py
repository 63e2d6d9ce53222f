"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

The reference has no tests at all (SURVEY.md §4); this suite follows the
strategy SURVEY.md prescribes — in-process queue/infeed unit tests plus
multi-device tests on a CPU-simulated mesh."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fresh_registry():
    from psana_ray_tpu.obs.registry import MetricsRegistry
    from psana_ray_tpu.transport.registry import Registry

    Registry.reset_default()
    MetricsRegistry.reset_default()
    yield
    Registry.reset_default()
    MetricsRegistry.reset_default()


@pytest.fixture(autouse=True)
def _no_compile_listener_left():
    """A CLI's ``main`` (or ``configure_compile_cache``) run in-process
    installs the process's compile-path listener (``obs/jitwatch.py``); it
    must not listen into the next test of this worker, nor hand that
    test's tracer what an earlier one compiled."""
    yield
    from psana_ray_tpu.obs import jitwatch

    jitwatch.WATCH.uninstall()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
