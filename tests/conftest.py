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


# -- a described v5e for tests/test_chip_compile*.py (their helpers: tests/chip.py) --------------

@pytest.fixture
def cache_setting():
    """Snapshot/restore the process-wide persistent-cache settings."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_include_full_tracebacks_in_locations",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


@pytest.fixture
def one_chip(cache_setting):
    """Sharding on one described v5e chip; persistent cache OFF around the
    compile (an entry written for a described device cannot be read back
    without one — the next run would warn and recompile)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # The pins below name kernels as a fresh process names them
    # (``%row_gather``, ``%peak_nms``: the ``pallas_call``'s own name, which
    # locations carry only with full tracebacks, JAX's default). An
    # earlier test of this xdist worker that ran a CLI's ``main`` in-process
    # (``tests/test_sfx.py``) has been through ``configure_compile_cache``,
    # which turns them off for good: the kernel is then named after the
    # function around it (``%gather_rows``), as on the chip. Which files
    # share a worker changes with every test added, so state it here;
    # ``cache_setting`` puts back what it found. The traces go too: a
    # kernel's wrapper asks ``default_backend()`` while it is TRACED and
    # the trace is cached by shapes alone, so one made on the CPU would be
    # lowered here in the kernel's place, and one made here would reach a
    # later CPU test.
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
