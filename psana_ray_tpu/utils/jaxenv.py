"""Process-level JAX settings shared by every entry point that compiles.

Three things a device run has to make visible instead of leaving to
defaults: where compiled programs are cached (a CLI that sets nothing
cold-compiles on every start), which device the process actually
compiled for (a run that fell to the CPU must say so in its own log),
and what the start itself cost: :func:`configure_compile_cache` also
installs the listener of :mod:`psana_ray_tpu.obs.jitwatch`, which turns
JAX's own timings of every trace, lowering, cache load and compile into
``jit.*`` spans and ``jit_*_total`` counters from the first jitted call
on (it is called on compile events only: a loop that compiles nothing
never reaches it).
"""

from __future__ import annotations

import os

# the checkout root, from this file's own location — never a temporary
# name, pid or time: the directory is part of the cache key, so a path
# that moves between runs never hits
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """Where compiled programs are cached: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (gitignored). Touches no JAX,
    so a launcher that must stay off the chip can ask too."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return it. Where the environment names
    the directory JAX reads it itself and no directory is set in code;
    either way every child process inherits the same one, so repeated CLI
    starts on one machine — and every process one script launches — share
    compiles. Also installs, once, the process's compile-path listener
    (``obs.jitwatch.install()``): every entry point comes through here
    before anything compiles, so the account of a start is whole."""
    import jax

    from psana_ray_tpu.obs import jitwatch

    jitwatch.install()

    # A Pallas kernel rides in the program as a serialized Mosaic module
    # whose op locations, by default, hold the Python call stack (10
    # frames) — opaque bytes the cache key cannot strip. The same step
    # reached from two entry points (a CLI, a benchmark, a script) then
    # gets two keys and never shares an entry (seen on the v5e). One
    # frame, the kernel's own line, names the op as well and keys alike.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the default
    backend — the line CLIs log once at start and result files carry, so
    a number can always be traced to the device that produced it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def startup_line() -> str:
    """Configure the cache, look at the devices, and return the one line
    a device entry point logs at start — so a run that fell to the CPU
    says so in its own log, in one format (``chip_smoke.py`` reads it)."""
    cache_dir = configure_compile_cache()
    dev = device_summary()
    return (
        f"jax devices: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}; compile cache: {cache_dir}"
    )
