"""Launchers: production meshes, the multi-pod dry-run, roofline
extraction, training/serving CLIs, and the plan-equivalence checker."""
import os
import sys


def simulate_host_devices(n: int) -> None:
    """Run this process on ``n`` simulated CPU devices.

    Pins the platform to the CPU together with the forced host device
    count, so a simulated mesh never opens an accelerator: a chip belongs
    to one process at a time, and a simulation that took it would starve
    (or hang) the process that needs it.  Call before jax first touches a
    device, because the device count locks then.  Launchers call this
    only for ``--devices N``; without it they use the real devices.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", ""))
    if "jax" in sys.modules:        # imported already: the env var was read
        sys.modules["jax"].config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    and nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moved between runs would never hit.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
