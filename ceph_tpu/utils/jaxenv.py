"""Shared bootstrap for the virtual multi-device CPU platform.

Multi-chip hardware is not available in CI: sharding correctness runs on
a virtual N-device CPU platform instead.  Both the test suite
(tests/conftest.py) and the driver dry-run (__graft_entry__.py) need the
same fragile recipe, kept here so they cannot drift:

  * JAX_PLATFORMS from the session must be
    DROPPED, not overridden — setting it to "cpu" does not reliably win;
    the platform is pinned via jax.config in-process instead.
  * any pre-existing xla_force_host_platform_device_count pin must be
    stripped (it may be smaller than the requested count) before adding
    ours.
  * JAX_ENABLE_X64 is required for bit-exact straw2 int64 math.
"""

from __future__ import annotations


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for a process that
    holds the chip, and return the directory in use.  Called from the
    process entry points (chip_smoke.py, benchmark/run.py,
    cli/vstart.py, cli/osdmaptool.py), never at package import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    no directory is set here.  Otherwise the cache lives at one fixed
    path inside the checkout, ``<checkout>/.jax_cache`` (git-ignored):
    the path is part of the cache key, so it must not move between
    runs.  Where that path cannot be written (the package installed
    outside a checkout) the process runs uncached, says so in the log,
    and None is returned.  In both modes every program is kept, however
    quick its compile — a cluster boot is many small programs."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache")
        try:
            os.makedirs(path, exist_ok=True)
            if not os.access(path, os.W_OK):
                raise PermissionError(path)
        except OSError as e:
            from .log import global_logger
            global_logger().error(
                "jaxenv", "no persistent compile cache: %r" % (e,))
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def force_virtual_cpu_env(env: dict, n_devices: int) -> dict:
    """Mutate ``env`` (an os.environ-like mapping) so a JAX process
    started with it sees an ``n_devices``-device CPU platform once it
    also runs ``jax.config.update("jax_platforms", "cpu")``."""
    env.pop("JAX_PLATFORMS", None)
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env.setdefault("JAX_ENABLE_X64", "1")
    return env
