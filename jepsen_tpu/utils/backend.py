"""Backend selection and JAX's persistent compile cache — one copy,
used by tests/conftest.py, the CLI's ``--cpu``, the campaign runner,
``bench.py`` and ``__graft_entry__.py``."""

from __future__ import annotations

import os

#: the fixed cache directory used when JAX_COMPILATION_CACHE_DIR is unset
#: (a fixed path, since the path is part of what a later run looks up)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu_backend(n_devices: int | None = None) -> None:
    """Make jax use the CPU backend, optionally with ``n_devices`` virtual
    host devices.  Must run before the first jax backend initialization;
    safe to call again after (no-op beyond config updates).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    import jax

    # a caller (or pytest plugin) may have imported jax before us,
    # binding jax_platforms to the outer env — override the config too
    jax.config.update("jax_platforms", "cpu")


def child_env(use_device: bool) -> dict:
    """The environment for a child process on this host.  A chip
    belongs to one process at a time, so callers give the device to at
    most one child at a time (``use_device``); every other child is
    held to the CPU backend before it imports JAX."""
    env = dict(os.environ)
    if not use_device:
        env["JAX_PLATFORMS"] = "cpu"
        env["JT_FORCE_CPU"] = "1"
    return env


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
    already read it and nothing is set here; otherwise the cache is the
    fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
