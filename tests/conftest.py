"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths
(jax.sharding.Mesh / shard_map) are exercised without TPU hardware, per
the project's environment contract.  Must run before jax is imported:
JAX reads JAX_COMPILATION_CACHE_DIR once, at import.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jepsen_tpu.utils.backend import force_cpu_backend

# Persistent test-scoped XLA compile cache: the suite compiles several
# hundred CPU executables and the inter-module jit-cache purge below
# re-compiles shared helpers; pointing jax at an on-disk cache makes both
# the purge re-compiles and full suite re-runs disk hits instead of XLA
# invocations (only compiles > 1 s are persisted, so the dir stays small).
# Disable with JT_NO_TEST_CACHE=1 when chasing a suspected stale-cache bug.
# Set before force_cpu_backend imports jax, which reads it then.
if not os.environ.get("JT_NO_TEST_CACHE"):
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache_tests"))

force_cpu_backend(8)

# AOT compile cache: memory-only for the suite (its default would be
# <JAX_COMPILATION_CACHE_DIR>/aot, shared by every test). Tests that
# exercise persistence pin a tmp dir via compilecache.set_cache_dir
# (overrides this env).
os.environ.setdefault("JT_COMPILECACHE", "mem")

import pytest


def pytest_sessionfinish(session, exitstatus):
    """Record the compile budget: how many XLA executables the suite
    compiled fresh vs served from the persistent cache this run.
    Printed in the terminal summary."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d or not os.path.isdir(d):
        return
    entries = os.listdir(d)
    t0 = getattr(session, "_jt_t0", None)
    fresh = 0
    if t0 is not None:
        for e in entries:
            try:
                if os.path.getmtime(os.path.join(d, e)) >= t0:
                    fresh += 1
            except OSError:
                pass
    print(f"\n[jepsen-tpu] persistent compile cache: {len(entries)} "
          f"entries, {fresh} written this run ({d})")


def pytest_sessionstart(session):
    import time

    session._jt_t0 = time.time()


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Release compiled executables between test modules.

    The full suite compiles several hundred XLA:CPU executables in one
    process; with all of them held live, a late large compile segfaults
    inside `backend_compile_and_load` (reproducible at the same test
    with and without background load).  Dropping the jit caches between
    modules caps live executable memory and keeps the suite green; the
    cost is re-compiling shared helpers a few times (~1 min over the
    whole suite).
    """
    yield
    import jax

    from jepsen_tpu import compilecache

    # the AOT executable table holds Compiled objects jax.clear_caches
    # doesn't see — drop it alongside or it defeats the memory cap
    compilecache.clear()
    jax.clear_caches()
