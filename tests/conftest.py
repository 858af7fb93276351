"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding without
hardware) — flags must be set before the first ``import jax`` anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may name another platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

try:
    # Persist XLA executables across suite runs (engine steps take seconds
    # to compile each; the cache is keyed by HLO+backend+flags so it can
    # never serve a stale program).
    from copycat_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
except ImportError:  # pragma: no cover - jax is part of the baked image
    pass

import pytest  # noqa: E402

#: XLA:CPU maps every compiled program into memory regions of its own (a
#: consensus step: about 600) and JAX keeps each executable for the life of
#: the process. The suite compiles over a hundred step-sized programs in one
#: process and grew to ``vm.max_map_count`` (65,530 here): LLVM then reports
#: "Cannot allocate memory" and the next compile segfaults (PR 25). Past this
#: many regions a module's end drops JAX's compile caches, which unmaps them;
#: later modules compile again or load from the persistent cache.
_MAP_BUDGET = 30_000


@pytest.fixture(autouse=True, scope="module")
def _bounded_memory_maps():
    yield
    try:
        with open("/proc/self/maps") as f:
            regions = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to bound
        return
    if regions > _MAP_BUDGET:
        import jax

        jax.clear_caches()
