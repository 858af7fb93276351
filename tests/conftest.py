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
