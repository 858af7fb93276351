"""Backend selection helpers for entry points."""

from __future__ import annotations

import os
import re


def device_info() -> dict:
    """What JAX runs on, as every benchmark result and artifact names it:
    ``platform``, ``device_kind`` and ``device_count``. Initializes the
    backend (one in-process ``jax.devices()`` call)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_platform() -> dict:
    """Ask ``jax.devices()`` once, in process, and exit 2 unless the
    platform is the one asked for: the first entry of ``JAX_PLATFORMS``
    when the caller set it, else the TPU. A measurement path that
    finds a different device fails; it never carries on elsewhere under
    the same label. Returns :func:`device_info`."""
    import sys

    asked = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() \
        or "tpu"
    info = device_info()
    if info["platform"] != asked:
        print(f"FATAL: asked for platform {asked!r}, JAX runs on "
              f"{info['platform']!r} ({info['device_kind']})",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    return info


#: The in-checkout compile cache used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset. One fixed path: the directory is part of the cache key, so
#: a cache that moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache; returns its directory.

    The engine's one-time jit compile dominates server-open latency, and
    XLA persists compiled executables keyed by (HLO, backend, flags), so
    every later process — server restarts, bench reps, recovery after a
    crash — skips straight to execution.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own handling of it
    stands and this sets no directory at all. Where it is not, the cache
    goes to :data:`DEFAULT_CACHE_DIR`. Idempotent; safe to call before
    backend initialization (it only sets jax config values).
    """
    import jax

    # The engine step takes seconds to compile, far above the 1 s default
    # threshold — but tests/small drivers compile many tiny programs too;
    # cache everything non-trivial.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    # The directory is bounded by _trim_cache_dir, NOT by jax's
    # ``jax_compilation_cache_max_size`` — that knob turns on per-entry
    # atime bookkeeping plus a directory-wide eviction scan under a lock
    # file, which with several concurrent processes on one dir produced
    # write-failure warnings and multi-minute stalls of child processes.
    _trim_cache_dir(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


#: XLA persistent-cache entry names carry a 64-hex program hash
#: (e.g. ``jit__foo-<64 hex>-cache``); the trim below refuses to touch
#: anything else, so a misconfigured cache path (someone's $HOME) can
#: never lose user files.
_CACHE_ENTRY_RE = re.compile(r".*-[0-9a-f]{64}(-cache|-atime)?$")


def _trim_cache_dir(path: str, max_bytes: int = 1 << 30) -> None:
    """Best-effort size bound for the cache dir: drop least-recently
    used entries (max of atime/mtime — atime advances on cache hits
    under relatime) until under ``max_bytes``. Runs once per process at
    enable time — no locks, no bookkeeping files; a concurrently-deleted
    file is simply skipped. Only files shaped like XLA cache entries are
    ever removed, and a removed entry only costs its owner a recompile."""
    try:
        entries = []
        with os.scandir(path) as it:
            for e in it:
                try:
                    if not e.is_file() or not _CACHE_ENTRY_RE.match(e.name):
                        continue
                    st = e.stat()
                except OSError:
                    continue  # concurrently deleted mid-scan
                entries.append((max(st.st_atime, st.st_mtime),
                                st.st_size, e.path))
        total = sum(s for _, s, _ in entries)
        if total <= max_bytes:
            return
        entries.sort()  # least recently used first
        for _, size, p in entries:
            try:
                os.remove(p)
            except OSError:
                continue
            total -= size
            if total <= max_bytes:
                return
    except OSError:
        return
