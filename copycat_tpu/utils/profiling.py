"""XLA profiler integration (SURVEY.md §5.1 build obligation).

The reference has no tracing beyond SLF4J loggers (§5.1 names the XLA
profiler hook a "free win on TPU"). :func:`xla_trace` wraps any step-loop
region in a ``jax.profiler`` trace whose artifacts open in
TensorBoard/XProf, or are read here with ``jax.profiler.ProfileData``
when no UI is available (:func:`summarize_trace` — a per-op table is how
the one-hot rewrite in ``ops/consensus.py`` was found; see PERF.md).

Usage::

    from copycat_tpu.utils.profiling import xla_trace

    with xla_trace("/tmp/copycat-trace"):   # no-op when dir is falsy
        for _ in range(5):
            rg.step_round()
"""

from __future__ import annotations

import contextlib
from typing import Iterator


@contextlib.contextmanager
def xla_trace(trace_dir: str | None) -> Iterator[None]:
    """Trace the enclosed region with ``jax.profiler`` (no-op if falsy)."""
    if not trace_dir:
        yield
        return
    import jax

    with jax.profiler.trace(str(trace_dir)):
        yield


def find_xplane_files(trace_dir: str) -> list[str]:
    """The ``*.xplane.pb`` files of the NEWEST capture session under
    ``trace_dir``.

    The standard jax layout is one timestamped subdir per capture under
    ``plugins/profile/``; some jax/tensorboard-plugin versions nest
    differently, so when that glob comes up empty the whole tree is
    scanned and the files are grouped by parent directory (newest
    mtime wins) — only the newest session is summarized either way, so
    reused trace dirs don't merge runs.
    """
    import glob
    import os

    sessions = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/"))
    if sessions:
        files = glob.glob(os.path.join(sessions[-1], "*.xplane.pb"))
        if files:
            return files
    # Layout fallback: find xplane files anywhere below, newest
    # session-dir (by mtime) only.
    by_dir: dict[str, list[str]] = {}
    for root, _dirs, names in os.walk(trace_dir):
        for name in names:
            if name.endswith(".xplane.pb"):
                by_dir.setdefault(root, []).append(os.path.join(root, name))
    if not by_dir:
        raise FileNotFoundError(
            f"no profile sessions under {trace_dir}: expected "
            f"plugins/profile/<session>/*.xplane.pb (or any *.xplane.pb "
            f"below it) — did the traced region actually run?")
    newest = max(by_dir, key=lambda d: os.path.getmtime(d))
    return sorted(by_dir[newest])


def aggregate_trace_events(events: list[dict],
                           top: int = 15) -> list[tuple[str, float, int]]:
    """Aggregate device-lane op time from trace-viewer JSON events.

    Returns ``[(op_name, total_ms, count), ...]`` sorted by time. Only
    events on device (TPU/accelerator) lanes are counted, so host-side
    spans and module wrappers don't drown the per-op numbers. Split out
    of :func:`summarize_trace` so the aggregation is testable against a
    canned event list without a TPU.
    """
    import collections

    # Map pid -> process name from metadata events; keep device lanes only.
    proc: dict = {}
    for event in events:
        if event.get("ph") == "M" and event.get("name") == "process_name":
            proc[event.get("pid")] = event.get("args", {}).get("name", "")
    device_pids = {pid for pid, name in proc.items()
                   if any(t in name for t in ("TPU", "GPU", "/device",
                                              "Device", "XLA Op"))}

    agg: collections.Counter = collections.Counter()
    cnt: collections.Counter = collections.Counter()
    for event in events:
        if event.get("ph") != "X" or event.get("pid") not in device_pids:
            continue
        name = event.get("name", "")
        agg[name] += event.get("dur", 0)
        cnt[name] += 1
    return [(name, dur / 1e3, cnt[name]) for name, dur in agg.most_common(top)]


#: the device line that holds one event per executed operation (module
#: and step lines wrap them and would count the same time twice)
OPS_LINE = "XLA Ops"


def summarize_trace(trace_dir: str, top: int = 15) -> list[tuple[str, float, int]]:
    """Aggregate device-op time from the NEWEST captured trace session.

    Returns ``[(op_name, total_ms, count), ...]`` sorted by time — enough
    to find the hot op without a TensorBoard UI. The raw ``.xplane.pb``
    capture is read with ``jax.profiler.ProfileData`` (part of the
    installed JAX, as ``benchmarks/trace_reduce.py`` reads it), so it
    works wherever the trace could be taken. Device planes contribute
    their per-operation line, named by the HLO instruction's own name
    (``%fusion.12 = ...`` is ``fusion.12``).
    """
    from jax.profiler import ProfileData

    events: list[dict] = []
    for path in find_xplane_files(trace_dir):
        try:
            data = ProfileData.from_file(path)
        except Exception as exc:  # noqa: BLE001 - a parse error, reworded
            raise RuntimeError(
                f"{path} is not a readable .xplane.pb capture (parsed with "
                f"jax.profiler.ProfileData; no xprof package is needed): "
                f"{exc}") from exc
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            pid = len(events)
            events.append({"ph": "M", "name": "process_name", "pid": pid,
                           "args": {"name": plane.name}})
            lines = list(plane.lines)
            ops = [line for line in lines if line.name == OPS_LINE] or lines
            for line in ops:
                for e in line.events:
                    name = e.name.split(" = ", 1)[0].strip().lstrip("%")
                    events.append({"ph": "X", "pid": pid, "name": name,
                                   "dur": e.duration_ns / 1e3})
    return aggregate_trace_events(events, top)
