"""From a profiler trace to busy time, idle share, top operations and idle gaps.

The reduction works on plain event lists ``(name, start_ns, duration_ns)`` so
that a test can hold it to a known answer; ``load_xplane`` is the thin adapter
from ``jax.profiler.ProfileData`` (part of the installed JAX) to those lists.
"""

from __future__ import annotations

import glob
import os

Event = tuple[str, float, float]

#: device lines that hold one event per operation / per program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: harness phases wrapped in ``jax.profiler.TraceAnnotation``
ANNOTATION_PREFIX = "bench."


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(events: list[Event], t0: float, t1: float) -> list[tuple[float, float]]:
    """``events`` as intervals cut to the window ``[t0, t1]``."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO instruction; keep what
    stands before the ``=``: ``%fusion.12 = ...`` is ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def top_ops(events: list[Event], t0: float, t1: float,
            n: int = 10) -> list[list]:
    """The ``n`` operation names with most device seconds inside the window.
    Operations nest (a ``while`` spans its body's operations), so each is
    charged its self time: its duration less its direct children's."""
    by_name: dict[str, float] = {}
    live: list[list] = []            # [end, name, self time] of open events
    clipped = sorted(((max(s, t0), min(s + d, t1), name)
                      for name, s, d in events if min(s + d, t1) > max(s, t0)),
                     key=lambda e: (e[0], -e[1]))

    def close(upto: float) -> None:
        for ev in [ev for ev in live if ev[0] <= upto]:
            live.remove(ev)
            by_name[ev[1]] = by_name.get(ev[1], 0.0) + max(ev[2], 0.0)

    for a, b, name in clipped:
        close(a)
        # the innermost open event that holds this one whole is its parent;
        # one that merely overlaps (an async copy) is not
        for parent in reversed(live):
            if parent[0] >= b:
                parent[2] -= b - a
                break
        live.append([b, short_name(name), b - a])
    close(float("inf"))
    ranked = sorted(by_name.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(device: list[Event], annotations: list[Event], t0: float,
              t1: float, n: int = 10) -> list[list]:
    """The ``n`` longest intervals of the window in which no device operation
    ran, each named by the harness annotation that covers most of it
    (``host: unattributed`` where none does); gaps of one name are summed."""
    busy = sorted(clip(device, t0, t1))
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    by_name: dict[str, float] = {}
    for gs, ge in gaps:
        best, cover = "host: unattributed", 0.0
        for name, s, d in annotations:
            c = min(ge, s + d) - max(gs, s)
            if c > cover:
                best, cover = name, c
        if cover * 2 < ge - gs:
            best = "host: unattributed"
        by_name[best] = by_name.get(best, 0.0) + (ge - gs)
    ranked = sorted(by_name.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_trace(devices: dict[str, dict[str, list[Event]]],
                 annotations: list[Event],
                 window: tuple[float, float] | None = None) -> dict:
    """Busy seconds (mean over devices of the union of operation intervals),
    the window, the idle share, the top operations and the longest idle gaps.

    ``devices`` maps a device plane's name to its lines' events. The window
    is the span of the ``bench.window`` annotation where the harness wrote
    one, else from the first to the last device operation."""
    per_device = {name: lines.get(OPS_LINE) or
                  [e for evs in lines.values() for e in evs]
                  for name, lines in devices.items()}
    per_device = {k: v for k, v in per_device.items() if v}
    if not per_device:
        return {}
    if window is None:
        marks = [(s, s + d) for name, s, d in annotations
                 if name == ANNOTATION_PREFIX + "window"]
        if marks:
            window = (min(s for s, _ in marks), max(e for _, e in marks))
        else:
            every = [e for evs in per_device.values() for e in evs]
            window = (min(s for _, s, _ in every),
                      max(s + d for _, s, d in every))
    t0, t1 = window
    busy = [union_ns(clip(evs, t0, t1)) for evs in per_device.values()]
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (t1 - t0) / 1e9
    first = next(iter(per_device.values()))
    modules = [e for lines in devices.values()
               for e in lines.get(MODULES_LINE, [])]
    notes = [e for e in annotations
             if e[0].startswith(ANNOTATION_PREFIX)
             and e[0] != ANNOTATION_PREFIX + "window"]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": top_ops(first, t0, t1),
        "idle_gaps": idle_gaps(first, notes, t0, t1),
        "modules": [[n, s, d] for n, s, d in modules
                    if s + d > t0 and s < t1],
        "n_devices": len(per_device),
    }


def load_xplane(trace_dir: str, device_prefix: str = "/device:TPU:"):
    """``(devices, annotations)`` of the newest ``.xplane.pb`` under
    ``trace_dir``, in the form :func:`reduce_trace` takes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}, []
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, dict[str, list[Event]]] = {}
    annotations: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                annotations += [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)]
    return devices, annotations
