"""Cluster-wide causal tracing: one trace id carried from the client
submit across every member a request touches (SURVEY.md §5.1 names
tracing a build obligation; the XLA profiler in :mod:`profiling` covers
the device plane — this covers the host request path, now including the
multi-group ingress/proxy/replication hops of docs/SHARDING.md).

Design constraints, in order:

1. **Zero overhead when disabled.** The hot path (client submit, server
   command handlers, the replication window stager, the apply loop) does
   ONE attribute read (``TRACER.enabled`` / ``request.trace is None`` /
   an empty-dict truthiness check) and branches away. No span objects,
   no clock reads, no dict lookups. Tested at each family of sites
   (``tests/test_pump_spans.py``, ``tests/test_bulk_spans.py``: the
   recording entry points refuse to be called) and measured on the chip
   by the builder's pairs with ``--trace 0`` and by the cost of tracing
   on (PERF.md section 6, the PR that added the family).
2. **Propagation rides the existing frames — invisibly when off.**
   ``CommandRequest`` / ``CommandBatchRequest`` carry ``trace`` as a
   regular field (PR 2); the cross-member hops added since ride
   *optional trailing* fields on ``ProxyRequest`` / ``ProxyResponse`` /
   ``AppendRequest`` / ``PublishRequest`` (protocol/messages.py): the
   field is OMITTED from the wire when ``None``, so with tracing off
   every frame is byte-identical to the pre-tracing plane (the golden
   differential in tests/test_trace_plane.py proves it). The client's
   flag IS the propagation switch: a traced client against untouched
   server configs still yields spans on every member the request
   crossed.
3. **Bounded storage.** Completed spans land in a per-process ring
   (``COPYCAT_TRACE_CAPACITY`` traces, oldest evicted; evicted ids are
   TOMBSTONED so a late remote span can never resurrect a partial
   trace); :meth:`Tracer.dump_slowest` renders the slowest N requests.

Usage::

    from copycat_tpu.utils import tracing

    tracing.enable()                  # or COPYCAT_TRACE=1 in the env
    ... drive requests ...
    print(tracing.TRACER.dump_slowest(5))

Span-name vocabulary (stable API, documented with the phase→histogram
mapping in docs/OBSERVABILITY.md):

- ``client.submit`` — client-side, submit flush -> responses correlated.
- ``ingress.queue`` — multi-group ingress: request receipt -> the
  routed sub-block's dispatch chain released it.
- ``proxy.hop`` — ingress -> owning group leader wire round trip (one
  span per attempt; failed attempts carry ``error=`` meta).
- ``group.append`` — owning leader: receipt -> log append staged.
- ``quorum.wait`` — append staged -> commit index covered the entry.
- ``group.fsync`` — the commit-boundary fsync that made it durable.
- ``apply`` — commit -> state-machine application / engine round done
  (the commit future resolved).
- ``respond`` — apply -> response object built.
- ``group.commit`` — coarse append->commit+apply span on the per-seq
  lanes (single command / general batch), where the commit index is
  not known at staging time.
- ``group.cached`` — exactly-once cache hit served without an append.
- ``follower.append`` — a follower ingesting the replication window
  that carried the traced entry (fsync included).
- ``event.push`` — session event delivery send -> ack.
- ``session.end`` — an unregister entry's apply: the session marked
  expired or closed -> every instance it owned closed (``instances``,
  ``vector`` of them in one staged block, ``rounds``, ``expired``).
- ``client.event`` — client-side receipt/dispatch of a traced publish.

Batch-scope spans (one per batch and stage of the served path's two
pumps, never per operation; ``parent`` in brackets). They are recorded
under an id the server mints per pump turn or read window
(:attr:`Tracer.batch`); the request-scope ``apply`` span that waited on
a batch carries ``batch=<id>`` and :func:`assemble_trace` lays the
batch's stages inside it. While the tracer is on, each is also a
``jax.profiler.TraceAnnotation`` of the same name, so any XLA profile
taken meanwhile shows them on the host lines beside the device ops.

- ``client.stage`` — first operation staged into the client's
  micro-batch -> its flush began.
- ``client.query`` — the read lane's ``client.submit``: query flush ->
  responses correlated (``n`` reads).
- ``client.resolve`` — responses correlated -> the batch's last future
  resolved.
- ``apply.classify`` [apply] — ``_apply_up_to`` began on the committed
  range -> its vector runs staged.
- ``apply.park`` [apply] — first run staged this turn -> the fused flush
  began (``forced=<why>`` when it did not wait for the turn's end).
- ``apply.marshal`` [apply] — ``dispatch_vector_rows`` entered ->
  ``engine.run_vector`` called (window barrier, rows into columns).
- ``apply.finalize`` [apply] — ``dispatch_vector_rows`` returned -> the
  flush's last run finalized (``rows``, ``groups``).
- ``engine.stage`` [apply | read.eval | engine.query] — ``step_round``
  entered -> the compiled step about to be called (host arrays, H2D).
- ``engine.wait`` — step called -> ``block_until_ready`` returned (the
  ``step_wall_ms`` interval).
- ``engine.fetch`` — the D2H fetch of the step's outputs (``bytes``).
- ``engine.harvest`` — after the fetch -> ``step_round`` returns, plus
  ``drive_vector``'s correlation pass.
- ``engine.query`` [read.eval] — ``drive_query_vector`` whole
  (``attempts``, ``width``).
- ``read.queue`` — the read window's first ``_stage_read`` ->
  ``_run_read_window`` began (``n`` reads, ``level``).
- ``read.gate`` — window began -> consistency gate passed.
- ``read.eval`` — ``_evaluate_reads`` whole (``device``/``per_op`` rows).
- ``read.drain`` [read.eval] — the forced fused flush at its head.
- ``read.finalize`` [read.eval] — ``run_query_vector`` returned -> the
  last read future resolved.

The bulk drive's spans (``models/bulk.py``; one per drive and stage,
never per operation, group or round). A drive mints its own id with the
root; every stage has ``parent="bulk.drive"`` and begins where the one
before it ended, so the root's ``self_ms`` is what no stage covers. A
straggler phase records its five stages again with ``phase=2``; a
classic drive (an engine without ``monotone_tag_accept``) records the
root and ``bulk.admit`` only.

- ``bulk.drive`` — ``BulkDriver.drive()`` entered -> its ``BulkResult``
  returned (``n``, ``rounds``, ``windows``, ``scan``).
- ``bulk.admit`` [bulk.drive] — ``drive()`` entered -> the plan begins
  (the arguments as arrays of the drive's length).
- ``bulk.plan`` [bulk.drive] — -> the accumulators about to be staged
  (starts, counts; ``segments``, and ``plan``: ``"sorted"`` where
  ``groups`` was not in group order and the drive sorted, ``"grouped"``
  where it was, ``"dense"`` where every group also sent the same count
  and no index per operation was built).
- ``bulk.stage`` [bulk.drive] — -> the program about to be called (the
  accumulators put, the stacked payload built and, in scan mode, each
  plane put as it is written; ``bytes`` put by ``_stage_acc``).
- ``bulk.dispatch`` [bulk.drive] — -> the scan's one call returned (over
  device arrays: the call alone), or the whole loop of windows in
  dispatch mode (``rounds``).
- ``bulk.wait`` [bulk.drive] — -> the accumulators ready (their copies
  to the host asked for at its head; a ``block_until_ready`` made only
  while the tracer is on).
- ``bulk.fetch`` [bulk.drive] — -> the first chip's block of the
  accumulators has arrived (the drive's one counted fetch; ``bytes``).
- ``bulk.harvest`` [bulk.drive] — -> every operation known resolved or
  not: the walk over the chips' blocks, each written from its own host
  copy while the later ones cross (``resolved``).
- ``bulk.return`` [bulk.drive] — -> ``_drive_deep`` returned (back to
  submission order where the drive sorted, the ``BulkResult`` built;
  ``host``: bytes of the arrays the drive took from its kept set or
  made, ``kept``: those of them that were there already).

``engine.staged_bytes`` (``RaftGroups._note_stage``) counts the bytes of
the host arrays such a drive hands the device, beside
``engine.fetch_bytes`` for what it takes back;
``engine.bulk_grouped_drives`` and ``engine.bulk_dense_drives`` count the
drives whose ``plan`` was not ``"sorted"``, and was ``"dense"``;
``engine.bulk_host_bytes`` and ``engine.bulk_kept_bytes`` the bytes of the
host arrays deep drives took by name and those that were kept from an
earlier drive (``models/bulk.py``, ``_KeptArrays``);
``engine.bulk_link_bytes`` every byte a deep drive hands the device or
fetches from it (``engine.staged_bytes`` and its ``_fetch_acc``s) and
``engine.bulk_early_bytes`` those of them whose transfer began ahead of
the stage that would otherwise start it (put by ``_stage_acc`` before the
program's call; read block by block from copies ``_ask_acc`` started at
dispatch).

:meth:`Tracer.report` is the whole-window account (docs/OBSERVABILITY.md
"The window report"): per-name aggregates that do not depend on what the
ring still holds, the window cut by innermost cover of the batch spans
(plus ``unspanned``), and the delta of every registered counter.

Every server-side span is tagged ``member=<address>`` and ``group=<id>``
so the cross-member assembly below can attribute phases. Spans store
``time.perf_counter()`` instants plus a per-process wall-clock anchor
(``wall`` in :meth:`Span.as_dict`): within one process alignment is
exact; across hosts it is as good as the hosts' clock sync, and the
assembly orders causally either way.
"""

from __future__ import annotations

import heapq
import itertools
import json
import sys
import time
import weakref
from collections import OrderedDict
from typing import Any, Iterable

from . import knobs

_ids = itertools.count(1)

#: perf_counter -> wall-clock anchor for this process: spans are
#: recorded on the monotonic clock (cheap, ordering-safe) and exported
#: with ``wall = start + _WALL_OFFSET`` so rings collected from
#: different processes can be laid on one timeline.
_WALL_OFFSET = time.time() - time.perf_counter()


#: the spans the report's timeline is cut from: one per batch and stage
#: (server pump turn, read window, engine round) or per client flush,
#: so their number is bounded by turns, not by operations
TIMELINE_SPANS = frozenset((
    "client.stage", "client.submit", "client.query", "client.resolve",
    "apply.classify", "apply.park", "apply.marshal", "apply.finalize",
    "engine.stage", "engine.wait", "engine.fetch", "engine.harvest",
    "engine.query", "read.queue", "read.gate", "read.eval", "read.drain",
    "read.finalize"))

#: cap on the intervals kept for the timeline (the ring's analogue): a
#: served cell records about 1,000 a second, so minutes fit; past it the
#: report says ``cut: true`` and the shares cover the kept part only
MAX_INTERVALS = 1 << 17


class Span:
    __slots__ = ("trace_id", "name", "start", "end", "meta", "parent")

    def __init__(self, trace_id: int, name: str, start: float, end: float,
                 meta: dict | None = None, parent: str | None = None) -> None:
        self.trace_id = trace_id
        self.name = name
        self.start = start
        self.end = end
        self.meta = meta
        #: name of the span that caused this one (batch-scope stages)
        self.parent = parent

    @property
    def duration_ms(self) -> float:
        return (self.end - self.start) * 1e3

    def as_dict(self) -> dict:
        d = {"trace": self.trace_id, "name": self.name,
             "start": round(self.start, 6),
             "wall": round(self.start + _WALL_OFFSET, 6),
             "duration_ms": round(self.duration_ms, 3)}
        if self.parent is not None:
            d["parent"] = self.parent
        if self.meta:
            d.update(self.meta)
        return d

    def __repr__(self) -> str:
        return (f"Span({self.name} trace={self.trace_id} "
                f"{self.duration_ms:.3f}ms)")


class OpenSpan:
    """A batch-scope span that has begun: its start instant and the
    profiler annotation opened with it. ``close`` records the span;
    ``then`` closes it and opens the next stage at the same instant
    (consecutive stages share one clock read per boundary)."""

    __slots__ = ("_tracer", "name", "trace_id", "parent", "start", "_note")

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 parent: str | None, start: float) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.parent = parent
        self.start = start
        self._note = _annotate(name)

    def close(self, **meta: Any) -> float:
        end = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(None, None, None)
        self._tracer.span(self.trace_id, self.name, self.start, end,
                          parent=self.parent, **meta)
        return end

    def drop(self) -> None:
        """End the annotation and record nothing."""
        if self._note is not None:
            self._note.__exit__(None, None, None)

    def then(self, name: str, **meta: Any) -> "OpenSpan":
        end = self.close(**meta)
        return OpenSpan(self._tracer, name, self.trace_id, self.parent, end)


def _annotate(name: str) -> Any:
    """An entered ``jax.profiler.TraceAnnotation`` (a complete event on
    the profiler's own clock when a profile is running, a few hundred
    nanoseconds otherwise), or ``None`` in a process that never loaded
    JAX: no profile can be running there, and tracing must not be what
    imports it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    note = jax.profiler.TraceAnnotation(name)
    note.__enter__()
    return note


class _BatchScope:
    """``with TRACER.scope(batch, parent):`` — the synchronous section
    of a pump turn or read window, inside which the engine's stages are
    recorded under ``batch`` with ``parent``; restores what was set
    before (a read window's forced drain nests a pump turn)."""

    __slots__ = ("_tracer", "_batch", "_parent", "_was")

    def __init__(self, tracer: "Tracer", batch: int, parent: str) -> None:
        self._tracer, self._batch, self._parent = tracer, batch, parent

    def __enter__(self) -> None:
        t = self._tracer
        self._was = (t.batch, t.batch_parent)
        t.batch, t.batch_parent = self._batch, self._parent

    def __exit__(self, *exc: Any) -> None:
        self._tracer.batch, self._tracer.batch_parent = self._was


class Tracer:
    """Ring-buffered span storage keyed by trace id.

    ``enabled`` is a plain attribute so the disabled check costs one
    LOAD_ATTR; every recording entry point re-checks nothing else.
    """

    #: hard cap on spans recorded per trace id: a request produces ~10
    #: across the cluster, so the cap only bites a peer replaying one id
    #: forever — without it that would grow a server-side list without
    #: bound (spans are recorded for ANY non-None wire id, even with
    #: local tracing off)
    MAX_SPANS_PER_TRACE = 64

    def __init__(self, capacity: int = 512) -> None:
        self.enabled = False
        self.capacity = capacity
        self._traces: "OrderedDict[int, list[Span]]" = OrderedDict()
        # Tombstones for recently-evicted ids: a late span (a straggler
        # ack, a replayed frame) for an evicted trace must be DROPPED,
        # not re-admitted — a resurrected entry holds a partial span
        # list that pollutes dump_slowest with nonsense totals. Bounded
        # at 2x capacity (older tombstones age out; by then the id is
        # process-ancient and a late span for it is noise either way).
        self._tombstones: "OrderedDict[int, None]" = OrderedDict()
        #: id and causing span of the batch whose synchronous section is
        #: running (set by the fused flush or the read window, read by
        #: the engine); ``None`` outside one
        self.batch: int | None = None
        self.batch_parent: str | None = None
        # whole-window account (:meth:`report`): per-name [n, total ms,
        # max ms], ms covered by direct children per parent name, the
        # timeline's intervals, and the registered counters' baselines
        self._agg: dict[str, list] = {}
        self._child_ms: dict[str, float] = {}
        self._intervals: list[tuple[float, float, str]] = []
        self._cut = False
        self._t_enabled: float | None = None
        self._t_disabled: float | None = None
        self._registries: list[list] = []   # [weakref, prefix, baseline]
        self._counter_delta: dict[str, int] = {}
        self._report: dict | None = None

    # -- switch ------------------------------------------------------------

    def enable(self) -> None:
        """Start (or restart) the window the report covers."""
        self.enabled = True
        self._reset_window()
        live = []
        for ref, prefix, _ in self._registries:
            registry = ref()
            if registry is not None:
                live.append([ref, prefix, registry.counter_values()])
        self._registries = live

    def disable(self) -> None:
        """Stop recording and freeze the window for :meth:`report`."""
        if self.enabled:
            self._t_disabled = time.perf_counter()
            self._counter_delta = self._read_counters()
            self._report = None
        self.enabled = False

    def register(self, registry: Any, prefix: str) -> None:
        """Tell the tracer of a ``MetricsRegistry`` whose counters the
        report should account for, under ``prefix`` (``engine.``,
        ``group.``, ``server.``, ``client.``, ``codec.``). A weak
        reference; nothing is read until :meth:`enable` (or now, if
        already enabled: the registry's counters so far are not the
        window's)."""
        self._registries.append([
            weakref.ref(registry), prefix,
            registry.counter_values() if self.enabled else {}])

    def _read_counters(self) -> dict[str, int]:
        """Every live registered registry's counters less its own
        baseline, summed by prefixed name (a registry collected inside
        the window takes its counts with it)."""
        out: dict[str, int] = {}
        for ref, prefix, base in self._registries:
            registry = ref()
            if registry is None:
                continue
            for name, value in registry.counter_values().items():
                key = prefix + name
                out[key] = out.get(key, 0) + value - base.get(name, 0)
        return out

    def _reset_window(self) -> None:
        self._agg.clear()
        self._child_ms.clear()
        self._intervals.clear()
        self._cut = False
        self._report = None
        self._t_enabled = time.perf_counter() if self.enabled else None
        self._t_disabled = None

    # -- recording ---------------------------------------------------------

    def new_trace(self) -> int:
        """A fresh trace id (call only when ``enabled`` — callers branch
        on the attribute first; ids are process-unique, not global)."""
        return next(_ids)

    def open_span(self, name: str, trace_id: int | None = None,
                  parent: str | None = None,
                  start: float | None = None) -> OpenSpan:
        """Begin a batch-scope span (call only when ``enabled``). Inside
        a batch's synchronous section the id and parent default to the
        batch's; outside one a fresh id is minted."""
        if trace_id is None:
            if self.batch is not None:
                trace_id = self.batch
                if parent is None:
                    parent = self.batch_parent
            else:
                trace_id = next(_ids)
        return OpenSpan(self, name, trace_id, parent,
                        time.perf_counter() if start is None else start)

    def scope(self, batch: int, parent: str) -> _BatchScope:
        return _BatchScope(self, batch, parent)

    def span(self, trace_id: int, name: str, start: float, end: float,
             parent: str | None = None, **meta: Any) -> None:
        """Record one completed span under ``trace_id``.

        Explicit timestamps fit the async call sites (the caller already
        holds t0 from before its awaits). Accepts any trace id —
        including one minted by a REMOTE client and carried in a frame —
        except ids evicted from this ring (tombstoned: late spans for
        them are dropped, never resurrected as partial traces).
        ``parent`` names the span that caused this one. The running
        aggregate counts every span, whatever the ring keeps.
        """
        if self._t_disabled is None:    # a frozen window takes no more
            ms = (end - start) * 1e3
            agg = self._agg.get(name)
            if agg is None:
                self._agg[name] = [1, ms, ms]
            else:
                agg[0] += 1
                agg[1] += ms
                if ms > agg[2]:
                    agg[2] = ms
            if parent is not None:
                self._child_ms[parent] = \
                    self._child_ms.get(parent, 0.0) + ms
            if name in TIMELINE_SPANS:
                if len(self._intervals) < MAX_INTERVALS:
                    self._intervals.append((start, end, name))
                else:
                    self._cut = True
        spans = self._traces.get(trace_id)
        if spans is None:
            if trace_id in self._tombstones:
                return
            if len(self._traces) >= self.capacity:
                evicted, _ = self._traces.popitem(last=False)
                self._tombstones[evicted] = None
                if len(self._tombstones) > 2 * self.capacity:
                    self._tombstones.popitem(last=False)
            spans = self._traces[trace_id] = []
        if len(spans) < self.MAX_SPANS_PER_TRACE:
            spans.append(Span(trace_id, name, start, end, meta or None,
                              parent))

    # -- reading -----------------------------------------------------------

    def traces(self) -> dict[int, list[Span]]:
        return dict(self._traces)

    def spans_for(self, trace_id: int, linked: bool = False) -> list[Span]:
        """The ring's spans for ``trace_id``; with ``linked`` also those
        of every batch one of them waited on (``batch=<id>`` meta)."""
        spans = list(self._traces.get(trace_id, ()))
        if linked:
            for batch in {s.meta["batch"] for s in spans
                          if s.meta and "batch" in s.meta}:
                spans += self._traces.get(batch, ())
        return spans

    def report(self) -> dict:
        """The whole-window account: ``spans`` (per name ``n``,
        ``total_ms``, ``mean_ms``, ``max_ms`` and ``self_ms`` = total
        less what direct children cover), ``timeline`` (the window cut
        by innermost cover of the batch spans, in % per name plus
        ``unspanned``; sums to 100), ``counters`` (registered counters'
        deltas over the window), ``window_s`` and ``cut``. Frozen by
        :meth:`disable`; while enabled it reads up to now."""
        if self._report is not None:
            return self._report
        frozen = not self.enabled and self._t_disabled is not None
        t1 = self._t_disabled if frozen else time.perf_counter()
        t0 = self._t_enabled
        if t0 is None:      # ``enabled`` was set by hand: first span on
            t0 = min((s for s, _, _ in self._intervals), default=t1)
        counters = self._counter_delta if frozen else self._read_counters()
        report = {
            "window_s": t1 - t0,
            "spans": {name: {"n": n, "total_ms": total,
                             "mean_ms": total / n, "max_ms": worst,
                             "self_ms": total - self._child_ms.get(name, 0.0)}
                      for name, (n, total, worst) in self._agg.items()},
            "timeline": _timeline_shares(self._intervals, t0, t1),
            "counters": dict(counters),
            "cut": self._cut,
        }
        if frozen:
            self._report = report
        return report

    def slowest(self, n: int = 10) -> list[tuple[int, float, list[Span]]]:
        """The slowest ``n`` traces as ``(trace_id, total_ms, spans)``,
        total = wall span from first start to last end."""
        scored = []
        for trace_id, spans in self._traces.items():
            total = (max(s.end for s in spans)
                     - min(s.start for s in spans)) * 1e3
            scored.append((trace_id, total, spans))
        scored.sort(key=lambda t: t[1], reverse=True)
        return scored[:n]

    def dump_slowest(self, n: int = 10, as_json: bool = False) -> str:
        slow = self.slowest(n)
        if as_json:
            return json.dumps([
                {"trace": trace_id, "total_ms": round(total, 3),
                 "spans": [s.as_dict() for s in spans],
                 **({"profile": prof} if (prof := self._profile_window(
                     spans)) else {})}
                for trace_id, total, spans in slow])
        lines = []
        for trace_id, total, spans in slow:
            lines.append(f"trace {trace_id}: {total:.3f} ms total")
            t0 = min(s.start for s in spans)
            for s in sorted(spans, key=lambda s: s.start):
                meta = (" " + " ".join(f"{k}={v}" for k, v in s.meta.items())
                        if s.meta else "")
                lines.append(f"  +{(s.start - t0) * 1e3:8.3f} ms "
                             f"{s.name:<16} {s.duration_ms:8.3f} ms{meta}")
            prof = self._profile_window(spans)
            if prof:
                top = prof["stacks"][0]
                lines.append(f"  profile: {prof['samples']} sample(s) in "
                             f"the window, hottest "
                             f"{top['stack'].rsplit(';', 1)[-1]} "
                             f"(x{top['count']})")
        return "\n".join(lines) if lines else "(no traces recorded)"

    @staticmethod
    def _profile_window(spans: list) -> dict | None:
        """The continuous profiler's top stacks over this trace's wall
        window (utils/profiler.py) — a slow trace names the code the
        process was ACTUALLY running while it was slow, not just its
        own spans. Empty/absent when the plane is off or no sample
        landed in the window."""
        from . import profiler  # lazy: tracing must not require the plane

        prof = profiler.PROFILER
        if prof is None:
            return None
        try:
            w0 = min(s.start for s in spans) + _WALL_OFFSET
            w1 = max(s.end for s in spans) + _WALL_OFFSET
            window = prof.window_top(w0, w1, top=3)
            return window if window["samples"] else None
        except Exception:  # noqa: BLE001 - never wound the dump
            return None

    def clear(self) -> None:
        self._traces.clear()
        self._tombstones.clear()
        self._reset_window()


def _timeline_shares(intervals: list, t0: float, t1: float) -> dict:
    """``[t0, t1]`` cut by innermost cover (the rule of
    :func:`_critical_path`: at every instant the open span that started
    last owns it), as a share in % per span name; instants no span
    covers go to ``unspanned``. One sweep over the sorted boundaries."""
    if t1 <= t0:
        return {"unspanned": 100.0}
    ivs = sorted((s, e, name) for s, e, name in intervals
                 if min(e, t1) > max(s, t0))
    points = sorted({t0, t1}
                    | {max(s, t0) for s, _, _ in ivs}
                    | {min(e, t1) for _, e, _ in ivs})
    held: dict[str, float] = {"unspanned": 0.0}
    live: list = []         # max-heap on start: (-start, end, name)
    i = 0
    for lo, hi in zip(points, points[1:]):
        while i < len(ivs) and ivs[i][0] <= lo:
            s, e, name = ivs[i]
            heapq.heappush(live, (-s, e, name))
            i += 1
        while live and live[0][1] <= lo:
            heapq.heappop(live)
        name = live[0][2] if live else "unspanned"
        held[name] = held.get(name, 0.0) + (hi - lo)
    return {name: 100.0 * secs / (t1 - t0) for name, secs in held.items()}


#: the per-process tracer every layer records into (client + server in
#: one process share it, so in-process tests see end-to-end traces; over
#: TCP each process keeps its own ring, correlated by trace id).
TRACER = Tracer(capacity=max(16, knobs.get_int("COPYCAT_TRACE_CAPACITY")))

if knobs.get_bool("COPYCAT_TRACE"):
    TRACER.enable()


def enable() -> None:
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def now() -> float:
    return time.perf_counter()


# ---------------------------------------------------------------------------
# Cross-member assembly: lay the spans collected from every member's
# ring (`/traces/<id>` on the stats listener, or the shared in-process
# ring filtered by the `member` tag) on one causal timeline, decide
# completeness, and extract the critical path.
# ---------------------------------------------------------------------------

#: span names that prove a group actually served a routed sub-request —
#: the completeness check looks for one of these after every dispatch
GROUP_PHASES = frozenset((
    "group.append", "group.commit", "group.cached", "quorum.wait",
    "apply", "respond"))


def _norm_span(raw: Any) -> dict:
    """One span as an assembly row: accepts a :class:`Span` or the
    ``as_dict``/JSON shape served by ``/traces/<id>``. ``member`` is
    left unset where the span carries none (the assembly decides)."""
    if isinstance(raw, Span):
        d = raw.as_dict()
    else:
        d = dict(raw)
    d.setdefault("wall", d.get("start", 0.0))
    return d


def assemble_trace(trace_id: int, spans_by_member: dict[str, Iterable],
                   failed_members: Iterable[str] = ()) -> dict:
    """Assemble one cross-member causal timeline.

    ``spans_by_member`` maps a member label to the spans fetched from
    that member's ring (Span objects or ``/traces/<id>`` dicts); members
    whose fetch FAILED go in ``failed_members`` — their absence marks
    the assembly ``incomplete`` rather than silently dropping it.

    Returns ``{trace, members, spans, e2e_ms, incomplete,
    incomplete_why, critical_path, critical_path_ms}`` — spans sorted by
    wall start with ``offset_ms`` relative to the earliest, the critical
    path as innermost-cover segments over the full wall interval (their
    durations sum to ``e2e_ms`` by construction), and completeness
    decided both structurally (a dispatched sub-block with no group-side
    phase landed) and operationally (an unreachable member).
    """
    seen: set = set()
    spans: list[dict] = []
    rows = [_norm_span(raw) for raw_spans in spans_by_member.values()
            for raw in raw_spans]
    # a request-scope span that waited on a batch (``batch=<id>``) pulls
    # that batch's stages onto its timeline, under its own member
    linked = {d["batch"]: d.get("member", "client") for d in rows
              if d.get("trace") in (None, trace_id) and "batch" in d}
    for d in rows:
        if d.get("trace") not in (None, trace_id):
            if d.get("trace") not in linked:
                continue
            d.setdefault("member", linked[d["trace"]])
        d.setdefault("member", "client")
        key = (d["member"], d["name"], round(d["wall"], 6),
               d.get("duration_ms"))
        if key in seen:  # in-process rings served by N listeners
            continue
        seen.add(key)
        spans.append(d)
    failed = sorted(set(failed_members))
    if not spans:
        return {"trace": trace_id, "members": [], "spans": [],
                "e2e_ms": 0.0, "incomplete": True,
                "incomplete_why": (["no spans landed"]
                                   + [f"member {m} unreachable"
                                      for m in failed]),
                "critical_path": [], "critical_path_ms": 0.0}
    spans.sort(key=lambda d: (d["wall"], -d.get("duration_ms", 0.0)))
    t0 = spans[0]["wall"]
    t1 = max(d["wall"] + d.get("duration_ms", 0.0) / 1e3 for d in spans)
    for d in spans:
        d["offset_ms"] = round((d["wall"] - t0) * 1e3, 3)

    why: list[str] = [f"member {m} unreachable" for m in failed]
    # structural completeness: every routed dispatch must be answered by
    # a group-side phase for the same group — a proxy hop (or a queued
    # sub-block) with no trace of the owning group's work is the
    # partition-in-flight signature
    served_groups = {d.get("group") for d in spans
                     if d["name"] in GROUP_PHASES}
    for d in spans:
        g = d.get("group")
        if d["name"] == "proxy.hop":
            if g in served_groups:
                continue  # a retry served it: an errored attempt alone
                # does not make the assembly incomplete
            if "error" in d:
                why.append(f"proxy hop to group {g} failed ({d['error']})")
            else:
                why.append(f"no group-side spans for proxied group {g}")
        elif d["name"] == "ingress.queue" and g not in served_groups:
            hops = [h for h in spans
                    if h["name"] == "proxy.hop" and h.get("group") == g]
            if not hops:
                why.append(f"sub-block for group {g} dispatched but "
                           f"never served")

    critical = _critical_path(spans, t0, t1)
    return {
        "trace": trace_id,
        "members": sorted({d["member"] for d in spans}),
        "spans": spans,
        "e2e_ms": round((t1 - t0) * 1e3, 3),
        "incomplete": bool(why),
        "incomplete_why": why,
        "critical_path": critical,
        "critical_path_ms": round(sum(c["duration_ms"] for c in critical),
                                  3),
    }


def _critical_path(spans: list[dict], t0: float, t1: float) -> list[dict]:
    """Innermost-cover decomposition of ``[t0, t1]``: at every instant
    the critical path charges the ACTIVE span that started last (the
    most specific phase — a ``quorum.wait`` inside a ``client.submit``
    wins the interval it covers); instants no span covers are charged to
    the most recent enclosing span, so the segment durations always sum
    to the end-to-end wall time."""
    if t1 <= t0:
        return []
    edges = sorted({t0, t1}
                   | {d["wall"] for d in spans}
                   | {d["wall"] + d.get("duration_ms", 0.0) / 1e3
                      for d in spans})
    edges = [e for e in edges if t0 <= e <= t1]
    segments: list[dict] = []
    last_owner: dict | None = None
    for lo, hi in zip(edges, edges[1:]):
        if hi - lo <= 0:
            continue
        mid = (lo + hi) / 2
        active = [d for d in spans
                  if d["wall"] <= mid
                  < d["wall"] + d.get("duration_ms", 0.0) / 1e3]
        owner = (max(active, key=lambda d: d["wall"]) if active
                 else last_owner)
        if owner is None:
            continue
        last_owner = owner
        if segments and segments[-1]["_owner"] is owner \
                and abs(segments[-1]["_end"] - lo) < 1e-9:
            segments[-1]["duration_ms"] += (hi - lo) * 1e3
            segments[-1]["_end"] = hi
            continue
        segments.append({"name": owner["name"],
                         "member": owner["member"],
                         "group": owner.get("group"),
                         "offset_ms": round((lo - t0) * 1e3, 3),
                         "duration_ms": (hi - lo) * 1e3,
                         "_owner": owner, "_end": hi})
    for seg in segments:
        seg["duration_ms"] = round(seg["duration_ms"], 3)
        del seg["_owner"], seg["_end"]
    return segments


def render_waterfall(assembly: dict) -> str:
    """The human rendering of one assembled trace: spans in causal
    order, one line each, critical-path phases starred; incomplete
    assemblies carry a loud banner (they are rendered, never dropped)."""
    lines = [f"trace {assembly['trace']}: {assembly['e2e_ms']:.3f} ms "
             f"end-to-end across {len(assembly['members'])} member(s) "
             f"({', '.join(assembly['members'])})"]
    if assembly["incomplete"]:
        lines.append("  !! INCOMPLETE ASSEMBLY: "
                     + "; ".join(assembly["incomplete_why"]))
    crit = {(c["name"], c["member"]) for c in assembly["critical_path"]}
    crit_ms = {}
    for c in assembly["critical_path"]:
        key = (c["name"], c["member"])
        crit_ms[key] = crit_ms.get(key, 0.0) + c["duration_ms"]
    for d in assembly["spans"]:
        key = (d["name"], d["member"])
        star = "*" if key in crit else " "
        g = f" g={d['group']}" if d.get("group") is not None else ""
        extra = (f"  [critical {crit_ms[key]:.3f} ms]"
                 if star == "*" else "")
        lines.append(
            f" {star} +{d['offset_ms']:9.3f} ms  {d['name']:<16} "
            f"{d.get('duration_ms', 0.0):9.3f} ms  "
            f"{d['member']}{g}{extra}")
    lines.append(f"  critical path: {assembly['critical_path_ms']:.3f} ms "
                 f"over {len(assembly['critical_path'])} segment(s)")
    return "\n".join(lines)
