"""Pipelined bulk data plane: client-visible throughput at device scale.

The queue-managed host runtime (``RaftGroups.submit``/``run_until``)
pays Python per op — deque staging, dict harvest, retry bookkeeping —
which caps client-visible throughput around ~10^5 ops/sec regardless of
device speed. This driver is the other end of the trade: a VECTORIZED
submit scheduler (numpy fancy-indexing end to end, zero per-op Python)
with DOUBLE-BUFFERED rounds — round N+1 is dispatched before round N's
outputs are fetched, so host staging/harvest overlaps device compute and
the device→host fetch (instead of one serialized submit→compute→fetch
cycle per round).

Safety vs the queue-managed path:

- SAFETY is unconditional: an op is resubmitted only if its slot was NOT
  accepted into a leader log (``out.accepted``); accepted ops are never
  re-sent, so double-apply is impossible under any fault.
- LIVENESS assumes fault-free delivery (the engine's own full-delivery
  default): an accepted entry lost to a leader change would never
  resolve and ``drive`` raises after ``max_rounds``. Clients running
  under nemesis/partitions belong on the queue-managed path, whose
  provable-loss retry handles exactly that (``raft_groups._harvest``).

Reference framing: the reference's client runtime pipelines sequenced
commands per session (Copycat client, SURVEY.md §2.3); this is the
batch-scale equivalent for the north-star metric (BASELINE.md: ≥1M
client-visible linearizable ops/sec).

Two dispatch modes, chosen by the engine's Config:

- CLASSIC (default engines): FIFO safety is host-enforced — a small
  synchronous ``accepted`` fetch per round gates the next window. One
  blocking device round-trip per round; correct under any engine.
- DEEP (``Config.monotone_tag_accept`` engines): FIFO + dedup are
  DEVICE-enforced by the monotone tag gate, so the host dispatches
  blindly with zero blocking fetches and collects results from
  on-device ``[G, B]`` accumulators in ONE fetch per drive
  (``ops/consensus.deep_step``): one blocking fetch per drive instead
  of one per round.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from functools import lru_cache, partial
from typing import Any

import jax
import numpy as np

from ..ops.consensus import Submits, deep_scan, deep_step
from ..utils.tracing import TRACER
from .raft_groups import RaftGroups, _pack_host


def _scatter(G: int, S: int, gi, slots, vals) -> np.ndarray:
    arr = np.zeros((G, S), np.int32)
    arr[gi, slots] = vals
    return arr


def stream_count_from_state(state, fetch=jax.device_get) -> np.ndarray:
    """[G] max live-ring stream tag per group, from the most-advanced
    lane's log — the device-authoritative value of the monotone stream
    cursor (``RaftGroups._stream_count``). Used to resync after an
    abandoned drive and to rebuild the cursor on checkpoint restore
    (election no-ops carry tag 0 and never inflate it). ``fetch``
    overrides the device→host transfer (multihost engines pass their
    local-block fetch so G is the process-local block)."""
    log_tag, last = (np.asarray(x) for x in fetch(
        (state.log_tag, state.last_index)))
    G, _, L = log_tag.shape
    lane = last.argmax(axis=1)                       # [G]
    lt = log_tag[np.arange(G), lane]                 # [G,L]
    ll = last[np.arange(G), lane]                    # [G]
    j = np.arange(L)[None, :]
    idx = ll[:, None] - ((ll[:, None] - (j + 1)) % L)
    in_log = (idx >= 1) & (idx <= ll[:, None])
    return np.where(in_log, lt, 0).max(axis=1).astype(np.int64)


def _window_rank(mask: np.ndarray, starts: np.ndarray, counts: np.ndarray,
                 S: int) -> tuple[np.ndarray, np.ndarray]:
    """First <=S True positions per segment, vectorized.

    ``mask`` lives in group-sorted space with segments described by
    ``starts``/``counts``; returns ``(positions, slots)`` where each
    position's slot is its rank among its segment's True entries. The
    per-pass scheduling core shared by the classic drive and the query
    drive (FIFO by construction: earlier pending ops always outrank
    later ones)."""
    mi = mask.astype(np.int64)
    excl = np.cumsum(mi) - mi
    base = np.repeat(excl[starts], counts)
    rank = excl - base
    sel = mask & (rank < S)
    pos = np.flatnonzero(sel)
    return pos, rank[pos]


def _named(fn, **static):
    """``partial(fn, **static)`` under ``fn``'s own name, so that JAX
    names the jitted module after it (``jit_deep_scan``, ``jit_deep_step``)
    and a trace tells the two deep programs apart; a bare partial is
    ``jit__unknown``. No Python frame of its own over the step."""
    bound = partial(fn, **static)
    bound.__name__ = fn.__name__
    return bound


@lru_cache(maxsize=None)
def _deep_scan_program(config, onehot: bool = False, donate: bool = False):
    """Jitted :func:`deep_scan` (whole blind phase as one program; W
    specializes by shape). Donation hands the state + accumulators back
    for in-place reuse on accelerators."""
    return jax.jit(_named(deep_scan, config=config, onehot=onehot),
                   donate_argnums=(0, 1, 2, 3, 4) if donate else ())


@lru_cache(maxsize=None)
def _deep_program(config, onehot: bool = False, donate: bool = False):
    """Jitted deep_step shared across drivers with the same static Config.

    ``onehot`` selects the accumulator formulation: sharded engines use
    the one-hot select-reduce (shard-local by construction — the .at[]
    scatter compiled to all-gathers of the [G,B] buffers on a mesh);
    single-device engines keep the O(G*A) scatter (measured faster on
    CPU; scatter never pays a collective off-mesh). ``donate`` hands
    state + accumulators back to XLA for in-place reuse — on for
    accelerators (saves a full state copy per round), off for CPU
    (donation is unimplemented there and only warns)."""
    return jax.jit(_named(deep_step, config=config, onehot=onehot),
                   donate_argnums=(0, 1, 2, 3, 4) if donate else ())


#: what ``resolve_round`` reads where no result came back: the seed of the
#: deep drive's round accumulator, far above any round of a drive.
#: ``tests/benchmark/test_benchmark_bulk_plane.py`` reads the mark from this
#: module's text, which it knows as the statement
#: ``rndbuf = rg._stage_acc(np.full((G, Bpad), 2**30, np.int32))``
#: (the seed is a kept array now): keep that line and this value in step.
_UNRESOLVED = 2**30


class _KeptArrays:
    """The deep drive's host arrays of an operation's or a group's size,
    kept from drive to drive.

    A drive of 12.8M operations uses 1.1 GB of them, each above the 32 MB
    up to which glibc recycles a freed block: allocated a drive, each is
    mapped afresh and faulted in page by page again, which was most of a
    drive (PERF.md §5). So the driver owns one set. An array is taken by
    ``name``; the one kept under it is handed back while its shape, dtype
    and ``tag`` (what its constant contents depend on) are what is asked
    for, and replaced otherwise; a name a drive did not take is released
    at its end, and a drive that raises releases everything. What changes
    from drive to drive is overwritten by the drive, in full over the
    region the last one wrote; what does not is written once, by ``fill``.

    ``RaftGroups.metrics`` ``bulk_host_bytes`` counts every array taken and
    ``bulk_kept_bytes`` those that were there already.
    """

    __slots__ = ("_rg", "_arrays", "_taken", "_handed")

    def __init__(self, rg) -> None:
        self._rg = rg
        self._arrays: dict[Any, tuple[np.ndarray, Any]] = {}
        self._taken: set = set()
        #: the arrays of the last two ``BulkResult``s, oldest first
        self._handed: list[tuple[np.ndarray, ...]] = []

    def take(self, name, shape: tuple, dtype, fill=None,
             tag=None) -> np.ndarray:
        """The array kept under ``name``. A new one is uninitialised
        (``fill=None``), filled with the scalar ``fill``, or zeroed and
        passed to the callable ``fill``: constant contents, never written
        again while ``tag`` stays."""
        self._taken.add(name)
        arr, was = self._arrays.get(name, (None, None))
        if (arr is not None and arr.shape == shape and arr.dtype == dtype
                and was == tag):
            self._rg._m_bulk_kept.inc(arr.nbytes)
        else:
            if fill is None:
                arr = np.empty(shape, dtype)
            elif callable(fill):
                arr = np.zeros(shape, dtype)
                fill(arr)
            else:
                arr = (np.full(shape, fill, dtype) if fill
                       else np.zeros(shape, dtype))
            self._arrays[name] = arr, tag
        self._rg._m_bulk_host.inc(arr.nbytes)
        return arr

    def handout(self, n: int, count: int) -> tuple[np.ndarray, ...]:
        """``count`` uninitialised ``int64[n]`` arrays for a ``BulkResult``:
        those of one of the last two results if nothing outside this
        object refers to any of them any more (a view keeps its base
        alive, so the base's reference count speaks for its views too),
        else fresh ones, remembered in place of the older of the two. A
        caller that holds its last result while the next drive runs, as a
        loop does, alternates two sets. (Whatever else holds a reference
        counts as a holder, a sampling profiler's grip on the caller's
        frame for its milliseconds too: the drive then makes its own.)"""
        handed = self._handed
        if handed and (len(handed[0]) != count or handed[0][0].size != n):
            handed.clear()
        self._rg._m_bulk_host.inc(8 * n * count)
        for i in range(len(handed)):
            # (this object's tuple, the loop's ``a`` and the call's
            # argument: three references to an array no one else holds)
            if all(sys.getrefcount(a) == 3 for a in handed[i]):
                self._rg._m_bulk_kept.inc(8 * n * count)
                handed.append(handed.pop(i))
                return handed[-1]
        handed.append(tuple(np.empty(n, np.int64) for _ in range(count)))
        del handed[:-2]
        return handed[-1]

    def sweep(self) -> None:
        """A drive's end: release what it did not take."""
        for name in self._arrays.keys() - self._taken:
            del self._arrays[name]
        self._taken.clear()

    def release(self) -> None:
        self._arrays.clear()
        self._taken.clear()
        self._handed.clear()


class BulkResult:
    """Results + client-observed latency percentiles for one drive.

    ``results``, ``dispatch_round`` and ``resolve_round`` are ``int64``
    arrays, one entry an operation in submission order. A caller that
    holds the result, one of its arrays or a view of one owns them for
    good: no later drive writes them. Arrays a caller has let go of may be
    handed out again by a later drive of the same driver. A deep drive
    whose groups each sent the same count hands out a ``dispatch_round``
    that is read-only and shared with the other results of that shape (it
    is the same for all of them).
    """

    __slots__ = ("results", "rounds", "wall_s", "dispatch_round",
                 "resolve_round")

    def __init__(self, results, rounds, wall_s, dispatch_round,
                 resolve_round) -> None:
        self.results = results
        self.rounds = rounds
        self.wall_s = wall_s
        self.dispatch_round = dispatch_round
        self.resolve_round = resolve_round

    def latency_rounds(self) -> np.ndarray:
        """Per-op submit→result latency in driver rounds (client view)."""
        return self.resolve_round - self.dispatch_round + 1

    def latency_percentiles_ms(self, qs=(50, 99)) -> dict:
        lat = self.latency_rounds().astype(np.float64)
        ms_per_round = self.wall_s * 1e3 / max(1, self.rounds)
        return {f"p{q}": float(np.percentile(lat, q)) * ms_per_round
                for q in qs}


class BulkDriver:
    """Vectorized pipelined driver over one :class:`RaftGroups` batch."""

    def __init__(self, rg, *, allow_sessions: bool = False,
                 deep_scan: bool = False) -> None:
        # The CLASSIC drive feeds host numpy straight into the step and
        # fetches whole outputs, bypassing the multihost staging/lockstep
        # hooks step_round routes through — single-host engines only.
        # The DEEP drive (monotone-tag engines) goes through the
        # _stage_acc/_fetch_acc/_deep_fn/_stage_submits hooks and agrees
        # on every stop decision, so it runs on multihost engines too.
        deep = bool(getattr(rg.config, "monotone_tag_accept", False))
        if not deep and (
                getattr(rg, "process_count", 1) > 1
                or type(rg)._stage_submits is not RaftGroups._stage_submits
                or type(rg)._fetch_outputs is not RaftGroups._fetch_outputs):
            raise NotImplementedError(
                "the classic bulk drive needs a single-host RaftGroups; "
                "multihost engines use the queue-managed lockstep path or "
                "the deep drive (Config(monotone_tag_accept=True))")
        # Device-session engines need the session tick + cleanup routing
        # the raw bulk loop never performs — the sessioned client
        # (models/session_client.BulkSessionClient) takes that duty and
        # opts in; refuse otherwise rather than silently expire sessions.
        if rg._sessions is not None and not allow_sessions:
            raise NotImplementedError(
                "BulkDriver does not pump device sessions; drive session "
                "engines through models.session_client.BulkSessionClient")
        # deep_scan: run the whole blind phase as ONE lax.scan program
        # (one dispatch + one stacked payload upload per drive) instead
        # of one dispatch per window. Single-host only: the stacked
        # staging is not wired through the multihost hooks.
        if deep_scan and (not deep or getattr(rg, "process_count", 1) > 1):
            raise NotImplementedError(
                "deep_scan needs a single-host monotone-tag engine")
        self._scan = deep_scan
        self._rg = rg
        self._kept = _KeptArrays(rg)

    def drive(self, groups, opcode, a=0, b=0, c=0,
              max_rounds: int = 10_000,
              deliver_schedule=None) -> BulkResult:
        """Commit one op per entry of ``groups`` (scalars broadcast) and
        return all results; ops of one group keep submission order.

        Scheduling rule (FIFO-safe by construction): each round every
        group dispatches its first ≤S not-yet-ACCEPTED ops in op order —
        an op the engine rejected (backpressure, lease-refusal) is
        re-sent before any later op of its group is ever dispatched.
        The tiny per-round ``accepted`` array is fetched synchronously
        to drive that rule; the large result arrays are harvested one
        round behind (double buffer), so host staging and the bulk of
        the D2H transfer overlap device compute.

        The deep drive (monotone-tag engines) plans from what one pass
        over ``groups`` finds, in three tiers, each a special case of the
        one before and equal to it in everything it returns and leaves:

        - ``sorted``: ``groups`` in any order. A stable argsort by group,
          sorted copies of the payload, per-operation index arrays for
          the payload and the harvest, an unsort at return.
        - ``grouped``: ``groups`` non-decreasing. The submission is its
          own sorted form: no argsort, no copies, and ``results``,
          ``dispatch_round`` and ``resolve_round`` are returned as the
          drive filled them, without an unsort.
        - ``dense``: grouped, and every group present sends the same
          count. No index array an operation either: the payload is
          slices of the operations reshaped ``[groups, count]``, the
          harvest slices of the accumulators.

        Nothing selects a tier but the submission's own order and counts
        (``RaftGroups.metrics`` ``bulk_grouped_drives`` and
        ``bulk_dense_drives`` count them, the ``bulk.plan`` span names
        the ``plan``); on a multihost engine each process reads its own.

        The deep drive keeps its host arrays for the next drive
        (:class:`_KeptArrays`): a burst of the shape of the last one
        allocates nothing of an operation's or a group's size. What the
        caller may rely on is :class:`BulkResult`'s: the arrays of a
        result it holds are never written again; those it let go of may be
        a later result's. The arguments are read during the call and never
        written or kept.
        """
        rg = self._rg
        S = rg.submit_slots
        t0 = time.perf_counter()
        # one span a drive and stage (utils/tracing.py "bulk.*"), all
        # under the id the root mints; off, no object and no clock read
        root = stage = None
        if TRACER.enabled:
            root = TRACER.open_span("bulk.drive", start=t0)
            stage = TRACER.open_span("bulk.admit", root.trace_id,
                                     "bulk.drive", start=t0)

        g_arr = np.asarray(groups, np.int64).ravel()
        n = g_arr.size
        if getattr(rg.config, "monotone_tag_accept", False):
            if stage is not None:
                host, kept = rg._m_bulk_host.value, rg._m_bulk_kept.value
            try:
                res, stage, windows = self._drive_deep(
                    g_arr, (opcode, a, b, c), max_rounds, t0,
                    deliver_schedule, stage)
            except BaseException:
                # transfers of the kept arrays may be in flight still, and
                # a constant may be half written: the next drive sizes anew
                self._kept.release()
                raise
            # nothing of the deep drive's is freed as it returns: the last
            # stage says what it took, and what of that was there already
            if stage is not None:
                stage.close(host=rg._m_bulk_host.value - host,
                            kept=rg._m_bulk_kept.value - kept)
                root.close(n=n, rounds=res.rounds, windows=windows,
                           scan=self._scan)
            return res
        bc = lambda x: np.broadcast_to(
            np.asarray(x, np.int32).ravel(), (n,)).copy()
        op_a, a_a, b_a, c_a = bc(opcode), bc(a), bc(b), bc(c)
        if stage is not None:   # the classic drive: the root and this
            stage.close()
        if deliver_schedule is not None:
            raise NotImplementedError(
                "deliver_schedule is a deep-drive feature (fault "
                "injection with mid-drive recovery); classic engines "
                "take faults through rg.deliver + step_round")

        # fixed group-stable order + segment starts for per-round ranking
        order = np.argsort(g_arr, kind="stable")
        g_sorted = g_arr[order]
        first = np.ones(n, bool)
        first[1:] = g_sorted[1:] != g_sorted[:-1]
        starts = np.flatnonzero(first)
        counts = np.diff(np.append(starts, n))

        # tags are a RESERVED contiguous block off the engine's counter,
        # so bulk tags can never collide with queue-path tags or an
        # earlier drive's re-reported entries
        tag0 = rg._next_tag
        rg._next_tag += n
        results = np.zeros(n, np.int64)
        resolved = np.zeros(n, bool)
        accepted_ops = np.zeros(n, bool)
        dispatched = np.zeros(n, bool)
        dispatch_round = np.zeros(n, np.int64)
        resolve_round = np.zeros(n, np.int64)

        def build(r: int):
            """First ≤S unaccepted ops per group, in op order."""
            pos, slots = _window_rank(~accepted_ops[order], starts,
                                      counts, S)
            idx = order[pos]
            sub = rg._empty_submits()
            gi = g_arr[idx]
            sub.opcode[gi, slots] = op_a[idx]
            sub.a[gi, slots] = a_a[idx]
            sub.b[gi, slots] = b_a[idx]
            sub.c[gi, slots] = c_a[idx]
            sub.tag[gi, slots] = (tag0 + idx).astype(np.int32)
            sub.valid[gi, slots] = True
            fresh = ~dispatched[idx]
            dispatch_round[idx[fresh]] = r
            dispatched[idx] = True
            return sub, idx, gi, slots

        def harvest(r: int, raw) -> None:
            raw = rg._fetch_outputs(raw)
            if rg.telemetry is not None and raw.telemetry is not None:
                rg.telemetry.ingest(raw.telemetry, rg.rounds + r)
            ov = raw.out_valid
            if ov.any():
                tags = raw.out_tag[ov]
                vals = raw.out_result[ov]
                keep = (tags >= tag0) & (tags < tag0 + n)
                t = tags[keep] - tag0
                results[t] = vals[keep]
                newly = ~resolved[t]
                resolve_round[t[newly]] = r
                resolved[t] = True
                # entries reported once: a queue-managed op that applied
                # during this drive must resolve into rg.results, not
                # vanish behind the bulk tag filter
                for tg, vl in zip(tags[~keep].tolist(),
                                  vals[~keep].tolist()):
                    if tg in rg._inflight:
                        rg._inflight.pop(tg)
                        rg._inflight_ops.pop(tg, None)
                        placed = rg._tag_index.pop(tg, None)
                        if placed is not None:
                            rg._drop_placement(placed[0], placed[1])
                        rg.results[tg] = vl
            # session events drained by these rounds must reach the host
            # buffer (the device pops its ring as it drains)
            rg._ingest_events(raw)

        deliver = rg.deliver
        inflight: list[tuple[int, Any]] = []
        r = 0
        while not resolved.all():
            if r > max_rounds:
                missing = int(n - resolved.sum())
                raise TimeoutError(
                    f"bulk drive: {missing} ops unresolved after "
                    f"{max_rounds} rounds (fault-free liveness assumption"
                    f" violated? use the queue-managed path under faults)")
            sub, idx, gi, slots = build(r)
            rg.state, rg._key, raw = rg._step(
                rg.state, rg._stage_round(sub), deliver, rg._key)
            # small synchronous fetch (the bool slab): acceptance gates
            # the NEXT round's dispatch window (FIFO safety)
            if idx.size:
                acc = dataclasses.replace(
                    raw, bools=np.asarray(raw.bools)).accepted
                accepted_ops[idx[acc[gi, slots]]] = True
            # big outputs: one round behind (double buffer)
            inflight.append((r, raw))
            if len(inflight) > 1:
                pr, praw = inflight.pop(0)
                harvest(pr, praw)
            r += 1
            if resolved.all():
                break
            # drain the pipe when nothing is left to dispatch so the
            # last round's results are seen without an extra device step
            if accepted_ops.all() and inflight:
                pr, praw = inflight.pop(0)
                harvest(pr, praw)
        while inflight:
            pr, praw = inflight.pop(0)
            harvest(pr, praw)
        if not resolved.all():  # pragma: no cover - defensive
            missing = int(n - resolved.sum())
            raise TimeoutError(f"bulk drive: {missing} ops unresolved")
        rg.rounds += r
        rg.metrics.counter("ops_committed").inc(n)
        res = BulkResult(results=results, rounds=r,
                         wall_s=time.perf_counter() - t0,
                         dispatch_round=dispatch_round,
                         resolve_round=resolve_round)
        if root is not None:
            root.close(n=n, rounds=r, scan=False)
        return res


    def drive_queries(self, groups, opcode, a=0, b=0, c=0,
                      consistency: str = "sequential",
                      max_rounds: int = 200) -> np.ndarray:
        """Serve one READ per entry of ``groups`` through the query lane
        (no log append — ops/consensus.query_step) and return results
        aligned with the input.

        ``consistency``: ``"sequential"``/``"causal"``/``"process"`` read
        the leader's applied state; ``"atomic"`` additionally gates each
        slot on the leader LEASE (BOUNDED_LINEARIZABLE — reference
        Consistency.java:157-176) so the read is linearizable without a
        quorum round. Unserved slots (leaderless group, fresh leader,
        applied < commit, cold lease) retry after stepping a settle
        round. Works on BOTH classic and monotone engines: queries never
        append, so the tag gate is irrelevant.

        Throughput shape: each pass evaluates up to S reads per group in
        ONE jitted call over all groups — B reads/group cost ceil(B/S)
        query calls (plus settle rounds only when slots go unserved).
        """
        rg = self._rg
        if getattr(rg, "process_count", 1) > 1:
            raise NotImplementedError(
                "drive_queries is single-host; multihost engines serve "
                "reads through the lockstep query lane (serve_query / "
                "submit_query)")
        from ..ops.apply import QUERY_OPCODES

        g_arr = np.asarray(groups, np.int64).ravel()
        n = g_arr.size
        if n == 0:
            return np.zeros(0, np.int64)
        bc = lambda x: np.broadcast_to(
            np.asarray(x, np.int32).ravel(), (n,)).copy()
        op_a, a_a, b_a, c_a = bc(opcode), bc(a), bc(b), bc(c)
        bad = set(np.unique(op_a).tolist()) - QUERY_OPCODES
        if bad:
            raise ValueError(
                f"opcodes {sorted(bad)} are not read-only; drive them "
                "as commands")
        levels = ("causal", "process", "sequential", "atomic")
        if consistency not in levels:
            raise ValueError(f"consistency {consistency!r}: one of {levels}")

        S = rg.submit_slots
        G = rg.num_groups
        order = np.argsort(g_arr, kind="stable")
        g_s = g_arr[order]
        op_s, a_s, b_s, c_s = (x[order] for x in (op_a, a_a, b_a, c_a))
        firsts = np.ones(n, bool)
        firsts[1:] = g_s[1:] != g_s[:-1]
        starts = np.flatnonzero(firsts)
        counts = np.diff(np.append(starts, n))

        results = np.zeros(n, np.int64)
        done = np.zeros(n, bool)
        want_atomic = consistency == "atomic"
        rounds = 0
        while not done.all():
            if rounds > max_rounds:
                raise TimeoutError(
                    f"bulk queries: {int(n - done.sum())} unserved after "
                    f"{max_rounds} passes")
            # Queries never mutate state, so EVERY pending window can be
            # dispatched back-to-back against the same state and fetched
            # in ONE device_get — one blocking fetch for the whole burst,
            # not one per window.
            windows = []
            shadow = done.copy()
            while not shadow.all():
                pos, slots = _window_rank(~shadow, starts, counts, S)
                gi = g_s[pos]
                sub = rg._empty_submits()
                sub.opcode[gi, slots] = op_s[pos]
                sub.a[gi, slots] = a_s[pos]
                sub.b[gi, slots] = b_s[pos]
                sub.c[gi, slots] = c_s[pos]
                sub.valid[gi, slots] = True
                atomic = np.zeros((G, S), bool)
                if want_atomic:
                    atomic[gi, slots] = True
                raw = rg._query(rg.state, _pack_host((*sub, atomic)))
                windows.append((pos, gi, slots, raw))
                shadow[pos] = True
                rounds += 1
            fetched = jax.device_get([raw for *_, raw in windows])
            any_miss = False
            for (pos, gi, slots, _), slab in zip(windows, fetched):
                # one slab a window: results beside served, S columns each
                res, served = slab[:, :S], slab[:, S:]
                hit = served[gi, slots] != 0
                results[pos[hit]] = res[gi[hit], slots[hit]]
                done[pos[hit]] = True
                any_miss |= not hit.all()
            if any_miss and not done.all():
                # only pay a consensus step when a slot went UNSERVED
                # (cold lease / fresh leader / apply lag)
                rg.step_round()
                rounds += 1

        out = np.zeros(n, np.int64)
        out[order] = results
        return out

    def recover(self, settle_rounds: int = 30,
                max_rounds: int = 500) -> None:
        """Re-arm the deep plane after an abandoned drive (TimeoutError).

        Call AFTER healing faults. Two hazards bracket the tag cursor:

        - too LOW: an entry replicated to a minority lineage can still
          commit (its leader re-wins) — reusing its tag would alias a
          fresh op's accumulator slot (mis-correlated results);
        - too HIGH: an isolated leader may have ACCEPTED a burst into
          its ring (acceptance is lane-local) that a post-heal election
          ERASES by rewind — the abandon-time conservative resync
          (max of host/device views) then leaves the cursor pointing
          past a ring that reverted, and every later drive is
          gate-rejected forever (found by the round-5 abandoned-flush
          test).

        So: settle, then wait until every group's lanes CONVERGE (same
        last/applied index, a leader present — no surviving divergent
        lineage), then trust the device outright (plain assignment).
        On dynamic-membership engines removed lanes never converge, so
        the check is skipped and the conservative max-resync kept — a
        churned group that hit the too-high hazard needs its membership
        restored before recovery (documented limitation; the deep plane
        runs static membership in-tree).
        """
        rg = self._rg
        for _ in range(settle_rounds):
            rg.step_round()
        if rg.config.dynamic_membership:
            self._resync_stream_count()
            return
        # Convergence polls are lockstep-agreed (step_round is a
        # collective program on multihost engines — a process-local
        # break would deadlock peers) and spaced POLL_EVERY rounds apart
        # so the host pays one blocking fetch per few rounds, not per
        # round.
        POLL_EVERY = 4
        for attempt in range(max_rounds):
            last, applied, role = (np.asarray(x) for x in rg._fetch_acc(
                (rg.state.last_index, rg.state.applied_index,
                 rg.state.role)))
            mine = bool((last.min(1) == last.max(1)).all()
                        and (applied.min(1) == applied.max(1)).all()
                        and ((role == 2).sum(1) >= 1).all())
            if rg._agree(mine):
                break
            for _ in range(POLL_EVERY):
                rg.step_round()
        else:
            raise TimeoutError(
                "recover: cluster did not converge — heal every fault "
                "before calling recover()")
        rg._stream_count = stream_count_from_state(rg.state,
                                                   fetch=rg._fetch_acc)

    def _resync_stream_count(self) -> None:
        """Set each group's stream cursor to the max live-ring tag on the
        most-advanced lane — every tag at or below it was consumed by the
        device, so the next drive's dense stream starts just past it.
        Exact in the deep plane's fault-free world; an error path only
        (one [G,P,L] fetch)."""
        rg = self._rg
        rg._stream_count = np.maximum(
            rg._stream_count,
            stream_count_from_state(rg.state, fetch=rg._fetch_acc))

    def _drive_deep(self, g_arr, leaves, max_rounds: int, t0: float,
                    deliver_schedule=None, stage=None) -> tuple:
        """Zero-sync pipelined drive for monotone-tag engines.

        The classic drive pays one BLOCKING ``accepted`` fetch per round
        to keep dispatch FIFO-safe, which serializes host and device
        every round. With device-enforced FIFO + dedup
        (``Config.monotone_tag_accept``) blind dispatch is safe, so:

        - phase 1 dispatches every op exactly once, S per group per
          round, back-to-back with NO device fetch (async dispatch keeps
          the device ~W rounds deep in useful work), then fetches ALL
          round outputs in one ``jax.device_get`` — every transfer is in
          flight concurrently, so the drive blocks on the device once;
        - phase 2 (rare: lease-refusal at a cold leader, backpressure)
          re-dispatches each group's unresolved SUFFIX — resolution is a
          per-group prefix by construction (the gate makes acceptance a
          prefix, applies report in log order), and re-sending an
          already-accepted op is rejected on device, never re-applied.

        Liveness matches the classic bulk plane (fault-free delivery);
        safety is the gate's and holds under any fault.

        Every array of an operation's or a group's size that phase 1
        names is taken from the driver's :class:`_KeptArrays` (``leaves``,
        the payload as the caller passed it, included where it has to be
        converted); a straggler pass allocates its own.

        The transfers run beside the host's passes. Out: an array starts
        across the link the moment its bytes exist (``_stage_acc``: the
        seeds first, then in scan mode each stacked plane as it is
        written), and the runtime reads the host array after the put has
        returned, so a kept array is not written again before the program
        that takes its copy has run: the next drive's writes come after
        this drive's harvest, which has waited for that program's outputs.
        Back: the accumulators' copies are asked for with the last call
        (``_ask_acc``) and harvested a chip's block at a time, each from
        the runtime's own host copy; nothing is assembled.

        ``stage`` is the drive's open span while the tracer is on
        (``bulk.admit``): every stage from here on closes into the next,
        and a straggler phase records its stages again with ``phase=2``.
        Returns the result, the stage left open (``bulk.return``: the
        caller closes it) and the blind phase's windows.
        """
        rg = self._rg
        kept = self._kept
        S = rg.submit_slots
        G = rg.num_groups
        n = g_arr.size
        multi = getattr(rg, "process_count", 1) > 1

        def admitted(i, x):
            """Payload leaf ``i`` as ``int32[n]``: the caller's own array
            where it is one (read, never written), else a kept one."""
            x = x if isinstance(x, np.ndarray) else np.asarray(x, np.int32)
            x = x.ravel()
            if x.dtype == np.int32 and x.size == n:
                return x
            out = kept.take(("leaf", i), (n,), np.int32)
            np.copyto(out, x, casting="unsafe")
            return out

        # (a leaf broadcast from one value is uniform by construction)
        scalar = tuple(np.size(x) == 1 for x in leaves)
        vals = tuple(admitted(i, x) for i, x in enumerate(leaves))
        if stage is not None:
            stage = stage.then("bulk.plan")

        # What the submission's own order and counts allow, read in one
        # pass each (see drive()): "sorted" pays for a permutation,
        # "grouped" plans over the admitted arrays as they lie, "dense"
        # (grouped, every segment B long) also needs no index per element.
        # (one scratch mask: the order, the segments' firsts, uniformity)
        mask = kept.take("mask", (n,), bool)
        order = None
        if not np.greater_equal(g_arr[1:], g_arr[:-1], out=mask[1:]).all():
            order = np.argsort(g_arr, kind="stable")
            g_s = np.take(g_arr, order, mode="clip",
                          out=kept.take("sorted.groups", (n,), np.int64))
            vals = tuple(
                np.take(x, order, mode="clip",
                        out=kept.take(("sorted.leaf", i), (n,), np.int32))
                for i, x in enumerate(vals))
        else:
            g_s = g_arr
        mask[:1] = True
        np.not_equal(g_s[1:], g_s[:-1], out=mask[1:])
        starts = np.flatnonzero(mask)
        edges = np.append(starts, n)
        counts = np.diff(edges)
        seg_groups = g_s[starts]
        nseg = starts.size
        dense = bool(order is None and n and counts.min() == counts.max())
        plan = ("sorted" if order is not None
                else "dense" if dense else "grouped")
        if order is None:
            rg._m_bulk_grouped.inc()
            if dense:
                rg._m_bulk_dense.inc()
        seg_base = rg._stream_count[seg_groups]            # [nseg]
        # tag-space check on an AGREED value: a per-process-local raise
        # before the collectives below would leave peer processes hung
        # in their allgather — every process must see the same verdict
        tag_end = rg._global_max_int(
            int((seg_base + counts).max(initial=0)) if n else 0)
        if tag_end > np.iinfo(np.int32).max:
            raise OverflowError(
                "per-group stream exceeds int32 tag space")

        # On-device result accumulators, fetched ONCE per drive: [G, B]
        # keyed by stream rank (ops/consensus.deep_step). B pads to a
        # power of two so repeated drives reuse the compiled program.
        # B is agreed ACROSS processes (multihost engines launch one
        # collective program, so every process must size — and compile —
        # identical buffers; a process with fewer local ops dispatches
        # empty windows for the surplus rounds).
        B = rg._global_max_int(int(counts.max(initial=0)))
        if B == 0:   # agreed: every process is idle this drive
            z = np.zeros(0, np.int64)
            if stage is not None:
                stage = stage.then("bulk.return", segments=0, plan=plan)
            return BulkResult(results=z, rounds=0, wall_s=0.0,
                              dispatch_round=z, resolve_round=z), stage, 0
        Bpad = 1 << max(0, B - 1).bit_length()
        # accumulators are [G, max-burst]: a skewed drive (one group with
        # a huge burst on a large-G engine) would allocate G*Bpad
        # regardless of total ops — refuse with advice instead of
        # swallowing device memory
        G_total = getattr(rg, "global_groups", G)
        if G_total * Bpad > 64_000_000:
            raise ValueError(
                f"deep drive accumulators would be [{G_total}, {Bpad}] "
                f"({G_total * Bpad / 1e6:.0f}M slots) for {n} ops — burst "
                "sizes are too skewed; split the drive into bursts of "
                "similar per-group size")

        # all bookkeeping lives in SORTED space; unsorted at return, where
        # a permutation was paid for. The harvest writes the three in
        # full. Every op's dispatch round is fixed by the blind phase-1
        # plan: its rank in its segment over S.
        outs = kept.handout(n, 2 if dense else 3)
        if order is None:
            results, resolve_round = outs[:2]
        else:
            results, resolve_round = (
                kept.take(name, (n,), np.int64)
                for name in ("sorted.results", "sorted.resolve_round"))
        resolved = kept.take("resolved", (n,), bool)
        if dense:
            # every segment is `per` long (the agreed B may be another
            # process's, and longer) and lies at seg_groups' rows, which
            # are every row in order where every group sends
            per = int(counts[0])
            rows = slice(None) if nseg == G else seg_groups

            def tiled(arr):
                arr.reshape(nseg, per)[:] = np.arange(per) // S

            # the same for every drive of `per` a group: shared, so
            # read-only in the caller's hand
            dispatch_round = kept.take("dispatch_round", (n,), np.int64,
                                       fill=tiled, tag=per)
            dispatch_round.flags.writeable = False
        else:
            rank = np.arange(n) - np.repeat(starts, counts)
            dispatch_round = (
                outs[2] if order is None else
                kept.take("sorted.dispatch_round", (n,), np.int64))
            np.floor_divide(rank, S, out=dispatch_round)
            slot_of = rank - dispatch_round * S
        # a rectangular burst over every group writes the same region of
        # the payload every drive; any other clears the windows first
        exact = dense and nseg == G
        if stage is not None:
            stage = stage.then("bulk.stage", segments=nseg, plan=plan)
            staged = rg._m_staged_bytes.value
            phase: dict = {}    # a straggler phase's stages say phase=2
        # the accumulators' seeds are kept constants; their device copies
        # are donated to the program, so they are put again every drive
        resbuf, valbuf, rndbuf = (
            rg._stage_acc(kept.take(name, (G, Bpad), dtype, fill=seed))
            for name, dtype, seed in (("seed.result", np.int32, 0),
                                      ("seed.valid", bool, False),
                                      ("seed.round", np.int32, _UNRESOLVED)))
        no_event = kept.take("seed.event", (G,), bool, fill=False)
        evflag = rg._stage_acc(no_event)  # per-group: no cross-shard reduce
        base = kept.take("stream.base", (G,), np.int32)
        np.copyto(base, rg._stream_count, casting="unsafe")
        base_dev = rg._stage_acc(base)
        _deep = rg._deep_fn()

        # burst-uniform payload leaves travel as SCALARS (zero H2D bytes);
        # per-op payloads fall back to full [G,S] arrays. Multihost
        # engines always stage full arrays: _stage_submits assembles a
        # global sharded array from each process's local block, and a
        # scalar has no local block (payload uniformity is also a
        # per-process fact the other processes can't see).
        # A leaf that varies says so in its head (the mixed pattern differs
        # inside its first 16 entries): only a leaf that may be uniform
        # pays the whole pass that proves it.
        def _const(x, known):
            uniform = n and (known or (x[:16] == x[0]).all()
                             and np.equal(x, x[0], out=mask).all())
            return np.int32(x[0]) if uniform else None

        consts = (None,) * 4 if multi else tuple(map(_const, vals, scalar))
        # telemetry stash: per-round [G] delta blocks kept ON DEVICE and
        # fetched with the accumulator harvest — the blind phase stays
        # one transfer per drive even with the flight recorder on
        tel_stash: list[Any] = []
        rounds0 = rg.rounds
        tel_ingested = 0
        # deliver_schedule(r) -> per-round delivery mask (already staged
        # for the engine's topology): the fault-injection seam — the
        # deep plane's liveness needs faults that HEAL, so a verdict/
        # nemesis harness schedules e.g. a partition for rounds < F and
        # full delivery after (testing/verdict.run_deep_verdict).
        deliver = rg.deliver
        ev_stash: list[Any] = []
        r = 0

        def dispatch(tagl, vnp, leaves) -> None:
            nonlocal r, resbuf, valbuf, rndbuf, evflag
            sub = rg._stage_submits(rg._note_stage(
                Submits(opcode=leaves[0], a=leaves[1], b=leaves[2],
                        c=leaves[3], tag=tagl, valid=vnp)))
            dl = deliver if deliver_schedule is None else deliver_schedule(r)
            rg._key, key = jax.random.split(rg._key)
            (rg.state, resbuf, valbuf, rndbuf, evflag, out) = _deep(
                rg.state, resbuf, valbuf, rndbuf, evflag, base_dev,
                np.int32(r), sub, dl, key)
            # keep only the ev (+ telemetry) leaves alive — retaining the
            # whole StepOutputs would pin every round's out arrays on device
            ev_stash.append((out.ev_seq, out.ev_code, out.ev_target,
                             out.ev_arg, out.ev_valid))
            if rg.telemetry is not None and out.telemetry is not None:
                tel_stash.append(out.telemetry)
            r += 1

        idle: tuple = ()

        def settle() -> None:
            """One round that submits nothing (kept constants)."""
            nonlocal idle
            idle = idle or (
                kept.take("idle.tag", (G, 1), np.int32, fill=0),
                kept.take("idle.valid", (G, S), bool, fill=False),
                (kept.take("idle.leaf", (G, S), np.int32, fill=0),) * 4
                if multi else (np.int32(0),) * 4)
            dispatch(*idle)

        def harvest() -> None:
            """ONE fetch of the [G,B] accumulators (+ telemetry, + the
            rare event leaves), harvested a chip's block at a time: the
            copies are asked for here, with the last call just made, so
            each block leaves as its chip's program ends, and a block's
            rows are written from its own host copy while the later
            blocks still cross."""
            nonlocal evflag, tel_ingested, stage
            asked = rg._ask_acc((resbuf, valbuf, rndbuf, evflag))
            if stage is not None:
                # while the tracer is on the device's share is the
                # wait's, not the fetch's; off, the fetch waits
                jax.block_until_ready((resbuf, valbuf, rndbuf, evflag))
                stage = stage.then("bulk.fetch", **phase)
                fetched_bytes = rg._m_fetch_bytes.value
            tels = rg._fetch_acc(tel_stash, asked=asked)
            for tel in tels:
                if np.asarray(tel.elections_started).ndim == 2:
                    w = int(np.asarray(tel.elections_started).shape[0])
                    rg.telemetry.ingest_stacked(
                        tel, rounds0 + tel_ingested)
                    tel_ingested += w
                else:
                    rg.telemetry.ingest(tel, rounds0 + tel_ingested)
                    tel_ingested += 1
            tel_stash.clear()
            # a block holds the rows row.. of the groups, so the segments
            # lo..hi of seg_groups (sorted) and the operations between
            # their starts, whatever the plan
            *accs, flags = asked
            row = lo = 0
            for blocks in zip(*accs):
                res_np, val_np, rnd_np = map(np.asarray, blocks)
                if not row and stage is not None:   # the first has arrived
                    stage = stage.then(
                        "bulk.harvest",
                        bytes=rg._m_fetch_bytes.value - fetched_bytes,
                        **phase)
                height = len(val_np)
                hi = lo + int(np.searchsorted(seg_groups[lo:], row + height))
                ops = slice(edges[lo], edges[hi])
                here = (slice(None) if hi - lo == height
                        else seg_groups[lo:hi] - row)
                if not dense:
                    colm = np.arange(Bpad)[None, :] < counts[lo:hi, None]
                for out, acc in ((resolved, val_np), (results, res_np),
                                 (resolve_round, rnd_np)):
                    if dense:
                        out[ops].reshape(hi - lo, per)[:] = acc[here, :per]
                    else:
                        out[ops] = acc[here][colm]
                row, lo = row + height, hi
            if any(np.asarray(flag).any() for flag in flags):
                # rare path (session-event ops in the burst): fetch the
                # stashed per-round event leaves and ingest with seq
                # dedup. Local-only decision — the fetch reads only this
                # process's shards, no collective program is launched.
                # Scan-mode stashes are stacked [W, ...]; unroll them.
                for st in ev_stash:
                    leaves = rg._fetch_acc(st)
                    if leaves[0].ndim == 3:
                        for w in range(leaves[0].shape[0]):
                            rg._ingest_events(
                                _EventView(*(x[w] for x in leaves)))
                    else:
                        rg._ingest_events(_EventView(*leaves))
                evflag = rg._stage_acc(no_event)
            ev_stash.clear()

        # phase 1: blind pipelined dispatch — NO device fetch at all. The
        # device runs ~windows rounds deep while the host only stages the
        # payload: a tag base [G,1], a valid mask [G,S] and the leaves
        # that vary [G,S] a window, stacked so that no window's arrays
        # are written while another's still cross. Scan mode goes further:
        # the stack holds the settle rounds' empty rows too and the whole
        # phase is ONE compiled lax.scan dispatch.
        windows = int(np.ceil(B / S))
        if self._scan and deliver_schedule is not None:
            raise NotImplementedError(
                "deep_scan compiles the whole blind phase with ONE "
                "deliver mask; per-round deliver_schedule fault "
                "injection needs the dispatch mode (BulkDriver without "
                "deep_scan)")
        # (+ replicate/commit/report settle)
        stack = windows + 3 if self._scan else windows
        # The scan takes each stacked plane as a device array, put the
        # moment its windows are written and cut by groups as the scan
        # takes it, so that one plane crosses while the next is copied;
        # dispatch mode hands a window's host rows to that window's call.
        put = partial(rg._stage_acc, axis=1) if self._scan else (
            lambda plane: plane)
        tagl_w = kept.take("payload.tag", (stack, G, 1), np.int32, fill=0)
        if not exact:
            tagl_w[:windows] = 0
        for w in range(windows):
            tagl_w[w, seg_groups, 0] = (seg_base + w * S + 1) \
                .astype(np.int32)
        tagl_w = put(tagl_w)

        def mark_valid(arr):
            if dense:
                for w in range(-(-per // S)):
                    arr[w, rows, :min(S, per - w * S)] = True
            else:
                for w in range(windows):
                    arr[w][seg_groups] = \
                        (w * S + np.arange(S))[None, :] < counts[:, None]

        valid_w = kept.take("payload.valid", (stack, G, S), bool,
                            fill=mark_valid if exact else False,
                            tag=per if exact else None)
        if not exact:
            valid_w[:windows] = False
            mark_valid(valid_w)
        valid_w = put(valid_w)
        planes: list = [None] * 4
        # (a uniform leaf's plane is a kept constant with nothing to
        # write: those cross first, under the copies of the others)
        for i in sorted(range(4), key=lambda i: consts[i] is None):
            c, x_s = consts[i], vals[i]
            if c is not None:
                # burst-uniform: a scalar a window; stacked, one fill
                if self._scan:
                    planes[i] = put(kept.take(
                        ("payload.leaf", i), (stack, G, S), np.int32,
                        fill=lambda arr, c=c: arr[:windows].fill(c),
                        tag=("uniform", int(c))))
                continue
            x_w = kept.take(("payload.leaf", i), (stack, G, S), np.int32,
                            fill=0, tag=("varying", per if exact else None))
            if not exact:
                x_w[:windows] = 0
            if dense:
                # window w is columns w*S.. of the operations as they
                # lie, [nseg, per]: a strided copy a window and plane
                x_d = x_s.reshape(nseg, per)
                for w in range(-(-per // S)):
                    k = min(S, per - w * S)
                    x_w[w, rows, :k] = x_d[:, w * S:w * S + k]
            else:
                x_w[dispatch_round, g_s, slot_of] = x_s
            planes[i] = put(x_w)
        if stage is not None:
            stage = stage.then(
                "bulk.dispatch", bytes=rg._m_staged_bytes.value - staged)
        if self._scan:
            _scan = _deep_scan_program(
                rg.config, onehot=rg.mesh is not None, donate=rg.donate)
            rg._key, key = jax.random.split(rg._key)
            (rg.state, resbuf, valbuf, rndbuf, evflag, evs, tels) = _scan(
                rg.state, resbuf, valbuf, rndbuf, evflag, base_dev,
                Submits(*planes, tag=tagl_w, valid=valid_w), deliver, key)
            r = stack
            ev_stash.append(evs)   # stacked [W, ...] leaves
            if rg.telemetry is not None and tels is not None:
                tel_stash.append(tels)  # stacked [W, G] leaves
        else:
            for w in range(windows):
                dispatch(tagl_w[w], valid_w[w], tuple(
                    c if c is not None else x_w[w]
                    for c, x_w in zip(consts, planes)))
            for _ in range(3):  # settle: replicate + commit + report lag
                settle()
        if stage is not None:
            stage = stage.then("bulk.wait", rounds=r)
        harvest()

        # phase 2: straggler suffixes (lease-cold leaders, backpressure).
        # Resolution is a per-group PREFIX (the gate makes acceptance a
        # prefix and applies report in log order), so the cursor is the
        # per-group resolved count; re-sending an already-accepted op is
        # rejected on device, never re-applied. The stop decision is
        # lockstep-agreed: a process whose local ops are done keeps
        # dispatching EMPTY windows until every process is done (each
        # iteration launches 3 collective rounds + a fetch on multihost).
        while not rg._agree(bool(resolved.all())):
            if stage is not None:
                stage = stage.then(
                    "bulk.stage", resolved=np.count_nonzero(resolved),
                    **phase)
                phase = {"phase": 2}
            if r > max_rounds:
                missing = int(n - resolved.sum())
                # abandoning mid-stream: tags up to the device ring max
                # were CONSUMED (some abandoned ops may still commit —
                # at-most-once, like a classic-path timeout). Resync the
                # host cursor from the device so later drives start past
                # every consumed tag instead of being gate-rejected
                # forever (round-4 review finding).
                self._resync_stream_count()
                raise TimeoutError(
                    f"bulk drive (deep): {missing} ops unresolved after "
                    f"{max_rounds} rounds (fault-free liveness assumption"
                    f" violated? use the queue-managed path under faults); "
                    f"stream cursors resynced from the device")
            # each segment's next <=S operations past its resolved prefix
            # (reduceat on bool would logical-or, not count — cast first)
            fu = np.add.reduceat(resolved.astype(np.int64), starts)
            want = np.clip(counts - fu, 0, S)
            segs = np.flatnonzero(want > 0)
            reps = want[segs]
            slots = np.arange(reps.sum()) \
                - np.repeat(np.cumsum(reps) - reps, reps)
            pos = np.repeat((starts + fu)[segs], reps) + slots
            tagl = np.zeros((G, 1), np.int32)
            tagl[seg_groups[segs], 0] = (seg_base[segs] + fu[segs] + 1) \
                .astype(np.int32)
            vnp = np.zeros((G, S), bool)
            vnp[seg_groups] = np.arange(S)[None, :] < want[:, None]
            leaves = tuple(
                c if c is not None else _scatter(G, S, g_s[pos], slots,
                                                 x_s[pos])
                for c, x_s in zip(consts, vals))
            if stage is not None:   # (a pass puts no accumulator)
                stage = stage.then("bulk.dispatch", phase=2)
            dispatch(tagl, vnp, leaves)
            settle()
            settle()
            if stage is not None:
                stage = stage.then("bulk.wait", rounds=3, phase=2)
            harvest()

        if stage is not None:
            stage = stage.then(
                "bulk.return", resolved=np.count_nonzero(resolved), **phase)
        if n:
            rg._stream_count[seg_groups] += counts
        rg.rounds += r
        rg.metrics.counter("ops_committed").inc(n)
        if order is not None:   # back to submission order
            for out, x in zip(outs, (results, resolve_round,
                                     dispatch_round)):
                out[order] = x
            results, resolve_round, dispatch_round = outs
        kept.sweep()
        return BulkResult(results=results, rounds=r,
                          wall_s=time.perf_counter() - t0,
                          dispatch_round=dispatch_round,
                          resolve_round=resolve_round), stage, windows


class _EventView:
    """Adapter: numpy event leaves → the ``ev_*`` attrs _ingest_events reads."""

    __slots__ = ("ev_seq", "ev_code", "ev_target", "ev_arg", "ev_valid")

    def __init__(self, seq, code, target, arg, valid) -> None:
        self.ev_seq, self.ev_code, self.ev_target = seq, code, target
        self.ev_arg, self.ev_valid = arg, valid


def drive_batch(rg, groups, opcode, a=0, b=0, c=0,
                max_rounds: int = 10_000) -> BulkResult:
    """Module-level convenience: ``BulkDriver(rg).drive(...)``."""
    return BulkDriver(rg).drive(groups, opcode, a, b, c,
                                max_rounds=max_rounds)
