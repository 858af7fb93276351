"""The TPU executor behind the Atomix SPI.

SURVEY.md §7.1: "the TPU executor selectable at replica build time (mirror
of ``withStateMachine(new ResourceManager())`` at ``AtomixReplica.java:374``)".
A replica/server built with ``executor="tpu"`` routes ``get``/``create`` of
the fixed-shape resource types to the batched device engine — one device
Raft group per resource instance, catalog unchanged in the
:class:`~copycat_tpu.manager.state.ResourceManager` — while every other
type (and device-pool overflow / non-int32 payloads) transparently stays on
the CPU state machines. Same public resource API either way.

Architecture (two replication planes, one state machine discipline):

- The CPU Raft log linearizes client ops ACROSS SERVER PROCESSES and owns
  sessions, durability and compaction — exactly as for CPU resources.
- Each server applies committed ops to its own in-process
  :class:`DeviceEngine` (a ``RaftGroups`` batch — the flagship vectorized
  consensus+apply program). Replica convergence across servers follows
  from determinism: the engine's visible resource state is a pure function
  of the committed device-op sequence, which is identical on every server
  because it is derived from the shared CPU log in apply order.

Determinism rules the device-backed machines must (and do) observe:

1. Device ops never carry device-clock TTLs (``c``/deadline args are 0 or
   sentinel): TTLs and lock timeouts run through the HOST'S replicated
   log-time timers (``StateMachineExecutor.schedule`` — SURVEY.md §5.9),
   so device resource state is independent of how many device rounds each
   server happened to step.
2. Queries never append device log entries (no escalation): the device
   log stays ``[election NoOp] + committed commands`` on every server, so
   log indexes — used as election fencing epochs — agree everywhere.
3. Commits are retained host-side exactly like the CPU machines retain
   them (``_Held`` discipline): the CPU log's compaction contract is
   preserved; the device holds the *data plane*.

Reference obligations: resource routing ``ResourceManager.java:56``,
executor selection ``AtomixReplica.java:374``, state machine semantics
``AtomicValueState.java:32``, ``MapState.java:32``, ``SetState.java:32``,
``QueueState.java:30``, ``LockState.java:33``, ``LeaderElectionState.java:31``.
"""

from __future__ import annotations

import inspect
import logging
from collections import deque

from typing import Any, Iterable, NamedTuple

from ..resource.state_machine import ResourceStateMachine
from ..server.state_machine import Commit
from ..atomic import commands as vc
from ..collections import commands as cc
from ..coordination import commands as oc
from ..utils.tracing import TRACER

logger = logging.getLogger(__name__)

INT32_MIN = -(2 ** 31)
INT32_MAX = 2 ** 31 - 1


def _devint(v: Any) -> bool:
    """True if ``v`` can live in a device int32 lane.

    ``bool`` is excluded (a device round-trip would turn ``True`` into
    ``1`` — a visible type change vs the CPU path), as are the engine's
    sentinels: INT_MIN (FAIL) and the two above it (the map's ABSENT and
    FULL, ``ops/apply.py``).
    """
    return (isinstance(v, int) and not isinstance(v, bool)
            and INT32_MIN + 2 < v <= INT32_MAX)


class DeviceEngineConfig(NamedTuple):
    """Shape of the per-server device batch (uniform across the cluster —
    the engine replicates deterministically only if every server runs the
    same shapes, like ``withStateMachine`` must be uniform in the
    reference)."""

    capacity: int = 1024      # device groups = max device-backed resources
    num_peers: int = 3
    log_slots: int = 64
    submit_slots: int = 4
    seed: int = 0             # shared PRNG seed — same election history
    # Optional jax.sharding.Mesh: shard the engine's group axis across
    # this server's local devices (parallel/mesh.py specs — zero
    # cross-device collectives, census-verified). A LOCAL placement
    # choice only: sharding never changes the integer state evolution,
    # so servers with different meshes (or none) still replicate
    # deterministically; the uniformity requirement above is about
    # shapes, not placement. The mesh's 'groups' axis size must divide
    # capacity (each shard holds capacity/shards groups).
    mesh: Any = None
    # Optional ops.apply.ResourceConfig: which device pools this engine
    # compiles in. Pool state is carried through every engine round, so a
    # deployment that hosts only counters can provision
    # ``ResourceConfig.counters_only()`` and nearly halve the round
    # (measured 9.3 -> 5.1 ms at capacity 1024 on CPU). Resource types
    # whose pool is compiled out (size 0) transparently fall back to the
    # CPU state machines — same public API, same semantics, no device
    # acceleration (``device_machine_for`` consults this). Must be
    # uniform across the cluster, like every other engine shape. None =
    # all pools at their defaults (previous behavior).
    resource: Any = None
    # Device-plane flight-recorder telemetry (models/telemetry.py):
    # compiles the per-group telemetry block into the engine step and
    # surfaces device.* metrics + /flight on the stats listener. Pure
    # output — never changes the engine's state evolution, so it may
    # differ across servers (a local observability choice, not a shape).
    # COPYCAT_TELEMETRY=1 / COPYCAT_INVARIANTS also enable it per-env.
    telemetry: bool = False


class _Job:
    """One device-op chain (a handler or timer generator) inside a window."""

    __slots__ = ("group", "gen", "settle", "ctx", "on_done", "tag",
                 "resume_round", "pending", "done", "result", "exc")

    def __init__(self, group: int | None, gen: Any, settle: bool,
                 ctx: Any = None, on_done: Any = None) -> None:
        self.group = group
        self.gen = gen
        self.settle = settle
        self.ctx = ctx
        self.on_done = on_done
        self.tag: int | None = None
        self.resume_round: int | None = None
        self.pending: int | None = None
        self.done = False
        self.result: Any = None
        self.exc: BaseException | None = None


class DeviceJob:
    """A device-backed handler's suspended execution.

    Device command handlers are generator functions — each device op is a
    ``yield`` — so the applying server can BATCH many handlers' chains into
    shared engine rounds (:class:`DeviceWindow`) instead of paying
    submit→commit→settle per op (the round-3 SPI bottleneck). A caller
    with no window drives the chain alone via :meth:`run`.
    """

    __slots__ = ("engine", "group", "settle", "gen")
    is_device_job = True  # duck-typing marker for the applying server

    def __init__(self, engine: "DeviceEngine", group: int, settle: bool,
                 gen: Any) -> None:
        self.engine = engine
        self.group = group
        self.settle = settle
        self.gen = gen

    def run(self) -> Any:
        return self.engine.run_now(self.group, self.gen, self.settle)


class DeviceWindow:
    """Shared round pump for one apply batch.

    Jobs added in CPU-log order are driven concurrently ACROSS device
    groups and strictly FIFO WITHIN a group: a group's next job starts
    only when its predecessor finished, so each group's device-op sequence
    is the concatenation of complete per-handler chains in log order —
    identical on every server regardless of how commit batches were cut
    (the determinism requirement of the two-plane design above). One
    engine round serves every group's current outstanding op, so a batch
    of K independent handlers costs ~max-chain-length rounds, not
    sum-of-chains.

    Finalization callbacks (response futures, event seal/push) run in add
    order — the reference's per-session program-order completion.
    """

    MAX_ROUNDS = 2000

    def __init__(self, engine: "DeviceEngine") -> None:
        self._eng = engine
        self._active: dict[int, _Job] = {}          # group -> running job
        self._waiting: dict[int, deque[_Job]] = {}  # group -> queued jobs
        self._order: list[_Job] = []                # finalization order
        self._finalized = 0
        #: device ops yielded but not yet submitted: (job, op, a, b, c).
        #: Submission is deferred so one vectorized ``submit_batch`` per
        #: pump cycle replaces a per-op ``submit`` (the per-op deque +
        #: dict staging was a top line of the SPI burst profile).
        self._staged: list = []
        #: per-entry context inherited by timer-spawned jobs (the applying
        #: server sets it around each command entry's tick+execute)
        self.job_ctx: Any = None

    @property
    def busy(self) -> bool:
        return bool(self._active) or self._finalized < len(self._order)

    # -- enqueue -----------------------------------------------------------

    def add_job(self, job: DeviceJob, ctx: Any = None,
                on_done: Any = None) -> None:
        """Defer a handler chain; ``on_done(result, exc)`` runs at its
        log-ordered finalization slot."""
        self._enqueue(_Job(job.group, job.gen, job.settle, ctx, on_done))

    def add_ready(self, on_done: Any) -> None:
        """Defer an already-computed completion so it finalizes in log
        order behind pending device jobs (no-op ordering shim when the
        window is idle)."""
        j = _Job(None, None, False, None, on_done)
        j.done = True
        self._order.append(j)
        self._try_finalize()

    def _enqueue(self, j: _Job) -> None:
        self._order.append(j)
        if j.group in self._active:
            self._waiting.setdefault(j.group, deque()).append(j)
        else:
            self._active[j.group] = j
            self._advance(j, None)
        self._try_finalize()

    # -- drive -------------------------------------------------------------

    def _advance(self, job: _Job, value: Any) -> None:
        """Resume ``job`` with ``value`` until it suspends on a device op
        or finishes; iteratively promote waiting jobs of freed groups (a
        long chain of no-op jobs must not recurse)."""
        work: list[tuple[_Job, Any]] = [(job, value)]
        while work:
            j, val = work.pop()
            try:
                if j.ctx is not None:
                    with j.ctx:
                        yielded = j.gen.send(val)
                else:
                    yielded = j.gen.send(val)
            except StopIteration as stop:
                j.done = True
                j.result = stop.value
            except BaseException as e:  # noqa: BLE001 — surfaced at finalize
                j.done = True
                j.exc = e
            if not j.done:
                if yielded[0] == "cmd":
                    # defer the engine submit: _flush_staged turns every
                    # op staged this cycle into ONE vectorized
                    # submit_batch call (tags assigned there)
                    self._staged.append((j, yielded[1], yielded[2],
                                         yielded[3], yielded[4]))
                    j.resume_round = None
                    continue
                # unknown yield: fail THIS job (still freeing its group
                # below so queued jobs keep running)
                j.done = True
                j.exc = RuntimeError(f"unknown device yield {yielded!r}")
                j.gen.close()
            del self._active[j.group]
            q = self._waiting.get(j.group)
            if q:
                nxt = q.popleft()
                if not q:
                    del self._waiting[j.group]
                self._active[j.group] = nxt
                work.append((nxt, None))

    def _collect(self, groups) -> bool:
        """Resolve finished tags / elapsed settle windows; returns whether
        any job progressed (False → the pump must step a round)."""
        progressed = False
        now = groups.rounds
        results = groups.results
        for j in list(self._active.values()):
            if j.tag is not None and j.tag in results:
                res = results.pop(j.tag)
                j.tag = None
                if j.settle:
                    # event consumers (lock/election) resume only after
                    # their op's session events drained to the host buffer
                    j.pending = res
                    j.resume_round = now + self._eng.SETTLE_ROUNDS
                else:
                    progressed = True
                    self._advance(j, res)
            elif (j.tag is None and j.resume_round is not None
                  and now >= j.resume_round):
                j.resume_round = None
                progressed = True
                self._advance(j, j.pending)
        return progressed

    def _flush_staged(self, groups) -> None:
        """Submit every staged device op in ONE vectorized call (tags
        assigned here); per-group FIFO holds because submit_batch's
        stable group sort preserves staging order within a group."""
        staged, self._staged = self._staged, []
        if not staged:
            return
        if len(staged) == 1:
            j, op, a, b, c = staged[0]
            j.tag = groups.submit(j.group, op, a, b, c)
            return
        tags = groups.submit_batch(
            [s[0].group for s in staged], [s[1] for s in staged],
            [s[2] for s in staged], [s[3] for s in staged],
            [s[4] for s in staged])
        for s, t in zip(staged, tags.tolist()):
            s[0].tag = t

    def pump(self) -> None:
        """Drive every pending job to completion, then run finalizations
        in add order."""
        if self._active:
            groups = self._eng._ensure()
            self._flush_staged(groups)
            start = groups.rounds
            while self._active:
                if groups.rounds - start > self.MAX_ROUNDS:
                    raise TimeoutError(
                        f"device window stuck after {self.MAX_ROUNDS} rounds"
                        f" without progress (groups {sorted(self._active)})")
                if self._collect(groups):
                    # a no-progress watchdog, not a total budget: a long
                    # FIFO chain on one group is legitimate work
                    start = groups.rounds
                    self._flush_staged(groups)
                elif self._active:
                    # When every active job is sitting out a KNOWN settle
                    # window (event consumers after their op committed),
                    # fuse exactly that many rounds into one compiled
                    # program + fetch — one blocking fetch instead of
                    # min(waits). A fresh submit needs no fusion: the
                    # step commits and reports in-round under full
                    # delivery (commit latency 1), so the loaded round
                    # resolves it.
                    waits = [j.resume_round - groups.rounds
                             for j in self._active.values()
                             if j.resume_round is not None]
                    if (len(waits) == len(self._active)
                            and min(waits) > 1):
                        groups.step_rounds(min(waits))
                    else:
                        groups.step_round()
        self._try_finalize()

    barrier = pump  # drain point before entries that read manager state

    def close(self) -> None:
        try:
            self.pump()
        finally:
            if self._eng._window is self:
                self._eng._window = None

    def _try_finalize(self) -> None:
        while self._finalized < len(self._order):
            j = self._order[self._finalized]
            if not j.done:
                break
            self._finalized += 1
            if j.on_done is not None:
                j.on_done(j.result, j.exc)
            elif j.exc is not None:
                # timer-spawned chain failed; mirror executor.tick's policy
                logger.exception("device timer chain failed", exc_info=j.exc)


class DeviceEngine:
    """In-process device batch shared by all device-backed resources of one
    server; allocates one group per resource instance.

    Freed groups ARE reused: every device-backed machine resets its
    device-resident state (clear/cancel/release commands) in ``delete()``
    before releasing its group, so a recycled group starts clean. Reuse is
    not just thrift — it makes the device-vs-CPU placement decision a
    function of the LIVE device-resource count only, which is identical
    between a full history and a compacted replay (compaction only drops
    create/delete pairs, preserving the live set at every retained log
    position); a monotonic allocator would instead diverge after restart.
    When all groups are live, allocation returns ``None`` and the manager
    falls back to the CPU state machine for that resource.
    """

    #: extra rounds stepped after a command before an event-consuming
    #: machine (lock/election) resumes, so session events emitted by the
    #: apply are drained into the host buffer first — a fixed,
    #: deterministic settle budget (events surface one round after the
    #: emitting apply).
    SETTLE_ROUNDS = 2

    def __init__(self, config: DeviceEngineConfig | None = None) -> None:
        self.config = config or DeviceEngineConfig()
        self._groups = None          # built lazily: first device resource
        self._next_group = 0
        self._free: list[int] = []   # released (reset) groups, lowest first
        self._window: DeviceWindow | None = None
        self._map_shadow = 0         # map keys held on the host
        self._map_ops = None         # the registry's two map counters
        self._lock_ops = None        # and its two lock counters
        self._elect_ops = None       # its two election counters
        self._end_ops = None         # and the two of a session's end
        self._lock_overflow = 0      # lock waiters held on the host
        self._wait_slots = None      # width of a lock's device wait ring
        self._listener_slots = None  # and of an election's listener ring
        #: dispatches of the vector lane so far: what a machine that looks
        #: ahead over its staged rows tells one run from the next by
        self.vector_epoch = 0
        #: events the staged rows of the next vector run will cause, which
        #: their finalize reads: group -> [the machine's cursor, how many]
        self._events_due: dict[int, list[int]] = {}

    # -- lifecycle ---------------------------------------------------------

    def _ensure(self):
        if self._groups is None:
            from ..models.raft_groups import RaftGroups
            from ..utils.platform import enable_compilation_cache
            enable_compilation_cache()  # restarts skip the jit stall
            cfg = self.config
            if cfg.mesh is not None:
                shards = cfg.mesh.shape.get("groups", 1)
                if cfg.capacity % shards:
                    raise ValueError(
                        f"DeviceEngineConfig.capacity={cfg.capacity} not "
                        f"divisible by the mesh 'groups' axis ({shards})")
                peer_shards = cfg.mesh.shape.get("peers", 1)
                if cfg.num_peers % peer_shards:
                    # Without this, the failure surfaces later as an
                    # opaque XLA sharding error inside device_put.
                    raise ValueError(
                        f"DeviceEngineConfig.num_peers={cfg.num_peers} not "
                        f"divisible by the mesh 'peers' axis ({peer_shards})")
            from ..ops.consensus import Config
            engine_cfg = None
            if cfg.resource is not None or cfg.telemetry:
                engine_cfg = Config(
                    telemetry=cfg.telemetry,
                    **({"resource": cfg.resource}
                       if cfg.resource is not None else {}))
            self._groups = RaftGroups(
                cfg.capacity, cfg.num_peers, log_slots=cfg.log_slots,
                submit_slots=cfg.submit_slots, seed=cfg.seed,
                mesh=cfg.mesh, config=engine_cfg)
            # Warm-up: deterministic election rounds (fixed seed). After
            # this, full delivery keeps every leader stable, so queries are
            # always servable without stepping.
            #
            # COST (measured, round 4): elections settle in ≤~15 rounds
            # at any capacity (max_rounds=200 is a bound, not the cost);
            # wall time is dominated by the one-time jit compile — ~8-9 s
            # on CPU at capacity 16/256/1024 alike, tens of seconds for a
            # first-ever TPU compile (then persistently cached). Servers
            # built through AtomixServer/AtomixReplica pay it at OPEN
            # (ResourceManager.prewarm), before any client session
            # exists — never as a hidden stall inside the first
            # create()'s apply.
            self._groups.wait_for_leaders(max_rounds=200)
            self._warm_capture()
        return self._groups

    def _warm_capture(self) -> None:
        """Compile the snapshot cut's program with the round's programs
        (one cut, thrown away), so that the first capture stalls no apply
        path; engines of one shape share the compiled program."""
        from ..models import checkpoint
        checkpoint.cut(self._groups)

    def count_map_op(self, chain: bool) -> None:
        """One more map command finalised on the vector lane, or (``chain``)
        run as a generator chain: ``engine.map_vector_ops`` and
        ``engine.map_chain_ops`` in the tracer's report."""
        counters = self._map_ops
        if counters is None:
            metrics = self._groups.metrics
            counters = self._map_ops = (metrics.counter("map_vector_ops"),
                                        metrics.counter("map_chain_ops"))
        counters[chain].inc()

    def count_lock_op(self, chain: bool) -> None:
        """One more lock command finalised on the vector lane, or
        (``chain``) run as a generator chain: ``engine.lock_vector_ops``
        and ``engine.lock_chain_ops`` in the tracer's report."""
        counters = self._lock_ops
        if counters is None:
            metrics = self._groups.metrics
            counters = self._lock_ops = (metrics.counter("lock_vector_ops"),
                                         metrics.counter("lock_chain_ops"))
        counters[chain].inc()

    def count_elect_op(self, chain: bool) -> None:
        """One more election command finalised on the vector lane, or
        (``chain``) run as a generator chain: ``engine.elect_vector_ops``
        and ``engine.elect_chain_ops`` in the tracer's report."""
        counters = self._elect_ops
        if counters is None:
            metrics = self._groups.metrics
            counters = self._elect_ops = (
                metrics.counter("elect_vector_ops"),
                metrics.counter("elect_chain_ops"))
        counters[chain].inc()

    def count_session_end(self, chain: bool, instances: int = 1) -> None:
        """``instances`` more instances of an ended session closed in its
        one vector turn, or (``chain``) each by a generator chain of its
        own: ``engine.session_end_vector_instances`` and
        ``engine.session_end_chain_instances``."""
        counters = self._end_ops
        if counters is None:
            metrics = self._groups.metrics
            counters = self._end_ops = (
                metrics.counter("session_end_vector_instances"),
                metrics.counter("session_end_chain_instances"))
        counters[chain].inc(instances)

    def listener_slots(self) -> int:
        """Slots of an election's listener ring on the device."""
        slots = self._listener_slots
        if slots is None:
            slots = self._listener_slots = \
                self._groups.state.resources.el_id.shape[-1]
        return slots

    def lock_wait_slots(self) -> int:
        """Slots of a lock's wait ring on the device."""
        slots = self._wait_slots
        if slots is None:
            slots = self._wait_slots = \
                self._groups.state.resources.lk_wait_id.shape[-1]
        return slots

    def count_lock_overflow(self, delta: int) -> None:
        """Lock waiters the device's wait ring refused and the host holds
        moved by ``delta``: the gauge ``lock.host_overflow_waiters``."""
        self._lock_overflow += delta
        if self._groups is not None:
            self._groups.metrics.gauge("lock.host_overflow_waiters").set(
                self._lock_overflow)

    def count_shadow(self, delta: int) -> None:
        """Map keys held on the host for want of room in their bucket or
        of an int32 shape moved by ``delta``."""
        self._map_shadow += delta
        self._groups.metrics.gauge("map.host_shadow_keys").set(
            self._map_shadow)

    def map_keys(self) -> tuple[int, int]:
        """(keys in the device's map tables, by the leader lanes' live
        counts; map keys shadowed on the host), also set as the gauges
        ``map.device_keys`` and ``map.host_shadow_keys``. Costs one
        fetch; nothing on the served path calls it."""
        import numpy as np
        from ..ops.consensus import current_leader
        groups = self._ensure()
        res = groups.state.resources
        count = res.map_count[..., 0] if res.map_count.shape[-1] \
            else res.map_live.sum(-1)
        lead = np.maximum(np.asarray(current_leader(groups.state)[0]), 0)
        on_device = int(np.asarray(count)[np.arange(lead.size), lead].sum())
        groups.metrics.gauge("map.device_keys").set(on_device)
        self.count_shadow(0)
        return on_device, self._map_shadow

    def allocate(self) -> int | None:
        """Lowest free device group, or ``None`` when all are live."""
        if self._free:
            self._ensure()
            import heapq
            return heapq.heappop(self._free)
        if self._next_group >= self.config.capacity:
            return None
        self._ensure()
        group = self._next_group
        self._next_group += 1
        return group

    def release(self, group: int) -> None:
        """Return a group to the pool. The caller (the machine's
        ``delete()``) must have reset the group's device state first."""
        import heapq
        heapq.heappush(self._free, group)

    def restore_snapshot(self, blob: bytes, next_group: int,
                         free: list[int]) -> None:
        """Rebuild the engine's ``RaftGroups`` from a server-plane
        snapshot (``models/checkpoint.py`` field-path bytes) plus the
        group-allocator bookkeeping captured with it — the device half of
        the crash-recovery plane (docs/DURABILITY.md)."""
        from ..models import checkpoint
        self._groups = checkpoint.load_bytes(blob, mesh=self.config.mesh)
        self._map_ops = None         # they were the replaced registry's
        self._lock_ops = None
        self._elect_ops = None
        self._end_ops = None
        self._map_shadow = 0         # the machines' restores count anew
        self._lock_overflow = 0
        self._warm_capture()
        self._next_group = int(next_group)
        self._free = sorted(int(g) for g in free)

    # -- op plane ----------------------------------------------------------

    def begin_window(self) -> DeviceWindow:
        """Open the shared round pump for one apply batch (the applying
        server closes it after the batch's last entry)."""
        window = DeviceWindow(self)
        self._window = window
        return window

    @property
    def window(self) -> DeviceWindow | None:
        return self._window

    def run_now(self, group: int, gen: Any, settle: bool = False) -> Any:
        """Drive one chain to completion on a private pump (the per-op
        path for callers outside any window)."""
        w = DeviceWindow(self)
        job = _Job(group, gen, settle)
        w._enqueue(job)
        w.pump()
        if job.exc is not None:
            raise job.exc
        return job.result

    def run_excl(self, group: int, gen: Any, settle: bool = False) -> Any:
        """Drain the open window (if any), then drive ``gen`` alone — for
        delete/session-close chains that must observe fully-applied state
        and complete before the caller proceeds (e.g. group release must
        precede any later allocate)."""
        if self._window is not None and self._window.busy:
            self._window.barrier()
        return self.run_now(group, gen, settle)

    def spawn(self, group: int, gen: Any, settle: bool = False) -> None:
        """Timer-fired device work.

        During a COMMAND entry's tick (``window.job_ctx`` set) the chain
        joins the window at its log-ordered slot — before the entry's own
        handler job — under the entry's context, so its publishes seal
        with that entry. Outside a command entry (non-command entries
        barrier the window first; or no window at all) it runs
        immediately: the window is empty then, so immediate execution IS
        the log-ordered slot, and publishes land in the live touched set
        the current entry seals."""
        if self._window is not None and self._window.job_ctx is not None:
            self._window._enqueue(
                _Job(group, gen, settle, self._window.job_ctx, None))
        else:
            self.run_now(group, gen, settle)

    def command(self, group: int, opcode: int, a: int = 0, b: int = 0,
                c: int = 0) -> int:
        """Submit one committed device op and return its applied result
        (standalone per-op path; handlers go through generator chains)."""
        def one():
            return (yield ("cmd", int(opcode), int(a), int(b), int(c)))

        return self.run_now(group, one(), settle=True)

    def query(self, group: int, opcode: int, a: int = 0, b: int = 0,
              c: int = 0) -> int:
        """Read-only op served from the leader lane's applied state.

        Never appends to the device log (determinism rule #2) —
        ``RaftGroups.serve_query`` is the non-escalating lane; after the
        warm-up election the leader is stable and has applied everything
        it committed, so it serves without stepping.
        """
        return self._ensure().serve_query(group, opcode, a, b, c)

    def take_events(self, group: int, cursor: int,
                    limit: int | None = None) -> tuple[list, int]:
        """Events for ``group`` with seq > cursor, the oldest ``limit`` of
        them where one is given; returns (events, cursor). The retained
        list ascends by seq, so what is new is found from its end: a call
        costs what it returns, not what the group has kept."""
        evs = self._groups.events.get(group) if self._groups is not None \
            else None
        if not evs or evs[-1][0] <= cursor:
            return [], cursor
        at = len(evs) - 1
        while at and evs[at - 1][0] > cursor:
            at -= 1
        out = evs[at:] if limit is None else evs[at:at + limit]
        return out, out[-1][0]

    def expect_event(self, group: int, cursor: int) -> None:
        """A row being staged for the next vector run will cause one
        session event on ``group``, which its finalize reads past
        ``cursor``: :meth:`run_vector` returns only once it is in the
        host's buffer."""
        due = self._events_due.get(group)
        if due is None:
            self._events_due[group] = [cursor, 1]
        else:
            due[1] += 1

    def _settle_events(self, groups: Any, due: dict[int, list[int]],
                       max_rounds: int = 16) -> None:
        """Step until every event the run's rows caused is in the host's
        buffer. The leader lane drains its outbox in the round it applies
        in, so a run's own round has brought them as a rule and nothing
        is stepped; else the rounds are stepped once for the turn, fused
        (``engine.settle``)."""
        events = groups.events
        stepped = 0
        while True:
            for group, (cursor, count) in due.items():
                evs = events.get(group)
                if not evs or len(evs) < count \
                        or evs[-count][0] <= cursor:
                    break
            else:
                return
            if stepped >= max_rounds:
                # the rows' finalize fails each row whose event is missing
                logger.error("vector pump: events of %d groups not drained "
                             "after %d settle rounds", len(due), stepped)
                return
            span = TRACER.open_span("engine.settle") if TRACER.enabled \
                else None
            groups.step_rounds(self.SETTLE_ROUNDS)
            stepped += self.SETTLE_ROUNDS
            if span is not None:
                span.close(rounds=self.SETTLE_ROUNDS, groups=len(due))

    def event_cursor(self, group: int) -> int:
        """Current newest event seq for ``group`` (start-of-life cursor)."""
        if self._groups is None:
            return -1
        evs = self._groups.events.get(group, [])
        return evs[-1][0] if evs else -1

    def run_vector(self, groups_idx, opcodes, a, b, c,
                   max_rounds: int = 200, query: Any = None) -> list[int]:
        """The batched server-side pump's device leg: stage EVERY row in
        one vectorized pass (the ``_stage_direct`` fast lane scatters a
        fitting burst straight into the next round's Submits) and step
        shared engine rounds until all rows committed — under full
        delivery the loaded round accepts, replicates, commits and
        reports in ONE round, so a 1k-op batch costs one engine round
        instead of 1k generator chains through the window machinery.
        Returns raw results aligned with the input rows. Per-group FIFO
        holds because the staging's stable group sort preserves row
        order within a group and the engine applies slots in log order.

        The primary lane is :meth:`RaftGroups.drive_vector` (untracked
        tags, output-array correlation — no per-op dict bookkeeping);
        when direct staging is refused (queued ops from generator
        chains, held groups) it degrades to the tracked submit_batch +
        results-dict walk, which interleaves correctly with the queue-
        managed machinery.

        ``query`` is a read window's staged rows
        (:meth:`stage_query_vector`): the primary lane offers them to the
        run's round, the tracked lane leaves them as they were, and
        :meth:`finish_query_vector` answers them either way."""
        groups = self._ensure()
        self.vector_epoch += 1
        due, self._events_due = self._events_due, {}
        res = groups.drive_vector(groups_idx, opcodes, a, b, c,
                                  max_rounds=max_rounds, query=query)
        if res is not None:
            if due:
                self._settle_events(groups, due)
            return res.tolist()
        tags = groups.submit_batch(groups_idx, opcodes, a, b, c)
        tag_l = tags.tolist()
        results = groups.results
        for _ in range(max_rounds):
            groups.step_round()
            if all(t in results for t in tag_l):
                if due:
                    self._settle_events(groups, due)
                return [results.pop(t) for t in tag_l]
        missing = sum(1 for t in tag_l if t not in results)
        raise TimeoutError(
            f"vector pump: {missing}/{len(tag_l)} rows uncommitted after "
            f"{max_rounds} rounds")

    def run_close_block(self, rows: list) -> list[int]:
        """A session's end as one vector turn: ``rows`` are ``(machine,
        session, spec)`` of the instances the ended session owned whose
        machines close them in one device op (``close_spec``). The open
        window's chains are drained first, as :meth:`run_excl` drains
        them, the rows ride ONE staged block, and the events they cause
        are in the host's buffer when this returns. Raw results aligned
        with ``rows``."""
        if self._window is not None and self._window.busy:
            self._window.barrier()
        opcodes, a, b, c, _kinds = zip(*(spec for _, _, spec in rows))
        raws = self.run_vector([machine._group for machine, _, _ in rows],
                               opcodes, a, b, c)
        self.count_session_end(chain=False, instances=len(rows))
        return raws

    def run_query_vector(self, groups_idx, opcodes, a, b, c) -> list[int]:
        """The batched READ pump's device leg: evaluate every row through
        ONE :func:`~copycat_tpu.ops.consensus.query_step` engine round
        (``RaftGroups.drive_query_vector``) instead of a blocking
        ``serve_query`` device round-trip per read. No log append, no
        state change — serving is leader-applied-state only, exactly the
        per-op :meth:`query` lane's semantics."""
        groups = self._ensure()
        return groups.drive_query_vector(
            groups_idx, opcodes, a, b, c).tolist()

    def stage_query_vector(self, groups_idx, opcodes, a, b, c) -> Any:
        """The read pump's rows marshalled ahead of a parked vector run,
        so that :meth:`run_vector` can take them along in the run's round
        (``RaftGroups.stage_query_vector``)."""
        return self._ensure().stage_query_vector(groups_idx, opcodes,
                                                 a, b, c)

    def finish_query_vector(self, query: Any) -> list[int]:
        """Results of staged read rows, aligned with them: what the run's
        round answered, and one evaluation of their own for what it did
        not (``RaftGroups.finish_query_vector``)."""
        return self._ensure().finish_query_vector(query).tolist()


class _Held:
    """Retained commit + optional host-side value + TTL timer.

    Mirrors the CPU machines' retained-commit discipline
    (``collections/state.py``): the commit is cleaned exactly when its
    effect is superseded, keeping CPU-log compaction correct while the
    value itself lives on device (``on_device=True``) or host-side
    (shadow overflow / non-int32 payloads).
    """

    __slots__ = ("commit", "value", "on_device", "timer")

    def __init__(self, commit: Commit, value: Any = None,
                 on_device: bool = False):
        self.commit = commit
        self.value = value
        self.on_device = on_device
        self.timer = None

    def discard(self) -> None:
        if self.timer is not None:
            self.timer.cancel()
            self.timer = None
        self.commit.clean()


# Vector-op finalize kinds (vector_spec's last element): how the host
# bookkeeping consumes the device result at the batched pump's finalize.
VK_CAS, VK_GET_AND_SET, VK_SET = 1, 2, 3
VK_MAP_PUT, VK_MAP_REMOVE, VK_MAP_PUT_IF_ABSENT, VK_MAP_REPLACE = 4, 5, 6, 7
VK_LOCK, VK_TRY_LOCK, VK_UNLOCK = 8, 9, 10
VK_ELECT_LISTEN, VK_ELECT_RESIGN = 11, 12

# Query-spec finalize kinds (query_spec's last element). Reads never
# mutate host bookkeeping, so the only consumption modes are the raw
# device int and its truthiness.
QK_RAW, QK_BOOL = 1, 2
# the map's: a telling reply as the value or None, or as the value or the
# operation's default; a size as "is empty"
QK_MAP_GET, QK_MAP_DEFAULT, QK_MAP_EMPTY = 3, 4, 5


class DeviceBackedStateMachine(ResourceStateMachine):
    """Base for state machines whose data plane is a device group.

    Command handlers (and every helper that issues device ops) are
    GENERATOR functions: ``result = yield from self._cmd(...)``. ``init``
    wraps each registered generator handler so the applying server
    receives a :class:`DeviceJob` it can batch into the open
    :class:`DeviceWindow` — the shared round pump — instead of a value.
    Query handlers stay plain functions (they never append device ops —
    determinism rule #2) and serve synchronously. Host-state-only command
    handlers (e.g. value ``listen``) still run as jobs (``yield from ()``)
    so their host mutations keep log order relative to in-flight chains.
    """

    #: True for machines that consume device session events (lock grants,
    #: election promotions): their chains resume only after each op's
    #: events settle into the host buffer.
    SETTLES = False

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__()
        self._eng = engine
        self._group = group
        # skip events addressed to a predecessor resource of this group
        self._ev_cursor = engine.event_cursor(group)

    def init(self, executor) -> None:
        super().init(executor)
        executor.rewrap(self._wrap_handler)

    def _wrap_handler(self, fn):
        inner = getattr(fn, "__func__", fn)
        if not inspect.isgeneratorfunction(inner):
            return fn

        def wrapped(commit, _fn=fn):
            self._note_chain()
            return DeviceJob(self._eng, self._group, type(self).SETTLES,
                             _fn(commit))

        return wrapped

    def _note_chain(self) -> None:
        """A command of this machine runs as a generator chain (a machine
        with a vector lane counts them beside that lane's)."""

    def _cmd(self, opcode: int, a: int = 0, b: int = 0, c: int = 0):
        """Issue one device command from inside a chain:
        ``result = yield from self._cmd(...)``."""
        result = yield ("cmd", int(opcode), int(a), int(b), int(c))
        return result

    def _spawn(self, gen) -> None:
        """Hand a timer-fired device chain to the engine (window-ordered)."""
        self._eng.spawn(self._group, gen, type(self).SETTLES)

    def _run_excl(self, gen):
        """Drive a chain to completion now (delete/session-close hooks)."""
        return self._eng.run_excl(self._group, gen, type(self).SETTLES)

    def _qry(self, opcode: int, a: int = 0, b: int = 0, c: int = 0) -> int:
        return self._eng.query(self._group, opcode, a, b, c)

    def _events(self) -> list:
        evs, self._ev_cursor = self._eng.take_events(
            self._group, self._ev_cursor)
        return evs

    # -- batched server-side pump (vector lane) ---------------------------
    #
    # A machine that can express an operation as ONE device op with no
    # host side effects beyond simple result bookkeeping opts into the
    # applying server's vector lane: ``vector_spec`` classifies the op at
    # stage time (None = take the generator slow path), ``vector_finalize``
    # consumes the device result in log order. The pair must be
    # bit-identical in visible state evolution to the generator handler —
    # tests/test_spi_vector_pump.py proves it differentially against the
    # host state machines (``executor="cpu"``).

    #: True where ``vector_spec`` also takes what the handler would read
    #: as ``commit.index`` and ``commit.session``:
    #: ``vector_spec(operation, index, session)`` (a lock's waiter id IS
    #: its ``Lock`` commit's index)
    VECTOR_BY_COMMIT = False

    def vector_spec(self, operation: Any
                    ) -> tuple[int, int, int, int, int] | None:
        """(opcode, a, b, c, finalize_kind) for a vector-eligible op, or
        ``None`` when the op needs its generator handler (host shadow,
        TTLs, listeners, multi-op chains). A row that is routed is
        staged, so a machine may note it here. What ``vector_finalize``
        publishes is sealed inside the row's own entry."""
        return None

    def vector_finalize(self, kind: int, operation: Any, raw: int,
                        commit: Commit) -> Any:
        raise NotImplementedError  # pragma: no cover — spec implies finalize

    # -- a session's end as one vector turn --------------------------------
    #
    # ``ResourceManager.close`` hands the instances an ended session owned
    # to their machines together: a machine that can close an instance in
    # ONE device op says which (``close_spec``), the manager stages those
    # rows as one block (``DeviceEngine.run_close_block``) and hands each
    # its result (``close_finalize``), in the fan-out's order. The pair
    # must leave what ``close`` leaves, bit for bit.

    #: ``close_spec``'s answer where the session's instance holds nothing
    #: in this machine: closed, and no device op
    CLOSED = ()

    def close_spec(self, session: Any
                   ) -> tuple[int, int, int, int, int] | tuple | None:
        """(opcode, a, b, c, finalize_kind) where closing ``session``'s
        instance is one device op, :attr:`CLOSED` where it is none, or
        ``None``: :meth:`close` runs as it is, once the rows staged
        before it have landed."""
        return None

    def close_finalize(self, session: Any, spec: tuple, raw: int) -> None:
        raise NotImplementedError  # pragma: no cover — spec implies finalize

    # -- batched read pump (query vector lane) -----------------------------
    #
    # The read-side analog of vector_spec/vector_finalize: a machine
    # whose query handler is exactly ONE device query (no host shadow, no
    # host-only answer) opts its reads into the applying server's read
    # window, which evaluates the whole window through one query_step
    # engine round. The pair must return exactly what the plain query
    # handler returns — tests/test_spi_read_pump.py proves it
    # differentially against the host state machines.

    def query_spec(self, operation: Any
                   ) -> tuple[int, int, int, int, int] | None:
        """(opcode, a, b, c, finalize_kind) for a read servable as ONE
        device query, or ``None`` when the read needs its handler (host
        shadow values, host-derived answers, mixed host/device state)."""
        return None

    #: A read window that finds a vector run parked routes its reads
    #: BEFORE the run's rows are finalized, so that they ride the run's
    #: round. True where that cannot change a reply: ``vector_finalize``
    #: writes nothing ``query_spec`` reads and leaves no device work
    #: behind. Elsewhere a read of a machine with a parked row is routed
    #: after the run has landed, as every read was
    #: (``RaftGroup._route_ahead``; tests/test_read_joins_round.py holds
    #: each class with a ``query_spec`` to its answer).
    ROUTE_OUTLIVES_FINALIZE = False

    def query_finalize(self, kind: int, operation: Any, raw: int) -> Any:
        """Shape the raw device int like the plain handler's return."""
        return bool(raw) if kind == QK_BOOL else raw

    def delete(self) -> None:
        self._eng.release(self._group)


# ---------------------------------------------------------------------------
# value / long
# ---------------------------------------------------------------------------

class DeviceAtomicValueState(DeviceBackedStateMachine):
    """Linearizable register: int32 values live in the device register;
    ``None``/non-int32 payloads shadow host-side (semantics identical to
    ``AtomicValueState`` — reference ``AtomicValueState.java:32``)."""

    _UNSET = object()

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        self._held: _Held | None = None      # None = register unset
        self._shadow: Any = self._UNSET      # host value when not on device
        self._listeners: dict[int, Commit] = {}
        self._timer = None

    # -- current value -----------------------------------------------------

    def _value(self) -> Any:
        if self._held is None:
            return None
        if self._held.on_device:
            return self._qry(ops().OP_VALUE_GET)
        return self._held.value

    def _set_current(self, commit: Commit, value: Any, ttl: float | None):
        """Install ``value``; returns the previous value. One device
        command at most (GET_AND_SET covers the device→device case)."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        was_device = self._held is not None and self._held.on_device
        if self._held is not None:
            previous_host = None if was_device else self._held.value
            self._held.discard()
        else:
            previous_host = None
        if _devint(value):
            previous_dev = yield from self._cmd(
                ops().OP_VALUE_GET_AND_SET, value)
            previous = previous_dev if was_device else previous_host
            self._held = _Held(commit, on_device=True)
        else:
            if was_device:
                previous = yield from self._cmd(ops().OP_VALUE_GET_AND_SET, 0)
            else:
                previous = previous_host
            self._held = _Held(commit, value=value)
        if ttl:
            self._arm_ttl(ttl)
        return previous

    def _arm_ttl(self, ttl: float) -> None:
        held = self._held

        def expire() -> None:  # fires at log time; the chain drives ordered
            def chain():
                if self._held is held:
                    yield from self._clear_value()
                    self._publish_change(None)

            self._spawn(chain())

        self._timer = self.executor.schedule(ttl, expire)

    def _clear_value(self):
        if self._held is not None:
            if self._held.on_device:
                yield from self._cmd(ops().OP_VALUE_SET, 0)
            self._held.discard()
            self._held = None
        self._timer = None

    # -- handlers ----------------------------------------------------------

    def get(self, commit: Commit[vc.Get]) -> Any:
        try:
            return self._value()
        finally:
            commit.close()

    def set(self, commit: Commit[vc.Set]) -> None:
        op = commit.operation
        previous = yield from self._set_current(commit, op.value, op.ttl)
        if previous != op.value:
            self._publish_change(op.value)

    def get_and_set(self, commit: Commit[vc.GetAndSet]) -> Any:
        op = commit.operation
        previous = yield from self._set_current(commit, op.value, op.ttl)
        if previous != op.value:
            self._publish_change(op.value)
        return previous

    def compare_and_set(self, commit: Commit[vc.CompareAndSet]) -> bool:
        op = commit.operation
        if (self._held is not None and self._held.on_device
                and _devint(op.expect) and _devint(op.update)):
            # single device CAS — the hot path (BASELINE config #1)
            if (yield from self._cmd(ops().OP_VALUE_CAS, op.expect,
                                     op.update)):
                self._held.discard()
                self._held = _Held(commit, on_device=True)
                self._reschedule_ttl(op.ttl)
                if op.update != op.expect:
                    self._publish_change(op.update)
                return True
            commit.clean()
            return False
        if self._value() == op.expect:
            yield from self._set_current(commit, op.update, op.ttl)
            if op.update != op.expect:
                self._publish_change(op.update)
            return True
        commit.clean()
        return False

    def _reschedule_ttl(self, ttl: float | None) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if ttl:
            self._arm_ttl(ttl)

    # -- snapshot hooks (crash-recovery plane, docs/DURABILITY.md) --------
    # The device register itself rides the engine's checkpoint blob; the
    # host bookkeeping here is one held-value record. States with armed
    # TTL timers or live change listeners opt OUT (NotImplemented) — they
    # hold commit references that cannot round-trip a snapshot, and the
    # manager then keeps the whole server on the replay-only recovery
    # path instead of persisting a lossy image.

    def snapshot_state(self) -> Any:
        if self._timer is not None or self._listeners:
            return NotImplemented
        held = None
        if self._held is not None:
            held = {"on_device": self._held.on_device,
                    "value": None if self._held.on_device
                    else self._held.value}
        return {"held": held}

    def restore_state(self, data: Any, sessions: dict) -> None:
        held = data["held"]
        if held is not None:
            # the creating commit is behind the snapshot boundary — its
            # log entry is already released, so a log-less stand-in
            # (clean() is a no-op) keeps the retained-commit discipline
            stand_in = Commit(0, None, 0.0, None, None)
            self._held = _Held(stand_in, value=held["value"],
                               on_device=held["on_device"])

    # -- edge read tier (docs/EDGE_READS.md) -------------------------------
    # The post-apply state row: device-resident values answer through
    # one device query (evaluated at delta-flush time, after the turn's
    # fused rows landed), host shadows answer from host state. An armed
    # TTL expires via a timer outside the apply path — invisible to the
    # delta plane's dirty marking — so TTL'd state opts out, retiring
    # its subscribers (the snapshot_state rule).

    def edge_state(self) -> Any:
        if self._timer is not None:
            return NotImplemented
        return ("val", self._value())

    # -- vector lane (batched server-side pump) ---------------------------
    # Eligible only in the steady device-resident state: value held ON
    # DEVICE, no TTL timer armed, no change listeners, devint payloads,
    # no TTL on the op. Under those gates each handler is exactly one
    # device op plus a held-commit swap, and within a vector run the
    # state stays in this regime (every eligible op leaves the value on
    # device), so stage-time classification remains valid at finalize.

    def vector_spec(self, operation: Any
                    ) -> tuple[int, int, int, int, int] | None:
        held = self._held
        if (held is None or not held.on_device or self._listeners
                or self._timer is not None):
            return None
        t = type(operation)
        if t is vc.CompareAndSet:
            if (operation.ttl or not _devint(operation.expect)
                    or not _devint(operation.update)):
                return None
            return (ops().OP_VALUE_CAS, operation.expect,
                    operation.update, 0, VK_CAS)
        if t is vc.GetAndSet:
            if operation.ttl or not _devint(operation.value):
                return None
            return (ops().OP_VALUE_GET_AND_SET, operation.value, 0, 0,
                    VK_GET_AND_SET)
        if t is vc.Set:
            if operation.ttl or not _devint(operation.value):
                return None
            return (ops().OP_VALUE_GET_AND_SET, operation.value, 0, 0,
                    VK_SET)
        return None

    def vector_finalize(self, kind: int, operation: Any, raw: int,
                        commit: Commit) -> Any:
        if kind == VK_CAS:
            # mirror of the generator's device-CAS arm (truthiness
            # included): success swaps the held commit, failure cleans
            if raw:
                self._held.discard()
                self._held = _Held(commit, on_device=True)
                return True
            commit.clean()
            return False
        # VK_GET_AND_SET / VK_SET: one GET_AND_SET, held commit swap
        # (the generator's _set_current with was_device=True, no TTL)
        self._held.discard()
        self._held = _Held(commit, on_device=True)
        return raw if kind == VK_GET_AND_SET else None

    # -- read pump (query vector lane) -------------------------------------
    # A get is one device query exactly when the value is held ON DEVICE
    # (host-shadowed and unset values answer from host state); listeners
    # and TTL timers don't gate reads — get never touches them.

    def query_spec(self, operation: Any
                   ) -> tuple[int, int, int, int, int] | None:
        if (type(operation) is vc.Get and self._held is not None
                and self._held.on_device):
            return (ops().OP_VALUE_GET, 0, 0, 0, QK_RAW)
        return None

    #: every vector-eligible op leaves the value held on the device (see
    #: the vector lane above), which is all ``query_spec`` reads
    ROUTE_OUTLIVES_FINALIZE = True

    # -- change listeners (same protocol as the CPU machine) ---------------
    # listen/unlisten are host-state-only but still run as ordered jobs
    # (``yield from ()``): a later listen must not observe state ahead of
    # an earlier in-flight set/CAS chain's publish.

    def listen(self, commit: Commit[vc.Listen]) -> None:
        yield from ()
        previous = self._listeners.get(commit.session.id)
        if previous is not None:
            previous.clean()
        self._listeners[commit.session.id] = commit

    def unlisten(self, commit: Commit[vc.Unlisten]) -> None:
        yield from ()
        previous = self._listeners.pop(commit.session.id, None)
        if previous is not None:
            previous.clean()
        commit.clean()

    def _publish_change(self, value: Any) -> None:
        for listen_commit in list(self._listeners.values()):
            if listen_commit.session.is_open:
                listen_commit.session.publish("change", value)

    def close(self, session: Any) -> None:
        listen_commit = self._listeners.pop(session.id, None)
        if listen_commit is not None:
            listen_commit.clean()

    def delete(self) -> None:
        def chain():
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            if self._held is not None:
                if self._held.on_device:
                    # reset for group reuse
                    yield from self._cmd(ops().OP_VALUE_SET, 0)
                self._held.discard()
                self._held = None
            for listen_commit in self._listeners.values():
                listen_commit.clean()
            self._listeners.clear()

        self._run_excl(chain())
        super().delete()


# ---------------------------------------------------------------------------
# map
# ---------------------------------------------------------------------------

#: the commands ``DeviceMapState.vector_spec`` takes
_MAP_VECTOR = frozenset({cc.MapPut, cc.MapRemove, cc.MapPutIfAbsent,
                         cc.MapReplace})
_MISSING = object()


class DeviceMapState(DeviceBackedStateMachine):
    """Hashed map: int32 (key, value) entries live in the device probe
    table; overflow and non-int32 payloads take the host shadow — a put
    into a full bucket SUCCEEDS transparently (SURVEY.md §7.3 #1
    "eviction-to-host for overflow"; the reference ``MapState.java:32``
    has no capacity bound, so neither may we).

    The device answers for what it holds: presence, value and size of
    the table come from it (``MAP_TELL`` replies tell an absent key from
    a stored 0), and the host keeps no record to answer from. What the
    host keeps: ``_held``, a record for each entry with a value on the
    host (shadow) or a TTL timer; and ``_commits``, the retained commit
    of each plain device entry, which a superseding command cleans
    (the log's compaction contract) and no answer reads."""

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        # key -> _Held: host-shadowed values, and device entries under a
        # TTL timer (on_device=True)
        self._held: dict[Any, _Held] = {}
        # key -> retained commit of a device entry without a record
        self._commits: dict[int, Commit] = {}
        # the device table may hold entries (a host-only map asks it
        # nothing)
        self._device = False

    def _note_chain(self) -> None:
        self._eng.count_map_op(chain=True)

    # -- internals ---------------------------------------------------------

    def _shadows(self) -> int:
        return sum(1 for h in self._held.values() if not h.on_device)

    def _record(self, key: Any, held: _Held | None) -> None:
        """Put (or with ``None`` drop) the host record of ``key``,
        keeping the engine's count of shadowed keys."""
        previous = self._held.pop(key, None)
        delta = int(held is not None and not held.on_device) \
            - int(previous is not None and not previous.on_device)
        if held is not None:
            self._held[key] = held
        if delta:
            self._eng.count_shadow(delta)

    def _retain(self, key: int, commit: Commit | None) -> None:
        """``commit`` is now what holds device entry ``key`` (``None``:
        nothing does); what held it before is superseded."""
        previous = self._commits.pop(key, None)
        if previous is not None:
            previous.clean()
        if commit is not None:
            self._commits[key] = commit
            self._device = True

    def _on_device(self, key: Any) -> bool:
        """May the device table hold ``key``? (Not where the host
        shadows it: a key lives in one place.)"""
        held = self._held.get(key)
        return (self._device and _devint(key)
                and (held is None or held.on_device))

    @staticmethod
    def _told(raw: int) -> Any:
        o = ops()
        return None if raw == o.ABSENT or raw == o.FULL else raw

    def _store(self, key: Any, value: Any, commit: Commit, ttl: float | None):
        """Insert/overwrite ``key``; returns the previous value."""
        o = ops()
        held = self._held.get(key)
        shadowed = held is not None and not held.on_device
        previous = held.value if shadowed else None
        on_device = False
        if shadowed or not _devint(key):
            pass                      # a shadowed key stays on the host
        elif _devint(value):
            raw = yield from self._cmd(o.OP_MAP_PUT, key, value, o.MAP_TELL)
            previous = self._told(raw)
            on_device = raw != o.FULL
        elif self._on_device(key):
            previous = self._told((yield from self._cmd(
                o.OP_MAP_REMOVE, key, 0, o.MAP_TELL)))
        if held is not None:
            held.discard()
        new = None
        if ttl or not on_device:
            new = _Held(commit, value=None if on_device else value,
                        on_device=on_device)
        self._record(key, new)
        if _devint(key):
            self._retain(key, commit if on_device and new is None else None)
        self._device = self._device or on_device
        if ttl:
            def expire() -> None:
                def chain():
                    if self._held.get(key) is new:
                        yield from self._evict(key)

                self._spawn(chain())

            new.timer = self.executor.schedule(ttl, expire)
        return previous

    def _find(self, key: Any) -> Any:
        """The value under ``key``, or ``_MISSING``."""
        held = self._held.get(key)
        if held is not None and not held.on_device:
            return held.value
        if self._on_device(key):
            o = ops()
            raw = self._qry(o.OP_MAP_GET, key, 0, o.MAP_TELL)
            if raw != o.ABSENT:
                return raw
        return _MISSING

    def _read(self, key: Any, default: Any = None) -> Any:
        found = self._find(key)
        return default if found is _MISSING else found

    def _evict(self, key: Any):
        """Remove ``key`` wherever it lives; returns the value it had."""
        held = self._held.get(key)
        previous = None
        if held is not None and not held.on_device:
            previous = held.value
        elif self._on_device(key):
            o = ops()
            previous = self._told((yield from self._cmd(
                o.OP_MAP_REMOVE, key, 0, o.MAP_TELL)))
        if held is not None:
            held.discard()
            self._record(key, None)
        if _devint(key):
            self._retain(key, None)
        return previous

    # -- queries -----------------------------------------------------------

    def contains_key(self, commit: Commit[cc.MapContainsKey]) -> bool:
        try:
            return self._find(commit.operation.key) is not _MISSING
        finally:
            commit.close()

    def contains_value(self, commit: Commit[cc.MapContainsValue]) -> bool:
        try:
            value = commit.operation.value
            if _devint(value) and self._device:
                if self._qry(ops().OP_MAP_CONTAINS_VALUE, value):
                    return True
            return any((not h.on_device) and h.value == value
                       for h in self._held.values())
        finally:
            commit.close()

    def get(self, commit: Commit[cc.MapGet]) -> Any:
        try:
            return self._read(commit.operation.key)
        finally:
            commit.close()

    def get_or_default(self, commit: Commit[cc.MapGetOrDefault]) -> Any:
        try:
            return self._read(commit.operation.key,
                              commit.operation.default)
        finally:
            commit.close()

    def _size(self) -> int:
        device = self._qry(ops().OP_MAP_SIZE) if self._device else 0
        return device + self._shadows()

    def is_empty(self, commit: Commit[cc.MapIsEmpty]) -> bool:
        try:
            return self._size() == 0
        finally:
            commit.close()

    def size(self, commit: Commit[cc.MapSize]) -> int:
        try:
            return self._size()
        finally:
            commit.close()

    # -- read pump (query vector lane) -------------------------------------
    # A keyed read is one device query whenever the key is a device
    # integer the host does not shadow: the device says whether it holds
    # it. Size and emptiness are one query while nothing is shadowed.

    def query_spec(self, operation: Any
                   ) -> tuple[int, int, int, int, int] | None:
        if not self._device:
            return None
        t = type(operation)
        o = ops()
        if t is cc.MapGet or t is cc.MapGetOrDefault:
            if self._on_device(operation.key):
                return (o.OP_MAP_GET, operation.key, 0, o.MAP_TELL,
                        QK_MAP_GET if t is cc.MapGet else QK_MAP_DEFAULT)
        elif t is cc.MapContainsKey:
            if self._on_device(operation.key) \
                    and operation.key not in self._held:
                return (o.OP_MAP_CONTAINS_KEY, operation.key, 0, 0, QK_BOOL)
        elif t is cc.MapSize or t is cc.MapIsEmpty:
            if not self._shadows():
                return (o.OP_MAP_SIZE, 0, 0, 0,
                        QK_RAW if t is cc.MapSize else QK_MAP_EMPTY)
        return None

    def query_finalize(self, kind: int, operation: Any, raw: int) -> Any:
        if kind == QK_MAP_GET:
            return self._told(raw)
        if kind == QK_MAP_DEFAULT:
            return operation.default if raw == ops().ABSENT else raw
        if kind == QK_MAP_EMPTY:
            return raw == 0
        return super().query_finalize(kind, operation, raw)

    # -- vector lane (batched server-side pump) ---------------------------
    # A keyed command on a device integer with no record on the host (no
    # shadow, no timer), an int32 value and no TTL is ONE device op: the
    # telling reply is the handler's answer, so nothing is read first.
    # A put that finds its bucket full is shadowed at finalize; a later
    # row of the same run on that key was classified before that, and
    # ``vector_finalize`` answers it from the record (``_after_full``).

    def vector_spec(self, operation: Any
                    ) -> tuple[int, int, int, int, int] | None:
        t = type(operation)
        if t not in _MAP_VECTOR:
            return None
        key = operation.key
        if not _devint(key) or key in self._held:
            return None
        o = ops()
        if t is cc.MapRemove:
            return (o.OP_MAP_REMOVE, key, 0, o.MAP_TELL, VK_MAP_REMOVE)
        if operation.ttl or not _devint(operation.value):
            return None
        if t is cc.MapPut:
            return (o.OP_MAP_PUT, key, operation.value, o.MAP_TELL,
                    VK_MAP_PUT)
        if t is cc.MapPutIfAbsent:
            return (o.OP_MAP_PUT_IF_ABSENT, key, operation.value,
                    o.MAP_TELL, VK_MAP_PUT_IF_ABSENT)
        if t is cc.MapReplace:
            return (o.OP_MAP_REPLACE, key, operation.value, o.MAP_TELL,
                    VK_MAP_REPLACE)
        return None

    def vector_finalize(self, kind: int, operation: Any, raw: int,
                        commit: Commit) -> Any:
        self._eng.count_map_op(chain=False)
        key = operation.key
        if key in self._held:
            return self._after_full(kind, operation, commit)
        o = ops()
        previous = self._told(raw)
        if kind == VK_MAP_REMOVE:
            commit.clean()
            self._retain(key, None)
            return previous
        if raw == o.FULL:        # the reference's map has no bound
            self._record(key, _Held(commit, value=operation.value))
            return None
        if kind == VK_MAP_PUT or raw == o.ABSENT \
                and kind == VK_MAP_PUT_IF_ABSENT:
            self._retain(key, commit)
            return previous
        if kind == VK_MAP_REPLACE and raw != o.ABSENT:
            self._retain(key, commit)
            return previous
        commit.clean()           # found (put_if_absent), absent (replace)
        return previous

    def _after_full(self, kind: int, operation: Any, commit: Commit) -> Any:
        """The row's key was shadowed since the run was staged (its
        bucket was full): the record answers, and whatever the row's
        own device op left in the table under the key goes."""
        key = operation.key
        held = self._held[key]
        previous = held.value
        if kind != VK_MAP_REMOVE:
            def chain():
                yield from self._cmd(ops().OP_MAP_REMOVE, key)

            self._spawn(chain())
        if kind == VK_MAP_PUT_IF_ABSENT:
            commit.clean()
            return previous
        held.discard()
        if kind == VK_MAP_REMOVE:
            commit.clean()
            self._record(key, None)
        else:
            self._record(key, _Held(commit, value=operation.value))
        return previous

    # -- commands ----------------------------------------------------------

    def put(self, commit: Commit[cc.MapPut]) -> Any:
        op = commit.operation
        return (yield from self._store(op.key, op.value, commit, op.ttl))

    def put_if_absent(self, commit: Commit[cc.MapPutIfAbsent]) -> Any:
        op = commit.operation
        found = self._find(op.key)
        if found is not _MISSING:
            commit.clean()
            return found
        yield from self._store(op.key, op.value, commit, op.ttl)
        return None

    def remove(self, commit: Commit[cc.MapRemove]) -> Any:
        commit.clean()
        return (yield from self._evict(commit.operation.key))

    def remove_if_present(self, commit: Commit[cc.MapRemoveIfPresent]) -> bool:
        op = commit.operation
        commit.clean()
        if self._find(op.key) != op.value:
            return False
        yield from self._evict(op.key)
        return True

    def replace(self, commit: Commit[cc.MapReplace]) -> Any:
        op = commit.operation
        if self._find(op.key) is _MISSING:
            commit.clean()
            return None
        return (yield from self._store(op.key, op.value, commit, op.ttl))

    def replace_if_present(self, commit: Commit[cc.MapReplaceIfPresent]) -> bool:
        op = commit.operation
        if self._find(op.key) != op.expect:
            commit.clean()
            return False
        yield from self._store(op.key, op.value, commit, op.ttl)
        return True

    def _reset(self):
        """Empty the map on both sides (clear, delete)."""
        if self._device:
            yield from self._cmd(ops().OP_MAP_CLEAR)
            self._device = False
        for key in list(self._held):
            self._held[key].discard()
            self._record(key, None)
        for retained in self._commits.values():
            retained.clean()
        self._commits.clear()

    def clear(self, commit: Commit[cc.MapClear]) -> None:
        yield from self._reset()
        commit.clean()

    # -- snapshot hooks (crash-recovery plane, docs/DURABILITY.md) --------
    # The device table rides the engine's checkpoint blob, and with it
    # every key it holds: the host writes one record for each key it
    # SHADOWS, and whether the table may hold any. Armed per-key TTL
    # timers hold commit references that cannot round-trip — opt out
    # (NotImplemented) and keep the whole manager on replay-only
    # recovery, like the value machine.

    def snapshot_state(self) -> Any:
        if any(h.timer is not None for h in self._held.values()):
            return NotImplemented
        return {"held": [(k, False, h.value)
                         for k, h in self._held.items()],
                # (a machine built without __init__ holds nothing there)
                **({"device": True} if getattr(self, "_device", False)
                   else {})}

    def restore_state(self, data: Any, sessions: dict) -> None:
        self._device = bool(data.get("device"))
        for key, on_device, value in data["held"]:
            if on_device:    # an image from before the table answered
                self._device = True
                continue
            # creating commits are behind the snapshot boundary: log-less
            # stand-ins (clean() is a no-op) keep the retained-commit
            # discipline
            self._record(key, _Held(Commit(0, None, 0.0, None, None),
                                    value=value))

    # -- edge read tier (docs/EDGE_READS.md): full-state delta ------------
    # The keys of the device table are the device's to know, so a map
    # that may hold any there serves no edge state (its subscribers
    # retire, the snapshot_state rule); a map held on the host alone
    # serves its records. Armed per-key TTLs opt out (timers fire
    # outside the apply path — the value machine's rule).

    def edge_state(self) -> Any:
        if self._device or any(
                h.timer is not None for h in self._held.values()):
            return NotImplemented
        return ("map", {k: h.value for k, h in self._held.items()})

    def delete(self) -> None:
        self._run_excl(self._reset())   # reset for group reuse
        super().delete()


# ---------------------------------------------------------------------------
# set
# ---------------------------------------------------------------------------

class DeviceSetState(DeviceBackedStateMachine):
    """Set: int32 members live in the device probe table, overflow/non-int32
    members shadow host-side (reference ``SetState.java:32``)."""

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        self._held: dict[Any, _Held] = {}

    def add(self, commit: Commit[cc.SetAdd]) -> bool:
        op = commit.operation
        if op.value in self._held:
            commit.clean()
            return False
        if _devint(op.value):
            added = yield from self._cmd(ops().OP_SET_ADD, op.value)
        else:
            added = FAIL()
        if added not in (FAIL(), 0):
            held = _Held(commit, on_device=True)
        else:
            held = _Held(commit, value=op.value)
        self._held[op.value] = held
        if op.ttl:
            def expire() -> None:
                def chain():
                    if self._held.get(op.value) is held:
                        yield from self._evict(op.value, held)

                self._spawn(chain())

            held.timer = self.executor.schedule(op.ttl, expire)
        return True

    def _evict(self, value: Any, held: _Held):
        del self._held[value]
        if held.on_device:
            yield from self._cmd(ops().OP_SET_REMOVE, value)
        held.discard()

    def remove(self, commit: Commit[cc.SetRemove]) -> bool:
        commit.clean()
        held = self._held.get(commit.operation.value)
        if held is None:
            return False
        yield from self._evict(commit.operation.value, held)
        return True

    def contains(self, commit: Commit[cc.SetContains]) -> bool:
        try:
            return commit.operation.value in self._held
        finally:
            commit.close()

    def is_empty(self, commit: Commit[cc.SetIsEmpty]) -> bool:
        try:
            return not self._held
        finally:
            commit.close()

    def size(self, commit: Commit[cc.SetSize]) -> int:
        try:
            return len(self._held)
        finally:
            commit.close()

    def clear(self, commit: Commit[cc.SetClear]) -> None:
        if any(h.on_device for h in self._held.values()):
            yield from self._cmd(ops().OP_SET_CLEAR)
        for held in self._held.values():
            held.discard()
        self._held.clear()
        commit.clean()

    # -- snapshot hooks (crash-recovery plane, docs/DURABILITY.md) --------
    # Same shape as the map machine: members on the device table ride
    # the engine blob, host shadows serialize here; armed TTL timers
    # opt the machine out.

    def snapshot_state(self) -> Any:
        if any(h.timer is not None for h in self._held.values()):
            return NotImplemented
        return {"held": [(v, h.on_device) for v, h in self._held.items()]}

    def restore_state(self, data: Any, sessions: dict) -> None:
        for value, on_device in data["held"]:
            self._held[value] = _Held(Commit(0, None, 0.0, None, None),
                                      value=None if on_device else value,
                                      on_device=on_device)

    # -- edge read tier (docs/EDGE_READS.md): full-state delta ------------
    # (membership is host-authoritative — `contains` never queries the
    # device — so no device round is needed; TTLs opt out as above)

    def edge_state(self) -> Any:
        if any(h.timer is not None for h in self._held.values()):
            return NotImplemented
        return ("set", list(self._held.keys()))

    def delete(self) -> None:
        def chain():
            if any(h.on_device for h in self._held.values()):
                # reset for group reuse
                yield from self._cmd(ops().OP_SET_CLEAR)
            for held in self._held.values():
                held.discard()
            self._held.clear()

        self._run_excl(chain())
        super().delete()


class DeviceMultiMapState(DeviceBackedStateMachine):
    """Multimap: int32 (key, value) pairs live in the device pair-probe
    table (``ops/apply.py`` OP_MM_*), overflow and non-int32 payloads
    shadow host-side; the host retains commits per pair (the reference's
    nested ``Map<Object, Map<Object, Commit>>`` discipline,
    ``MultiMapState.java:30``)."""

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        # (key, value) -> _Held; on_device=True ⇒ pair lives on device
        self._held: dict[tuple, _Held] = {}

    def _evict(self, pair: tuple, held: _Held):
        del self._held[pair]
        if held.on_device:
            yield from self._cmd(ops().OP_MM_REMOVE_ENTRY, pair[0], pair[1])
        held.discard()

    def put(self, commit: Commit[cc.MultiMapPut]) -> bool:
        op = commit.operation
        pair = (op.key, op.value)
        if pair in self._held:
            commit.clean()
            return False
        if _devint(op.key) and _devint(op.value):
            placed = yield from self._cmd(ops().OP_MM_PUT, op.key, op.value)
        else:
            placed = FAIL()
        if placed not in (FAIL(), 0):
            held = _Held(commit, on_device=True)
        else:
            held = _Held(commit)
        self._held[pair] = held
        if op.ttl:
            def expire() -> None:
                def chain():
                    if self._held.get(pair) is held:
                        yield from self._evict(pair, held)

                self._spawn(chain())

            held.timer = self.executor.schedule(op.ttl, expire)
        return True

    def get(self, commit: Commit[cc.MultiMapGet]) -> list:
        try:
            key = commit.operation.key
            return [v for (k, v) in self._held if k == key]
        finally:
            commit.close()

    def remove(self, commit: Commit[cc.MultiMapRemove]) -> list:
        key = commit.operation.key
        commit.clean()
        pairs = [p for p in self._held if p[0] == key]
        if any(self._held[p].on_device for p in pairs):
            # drops every device pair
            yield from self._cmd(ops().OP_MM_REMOVE, key)
        out = []
        for pair in pairs:
            held = self._held.pop(pair)
            out.append(pair[1])
            held.discard()
        return out

    def remove_entry(self, commit: Commit[cc.MultiMapRemoveEntry]) -> bool:
        op = commit.operation
        commit.clean()
        held = self._held.get((op.key, op.value))
        if held is None:
            return False
        yield from self._evict((op.key, op.value), held)
        return True

    def contains_key(self, commit: Commit[cc.MultiMapContainsKey]) -> bool:
        try:
            key = commit.operation.key
            return any(k == key for (k, _v) in self._held)
        finally:
            commit.close()

    def contains_entry(self, commit: Commit[cc.MultiMapContainsEntry]) -> bool:
        # The host dict key IS the (key, value) pair, kept in lockstep
        # with the device table (TTLs run host-side), so it is
        # authoritative — no device round-trip needed.
        try:
            return (commit.operation.key,
                    commit.operation.value) in self._held
        finally:
            commit.close()

    def contains_value(self, commit: Commit[cc.MultiMapContainsValue]) -> bool:
        try:
            value = commit.operation.value
            return any(v == value for (_k, v) in self._held)
        finally:
            commit.close()

    def is_empty(self, commit: Commit[cc.MultiMapIsEmpty]) -> bool:
        try:
            return not self._held
        finally:
            commit.close()

    def size(self, commit: Commit[cc.MultiMapSize]) -> int:
        try:
            key = commit.operation.key
            if key is not None:
                return sum(1 for (k, _v) in self._held if k == key)
            return len(self._held)
        finally:
            commit.close()

    def clear(self, commit: Commit[cc.MultiMapClear]) -> None:
        if any(h.on_device for h in self._held.values()):
            yield from self._cmd(ops().OP_MM_CLEAR)
        for held in self._held.values():
            held.discard()
        self._held.clear()
        commit.clean()

    def delete(self) -> None:
        def chain():
            if any(h.on_device for h in self._held.values()):
                # reset for group reuse
                yield from self._cmd(ops().OP_MM_CLEAR)
            for held in self._held.values():
                held.discard()
            self._held.clear()

        self._run_excl(chain())
        super().delete()


# ---------------------------------------------------------------------------
# queue
# ---------------------------------------------------------------------------

class DeviceQueueState(DeviceBackedStateMachine):
    """FIFO queue: device ring holds int32 payloads, a host marker deque
    defines global order across device/host entries so interleaved
    overflow keeps exact FIFO semantics (reference ``QueueState.java:30``).

    Values are mirrored host-side so ``contains``/``remove(v)`` (which the
    device ring cannot serve from the middle) stay supported: a mid-ring
    removal drains and re-offers the ring minus the removed payload
    (``_tombstone_device``) — queue remove-by-value is rare, ring
    capacity small.
    """

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        self._queue: deque[_Held] = deque()  # live entries, global FIFO

    def _enqueue(self, commit: Commit, value: Any):
        if _devint(value):
            offered = yield from self._cmd(ops().OP_Q_OFFER, value)
        else:
            offered = 0
        if offered == 1:
            held = _Held(commit, value=value, on_device=True)
        else:
            held = _Held(commit, value=value)
        self._queue.append(held)
        return True

    def _device_poll(self):
        return (yield from self._cmd(ops().OP_Q_POLL))

    def _pop_head(self):
        held = self._queue.popleft()
        if held.on_device:
            yield from self._device_poll()
        held.discard()
        return held

    def add(self, commit: Commit[cc.QueueAdd]) -> bool:
        return (yield from self._enqueue(commit, commit.operation.value))

    def offer(self, commit: Commit[cc.QueueOffer]) -> bool:
        return (yield from self._enqueue(commit, commit.operation.value))

    def peek(self, commit: Commit[cc.QueuePeek]) -> Any:
        try:
            return self._queue[0].value if self._queue else None
        finally:
            commit.close()

    def poll(self, commit: Commit[cc.QueuePoll]) -> Any:
        commit.clean()
        if not self._queue:
            return None
        held = yield from self._pop_head()
        return held.value

    def element(self, commit: Commit[cc.QueueElement]) -> Any:
        yield from ()
        commit.clean()
        if not self._queue:
            raise ValueError("queue is empty")
        return self._queue[0].value

    def remove(self, commit: Commit[cc.QueueRemove]) -> Any:
        op = commit.operation
        commit.clean()
        if op.value is None:
            if not self._queue:
                raise ValueError("queue is empty")
            held = yield from self._pop_head()
            return held.value
        for held in self._queue:
            if held.value == op.value:
                if held is self._queue[0]:
                    yield from self._pop_head()
                else:
                    # mid-queue: tombstone; the device copy (if any) is
                    # drained when it reaches the ring head
                    self._queue.remove(held)
                    if held.on_device:
                        yield from self._tombstone_device(held)
                    held.discard()
                return True
        return False

    def _tombstone_device(self, held: _Held):
        # Re-synchronize the ring with the live deque: device entries
        # before this one are still live; we pop-and-reoffer the ring so
        # the removed payload is dropped. Device ring order == order of
        # on_device entries in self._queue, so draining/refilling keeps it.
        live_device = [h.value for h in self._queue if h.on_device]
        while (yield from self._device_poll()) != FAIL():
            pass
        for v in live_device:
            yield from self._cmd(ops().OP_Q_OFFER, v)

    def contains(self, commit: Commit[cc.QueueContains]) -> bool:
        try:
            return any(h.value == commit.operation.value
                       for h in self._queue)
        finally:
            commit.close()

    def is_empty(self, commit: Commit[cc.QueueIsEmpty]) -> bool:
        try:
            return not self._queue
        finally:
            commit.close()

    def size(self, commit: Commit[cc.QueueSize]) -> int:
        try:
            return len(self._queue)
        finally:
            commit.close()

    def clear(self, commit: Commit[cc.QueueClear]) -> None:
        if any(h.on_device for h in self._queue):
            yield from self._cmd(ops().OP_Q_CLEAR)
        for held in self._queue:
            held.discard()
        self._queue.clear()
        commit.clean()

    def delete(self) -> None:
        def chain():
            if any(h.on_device for h in self._queue):
                # reset for group reuse
                yield from self._cmd(ops().OP_Q_CLEAR)
            for held in self._queue:
                held.discard()
            self._queue.clear()

        self._run_excl(chain())
        super().delete()


# ---------------------------------------------------------------------------
# restored waiters and listeners (lock, leader election)
# ---------------------------------------------------------------------------

class _UnboundSession:
    """What a restored waiter or listener holds until the manager
    re-registers its instance's session (``ResourceManager.restore_state``
    calls ``register`` for every restored instance, after the machines'
    ``restore_state``): it carries the instance id and counts as closed,
    so nothing is published to it."""

    __slots__ = ("id",)
    is_open = False

    def __init__(self, instance_id: int) -> None:
        self.id = instance_id

    def publish(self, event: str, message: Any = None) -> None:
        pass


def _rebind(commits: Iterable[Commit], session: Any) -> None:
    """Give restored stand-in commits of ``session``'s instance the live
    session back."""
    for commit in commits:
        if type(commit.session) is _UnboundSession \
                and commit.session.id == session.id:
            commit.session = session


# ---------------------------------------------------------------------------
# lock
# ---------------------------------------------------------------------------

class _LockAhead:
    """A lock as the rows staged for one vector run (``epoch``) will leave
    it: the holder's waiter id and its session's id, and the queue's
    ``(waiter id, session id)`` in order."""

    __slots__ = ("epoch", "holder", "session", "queue")

    def __init__(self, epoch: int, holder: int | None, session: Any,
                 queue: deque) -> None:
        self.epoch = epoch
        self.holder = holder
        self.session = session
        self.queue = queue


class DeviceLockState(DeviceBackedStateMachine):
    """Mutex on the device lock kernel: waiter id = the Lock commit index
    (unique per acquire, same as the CPU machine), grants delivered as
    "lock" session events when the device emits EV_LOCK_GRANT.

    Timeouts run host-side through the replicated log-time timers and
    resolve the grant-vs-timeout race via OP_LOCK_CANCEL (totally ordered
    in the device log). Session death releases held locks and dequeues
    waiters — the capability fix over the reference, preserved from the
    CPU machine (``coordination/state.py:21-23``).
    """

    SETTLES = True  # grants arrive as device events; chains resume settled

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        self._waiters: dict[int, Commit] = {}   # waiter id -> Lock commit
        self._holder_id: int | None = None
        self._timers: dict[int, Any] = {}
        self._overflow: deque[int] = deque()    # ids the device ring rejected
        # the lock as the rows staged for the next vector run will leave
        # it (``vector_spec``)
        self._ahead: _LockAhead | None = None
        engine.count_lock_overflow(0)           # the gauge, from the first

    def _note_chain(self) -> None:
        self._eng.count_lock_op(chain=True)

    def _overflowed(self, wid: int) -> None:
        self._overflow.append(wid)
        self._eng.count_lock_overflow(1)

    # -- event pump --------------------------------------------------------

    def _pump(self, events: list | None = None):
        for _seq, code, target, _arg in (self._events() if events is None
                                         else events):
            if code != ops().EV_LOCK_GRANT:
                continue
            waiter = self._waiters.get(target)
            if waiter is None:
                # grant to a dead waiter (cancelled/closed): release it so
                # the queue keeps moving
                yield from self._cmd(ops().OP_LOCK_RELEASE, target)
                continue
            self._holder_id = target
            timer = self._timers.pop(target, None)
            if timer is not None:
                timer.cancel()
            if waiter.session.is_open:
                waiter.session.publish(
                    "lock", {"id": target, "acquired": True})
        yield from self._flush_overflow()

    def _flush_overflow(self):
        while self._overflow:
            wid = self._overflow[0]
            if wid in self._waiters:
                result = yield from self._cmd(ops().OP_LOCK_ACQUIRE, wid, -1)
                if result == 0:  # ring still full
                    break
                if result == 1:  # granted immediately (2: queued on device)
                    self._on_grant(wid)
            self._overflow.popleft()
            self._eng.count_lock_overflow(-1)

    def _on_grant(self, wid: int) -> None:
        waiter = self._waiters.get(wid)
        self._holder_id = wid
        timer = self._timers.pop(wid, None)
        if timer is not None:
            timer.cancel()
        if waiter is not None and waiter.session.is_open:
            waiter.session.publish("lock", {"id": wid, "acquired": True})

    # -- handlers ----------------------------------------------------------

    def lock(self, commit: Commit[oc.Lock]) -> int:
        wid = commit.index
        timeout = commit.operation.timeout
        yield from self._pump()
        if timeout == 0:
            result = yield from self._cmd(ops().OP_LOCK_ACQUIRE, wid, 0)
            if result == 1:
                self._waiters[wid] = commit
                self._on_grant(wid)
            else:
                commit.session.publish(
                    "lock", {"id": wid, "acquired": False})
                commit.clean()
            yield from self._pump()
            return wid
        self._waiters[wid] = commit
        if self._overflow:
            self._overflowed(wid)  # preserve FIFO behind overflow
        else:
            result = yield from self._cmd(ops().OP_LOCK_ACQUIRE, wid, -1)
            if result == 1:
                self._on_grant(wid)
            elif result == 0:  # device wait ring full — host absorbs
                self._overflowed(wid)
        if timeout and timeout > 0 and self._holder_id != wid:
            def expire() -> None:
                def chain():
                    self._timers.pop(wid, None)
                    yield from self._cancel_waiter(wid, publish=True)

                self._spawn(chain())

            self._timers[wid] = self.executor.schedule(timeout, expire)
        yield from self._pump()
        return wid

    def _cancel_waiter(self, wid: int, publish: bool):
        waiter = self._waiters.get(wid)
        if waiter is None or self._holder_id == wid:
            return
        if wid in self._overflow:
            self._overflow.remove(wid)
            self._eng.count_lock_overflow(-1)
            outcome = 1
        else:
            outcome = yield from self._cmd(ops().OP_LOCK_CANCEL, wid)
        if outcome == 2:
            # race resolved in our favor: already granted — the grant
            # event is (or will be) in the pump
            yield from self._pump()
            return
        del self._waiters[wid]
        if publish and waiter.session.is_open:
            waiter.session.publish("lock", {"id": wid, "acquired": False})
        waiter.clean()
        yield from self._pump()

    def unlock(self, commit: Commit[oc.Unlock]) -> None:
        try:
            yield from self._pump()
            if self._holder_id is None:
                return
            holder = self._waiters.get(self._holder_id)
            if holder is None or holder.session.id != commit.session.id:
                raise ValueError("not the lock holder")
            yield from self._release_holder()
        finally:
            commit.clean()

    def _release_holder(self):
        wid = self._holder_id
        holder = self._waiters.pop(wid, None)
        self._holder_id = None
        if holder is not None:
            holder.clean()
        yield from self._cmd(ops().OP_LOCK_RELEASE, wid)
        yield from self._pump()

    # -- vector lane (batched server-side pump) ---------------------------
    # With no acquire timer armed and no waiter in the host overflow the
    # host's record (``_holder_id``, ``_waiters`` in arrival order) IS the
    # device's lock, so ``Lock(-1)``, ``Lock(0)`` and ``Unlock`` are ONE
    # device op each: an acquire under the commit's index, or a release of
    # the holder. Which waiter an ``Unlock`` releases depends on the rows
    # staged before it and not yet finalized (a run may hold a lock's
    # ``Unlock`` and the next holder's), so ``vector_spec`` looks ahead:
    # ``_ahead`` is the holder and the queue as the staged rows will leave
    # them, drawn from the record when a run's first row is staged. A row
    # it cannot answer for one device op (a positive timeout, a timer
    # armed, a waiter in overflow, a ring the staged rows could fill, an
    # ``Unlock`` that is not the holder's) takes its generator handler,
    # which the pump applies after the staged rows have landed. The grant
    # a release causes comes from the device's event ring as ever:
    # ``run_vector`` returns once it is in the host's buffer, and the
    # ``Unlock`` row that caused it publishes it, one event a release.

    VECTOR_BY_COMMIT = True   # the waiter id, and whose unlock it is

    def vector_spec(self, operation: Any, index: int, session: Any
                    ) -> tuple[int, int, int, int, int] | None:
        t = type(operation)
        if t is oc.Lock:
            timeout = operation.timeout
            if timeout and timeout > 0:
                return None
        elif t is not oc.Unlock:
            return None
        if self._timers or self._overflow:
            return None
        eng = self._eng
        ahead = self._ahead
        if ahead is None or ahead.epoch != eng.vector_epoch:
            held = self._holder_id
            ahead = self._ahead = _LockAhead(
                eng.vector_epoch, held,
                None if held is None else self._waiters[held].session.id,
                deque((wid, c.session.id)
                      for wid, c in self._waiters.items() if wid != held))
        o = ops()
        if t is oc.Unlock:
            released = ahead.holder
            if released is None or ahead.session != session.id:
                return None       # nothing to release, or not the holder
            if ahead.queue:
                ahead.holder, ahead.session = ahead.queue.popleft()
                eng.expect_event(self._group, self._ev_cursor)
            else:
                ahead.holder = ahead.session = None
            return (o.OP_LOCK_RELEASE, released, 0, 0, VK_UNLOCK)
        if ahead.holder is None:
            ahead.holder, ahead.session = index, session.id
        elif timeout == 0:
            pass                  # a try-lock of a held lock is refused
        elif len(ahead.queue) >= eng.lock_wait_slots():
            return None           # the ring could refuse it: the overflow
        else:
            ahead.queue.append((index, session.id))
        if timeout == 0:
            return (o.OP_LOCK_ACQUIRE, index, 0, 0, VK_TRY_LOCK)
        return (o.OP_LOCK_ACQUIRE, index, -1, 0, VK_LOCK)

    def vector_finalize(self, kind: int, operation: Any, raw: int,
                        commit: Commit) -> Any:
        self._eng.count_lock_op(chain=False)
        if kind == VK_UNLOCK:
            # the generator's _release_holder and the pump of its one
            # event: the record, then the grant the release caused
            try:
                holder = self._waiters.pop(self._holder_id, None)
                self._holder_id = None
                if holder is not None:
                    holder.clean()
                if self._waiters:
                    self._take_grant()
            finally:
                commit.clean()
            return None
        wid = commit.index
        if kind == VK_TRY_LOCK:
            if raw == 1:
                self._waiters[wid] = commit
                self._on_grant(wid)
            else:
                commit.session.publish(
                    "lock", {"id": wid, "acquired": False})
                commit.clean()
            return wid
        self._waiters[wid] = commit
        if raw == 1:
            self._on_grant(wid)
        elif raw == 0:            # not reached: vector_spec counts the ring
            self._overflowed(wid)
        return wid

    def _take_grant(self) -> None:
        """The one event the release just finalized caused: the grant to
        the ring's first waiter, published inside the ``Unlock``'s entry."""
        events, self._ev_cursor = self._eng.take_events(
            self._group, self._ev_cursor, limit=1)
        if not events:
            raise RuntimeError("lock: the release's grant has not surfaced")
        _seq, code, target, _arg = events[0]
        waiter = self._waiters.get(target)
        if code != ops().EV_LOCK_GRANT or waiter is None:
            self._spawn(self._pump(events))   # a dead waiter: as the pump
            return
        self._holder_id = target
        if waiter.session.is_open:
            waiter.session.publish("lock", {"id": target, "acquired": True})

    # -- snapshot hooks (crash-recovery plane, docs/DURABILITY.md) --------
    # The device lock (holder, wait ring) rides the engine's checkpoint
    # blob; the host bookkeeping is the holder's id, the waiters in
    # arrival order with their instance's session id, and the ids the
    # device ring rejected. The Lock commits themselves are behind the
    # snapshot boundary: log-less stand-ins (clean() is a no-op) take
    # their place, as in the value machine, and ``register`` re-binds
    # their sessions. An armed acquire timeout holds a timer closed over
    # its commit, which cannot round-trip: such a state opts out
    # (NotImplemented) and keeps the manager on replay-only recovery.

    def snapshot_state(self) -> Any:
        if self._timers:
            return NotImplemented
        return {"holder": self._holder_id,
                "waiters": [(wid, c.session.id)
                            for wid, c in self._waiters.items()],
                "overflow": list(self._overflow)}

    def restore_state(self, data: Any, sessions: dict) -> None:
        self._holder_id = data["holder"]
        for wid, sid in data["waiters"]:
            self._waiters[wid] = Commit(wid, _UnboundSession(sid), 0.0,
                                        None, None)
        self._overflow = deque(data["overflow"])
        self._eng.count_lock_overflow(len(self._overflow))

    def register(self, session: Any) -> None:
        super().register(session)
        _rebind(self._waiters.values(), session)

    # -- session lifecycle -------------------------------------------------

    def close(self, session: Any) -> None:
        self._eng.count_session_end(chain=True)

        def chain():
            yield from self._pump()
            for wid in [w for w, c in self._waiters.items()
                        if c.session.id == session.id
                        and w != self._holder_id]:
                yield from self._cancel_waiter(wid, publish=False)
            if self._holder_id is not None:
                holder = self._waiters.get(self._holder_id)
                if holder is not None and holder.session.id == session.id:
                    yield from self._release_holder()

        self._run_excl(chain())

    def delete(self) -> None:
        def chain():
            for timer in self._timers.values():
                timer.cancel()
            self._timers.clear()
            # Reset the device lock for group reuse: dequeue every waiter
            # FIRST so releasing the holder cannot grant one of them.
            for wid in list(self._waiters):
                if wid != self._holder_id and wid not in self._overflow:
                    yield from self._cmd(ops().OP_LOCK_CANCEL, wid)
            if self._holder_id is not None:
                yield from self._cmd(ops().OP_LOCK_RELEASE, self._holder_id)
                self._holder_id = None
            for waiter in self._waiters.values():
                waiter.clean()
            self._waiters.clear()
            self._eng.count_lock_overflow(-len(self._overflow))
            self._overflow.clear()

        self._run_excl(chain())
        super().delete()


# ---------------------------------------------------------------------------
# leader election
# ---------------------------------------------------------------------------

class _ElectAhead:
    """An election as the rows staged for one vector run (``epoch``) will
    leave it: the leader's candidate id and the line behind it, in order."""

    __slots__ = ("epoch", "leader", "queue")

    def __init__(self, epoch: int, leader: int | None, queue: deque) -> None:
        self.epoch = epoch
        self.leader = leader
        self.queue = queue


class DeviceLeaderElectionState(DeviceBackedStateMachine):
    """Leader election on the device election kernel: candidate id = the
    client session id (CPU machine keys listeners by session), epoch =
    device log index of the winning listen (an opaque fencing token to the
    client, exactly as the reference's commit-index epoch,
    ``LeaderElectionState.java:31``)."""

    SETTLES = True  # promotions arrive as device events

    def __init__(self, engine: DeviceEngine, group: int) -> None:
        super().__init__(engine, group)
        self._listens: dict[int, Commit] = {}   # session id -> Listen commit
        self._leader: int | None = None         # session id
        self._epoch: int | None = None
        self._overflow: deque[int] = deque()
        # the election as the rows staged for the next vector run will
        # leave it (``vector_spec``, ``close_spec``)
        self._ahead: _ElectAhead | None = None

    def _note_chain(self) -> None:
        self._eng.count_elect_op(chain=True)

    def _pump(self, events: list | None = None):
        for _seq, code, target, arg in (self._events() if events is None
                                        else events):
            if code != ops().EV_ELECT:
                continue
            listen = self._listens.get(target)
            if listen is None:
                # promoted a dead candidate: resign it to move succession
                yield from self._cmd(ops().OP_ELECT_RESIGN, target)
                continue
            self._leader, self._epoch = target, arg
            if listen.session.is_open:
                listen.session.publish("elect", arg)
        yield from self._flush_overflow()

    def _flush_overflow(self):
        while self._overflow:
            sid = self._overflow[0]
            if sid not in self._listens:
                self._overflow.popleft()
                continue
            result = yield from self._cmd(ops().OP_ELECT_LISTEN, sid)
            if result == FAIL():
                break  # listener ring still full
            self._overflow.popleft()
            if result > 0:
                self._on_elected(sid, result)

    def _on_elected(self, sid: int, epoch: int) -> None:
        self._leader, self._epoch = sid, epoch
        listen = self._listens.get(sid)
        if listen is not None and listen.session.is_open:
            listen.session.publish("elect", epoch)

    def listen(self, commit: Commit[oc.ElectionListen]) -> None:
        sid = commit.session.id
        yield from self._pump()
        previous = self._listens.get(sid)
        if previous is not None:
            previous.clean()
            self._listens[sid] = commit
            yield from self._pump()
            return
        self._listens[sid] = commit
        if self._overflow:
            self._overflow.append(sid)
        else:
            result = yield from self._cmd(ops().OP_ELECT_LISTEN, sid)
            if result == FAIL():
                self._overflow.append(sid)  # host absorbs ring overflow
            elif result > 0:
                self._on_elected(sid, result)
        yield from self._pump()

    def unlisten(self, commit: Commit[oc.ElectionUnlisten]) -> None:
        try:
            yield from self._resign(commit.session.id)
        finally:
            commit.clean()

    def is_leader(self, commit: Commit[oc.ElectionIsLeader]) -> bool:
        # NO pump here: queries execute on a single server, and _pump can
        # issue device commands (overflow flush / dead-candidate resign)
        # that would fork that server's device log from its peers. The
        # mirror is always current as of the last command (every command
        # settles its events before returning; a row parked on the vector
        # lane has landed and been finalized before a read window walks
        # its reads, ``RaftGroup._evaluate_reads``), which is exactly the
        # linearization point a query may observe.
        try:
            return self._epoch is not None \
                and self._epoch == commit.operation.epoch
        finally:
            commit.close()

    def _resign(self, sid: int):
        yield from self._pump()
        listen = self._listens.pop(sid, None)
        if listen is None:
            return
        listen.clean()
        if sid in self._overflow:
            self._overflow.remove(sid)
        else:
            yield from self._cmd(ops().OP_ELECT_RESIGN, sid)
        if self._leader == sid:
            self._leader = self._epoch = None
        yield from self._pump()

    # -- vector lane (batched server-side pump) ---------------------------
    # With no candidate in the host overflow the mirror (``_leader``,
    # ``_listens`` in arrival order) IS the device's election, so an
    # ``ElectionListen`` of a candidate not yet listed and an
    # ``ElectionUnlisten`` of one that is are ONE device op each. Who a
    # resign promotes depends on the rows staged before it and not yet
    # finalized, so ``vector_spec`` looks ahead as the lock's does:
    # ``_ahead`` is the leader and the line as the staged rows will leave
    # them, drawn from the mirror when a run's first row is staged. A row
    # it cannot answer for one device op (a listen again of a candidate
    # already listed, a candidate in overflow, a ring the staged rows
    # could fill, an unlisten of a candidate not listed) takes its
    # generator handler, which the pump applies after the staged rows
    # have landed. The "elect" a resign causes comes from the device's
    # event ring as ever: ``run_vector`` returns once it is in the host's
    # buffer, and the row that caused it publishes it, one event a
    # hand-over, inside that row's entry. A session's end closes its
    # candidacies through the same two functions (``close_spec``).

    VECTOR_BY_COMMIT = True   # the candidate is the instance's session

    def _look_ahead(self) -> _ElectAhead:
        eng = self._eng
        ahead = self._ahead
        if ahead is None or ahead.epoch != eng.vector_epoch:
            leader = self._leader
            ahead = self._ahead = _ElectAhead(
                eng.vector_epoch, leader,
                deque(sid for sid in self._listens if sid != leader))
        return ahead

    def _resign_spec(self, sid: int
                     ) -> tuple[int, int, int, int, int] | None:
        """The one device op that takes candidate ``sid`` out, noted in
        the look-ahead; ``None`` where ``sid`` is not listed there."""
        ahead = self._look_ahead()
        if ahead.leader == sid:
            if ahead.queue:
                ahead.leader = ahead.queue.popleft()
                self._eng.expect_event(self._group, self._ev_cursor)
            else:
                ahead.leader = None
        elif sid in ahead.queue:
            ahead.queue.remove(sid)
        else:
            return None
        return (ops().OP_ELECT_RESIGN, sid, 0, 0, VK_ELECT_RESIGN)

    def vector_spec(self, operation: Any, index: int, session: Any
                    ) -> tuple[int, int, int, int, int] | None:
        t = type(operation)
        if self._overflow or (t is not oc.ElectionListen
                              and t is not oc.ElectionUnlisten):
            return None
        sid = session.id
        if t is oc.ElectionUnlisten:
            return self._resign_spec(sid)
        ahead = self._look_ahead()
        if ahead.leader is None:
            ahead.leader = sid
        elif ahead.leader == sid or sid in ahead.queue:
            return None           # listed already: the handler's to replace
        elif len(ahead.queue) >= self._eng.listener_slots():
            return None           # the ring could refuse it: the overflow
        else:
            ahead.queue.append(sid)
        return (ops().OP_ELECT_LISTEN, sid, 0, 0, VK_ELECT_LISTEN)

    def vector_finalize(self, kind: int, operation: Any, raw: int,
                        commit: Commit) -> Any:
        self._eng.count_elect_op(chain=False)
        sid = commit.session.id
        if kind == VK_ELECT_RESIGN:
            try:
                self._resigned(sid)
            finally:
                commit.clean()
            return None
        self._listens[sid] = commit
        if raw == FAIL():         # not reached: vector_spec counts the ring
            self._overflow.append(sid)
        elif raw > 0:
            self._on_elected(sid, raw)
        return None

    def _resigned(self, sid: int) -> None:
        """The mirror after ``sid``'s one ``OP_ELECT_RESIGN`` has landed:
        the generator's ``_resign`` and the pump of its one event."""
        self._listens.pop(sid).clean()
        if self._leader == sid:
            self._leader = self._epoch = None
            if self._listens:
                self._take_elect()

    def _take_elect(self) -> None:
        """The one event the resign just finalized caused: the promotion
        of the ring's first candidate, published inside the entry that
        caused it."""
        events, self._ev_cursor = self._eng.take_events(
            self._group, self._ev_cursor, limit=1)
        if not events:
            raise RuntimeError("election: the resign's elect has not "
                               "surfaced")
        _seq, code, target, arg = events[0]
        listen = self._listens.get(target)
        if code != ops().EV_ELECT or listen is None:
            self._spawn(self._pump(events))   # a dead candidate: as the pump
            return
        self._leader, self._epoch = target, arg
        if listen.session.is_open:
            listen.session.publish("elect", arg)

    # -- snapshot hooks (crash-recovery plane, docs/DURABILITY.md) --------
    # The device election (leader, listener ring, epoch) rides the
    # engine's checkpoint blob; the host mirror is the listeners'
    # instance session ids in arrival order, the leader with its epoch
    # and the ids the device ring rejected. No timers: it always
    # round-trips. Listen commits become log-less stand-ins whose
    # sessions ``register`` re-binds, as in the lock machine.

    def snapshot_state(self) -> Any:
        return {"listens": list(self._listens), "leader": self._leader,
                "epoch": self._epoch, "overflow": list(self._overflow)}

    def restore_state(self, data: Any, sessions: dict) -> None:
        for sid in data["listens"]:
            self._listens[sid] = Commit(0, _UnboundSession(sid), 0.0,
                                        None, None)
        self._leader, self._epoch = data["leader"], data["epoch"]
        self._overflow = deque(data["overflow"])

    def register(self, session: Any) -> None:
        super().register(session)
        _rebind(self._listens.values(), session)

    def close(self, session: Any) -> None:
        self._eng.count_session_end(chain=True)
        self._run_excl(self._resign(session.id))

    def close_spec(self, session: Any
                   ) -> tuple[int, int, int, int, int] | tuple | None:
        if self._overflow:
            return None
        if session.id not in self._listens:
            return self.CLOSED    # ``_resign`` finds nothing to take out
        return self._resign_spec(session.id)

    def close_finalize(self, session: Any, spec: tuple, raw: int) -> None:
        self._resigned(session.id)

    def delete(self) -> None:
        def chain():
            # Reset the device election for group reuse: unlist waiters
            # first, resign the leader last (empty ring → no succession
            # event).
            for sid in list(self._listens):
                if sid != self._leader and sid not in self._overflow:
                    yield from self._cmd(ops().OP_ELECT_RESIGN, sid)
            if self._leader is not None:
                yield from self._cmd(ops().OP_ELECT_RESIGN, self._leader)
                self._leader = self._epoch = None
            for listen in self._listens.values():
                listen.clean()
            self._listens.clear()
            self._overflow.clear()

        self._run_excl(chain())
        super().delete()


# ---------------------------------------------------------------------------
# registry + lazy opcode access
# ---------------------------------------------------------------------------

_ops_mod = None


def ops():
    """The device opcode/event-code module, imported lazily so constructing
    a pure-CPU cluster never imports JAX. Memoized: the import-machinery
    lookup (sys.modules + parent resolution) was the single hottest line
    of the SPI burst profile when paid per op."""
    global _ops_mod
    if _ops_mod is None:
        from ..ops import apply as _ops_mod_local
        _ops_mod = _ops_mod_local
    return _ops_mod


def FAIL() -> int:
    return INT32_MIN


def device_machine_for(machine_cls: type,
                       resource_config: Any = None) -> type | None:
    """Device-backed equivalent for a CPU state machine class, or ``None``
    when the type must stay on the CPU path: topic/group/bus are
    host-push-bound (their work is session event fan-out and out-of-band
    transport, not state-machine compute — the device topic kernel serves
    the raw batch path instead), and any user-defined machine has
    arbitrary Python state.

    ``resource_config`` (the engine's provisioned pools,
    ``DeviceEngineConfig.resource``) gates placement further: a type
    whose pool is compiled out of this engine (size 0) falls back to the
    CPU machine — the pool-provisioning deployment knob must degrade to
    the slower path, never to FAIL-sentinel device ops."""
    from ..atomic.state import AtomicValueState
    from ..collections.state import (
        MapState, MultiMapState, QueueState, SetState)
    from ..coordination.state import LeaderElectionState, LockState
    cls = {
        AtomicValueState: DeviceAtomicValueState,
        MapState: DeviceMapState,
        MultiMapState: DeviceMultiMapState,
        SetState: DeviceSetState,
        QueueState: DeviceQueueState,
        LockState: DeviceLockState,
        LeaderElectionState: DeviceLeaderElectionState,
    }.get(machine_cls)
    if cls is None or resource_config is None:
        return cls
    rc = resource_config
    required = {
        DeviceMapState: rc.map_slots,
        DeviceSetState: rc.set_slots,
        DeviceQueueState: rc.queue_slots,
        DeviceMultiMapState: rc.multimap_slots,
        # lock grants and election promotions ride the event outbox
        DeviceLockState: min(rc.wait_slots, rc.event_slots),
        DeviceLeaderElectionState: min(rc.listener_slots, rc.event_slots),
    }.get(cls, 1)  # value/long registers always exist
    return cls if required > 0 else None
