"""Host runtime around the batched consensus step.

The reference hosts one state machine per server process and drives it with
asyncio-style RPC (``CopycatServer``, consumed per SURVEY.md §2.3). Here the
host owns G logical Raft groups living on device and drives them round by
round: queue client ops, call the jitted step, harvest per-op results by
correlation tag.

This is the device executor the Resource/StateMachine SPI targets
(SURVEY.md §7.1: "the TPU executor selectable at replica build time");
the session protocol, exactly-once caching and event push stay host-side
in ``copycat_tpu.server`` — the device provides ordered, replicated,
deterministic apply at batch scale.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import lru_cache, partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.apply import FAIL, OP_CFG_ADD, OP_CFG_REMOVE, QUERY_OPCODES
from ..ops.consensus import (
    Config,
    RaftState,
    StepOutputs,
    Submits,
    full_delivery,
    init_state,
    install_snapshots,
    query_step,
    step,
)
from ..utils.tracing import TRACER


def _pack_host(planes) -> np.ndarray:
    """``[G, w]`` host planes side by side in one ``int32 [G, sum w]``
    buffer: the runtime puts one buffer where it put one per plane."""
    return np.concatenate(planes, axis=1, dtype=np.int32, casting="unsafe")


def _unpack_submits(packed: jax.Array, planes: int = 6) -> tuple:
    """Inside a program: ``(Submits, [further planes])`` out of
    :func:`_pack_host`'s buffer of equally wide planes."""
    S = packed.shape[1] // planes
    cut = [packed[:, i * S:(i + 1) * S] for i in range(planes)]
    return Submits(*cut[:5], valid=cut[5] != 0), cut[6:]


@partial(jax.tree_util.register_dataclass,
         data_fields=("ints", "bools", "telemetry"), meta_fields=("layout",))
@dataclasses.dataclass(frozen=True)
class PackedOutputs:
    """A round's ``StepOutputs`` as the programs return them: one
    group-leading slab per dtype (``[G]`` leaves are columns), so the
    runtime allocates and fetches two buffers for twenty leaves and a mesh
    engine's slabs stay shard-local. ``layout`` is static, one
    ``(slab, start, width or None)`` per field in ``StepOutputs`` order;
    it leaves the program in the pytree's structure. The telemetry
    subtree (off unless asked for) rides unpacked."""

    ints: Any      # int32 [..., G, K]
    bools: Any     # bool  [..., G, Kb]
    telemetry: Any
    layout: tuple

    @classmethod
    def pack(cls, out: StepOutputs) -> "PackedOutputs":
        cols: tuple[list, list] = ([], [])
        at = [0, 0]
        layout = []
        for x in out[:-1]:  # telemetry is StepOutputs' last field
            k = int(x.dtype == jnp.bool_)
            width = None if x.ndim == 1 else x.shape[1]
            layout.append((k, at[k], width))
            cols[k].append(x[:, None] if width is None else x)
            at[k] += width or 1
        return cls(jnp.concatenate(cols[0], axis=1),
                   jnp.concatenate(cols[1], axis=1),
                   out.telemetry, tuple(layout))

    def _field(self, i: int) -> Any:
        k, at, width = self.layout[i]
        slab = self.bools if k else self.ints
        return slab[..., at] if width is None else slab[..., at:at + width]

    def __getattr__(self, name: str) -> Any:
        """A ``StepOutputs`` field by its name: its slice of the slab (of
        a device slab too, at one dispatch: the rare snapshot install
        reads ``stale`` and ``leader`` so)."""
        if name in StepOutputs._fields:
            return self._field(StepOutputs._fields.index(name))
        raise AttributeError(name)

    def unpack(self) -> StepOutputs:
        """Views of the slabs under the fields' names (for host slabs,
        after a fetch)."""
        return StepOutputs(*map(self._field, range(len(self.layout))),
                           telemetry=self.telemetry)


def _query_slab(state, packed, config: Config):
    """Inside a program: ``query_step`` over :func:`_pack_host`'s seven
    query planes (the six of ``Submits`` and ``atomic``); ``(results,
    served)`` leave side by side as one int32 slab."""
    queries, (atomic,) = _unpack_submits(packed, planes=7)
    results, served = query_step(state, queries, atomic != 0, config=config)
    return jnp.concatenate([results, served.astype(jnp.int32)], axis=1)


@lru_cache(maxsize=None)
def _jitted_programs(config: Config):
    """(step, query, install, step-and-query) jit wrappers shared across
    all RaftGroups instances with the same static Config (Config is a
    hashable NamedTuple, so it keys the cache; shapes are handled inside
    each jit wrapper).

    The signatures are cut to what a call costs the runtime, which is per
    buffer and not per byte: the state and the PRNG key are donated (their
    outputs alias them), the submits arrive as one buffer, the key is split
    inside (the same integers as an eager split) and the outputs leave as
    :class:`PackedOutputs`. The query program reads the state again and
    donates nothing; its ``(results, served)`` leave as one int32 slab.

    The fourth is both in one call, for a read window that finds a vector
    run parked (:meth:`RaftGroups.step_round`): the round, then the query
    planes that rode in behind the submits' (``slots`` wide each, static)
    evaluated on the state the round wrote. Same ``step``, same
    ``query_step``, one call and one fetch where the two programs cost two
    of each. Its compiled name has to start ``jit_round_``: the benchmark
    finds a served round's modules by that."""

    def round_(state, packed, deliver, key):
        key, k = jax.random.split(key)
        submits, _ = _unpack_submits(packed)
        state, out = step(state, submits, deliver, k, config=config)
        return state, key, PackedOutputs.pack(out)

    def query(state, packed):
        return _query_slab(state, packed, config)

    def round_query(state, packed, deliver, key, slots):
        cut = packed.shape[1] - 7 * slots
        state, key, out = round_(state, packed[:, :cut], deliver, key)
        return state, key, out, _query_slab(state, packed[:, cut:], config)

    return (jax.jit(round_, donate_argnums=(0, 3)),
            jax.jit(query),
            jax.jit(partial(install_snapshots, config=config),
                    donate_argnums=0),
            jax.jit(round_query, donate_argnums=(0, 3), static_argnums=4))


@lru_cache(maxsize=None)
def _fused_rounds_program(config: Config, n: int):
    """``n`` consensus rounds in ONE compiled program: round 0 carries
    the caller's submits, rounds 1..n-1 run empty (the commit pipeline —
    replicate, commit, report — advancing). Returns the new state, the
    carried key, round 0's outputs, and the stacked outputs of the
    remaining rounds, both packed. One dispatch + one blocking fetch
    instead of ``n`` per SPI window pump cycle; the call signature is
    :func:`_jitted_programs`' step's."""

    def fused(state, packed, deliver, key):
        key, k = jax.random.split(key)
        keys = jax.random.split(k, n)
        submits, _ = _unpack_submits(packed)
        state, out0 = step(state, submits, deliver, keys[0], config=config)
        empty = jax.tree.map(jnp.zeros_like, submits)

        def body(st, kk):
            st, out = step(st, empty, deliver, kk, config=config)
            return st, PackedOutputs.pack(out)

        state, outs = jax.lax.scan(body, state, keys[1:])
        return state, key, PackedOutputs.pack(out0), outs

    return jax.jit(fused, donate_argnums=(0, 3))


def _split_slab(slab: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(results, served)`` out of the query's one int32 slab."""
    S = slab.shape[1] // 2
    return slab[:, :S], slab[:, S:] != 0


def _group_slot_pack(g: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stable per-group slot assignment for ``[N]`` rows: returns
    ``(order, gs, slots)`` such that rows taken in ``order`` land at
    ``(gs[i], slots[i])`` of a ``[G, S]`` buffer, with row order within
    a group preserved (the per-group FIFO witness both the vector
    submit lane and the vector read lane rely on)."""
    order = np.argsort(g, kind="stable")
    gs = g[order]
    n = gs.size
    first = np.ones(n, bool)
    first[1:] = gs[1:] != gs[:-1]
    starts = np.flatnonzero(first)
    cnt = np.diff(np.append(starts, n))
    slots = np.arange(n) - np.repeat(starts, cnt)
    return order, gs, slots


class QueryVector:
    """``[N]`` read rows marshalled for ``query_step``: the rows in their
    groups' slots (``planes``: the six of ``Submits`` and ``atomic``, each
    ``[G, slots]``), where each row went (``order``, ``gs``, ``at``), and
    what has come back (``out`` aligned with the input rows, ``done``).
    :meth:`RaftGroups.stage_query_vector` makes one; a vector run's first
    round takes it along (``rode``: the slab that round brought back, until
    the run says whether it may be kept) or
    :meth:`RaftGroups.finish_query_vector` evaluates it alone."""

    __slots__ = ("n", "slots", "planes", "order", "gs", "at", "out", "done",
                 "rode", "evaluations")

    def __init__(self, g: np.ndarray, num_groups: int, op, a, b, c,
                 atomic) -> None:
        self.n = n = g.size
        counts = np.bincount(g, minlength=num_groups)
        width = int(counts.max(initial=1))
        # pow2: burst-size jitter compiles at most log2 variants
        self.slots = S = 1 << (width - 1).bit_length()
        self.order, self.gs, self.at = order, gs, at = _group_slot_pack(g)
        planes = [np.zeros((num_groups, S), np.int32) for _ in range(5)]
        for plane, rows in zip(planes, (op, a, b, c)):
            plane[gs, at] = rows[order]
        valid = np.zeros((num_groups, S), bool)
        valid[gs, at] = True
        lease = np.zeros((num_groups, S), bool)
        lease[gs, at] = atomic[order]
        self.planes = (*planes, valid, lease)
        self.out = np.zeros(n, np.int64)
        self.done = np.zeros(n, bool)
        self.rode: np.ndarray | None = None
        self.evaluations = 0

    def take(self, results: np.ndarray, served: np.ndarray) -> int:
        """One evaluation's answers: rows served now are done and leave
        the next evaluation; returns how many."""
        self.evaluations += 1
        gs, at, order = self.gs, self.at, self.order
        hit = served[gs, at] & ~self.done[order]
        if not hit.any():
            return 0
        rows = order[hit]
        self.out[rows] = results[gs[hit], at[hit]]
        self.done[rows] = True
        self.planes[5][gs[hit], at[hit]] = False
        return rows.size


class RaftGroups:
    """G Raft groups × P peers, stepped as one compiled program."""

    MAX_EVENTS_PER_GROUP = 4096

    def __init__(
        self,
        num_groups: int,
        num_peers: int = 3,
        log_slots: int = 64,
        submit_slots: int = 4,
        config: Config | None = None,
        seed: int = 0,
        mesh: Any | None = None,
        voters: int | None = None,
        *,
        _build_state: bool = True,
    ) -> None:
        self.num_groups = num_groups
        self.num_peers = num_peers
        self.log_slots = log_slots
        self.submit_slots = submit_slots
        self.config = config or Config()
        # Environment opt-in for the device-plane flight recorder
        # (COPYCAT_TELEMETRY=1 / COPYCAT_INVARIANTS=observe|strict):
        # flips the static knob BEFORE any program is compiled so CI can
        # run the whole nemesis suite under strict invariants without
        # touching each test's Config. Telemetry never changes the
        # state evolution (it is pure output), so this is safe to apply
        # to any engine.
        from .telemetry import telemetry_env_enabled
        if not self.config.telemetry and telemetry_env_enabled():
            self.config = self.config._replace(telemetry=True)
        self.mesh = mesh
        if mesh is not None and self.config.use_pallas:
            self.config = self.config._replace(kernel_mesh=mesh)
        # The deep-drive programs' own flag (models/bulk.py): they donate
        # state + accumulators off the CPU only, though JAX 0.9 donates
        # there too; asked of the device once, here, never while tracing.
        # The round's programs (_jitted_programs) donate everywhere.
        device = mesh.devices.flat[0] if mesh is not None \
            else jax.devices()[0]
        self.donate = device.platform != "cpu"
        members = None
        if voters is not None:
            if not 0 < voters <= num_peers:
                raise ValueError(f"voters={voters} outside 1..{num_peers}")
            if voters < num_peers and not self.config.dynamic_membership:
                raise ValueError(
                    "voters < num_peers needs Config(dynamic_membership"
                    "=True) — the static step tallies all P lanes")
            if voters < num_peers:
                members = np.arange(num_peers) < voters

        key = jax.random.PRNGKey(seed)
        self._key, init_key = jax.random.split(key)
        if _build_state:
            build = partial(init_state, num_groups, num_peers, log_slots,
                            config=self.config, members=members)
            if mesh is None:
                # jitted: init_state hands one zeros array to several
                # fields, and a buffer cannot be donated twice; a
                # program's outputs are buffers of their own
                self.state: RaftState = jax.jit(build)(init_key)
                self.deliver = full_delivery(num_groups, num_peers)
            else:
                # Born sharded: each device builds only its own block of
                # every leaf (same integers as the eager build — the RNG
                # is partitionable), so no device ever holds the whole
                # state on the way to holding its share of it.
                from ..parallel import raft_shardings
                state_sh, deliver_sh = raft_shardings(
                    mesh, jax.eval_shape(build, init_key))
                self.state = jax.jit(build, out_shardings=state_sh)(init_key)
                self.deliver = jax.jit(
                    partial(full_delivery, num_groups, num_peers),
                    out_shardings=deliver_sh)()

            # Config-keyed jit cache: many RaftGroups instances with the
            # same Config (e.g. one device engine per server in a
            # multi-server test) share ONE compiled program instead of
            # recompiling per instance.
            (self._step, self._query, self._install,
             self._round_query) = _jitted_programs(self.config)
        else:
            # A subclass (parallel/multihost.py) supplies globally sharded
            # state/deliver and sharding-pinned jit wrappers itself —
            # building throwaway local versions here wasted a full state
            # allocation at startup (ADVICE r3 #2).
            self.state = None
            self.deliver = None
            self._step = self._query = self._install = None
            # no round that takes a read window's rows along: a driver
            # with round programs of its own evaluates them alone
            self._round_query = None
        self._queues: dict[int, deque] = {}
        self._query_queues: dict[int, deque] = {}
        self._query_atomic: set[int] = set()  # tags needing the lease gate
        self._next_tag = 1
        self._inflight: dict[int, tuple[int, int]] = {}  # tag -> (group, round)
        # exactly-once retry (queue-managed ops only): an op accepted into
        # a leader log can still be LOST — a partitioned leader's
        # unreplicated tail is overwritten by its successor. The host
        # re-submits only on PROOF of loss: once an entry with term
        # T > term_e applies at index j ≤ idx, the pending placement
        # (idx, term_e) can never be in the committed log (log terms are
        # monotone, so its log had term ≤ term_e < T at j — prefix
        # mismatch), hence re-submitting cannot double-apply. The
        # device-path analogue of the reference's session-sequenced
        # client resubmit (Copycat client runtime, SURVEY §2.3).
        self._inflight_ops: dict[int, tuple[int, int, int, int]] = {}
        # group -> {index -> (tag, append term)} — current placements only
        self._placements: dict[int, dict[int, tuple[int, int]]] = {}
        self._tag_index: dict[int, tuple[int, int]] = {}  # tag -> (group, idx)
        # highest post-round leader term observed per group: while a
        # placement's append term is older, that op's fate is uncertain
        # (its leader changed) and the group's queue is HELD — new ops
        # must not land in a log line that may lack an earlier op, or
        # per-group FIFO completion (the reference's session program
        # order) would break. The held set and the per-group min pending
        # append term (a lower bound — left stale on removals, refreshed
        # during loss scans) are maintained incrementally so the steady
        # state (no leader changes) costs no per-round Python scans.
        self._leader_term = np.zeros(num_groups, np.int64)
        self._held: set[int] = set()
        self._pend_min: dict[int, int] = {}
        self.results: dict[int, int] = {}    # tag -> result
        self.rounds = 0
        # first-class ops/sec + latency metrics (SURVEY.md §5.5)
        from ..utils.metrics import MetricsRegistry
        self.metrics = MetricsRegistry()
        # what the host pays around the compiled step: wall time of the
        # step call itself, blocking device->host fetches and their bytes
        # (the whole-window report reads the counters' deltas as engine.*)
        self._m_step_wall = self.metrics.histogram("step_wall_ms")
        self._m_fetches = self.metrics.counter("fetches")
        self._m_fetch_bytes = self.metrics.counter("fetch_bytes")
        # bytes of the host arrays the deep drive hands the device
        self._m_staged_bytes = self.metrics.counter("staged_bytes")
        # deep drives whose submission arrived in group order, and those
        # of them in which every group sent the same count (models/bulk.py)
        self._m_bulk_grouped = self.metrics.counter("bulk_grouped_drives")
        self._m_bulk_dense = self.metrics.counter("bulk_dense_drives")
        self._m_bulk_host = self.metrics.counter("bulk_host_bytes")
        self._m_bulk_kept = self.metrics.counter("bulk_kept_bytes")
        # bytes the deep drive's hooks hand the device or fetch from it,
        # and those of them whose transfer began ahead of the program's
        # call or of the fetch (_stage_acc, _ask_acc)
        self._m_bulk_link = self.metrics.counter("bulk_link_bytes")
        self._m_bulk_early = self.metrics.counter("bulk_early_bytes")
        self._m_settle_rounds = self.metrics.counter("query_settle_rounds")
        self._m_events_ingested = self.metrics.counter("events_ingested")
        # read windows evaluated (every one), and those of them whose rows
        # all rode a vector run's round and cost no call of their own
        self._m_queries_served = self.metrics.counter("queries_served")
        self._m_query_drives = self.metrics.counter("query_vector_drives")
        self._m_query_joined = self.metrics.counter("query_joined_drives")
        # buffers the runtime handles per call of the round's and the
        # query's programs (see _count_dispatch)
        self._m_dispatch_leaves = self.metrics.counter("dispatch_leaves")
        self._program_leaves: dict[Any, int] = {}
        TRACER.register(self.metrics, "engine.")
        # device-plane flight recorder: hub folds the step's telemetry
        # deltas into the device.* metric family, the flight ring, and
        # the online invariant monitor (models/telemetry.py)
        if self.config.telemetry:
            from .telemetry import DeviceTelemetryHub
            self.telemetry: Any = DeviceTelemetryHub(num_groups)
        else:
            self.telemetry = None
        self.clock = 0                       # mirrors the device logical clock
        # session events per group: list of (seq, code, target, arg);
        # deduped by absolute seq (ring re-delivers across leader changes)
        self.events: dict[int, list[tuple[int, int, int, int]]] = {}
        self._ev_seen: dict[int, int] = {}   # group -> highest seq consumed
        self._sessions: Any = None           # lazy DeviceSessionRegistry
        # monotone-tag engines: per-group count of stream ops committed so
        # far — the next drive's dense tags continue from here (the device
        # gate tracks the same value as the max live-ring tag)
        if self.config.monotone_tag_accept:
            self._stream_count = np.zeros(num_groups, np.int64)
        # direct-staged submit buffer (submit_batch fast lane): rows
        # scattered straight into the next round's Submits, bypassing
        # the per-group deque fan-out + re-drain (two Python loops that
        # dominated the SPI window's loaded round at 1k ops)
        self._staged_sub: Submits | None = None

    @property
    def sessions(self):
        """Device-path session registry (keep-alives + deterministic expiry
        fan-out through the log — see ``models/sessions.py``)."""
        if self._sessions is None:
            from .sessions import DeviceSessionRegistry
            self._sessions = DeviceSessionRegistry(self)
        return self._sessions

    # -- op submission ---------------------------------------------------

    def _empty_submits(self) -> Submits:
        G, S = self.num_groups, self.submit_slots
        return Submits(opcode=np.zeros((G, S), np.int32),
                       a=np.zeros((G, S), np.int32),
                       b=np.zeros((G, S), np.int32),
                       c=np.zeros((G, S), np.int32),
                       tag=np.zeros((G, S), np.int32),
                       valid=np.zeros((G, S), bool))

    def _refuse_monotone(self) -> None:
        """Monotone-tag engines (``Config.monotone_tag_accept``) accept only
        the bulk plane's dense per-group tag streams — a queue-managed
        submit (whose retries re-send OLD tags) would be silently rejected
        by the device gate forever, so refuse it loudly up front. Queries
        never append and stay allowed."""
        if self.config.monotone_tag_accept:
            raise NotImplementedError(
                "queue-managed submits are incompatible with "
                "Config(monotone_tag_accept=True) engines; drive them "
                "through models.bulk.BulkDriver")

    def submit(self, group: int, opcode: int, a: int = 0, b: int = 0,
               c: int = 0) -> int:
        """Queue one op; returns a correlation tag resolved in ``results``."""
        if opcode in (OP_CFG_ADD, OP_CFG_REMOVE):
            # raw config submits get the same validation as
            # add_peer/remove_peer — otherwise an out-of-range lane or a
            # static-membership engine would commit a no-op entry that
            # resolves as a silent success
            if not self.config.dynamic_membership:
                raise ValueError("membership changes need "
                                 "Config(dynamic_membership=True)")
            if not 0 <= a < self.num_peers:
                raise ValueError(
                    f"peer {a} outside 0..{self.num_peers - 1}")
        self._refuse_monotone()
        tag = self._next_tag
        self._next_tag += 1
        self._queues.setdefault(group, deque()).append((opcode, a, b, c, tag))
        self._inflight[tag] = (group, self.rounds)
        self._inflight_ops[tag] = (opcode, a, b, c)
        self.metrics.counter("ops_submitted").inc()
        return tag

    def submit_query(self, group: int, opcode: int, a: int = 0, b: int = 0,
                     c: int = 0, consistency: str = "sequential") -> int:
        """Queue a read-only op on the fast query lane (no log append).

        ``consistency="sequential"`` serves from the leader's applied
        state (the reference's sub-ATOMIC query routing,
        ``Consistency.java``); ``"atomic"`` additionally requires the
        leader LEASE (quorum-acked latest round) — BOUNDED_LINEARIZABLE
        reads without a log entry (``Consistency.java:157-176``). Either
        escalates to the command path automatically when unservable.
        Resolves in ``results`` like :meth:`submit`."""
        if opcode not in QUERY_OPCODES:
            # query_step discards state: a write here would be silently
            # dropped while acking success (reference rejects them too)
            raise ValueError(
                f"opcode {opcode} is not read-only; submit it as a command")
        if consistency not in ("sequential", "atomic"):
            raise ValueError(f"unknown query consistency {consistency!r}")
        tag = self._next_tag
        self._next_tag += 1
        self._query_queues.setdefault(group, deque()).append(
            (opcode, a, b, c, tag))
        if consistency == "atomic":
            self._query_atomic.add(tag)
        self._inflight[tag] = (group, self.rounds)
        self.metrics.counter("queries_submitted").inc()
        return tag

    def _drop_placement(self, g: int, idx: int) -> None:
        """Remove one placement; prune empty per-group state and
        re-evaluate the group's hold."""
        pend = self._placements.get(g)
        if pend is None:
            return
        pend.pop(idx, None)
        if not pend:
            del self._placements[g]
            self._pend_min.pop(g, None)
            self._held.discard(g)
        elif g in self._held:
            lt = self._leader_term[g]
            if all(te >= lt for _, te in pend.values()):
                self._held.discard(g)

    def _drain_into(self, queues: dict[int, deque], sub: Submits,
                    skip: set[int] | None = None) -> list[tuple[int, int]]:
        """Pop up to ``submit_slots`` queued ops per group into ``sub``;
        returns the (group, slot) pairs filled. Values are staged into
        Python lists and written with ONE fancy-indexed assignment per
        array — six scalar numpy ``__setitem__`` calls per op dominated
        the host loop before."""
        placed: list[tuple[int, int]] = []
        ops: list[int] = []
        avs: list[int] = []
        bvs: list[int] = []
        cvs: list[int] = []
        tgs: list[int] = []
        slots = self.submit_slots
        for g, q in list(queues.items()):
            if skip and g in skip:
                continue
            s = 0
            while q and s < slots:
                opcode, a, b, c, tag = q.popleft()
                ops.append(opcode)
                avs.append(a)
                bvs.append(b)
                cvs.append(c)
                tgs.append(tag)
                placed.append((g, s))
                s += 1
            if not q:
                del queues[g]
        if placed:
            rows = np.fromiter((p[0] for p in placed), np.int64,
                               len(placed))
            cols = np.fromiter((p[1] for p in placed), np.int64,
                               len(placed))
            sub.opcode[rows, cols] = ops
            sub.a[rows, cols] = avs
            sub.b[rows, cols] = bvs
            sub.c[rows, cols] = cvs
            sub.tag[rows, cols] = tgs
            sub.valid[rows, cols] = True
        return placed

    def _build_submits(self) -> Submits:
        if self._staged_sub is not None:
            # consume the direct-staged buffer. Queue entries that
            # appeared AFTER staging (post-step requeues, stray
            # submit()s) wait one round — per-group FIFO holds because
            # staging refuses while queues are non-empty, so anything
            # queued is strictly newer than everything staged.
            sub = self._staged_sub
            self._staged_sub = None
            return sub
        sub = self._empty_submits()
        if self._queues:
            self._drain_into(self._queues, sub,
                             skip=self._held or None)
        return sub

    def _stage_direct(self, g: np.ndarray, op, a, b, c,
                      tags: np.ndarray) -> bool:
        """Scatter rows straight into the next round's submit buffer
        (pure numpy, no per-op Python). Refused (``False`` — caller
        takes the deque path) whenever ordering could be observable:
        queued ops exist (FIFO vs them), holds are active, the engine is
        monotone (deep plane owns its streams), or a group would
        overflow its submit window."""
        if (self._queues or self._held or self._staged_sub is not None
                or self.config.monotone_tag_accept):
            return False
        counts = np.bincount(g, minlength=self.num_groups)
        if counts.max(initial=0) > self.submit_slots:
            return False
        order, gs, slots = _group_slot_pack(g)
        sub = self._empty_submits()
        sub.opcode[gs, slots] = op[order]
        sub.a[gs, slots] = a[order]
        sub.b[gs, slots] = b[order]
        sub.c[gs, slots] = c[order]
        sub.tag[gs, slots] = tags[order]
        sub.valid[gs, slots] = True
        self._staged_sub = sub
        return True

    # -- stepping ----------------------------------------------------------

    # Hooks the multi-host driver overrides (parallel/multihost.py): the
    # base engine stages host numpy straight onto the device and fetches
    # whole output arrays; a multi-process driver assembles GLOBAL arrays
    # from each process's local block and fetches only addressable shards.
    # _agree/_any_across are the lockstep primitives: identity on one
    # host, allgathered across processes — every driver loop that stops
    # or branches around a collective program decides through them, so
    # the multi-host subclass needs no copied control flow.

    def _agree(self, mine: bool) -> bool:
        """True when every process's local condition holds (identity on
        a single host)."""
        return mine

    def _any_across(self, mine: bool) -> bool:
        """True when any process's local condition holds (identity on a
        single host)."""
        return mine

    def _stage_submits(self, submits: Submits) -> Submits:
        return submits

    def _stage_round(self, submits: Submits) -> Any:
        """What the round's program takes for its submits: here the six
        planes in one host buffer (one ``device_put``, unpacked inside
        the program); a driver with programs of its own (multihost)
        hands them the staged ``Submits``."""
        return _pack_host(submits)

    def _stage_deliver(self, deliver: Any) -> Any:
        return deliver

    def _note_fetch(self, host: Any) -> Any:
        """Count one blocking device->host fetch and its bytes (every
        fetch hook, the multi-host overrides too, ends in this)."""
        self._m_fetches.inc()
        self._m_fetch_bytes.inc(
            sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(host)))
        return host

    def _note_stage(self, host: Any) -> Any:
        """Count the bytes of the host arrays the deep drive hands the
        device: an accumulator (``_stage_acc``, the multi-host override
        too, ends in this) or the payload leaves that travel as
        arguments of the deep program (``models/bulk.py`` calls it where
        it hands them over). A scalar leaf counts 0."""
        nbytes = sum(x.nbytes for x in jax.tree.leaves(host)
                     if getattr(x, "ndim", 0))
        self._m_staged_bytes.inc(nbytes)
        self._m_bulk_link.inc(nbytes)
        return host

    def _fetch_outputs(self, raw: PackedOutputs) -> StepOutputs:
        # ONE overlapped device->host transfer for both slabs; the harvest
        # reads numpy views of them under StepOutputs' names.
        for leaf in jax.tree.leaves(raw):
            leaf.copy_to_host_async()
        return self._note_fetch(jax.tree.map(np.asarray, raw)).unpack()

    def _stale_any(self, raw: Any, out: StepOutputs) -> bool:
        return bool(out.stale.any())

    def _run_query(self, sub: Submits, atomic) -> tuple[Any, Any]:
        packed = _pack_host((*sub, atomic))
        raw = self._query(self.state, packed)
        slab = self._note_fetch(np.asarray(raw))
        self._count_dispatch(self._query, packed, raw)
        return _split_slab(slab)

    #: the round's programs donate the state and the key, so those
    #: outputs alias their inputs and cost the runtime no buffer; a
    #: driver whose own programs do not donate says so (multihost)
    round_donates = True

    def _count_dispatch(self, program: Any, put: Any, out: Any) -> None:
        """``engine.dispatch_leaves``: the buffers one call of ``program``
        costs the runtime — host leaves put, output leaves that alias no
        donated input, leaves fetched (every fresh output is fetched) —
        counted from the pytrees the first time the program is called.
        The delivery mask lives on the device and is not in it."""
        n = self._program_leaves.get(program)
        if n is None:
            n = self._program_leaves[program] = (
                len(jax.tree.leaves(put)) + 2 * len(jax.tree.leaves(out)))
        self._m_dispatch_leaves.inc(n)

    # Deep-plane hooks (models/bulk.py _drive_deep): accumulator staging,
    # fetch, and the jitted deep program. The multihost subclass overrides
    # them to assemble/fetch global group-sharded arrays and to pin output
    # shardings, which is what lifts the deep pipelined drive to
    # multi-process engines (VERDICT r4 directive 2).

    def _global_max_int(self, v: int) -> int:
        """Max of ``v`` across processes (identity on one host) — sizes
        the deep drive's shared accumulator width so every process
        compiles/launches the same program."""
        return v

    def _stage_acc(self, arr: np.ndarray, axis: int = 0) -> Any:
        """Host numpy -> device array for what a deep drive puts ahead of
        its program's call: an accumulator, whose leading axis is groups,
        or a stacked ``[stack, G, .]`` payload plane (``axis=1``). The
        transfer starts here, so the host goes on while it crosses; the
        caller does not write ``arr`` again before the program that takes
        the copy has run. On a single-host mesh the group axis is sharded
        like the state (placement-only, so the deep_step scatter stays
        shard-local — parallel/mesh.py rule)."""
        self._m_bulk_early.inc(self._note_stage(arr).nbytes)
        if self.mesh is None:
            return jax.device_put(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P
        g_ax = "groups" if "groups" in self.mesh.axis_names else None
        spec = [None] * arr.ndim
        spec[axis] = g_ax
        # straight from host memory to each device's block
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    @staticmethod
    def _blocks(x: Any) -> list:
        """The single-device arrays of this process's shards of a
        group-sharded array, in the order of their group-axis offset."""
        return [s.data for s in sorted(
            x.addressable_shards, key=lambda s: s.index[0].start or 0)]

    @classmethod
    def _local_block(cls, x: Any) -> np.ndarray:
        """This process's contiguous block of a group-sharded array."""
        return np.concatenate([np.asarray(b) for b in cls._blocks(x)],
                              axis=0)

    def _ask_acc(self, arrays: Any) -> list[list]:
        """Start the device->host copies of group-sharded device arrays
        without waiting for any: each chip's block leaves as the program
        that writes it ends. Returns each array's :meth:`_blocks`, which
        the caller reads one at a time (``np.asarray`` of a block waits
        for that block's copy alone and is the runtime's own host copy)
        and hands to :meth:`_fetch_acc` as ``asked``."""
        asked = [self._blocks(x) for x in arrays]
        for blocks in asked:
            for block in blocks:
                block.copy_to_host_async()
        return asked

    def _to_host(self, leaves: list) -> list:
        """Device arrays -> host numpy, whole (this process's local block
        of each on multihost)."""
        return jax.device_get(leaves)

    def _fetch_acc(self, arrays: Any, asked: Any = ()) -> Any:
        """Fetch a pytree of group-leading device arrays to host numpy
        (this process's local block on multihost), in one transfer.
        ``asked``: what :meth:`_ask_acc` returned for further arrays of
        the same fetch, read block by block by the caller: their bytes
        are counted here, with the one fetch they belong to."""
        leaves, tree = jax.tree.flatten(arrays)
        early = sum(x.nbytes for x in jax.tree.leaves(asked))
        host = self._to_host(leaves)
        self._note_fetch((host, asked))
        self._m_bulk_early.inc(early)
        self._m_bulk_link.inc(
            early + sum(getattr(x, "nbytes", 0) for x in host))
        return tree.unflatten(host)

    def _deep_fn(self) -> Any:
        """The jitted ``deep_step`` used by the deep drive. One-hot
        accumulator formulation on a mesh (shard-local by construction)."""
        from .bulk import _deep_program
        return _deep_program(self.config, onehot=self.mesh is not None,
                             donate=self.donate)

    def step_round(self, submits: Submits | None = None,
                   deliver: Any | None = None,
                   correlate: Any | None = None,
                   query: QueryVector | None = None) -> StepOutputs:
        """Advance every group one round; harvests results into ``results``.
        ``correlate(out)`` is a caller's own pass over the round's outputs
        (``drive_vector``'s): it runs at the end of the round, inside its
        harvest stage. ``query`` is a read window's rows
        (:meth:`stage_query_vector`): where this driver has the program
        for it they ride the round, evaluated on the state it writes,
        their planes in behind the submits' and their slab back in the
        round's one fetch, and ``query.rode`` holds that slab; elsewhere
        it stays ``None`` and the caller evaluates them alone. One
        function, no inner helper: every Python frame under the first call
        of the compiled step lengthens each of its operations' source
        locations, and lowering pays for it."""
        stage = TRACER.open_span("engine.stage") if TRACER.enabled else None
        explicit = submits is not None
        if submits is None:
            submits = self._build_submits()
        dl = self.deliver if deliver is None else self._stage_deliver(deliver)
        if query is None or self._round_query is None:
            program, width = self._step, ()
            staged = self._stage_round(submits)
        else:
            program, width = self._round_query, (query.slots,)
            staged = _pack_host((*submits, *query.planes))
        if stage is not None:
            stage = stage.then("engine.wait")
            t0 = stage.start
        else:
            t0 = time.perf_counter()
        # (with a window's rows along, their slab comes back fourth)
        self.state, self._key, raw, *rider = program(
            self.state, staged, dl, self._key, *width)
        raw = jax.block_until_ready(raw)  # time compute, not dispatch
        if stage is not None:
            stage = stage.then("engine.fetch")
            t1 = stage.start
        else:
            t1 = time.perf_counter()
        self._m_step_wall.record((t1 - t0) * 1e3)
        fetched = self._m_fetch_bytes.value
        if rider:
            rider[0].copy_to_host_async()  # in the one transfer below
        out = self._fetch_outputs(raw)
        if rider:
            query.rode = np.asarray(rider[0])
            self._m_fetch_bytes.inc(query.rode.nbytes)
        self._count_dispatch(
            program, staged,
            (raw, rider) if self.round_donates
            else (self.state, self._key, raw))
        if stage is not None:
            stage = stage.then(
                "engine.harvest", bytes=self._m_fetch_bytes.value - fetched)
        self.rounds += 1
        self.metrics.counter("rounds").inc()
        if not explicit:
            self._requeue_rejected(submits, out)
        self._harvest(out)
        # Placements are recorded AFTER the harvest: an op that committed
        # in the round it was accepted (the steady state) never enters
        # the retry bookkeeping at all — _record_assigned skips tags
        # _harvest already resolved. Same-round loss is impossible (a
        # loss proof needs a committed entry with a HIGHER term at or
        # before the op's index, and terms can't rise past the accepting
        # leader's within its own round).
        if not explicit:
            self._record_assigned(submits, out)
        if self._any_across(bool(self._query_queues)):
            self._serve_queries()
        # Followers lagging beyond the ring window can't be served by
        # AppendEntries: install a snapshot of the leader's lane (log ring +
        # applied resource state) so they reconverge.
        if self._stale_any(raw, out):
            self.state = self._install(self.state, raw.stale, raw.leader)
        if self._sessions is not None:
            self._sessions.tick()
        if correlate is not None:
            correlate(out)
        if stage is not None:
            stage.close()
        return out

    def step_rounds(self, n: int) -> None:
        """Advance ``n`` rounds with ONE device dispatch + ONE fetch.

        Semantically equivalent to ``n`` ``step_round()`` calls whose
        rounds 1..n-1 found empty submit queues: round 0 drains the
        queues as usual; later rounds advance the commit pipeline
        (replicate → commit → report) of whatever round 0 accepted.
        Queued ops beyond round 0's submit window simply ride the next
        call (the caller's drive loop keeps calling until resolved).
        The SPI device window uses this for its pump cycles — it
        collapses the per-cycle cost from ~n blocking fetches to one.

        Falls back to per-round stepping for n <= 1 and for engines with
        overridden staging hooks (multihost lockstep drives per-round
        decisions). Deliver masks need no fallback: nemesis faults are
        installed via ``self.deliver`` and the fused program reads the
        same mask every round, exactly like n sequential step_round
        calls with an unchanged mask.
        """
        if n <= 1 or type(self)._stage_submits is not RaftGroups._stage_submits:
            for _ in range(n):
                self.step_round()
            return
        stage = TRACER.open_span("engine.stage") if TRACER.enabled else None
        submits = self._build_submits()
        staged = self._stage_round(submits)
        fused = _fused_rounds_program(self.config, n)
        if stage is not None:
            stage = stage.then("engine.wait", rounds=n)
            t0 = stage.start
        else:
            t0 = time.perf_counter()
        self.state, self._key, raw0, raws = fused(
            self.state, staged, self.deliver, self._key)
        raws = jax.block_until_ready(raws)
        if stage is not None:
            stage = stage.then("engine.fetch", rounds=n)
            t1 = stage.start
        else:
            t1 = time.perf_counter()
        self._m_step_wall.record((t1 - t0) * 1e3)
        # overlap BOTH transfers (round 0 + the stacked tail) before the
        # first blocking conversion — one round-trip for the whole fetch
        for leaf in jax.tree.leaves(raws):
            leaf.copy_to_host_async()
        fetched = self._m_fetch_bytes.value
        out0 = self._fetch_outputs(raw0)
        outs = jax.tree.map(np.asarray, raws)
        self._m_fetch_bytes.inc(sum(x.nbytes for x in jax.tree.leaves(outs)))
        outs = outs.unpack()
        self._count_dispatch(fused, staged, (raw0, raws))
        if stage is not None:
            stage = stage.then(
                "engine.harvest", rounds=n,
                bytes=self._m_fetch_bytes.value - fetched)
        self.rounds += 1
        self.metrics.counter("rounds").inc()
        self._requeue_rejected(submits, out0)
        self._harvest(out0)
        self._record_assigned(submits, out0)
        if self._sessions is not None:
            self._sessions.tick()
        for i in range(n - 1):
            out_i = jax.tree.map(lambda x, i=i: x[i], outs)
            self.rounds += 1
            self.metrics.counter("rounds").inc()
            self._harvest(out_i)
            if self._sessions is not None:
                self._sessions.tick()
        if self._any_across(bool(self._query_queues)):
            self._serve_queries()
        # snapshot-install decision from the LAST round's view (deferring
        # a mid-scan stale follower one cycle is the same recovery path)
        if bool(outs.stale[-1].any()):
            self.state = self._install(self.state, raws.stale[-1],
                                       raws.leader[-1])
        if stage is not None:
            stage.close(rounds=n)

    def serve_query(self, group: int, opcode: int, a: int = 0, b: int = 0,
                    c: int = 0, max_attempts: int = 50,
                    consistency: str = "sequential") -> int:
        """Serve ONE read-only op from the leader's applied state, never
        touching the log (unlike :meth:`submit_query`, whose unserved
        slots escalate to the command path and append an entry).

        For callers that replicate the engine deterministically across
        processes (the SPI device executor), log content must be a pure
        function of the committed command stream — so the no-leader
        fallback here only *steps* (advancing the clock, which no
        resource state depends on) and retries; it never appends.
        """
        from ..ops.apply import QUERY_OPCODES
        if opcode not in QUERY_OPCODES:
            raise ValueError(
                f"opcode {opcode} is not read-only; submit it as a command")
        sub = self._empty_submits()
        sub.opcode[group, 0] = opcode
        sub.a[group, 0] = a
        sub.b[group, 0] = b
        sub.c[group, 0] = c
        sub.valid[group, 0] = True
        atomic = np.zeros_like(sub.valid)
        atomic[group, 0] = consistency == "atomic"
        mine = False
        for _ in range(max_attempts):
            results, served = self._run_query(sub, atomic)
            mine = bool(served[group, 0])
            if self._agree(mine):
                self.metrics.counter("queries_served").inc()
                return int(results[group, 0])
            self.step_round()  # no leader yet / applied < commit: settle
        raise TimeoutError(
            f"group {group} query unservable after {max_attempts} rounds"
            + (" (local read was served; a peer process is stuck)"
               if mine else ""))

    def _serve_queries(self) -> None:
        """Drain the query lane: serve from the leader's applied state; a
        slot the device can't serve (leaderless group, applied < commit)
        escalates to the command path — same consistency, one log entry."""
        sub = self._empty_submits()
        placed = self._drain_into(self._query_queues, sub)
        atomic = np.zeros_like(sub.valid)
        for g, s in placed:
            if int(sub.tag[g, s]) in self._query_atomic:
                atomic[g, s] = True
        results, served = self._run_query(sub, atomic)
        fell_back = self.metrics.counter("queries_escalated")
        done = self.metrics.counter("queries_served")
        for g, s in placed:
            tag = int(sub.tag[g, s])
            self._query_atomic.discard(tag)
            if served[g, s]:
                if tag in self._inflight:
                    self._inflight.pop(tag)
                    self.results[tag] = int(results[g, s])
                    done.inc()
            else:
                op = (int(sub.opcode[g, s]), int(sub.a[g, s]),
                      int(sub.b[g, s]), int(sub.c[g, s]))
                if self.config.monotone_tag_accept:
                    # the command path is closed on monotone-tag engines
                    # (the gate would reject the escalated tag forever) —
                    # retry on the query lane instead; it becomes
                    # servable once a leader/lease settles
                    self._query_queues.setdefault(g, deque()).append(
                        (*op, tag))
                    if atomic[g, s]:
                        self._query_atomic.add(tag)
                    fell_back.inc()
                    continue
                # escalate: re-enter as a command (quorum-committed read —
                # always at least as strong as the requested level)
                self._queues.setdefault(g, deque()).append((*op, tag))
                self._inflight_ops[tag] = op  # joins the loss-retry protocol
                fell_back.inc()

    def drive_query_vector(self, groups, opcode, a=0, b=0, c=0,
                           atomic=False,
                           max_attempts: int = 50) -> np.ndarray:
        """One-shot vectorized READ serve: stage ``[N]`` read rows into
        per-group slots of ONE :func:`query_step` evaluation (no log
        append, no correlation tags, no per-op dicts) and return results
        aligned with the input rows. The read analog of
        :meth:`drive_vector` — the applying server's batched read pump
        stages a whole window here instead of paying a full device
        round-trip per ``serve_query`` call.

        ``atomic`` (scalar or ``[N]``) marks rows needing the leader
        LEASE (BOUNDED_LINEARIZABLE freshness); the SPI read pump passes
        False — its host-side gate already established the linearization
        point, exactly like the per-op ``DeviceEngine.query`` lane.

        Unserved rows (group mid-election, applied < commit) retry after
        a settling :meth:`step_round`, like :meth:`serve_query`; in the
        warm steady state every row serves on the first evaluation. The
        slot width pads to the next power of two so burst-size jitter
        compiles at most log2 variants of the query program.

        The two halves, for a window whose rows may ride a vector run's
        round between them: :meth:`stage_query_vector`,
        :meth:`finish_query_vector`."""
        if np.size(groups) == 0:
            return np.zeros(0, np.int64)
        span = TRACER.open_span("engine.query") if TRACER.enabled else None
        query = self._marshal_query(groups, opcode, a, b, c, atomic)
        return self.finish_query_vector(query, max_attempts, span)

    def _marshal_query(self, groups, opcode, a, b, c,
                       atomic) -> QueryVector:
        from ..ops.apply import QUERY_OPCODES
        g = np.asarray(groups, np.int64).ravel()
        n = g.size
        bc = lambda x: np.broadcast_to(
            np.asarray(x, np.int32).ravel(), (n,))
        op_a = bc(opcode)
        bad = ~np.isin(op_a, tuple(QUERY_OPCODES))
        if bad.any():
            raise ValueError(
                f"opcode {int(op_a[bad][0])} is not read-only; submit it "
                "as a command")
        return QueryVector(
            g, self.num_groups, op_a, bc(a), bc(b), bc(c),
            np.broadcast_to(np.asarray(atomic, bool).ravel(), (n,)))

    def stage_query_vector(self, groups, opcode, a=0, b=0, c=0,
                           atomic=False) -> QueryVector:
        """:meth:`drive_query_vector`'s marshalling alone, for a read
        window that finds a vector run parked: ``drive_vector(...,
        query=)`` offers the rows to the run's first round, and
        :meth:`finish_query_vector` returns their results, evaluating
        what that round did not answer. ``engine.query`` is the marshal
        here; the unpacking is part of the round's harvest."""
        span = TRACER.open_span("engine.query") if TRACER.enabled else None
        query = self._marshal_query(groups, opcode, a, b, c, atomic)
        if span is not None:
            span.close(attempts=0, width=query.slots, n=query.n)
        return query

    def finish_query_vector(self, query: QueryVector,
                            max_attempts: int = 50,
                            span: Any = None) -> np.ndarray:
        """Results aligned with ``query``'s rows: one evaluation, then a
        settling round and another for as long as a row is unserved. Rows
        that rode a round and were all answered there cost nothing here
        (``query_joined_drives``); every window counts one
        ``query_vector_drives``."""
        S, n = query.slots, query.n
        planes = query.planes
        rode = query.evaluations
        while True:
            if query.evaluations and self._agree(bool(query.done.all())):
                self._m_query_drives.inc()
                if rode and query.evaluations == rode:
                    self._m_query_joined.inc()
                if span is not None:
                    span.close(attempts=query.evaluations, width=S, n=n)
                return query.out
            if query.evaluations - rode >= max_attempts:
                break
            if span is None and TRACER.enabled:
                span = TRACER.open_span("engine.query")
            if query.evaluations:
                # no leader yet / applied < commit: settle
                self._m_settle_rounds.inc()
                if span is not None:
                    with TRACER.scope(span.trace_id, "engine.query"):
                        self.step_round()
                else:
                    self.step_round()
            results, served = self._run_query(Submits(*planes[:6]),
                                              planes[6])
            self._m_queries_served.inc(query.take(results, served))
        if span is not None:
            span.close(attempts=query.evaluations, width=S, n=n,
                       error="timeout")
        raise TimeoutError(
            f"query vector: {int((~query.done).sum())}/{n} rows unservable "
            f"after {max_attempts} attempts")

    def _record_assigned(self, submits: Submits, out: StepOutputs) -> None:
        """Remember the (log index, term) each accepted queue-managed op
        landed at (its current placement) for provable-loss retry — see
        _harvest."""
        if not self._inflight_ops:
            return  # everything accepted this round already resolved
        acc = np.asarray(out.accepted)
        if not acc.any():
            return
        gi, si = np.nonzero(acc)
        g_l = gi.tolist()
        tag_l = np.asarray(submits.tag)[gi, si].tolist()
        idx_l = np.asarray(out.assigned)[gi, si].tolist()
        trm_l = np.asarray(out.assigned_term)[gi, si].tolist()
        for k, tag in enumerate(tag_l):
            if tag in self._inflight_ops:
                g = g_l[k]
                old = self._tag_index.get(tag)
                if old is not None:  # superseded placement (re-accept)
                    self._drop_placement(old[0], old[1])
                te = trm_l[k]
                self._placements.setdefault(g, {})[idx_l[k]] = (tag, te)
                self._tag_index[tag] = (g, idx_l[k])
                if te < self._pend_min.get(g, te + 1):
                    self._pend_min[g] = te
                # _harvest updated _leader_term BEFORE this runs: when the
                # accepting leader was deposed in the SAME step (accept in
                # phase 1, election in phase 4), the term has already
                # risen past te and no future rise would re-trigger the
                # hold scan — engage the hold here
                if te < self._leader_term[g]:
                    self._held.add(g)

    def _requeue_rejected(self, submits: Submits, out: StepOutputs) -> None:
        acc = np.asarray(out.accepted)
        valid = np.asarray(submits.valid)
        refused = np.asarray(out.refused)
        if refused.any():
            # permanent rejection (e.g. a config change that would empty
            # the group): fail to the client now — requeueing would block
            # the group's queue forever behind the FIFO suffix-reject
            for g, s in zip(*np.nonzero(refused & valid)):
                tag = int(submits.tag[g, s])
                # recorded for UNTRACKED tags too: drive_vector's rows
                # have no _inflight entry, and without the FAIL record a
                # refused row would spin the whole run to TimeoutError —
                # failing rows that DID commit on device
                self.results[tag] = FAIL
                if tag in self._inflight:
                    self._inflight.pop(tag)
                    self._inflight_ops.pop(tag, None)
        rejected = valid & ~acc & ~refused
        if not rejected.any():
            return
        # appendleft in REVERSE slot order so retried ops keep submission order
        for g, s in reversed(list(zip(*np.nonzero(rejected)))):
            self._queues.setdefault(int(g), deque()).appendleft(
                (int(submits.opcode[g, s]), int(submits.a[g, s]),
                 int(submits.b[g, s]), int(submits.c[g, s]),
                 int(submits.tag[g, s])))

    def _harvest(self, out: StepOutputs) -> None:
        if self.telemetry is not None and out.telemetry is not None:
            self.telemetry.ingest(out.telemetry, self.rounds)
        self.clock = int(np.asarray(out.clock).max(initial=self.clock))
        lt = np.asarray(out.leader_term)
        rose = self._placements and bool((lt > self._leader_term).any())
        np.maximum(self._leader_term, lt, out=self._leader_term,
                   casting="unsafe")
        if rose:  # leader changes are rare; only then re-derive holds
            for g, pend in self._placements.items():
                if any(te < self._leader_term[g] for _, te in pend.values()):
                    self._held.add(g)
        valid = np.asarray(out.out_valid)
        if valid.any() and (self._inflight or self._placements):
            # flat native-int views: per-element numpy scalar indexing and
            # int() conversion in this loop were a measurable share of the
            # client-visible op cost at 10k groups. Skipped entirely when
            # nothing is tracked — untracked commits (the vector drive's
            # rows, which correlate from the step outputs themselves)
            # have no routing to do here.
            gi, ii = np.nonzero(valid)
            g_l = gi.tolist()
            tags_l = np.asarray(out.out_tag)[gi, ii].tolist()
            res_l = np.asarray(out.out_result)[gi, ii].tolist()
            idx_l = np.asarray(out.out_index)[gi, ii].tolist()
            term_l = np.asarray(out.out_term)[gi, ii].tolist()
            latency = self.metrics.histogram("commit_latency_rounds")
            inflight = self._inflight
            results = self.results
            rounds = self.rounds
            n_done = 0
            for k, tag in enumerate(tags_l):
                g = g_l[k]
                if self._placements:  # retry bookkeeping only when pending
                    j, T = idx_l[k], term_l[k]
                    pend = self._placements.get(g)
                    at_j = pend.get(j) if pend else None
                    if pend and ((at_j is not None and at_j[1] != T)
                                 or T > self._pend_min.get(g, T)):
                        # provable loss: a pending placement (idx, term_e)
                        # can never commit once (a) an entry with term
                        # T > term_e applied at j <= idx — its log
                        # mismatches the committed prefix at j — or (b)
                        # THIS index applied under a different term
                        # (entries never move between indices). Guarded by
                        # the _pend_min lower bound so the steady state
                        # (T == every pending term) skips the scan.
                        lost = sorted(
                            (idx, t) for idx, (t, te) in pend.items()
                            if (idx >= j and te < T)
                            or (idx == j and te != T))
                        # appendleft in reverse idx order: co-lost ops
                        # keep their original relative order in the queue
                        for idx, owner in reversed(lost):
                            self._drop_placement(g, idx)
                            self._tag_index.pop(owner, None)
                            if owner in inflight:
                                self._queues.setdefault(
                                    g, deque()).appendleft(
                                    (*self._inflight_ops[owner], owner))
                        pend = self._placements.get(g)
                        if pend:  # refresh the stale lower bound
                            self._pend_min[g] = min(
                                te for _, te in pend.values())
                if tag and tag in inflight:
                    _, submit_round = inflight.pop(tag)
                    self._inflight_ops.pop(tag, None)
                    if self._tag_index:
                        placed = self._tag_index.pop(tag, None)
                        if placed is not None:
                            self._drop_placement(placed[0], placed[1])
                    results[tag] = res_l[k]
                    n_done += 1
                    latency.record(rounds - submit_round)
            if n_done:
                self.metrics.counter("ops_committed").inc(n_done)
        self._ingest_events(out)

    def _ingest_events(self, out) -> None:
        """Append this round's drained session events to the host buffer
        (dedup by absolute seq). Shared by every driver that steps the
        engine — the device pops events off its ring when drained, so a
        driver that skipped this would LOSE them."""
        ev_valid = np.asarray(out.ev_valid)
        if ev_valid.any():
            # one pass: the round's events as plain lists (row-major, so a
            # group's ascend by seq), then an append each
            gi, ii = np.nonzero(ev_valid)
            seen, events = self._ev_seen, self.events
            keep = self.MAX_EVENTS_PER_GROUP
            fresh = 0
            for g, event in zip(gi.tolist(), zip(
                    np.asarray(out.ev_seq)[gi, ii].tolist(),
                    np.asarray(out.ev_code)[gi, ii].tolist(),
                    np.asarray(out.ev_target)[gi, ii].tolist(),
                    np.asarray(out.ev_arg)[gi, ii].tolist())):
                if event[0] <= seen.get(g, -1):
                    continue  # re-delivered after a leader change
                seen[g] = event[0]
                evs = events.get(g)
                if evs is None:
                    evs = events[g] = []
                evs.append(event)
                fresh += 1
                # bounded buffer: facades track absolute seqs, so trimming
                # old events never invalidates a consumer cursor
                if len(evs) > keep:
                    del evs[: len(evs) - keep]
            self._m_events_ingested.inc(fresh)

    def run(self, rounds: int) -> None:
        for _ in range(rounds):
            self.step_round()

    def run_until(self, tags: list[int], max_rounds: int = 200) -> None:
        """Step until all given tags have results (or raise). Lockstep on
        multi-host: every process passes ITS tags ([] if idle) and all
        stop together."""
        for _ in range(max_rounds):
            if self._agree(all(t in self.results for t in tags)):
                return
            self.step_round()
        missing = [t for t in tags if t not in self.results]
        raise TimeoutError(
            f"ops not committed after {max_rounds} rounds: "
            f"{missing if missing else 'local tags done — a peer process is stuck'}")

    def wait_for_leaders(self, max_rounds: int = 100) -> np.ndarray:
        """Step until every group has a leader; returns leader indices [G]
        (this process's local groups on multi-host)."""
        for _ in range(max_rounds):
            out = self.step_round()
            leaders = np.asarray(out.leader)
            if self._agree(bool((leaders >= 0).all())):
                return leaders
        raise TimeoutError(f"not all groups elected a leader in {max_rounds} rounds")

    # -- cluster membership (server join/leave) ----------------------------

    def submit_batch(self, groups, opcode, a=0, b=0, c=0) -> np.ndarray:
        """Vectorized bulk submit: queue one op per entry of ``groups``
        (scalars broadcast) in a single call; returns the correlation
        tags as an array aligned with the input. Amortizes the per-op
        Python staging cost (~5 µs/op through :meth:`submit`) for
        callers driving many groups per round. Config opcodes must go
        through :meth:`add_peer`/:meth:`remove_peer`."""
        groups_a = np.asarray(groups, np.int64).ravel()
        n = groups_a.size
        bc = lambda x: np.broadcast_to(
            np.asarray(x, np.int64).ravel(), (n,))
        op_a, a_a, b_a, c_a = bc(opcode), bc(a), bc(b), bc(c)
        if np.isin(op_a, (OP_CFG_ADD, OP_CFG_REMOVE)).any():
            raise ValueError("membership changes go through "
                             "add_peer/remove_peer, not submit_batch")
        self._refuse_monotone()
        tags = np.arange(self._next_tag, self._next_tag + n)
        if n == 0:
            return tags
        self._next_tag += n
        tag_l = tags.tolist()
        g_l = groups_a.tolist()
        rnd = self.rounds
        self._inflight.update(zip(tag_l, ((g, rnd) for g in g_l)))
        op_l, a_l, b_l, c_l = (op_a.tolist(), a_a.tolist(),
                               b_a.tolist(), c_a.tolist())
        self._inflight_ops.update(
            zip(tag_l, zip(op_l, a_l, b_l, c_l)))
        if not self._stage_direct(groups_a, op_a, a_a, b_a, c_a, tags):
            order = np.argsort(groups_a, kind="stable")
            bounds = np.flatnonzero(np.diff(groups_a[order])) + 1
            for seg in np.split(order, bounds):
                seg_l = seg.tolist()
                q = self._queues.setdefault(g_l[seg_l[0]], deque())
                q.extend((op_l[i], a_l[i], b_l[i], c_l[i], tag_l[i])
                         for i in seg_l)
        self.metrics.counter("ops_submitted").inc(n)
        return tags

    def drive_vector(self, groups, opcode, a, b, c,
                     max_rounds: int = 200,
                     query: QueryVector | None = None) -> np.ndarray | None:
        """One-shot vectorized drive for full-delivery engines (the
        applying server's batched pump): stage every row straight into
        the next round's submit buffer, step shared rounds until all
        rows committed, and correlate results FROM THE STEP OUTPUTS in
        one numpy pass per round — no per-op tag dicts, no harvest
        routing, no result-cache churn. Returns results aligned with the
        input rows, or ``None`` when direct staging is refused (queued
        ops, holds, monotone engines, overfull groups) and the caller
        must take the tracked :meth:`submit_batch` path.

        Per-group FIFO holds because ``_stage_direct``'s stable group
        sort preserves row order within a group and the engine applies
        accepted slots in log order; a rejected row (rare: group mid-
        election) is requeued by ``_requeue_rejected`` and caught by a
        later round's correlation pass.

        ``query`` is a read window's staged rows, to be answered on the
        state this run leaves. They ride the first round
        (:meth:`step_round`); their answers are kept only if that round
        resolved every row of the run, for a round that left one
        unresolved evaluated them on a state the run had not finished
        writing. Refused, or not kept, they are as they were staged and
        :meth:`finish_query_vector` evaluates them alone."""
        g = np.asarray(groups, np.int64)
        n = g.size
        tags = np.arange(self._next_tag, self._next_tag + n)
        if not self._stage_direct(g, np.asarray(opcode, np.int64),
                                  np.asarray(a, np.int64),
                                  np.asarray(b, np.int64),
                                  np.asarray(c, np.int64), tags):
            return None
        self._next_tag += n
        tag0 = tags[0] if n else 0
        res = np.zeros(n, np.int64)
        done = np.zeros(n, bool)
        self.metrics.counter("ops_submitted").inc(n)
        remaining = n

        def correlate(out: StepOutputs) -> None:
            """This block's rows among the round's reports, in one numpy
            pass (part of the round's harvest); then, after the first
            round, the answers of the reads that rode it."""
            nonlocal remaining, query
            valid = np.asarray(out.out_valid)
            if valid.any():
                gi, ii = np.nonzero(valid)
                t = np.asarray(out.out_tag)[gi, ii]
                mine = (t >= tag0) & (t < tag0 + n)
                if mine.any():
                    k = (t[mine] - tag0).astype(np.int64)
                    fresh = ~done[k]
                    k = k[fresh]
                    res[k] = np.asarray(out.out_result)[gi, ii][mine][fresh]
                    done[k] = True
                    remaining -= k.size
            if remaining and self.results:
                # terminal refusals (_requeue_rejected records FAIL for
                # this block's tags): resolve those rows to the sentinel
                # so the rest of the run still returns — the caller maps
                # FAIL to a per-row error
                for t in [t for t in self.results
                          if tag0 <= t < tag0 + n]:
                    k = int(t - tag0)
                    v = self.results.pop(t)
                    if not done[k]:
                        res[k] = v
                        done[k] = True
                        remaining -= 1
            if query is not None:
                rode, query.rode = query.rode, None
                if rode is not None and remaining == 0:
                    self._m_queries_served.inc(
                        query.take(*_split_slab(rode)))
                query = None

        for _ in range(max_rounds):
            self.step_round(correlate=correlate, query=query)
            if remaining == 0:
                self.metrics.counter("ops_committed").inc(n)
                return res
        raise TimeoutError(
            f"vector drive: {remaining}/{n} rows uncommitted after "
            f"{max_rounds} rounds")

    def add_peer(self, group: int, peer: int) -> int:
        """Add ``peer``'s lane to ``group``'s voter set (the reference's
        server join — ``AtomixServerTest.testServerJoin``). A single-server
        Raft config change through the log: returns a correlation tag that
        resolves in ``results`` once the entry is APPLIED (the step
        serializes config changes — one in flight per group — by rejecting
        early submits, which simply requeue here). Needs
        ``Config(dynamic_membership=True)``."""
        from ..ops.apply import OP_CFG_ADD
        if not self.config.dynamic_membership:
            raise ValueError("membership changes need "
                             "Config(dynamic_membership=True)")
        if not 0 <= peer < self.num_peers:
            raise ValueError(f"peer {peer} outside 0..{self.num_peers - 1}")
        return self.submit(group, OP_CFG_ADD, peer)

    def remove_peer(self, group: int, peer: int) -> int:
        """Remove ``peer``'s lane from ``group``'s voter set (server leave
        — ``testServerLeave``). Removing the last member is refused: the
        tag resolves to ``apply.FAIL``. A leader removing itself commits
        the change under the old config and then steps down."""
        if not self.config.dynamic_membership:
            raise ValueError("membership changes need "
                             "Config(dynamic_membership=True)")
        if not 0 <= peer < self.num_peers:
            raise ValueError(f"peer {peer} outside 0..{self.num_peers - 1}")
        return self.submit(group, OP_CFG_REMOVE, peer)

    @staticmethod
    def _config_mask(member: np.ndarray, applied: np.ndarray,
                     term: np.ndarray, role: np.ndarray) -> int:
        """Freshest applied config bitmask among one group's [P] lanes.

        Prefers the CURRENT leader's lane (it serializes config changes,
        so it carries the freshest applied config) — guarded by term so a
        partitioned zombie leader (still role==leader at a stale term)
        cannot shadow the committed config. Leaderless, falls back to the
        most-applied lane, which can transiently lag by one change during
        a snapshot-install/catch-up window (callers that need the
        post-change view step the engine first, as the membership tests
        do)."""
        leaders = np.nonzero(role == 2)[0]
        if len(leaders):
            lead = int(leaders[np.argmax(term[leaders])])
            if term[lead] == term.max():
                return int(member[lead])
        return int(member[int(np.argmax(applied))])

    def voting_members(self, group: int) -> list[int]:
        """Current voter lanes of ``group`` (see :meth:`_config_mask` for
        the lane-selection rule)."""
        s = self.state
        mask = self._config_mask(np.asarray(s.member[group]),
                                 np.asarray(s.applied_index[group]),
                                 np.asarray(s.term[group]),
                                 np.asarray(s.role[group]))
        return [p for p in range(self.num_peers) if (mask >> p) & 1]

    # -- inspection --------------------------------------------------------

    def device_snapshot(self) -> dict:
        """The ``device.*`` telemetry family as a mergeable snapshot
        dict (empty when telemetry is off). This is what ``/stats``
        embeds and ``merge_snapshots`` folds across shards/processes."""
        if self.telemetry is None:
            return {}
        return self.telemetry.snapshot()

    def merged_device_snapshot(self) -> dict:
        """Cluster-wide ``device.*`` snapshot. Identity on one process;
        the multihost subclass allgathers every process's local family
        and folds them with ``merge_snapshots`` (counters sum, gauges
        max) so elections/commit-advance attribute per shard."""
        return self.device_snapshot()

    def leader(self, group: int) -> int:
        role = np.asarray(self.state.role[group])
        term = np.asarray(self.state.term[group])
        leaders = np.nonzero(role == 2)[0]
        if len(leaders) == 0:
            return -1
        return int(leaders[np.argmax(term[leaders])])

    def value(self, group: int, peer: int = 0) -> int:
        return int(self.state.resources.value[group, peer])
