"""Batched Raft consensus as one jitted XLA step.

The reference's consensus core (external Copycat, consumed per SURVEY.md §2.3)
runs one Raft group per server over asyncio-style RPC. Here ALL groups run at
once: state is ``[num_groups, num_peers]`` tensors and one ``step()`` call
advances every group by one synchronous message round —

1. client ops are injected into leader logs,
2. leaders send AppendEntries (log-matching check, ring-buffer entry copy),
3. acks update matchIndex, quorum sort advances commitIndex,
4. election timers fire, RequestVote tallies elect leaders,
5. committed entries are applied through the vectorized resource kernels.

Quorum tallies are sums over the peer axis; when the peer axis is sharded
over a ``jax.sharding.Mesh`` those sums become ICI collectives (XLA inserts
them from the sharding annotations — see ``copycat_tpu.parallel``).

Message loss is first-class: ``deliver[g, from, to]`` masks every exchange,
so partitions/nemesis run *inside* the compiled step (SURVEY.md §4's
"real consensus, fake network" strategy, on device).

Safety properties preserved (tested in tests/test_tpu_consensus.py):
 - election safety: ≤1 leader per (group, term) — single ``voted_for`` per
   voter per term, deterministic lowest-index tie-break among candidates;
 - log matching: AppendEntries carries (prevIndex, prevTerm); mismatch
   rejects and rewinds nextIndex;
 - leader completeness: vote granted only to candidates with up-to-date
   logs (last term, last index) ≥ voter's;
 - commit safety: commitIndex advances only onto entries of the leader's
   current term (Raft §5.4.2 — a fresh leader appends a NoOp to unlock).

The log is a fixed-capacity ring per replica (SURVEY.md §5.7): slot(i) =
(i-1) mod L. Followers lagging beyond the ring window are flagged ``stale``
and stop receiving (snapshot install catches them up — see
``models/raft_groups.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .apply import (
    NUM_POOLS,
    OP_CFG_ADD,
    OP_CFG_REMOVE,
    ResourceConfig,
    ResourceState,
    _gather3,
    apply_entry,
    apply_window,
    drain_events,
    init_resources,
    pool_of,
)

FOLLOWER, CANDIDATE, LEADER = 0, 1, 2


class DeviceTelemetry(NamedTuple):
    """Per-group on-device telemetry deltas for ONE consensus round.

    Every leaf is ``[G]`` i32 (``applies`` is ``[G, NUM_POOLS+1]``) —
    deliberately group-leading and group-local: on a group-sharded mesh
    each value reduces only over the peer/slot axes of its own shard, so
    the telemetry block compiles to ZERO cross-device collectives (the
    same rule the deep accumulators follow — a scalar total here would
    be the one all-reduce in the program). The host sums over G.

    Derived entirely from values the step already computes — no extra
    RNG, no state writes — so the telemetry-off step is bit-identical
    to a tree without the block (``Config.telemetry`` is static; off
    compiles it out entirely and ``StepOutputs.telemetry`` is None).
    """

    elections_started: jnp.ndarray  # lanes whose timer fired this round
    leader_changes: jnp.ndarray     # election won by a lane != round-start
    #                                 leader (or the group was leaderless)
    term_bumps: jnp.ndarray         # delta of the group-max term
    leaderless: jnp.ndarray         # 1 iff no leader at round start
    commit_advance: jnp.ndarray     # delta of the group-max commit index
    commit_max: jnp.ndarray         # post-round max commit index (monotone
    #                                 — the invariant monitor's witness)
    term_max: jnp.ndarray           # post-round max term over lanes
    leader_lane: jnp.ndarray        # post-round leader lane (-1 none) —
    leader_term: jnp.ndarray        # paired with its term (-1 none): the
    #                                 watch-list's ≤1-leader-per-term feed
    applies: jnp.ndarray            # [G, NUM_POOLS+1] entries applied by
    #                                 the reporting lane, by resource pool
    #                                 (last column = NoOp/config entries)
    ring_occ_max: jnp.ndarray       # max over lanes of last-applied
    submit_rejections: jnp.ndarray  # valid slots rejected (backpressure,
    #                                 lease/tag gate) — requeued, not lost
    vote_splits: jnp.ndarray        # 1 iff candidates existed and nobody won
    events_drained: jnp.ndarray     # leader-lane outbox events popped
    events_dropped: jnp.ndarray     # outbox ring drop-oldest overwrites


class RaftState(NamedTuple):
    """Device-resident replicated state for G groups × P peers."""

    term: jnp.ndarray          # [G,P] i32
    voted_for: jnp.ndarray     # [G,P] i32, -1 = none
    role: jnp.ndarray          # [G,P] i32 ∈ {FOLLOWER, CANDIDATE, LEADER}
    leader_hint: jnp.ndarray   # [G,P] i32 peer index, -1 = unknown
    timer: jnp.ndarray         # [G,P] i32 rounds until election timeout
    clock: jnp.ndarray         # [G,P] i32 logical round clock (replicated —
    #                            identical in every lane; stamps log entries
    #                            so TTL/timeout evaluation is deterministic)
    last_index: jnp.ndarray    # [G,P] i32
    commit_index: jnp.ndarray  # [G,P] i32
    applied_index: jnp.ndarray  # [G,P] i32
    next_index: jnp.ndarray    # [G,P,P] i32 (axis1 = owner-as-leader, axis2 = target)
    match_index: jnp.ndarray   # [G,P,P] i32
    log_term: jnp.ndarray      # [G,P,L] i32 ring
    log_op: jnp.ndarray        # [G,P,L] i32 opcode
    log_a: jnp.ndarray         # [G,P,L] i32 arg
    log_b: jnp.ndarray         # [G,P,L] i32 arg
    log_c: jnp.ndarray         # [G,P,L] i32 arg
    log_time: jnp.ndarray      # [G,P,L] i32 logical timestamp at append
    log_tag: jnp.ndarray       # [G,P,L] i32 host correlation tag
    resources: ResourceState
    # Leader lease (appended last — checkpoint leaf padding relies on new
    # fields being strictly trailing): True iff the current leader
    # received same-term acks from a QUORUM in the latest round. Sound in
    # the synchronous round model: a competing leader elected by round R
    # needs a majority of voters at a higher term, any quorum of
    # same-term acks must intersect that majority, and the intersecting
    # node's higher-term reject would have cleared the lease — so a held
    # lease proves no other leader could have committed anything yet,
    # which is exactly the freshness BOUNDED_LINEARIZABLE reads need
    # (reference Consistency.java:157-176) without a log append.
    lease: jnp.ndarray         # [G,P] bool (replicated per lane)
    # Voting membership as of each lane's APPLIED prefix, a bitmask over
    # peer lanes (bit p = lane p votes). Config entries carry the FULL
    # new config (the leader composes the bitmask at append from its
    # current view — Raft §4.1's C_new entries), and a lane's ACTIVE view
    # is derived per round as the latest config entry in its log —
    # adopted at append, reverted on truncation — falling back to this
    # applied mask (Raft's "latest configuration in the log" rule; the
    # applied prefix is immutable, so the fallback is always available).
    # Single-server changes at a time (step-enforced at append) keep any
    # two adjacent configs quorum-intersecting. All-ones unless
    # ``Config.dynamic_membership`` — the static path never reads it.
    member: jnp.ndarray        # [G,P] i32 bitmask


class Submits(NamedTuple):
    """Client ops to inject this round, S slots per group."""

    opcode: jnp.ndarray  # [G,S] i32
    a: jnp.ndarray       # [G,S] i32
    b: jnp.ndarray       # [G,S] i32
    c: jnp.ndarray       # [G,S] i32
    tag: jnp.ndarray     # [G,S] i32
    valid: jnp.ndarray   # [G,S] bool


class StepOutputs(NamedTuple):
    accepted: jnp.ndarray    # [G,S] bool — submit made it into the leader log
    # Results are reported from the MOST-ADVANCED lane (argmax post-apply
    # applied_index), not the leader lane: an entry applied during a
    # leaderless round would otherwise never be reported (its result is
    # not re-derivable later). Every entry is applied by that lane in the
    # first round the global max applied_index passes it; re-reports from
    # lanes catching up later are possible (at-least-once) — consumers
    # dedup by tag (models/raft_groups.py _harvest pops _inflight).
    out_valid: jnp.ndarray   # [G,A] bool — a command applied this round
    out_tag: jnp.ndarray     # [G,A] i32
    out_result: jnp.ndarray  # [G,A] i32
    out_latency: jnp.ndarray  # [G,A] i32 rounds from log append to apply
    #                           (commit latency in logical rounds —
    #                           BASELINE.md p99 metric)
    leader: jnp.ndarray      # [G] i32 leader peer at round start (-1 none)
    commit_index: jnp.ndarray  # [G] i32 leader commit after the round
    stale: jnp.ndarray       # [G,P] bool — lagging beyond ring window
    clock: jnp.ndarray       # [G] i32 post-step logical clock
    # session events drained from the leader lane's outbox ring; host dedups
    # by seq (at-least-once across leader changes)
    ev_seq: jnp.ndarray      # [G,D] i32
    ev_code: jnp.ndarray     # [G,D] i32
    ev_target: jnp.ndarray   # [G,D] i32
    ev_arg: jnp.ndarray      # [G,D] i32
    ev_valid: jnp.ndarray    # [G,D] bool
    # (index, term) each accepted submit landed at / each applied entry
    # came from. Together these give the host PROVABLE loss detection for
    # exactly-once retry without any kernel dedup state (the device-path
    # analogue of the reference's session-sequenced resubmit, Copycat
    # client runtime per SURVEY §2.3): a pending entry (idx, term_e) is
    # certainly lost once an entry with term T > term_e is applied at any
    # index j ≤ idx — log terms are monotone within a log, so the log that
    # held the pending entry had term ≤ term_e < T at j and can never be
    # the committed log; re-submitting cannot double-apply. (idx == j with
    # a different tag is the special case T != term_e of the same rule.)
    assigned: jnp.ndarray       # [G,S] i32 (0 where not accepted)
    assigned_term: jnp.ndarray  # [G,S] i32
    out_index: jnp.ndarray      # [G,A] i32 (0 where not out_valid)
    out_term: jnp.ndarray       # [G,A] i32
    # POST-round leader term (-1 when leaderless): the host gates new
    # submissions for a group while any accepted op's append term is
    # older than this (the op's fate is uncertain across the leader
    # change) — preserving per-group FIFO completion, the reference's
    # session program-order guarantee. Post-round (not round-start) so
    # the gate engages before anything can be drained into a fresh
    # leader's log.
    leader_term: jnp.ndarray    # [G] i32
    # Submit slots rejected PERMANENTLY (a config change that would
    # empty the group): the host fails them to the client immediately
    # instead of requeueing — a forever-retrying config op would block
    # its group's whole queue behind the FIFO suffix-reject.
    refused: jnp.ndarray        # [G,S] bool
    # Per-group telemetry deltas (:class:`DeviceTelemetry`) when
    # ``Config.telemetry`` — None otherwise (a None pytree subtree costs
    # nothing to carry, stack, or fetch). Trailing with a default so
    # every existing positional constructor stays valid.
    telemetry: Any = None


class Config(NamedTuple):
    """Static step configuration (hashable → usable as a jit static arg)."""

    append_window: int = 4    # entries per AppendEntries per round
    applies_per_round: int = 4
    # Per-pool apply budgets (value, map, set, queue, lock, election):
    # the apply phase folds each pool's entries separately, carrying only
    # that pool's arrays — entries in different pools commute — and admits
    # the longest window prefix in which no pool exceeds its budget
    # (apply.py apply_window; PERF.md "conflict-partitioned apply").
    # None = every pool gets the full applies_per_round budget. For mixed
    # workloads where each round touches each pool once or twice, small
    # budgets for the big pools (map/set/queue/lock/election) cut the
    # apply phase's HBM traffic by ~budget/A.
    pool_budgets: tuple | None = None
    timer_min: int = 4        # election timeout in rounds (randomized range)
    timer_max: int = 9
    events_per_round: int = 4  # outbox events drained per step
    resource: ResourceConfig = ResourceConfig()
    use_pallas: bool = False  # Pallas quorum-tally kernel (TPU hot path)
    # Run that kernel in Pallas's interpreter instead of compiling it for
    # the TPU: the only way it runs on the CPU (tests). Never inferred
    # from the backend — the same Config builds the same program wherever
    # it is traced.
    pallas_interpret: bool = False
    # The ``jax.sharding.Mesh`` the state is sharded over, when there is
    # one and the kernel is on: a Mosaic kernel is never partitioned
    # automatically, so it has to be told (ops/pallas_kernels.py).
    # ``RaftGroups(mesh=...)`` fills it in; it changes no result.
    kernel_mesh: Any = None
    # Per-group dynamic voter membership (server join/leave — reference
    # AtomixServerTest.testServerJoin/testServerLeave). When True, quorum
    # tallies count only each lane's ``RaftState.member`` view (dynamic
    # per-group quorum via rank-select), non-member lanes neither
    # campaign nor receive AppendEntries, and OP_CFG_ADD/REMOVE entries
    # change membership at apply time. When False (default) the step
    # compiles exactly as before — static P-lane quorum, member unread.
    dynamic_membership: bool = False
    # Refuse submit acceptance at a leader that did not hold the lease
    # (quorum-acked latest round) LAST round. An entry appended to a
    # partitioned leader's log otherwise rots until heal/supersession —
    # the round-3 mixed-bench p99 of 459 ms was exactly one op waiting
    # out a whole isolation window. Refused slots requeue host-side and
    # land on a live leader within ~an election of the fault, pulling
    # the tail to the election timescale at unchanged throughput.
    lease_gated_accept: bool = True
    # Device-enforced per-group FIFO + dedup for the bulk data plane
    # (models/bulk.py deep pipeline): a submit is accepted only when its
    # tag is EXACTLY (max live-ring tag of the leader log) + 1 + (its
    # rank among this window's valid slots) — i.e. tags must arrive as a
    # dense monotone per-group sequence (1, 2, 3, ...). Duplicates
    # (tag <= ring max) and out-of-order futures are rejected, so the
    # host may re-send ANY unresolved op at ANY time without risking
    # double-apply — the device-side analogue of the reference client's
    # session command sequencing (Copycat client, SURVEY §2.3), derived
    # entirely from the replicated log (election no-ops carry tag 0 and
    # never disturb the max; no new replicated state). Safety
    # (exactly-once) is UNCONDITIONAL: a duplicate whose original still
    # sits in any electable log is rejected, because either the original
    # is inside the ring window (max >= tag) or >= L newer higher-tag
    # stream entries scrolled past it (max > tag); acceptance therefore
    # implies the original can never commit. Liveness under leader
    # churn can wedge on truncated-slot tag inflation — engines with
    # this flag are bulk-plane engines (fault-free delivery), and the
    # driver surfaces a TimeoutError rather than stalling silently.
    # Queue-managed submits (retries of old tags) are incompatible;
    # RaftGroups refuses them on monotone engines.
    monotone_tag_accept: bool = False
    # Device-plane flight-recorder telemetry (docs/OBSERVABILITY.md §
    # device plane): compile a :class:`DeviceTelemetry` block of per-
    # group reductions into the step, returned as
    # ``StepOutputs.telemetry`` and fetched with the existing output
    # transfer (amortized — the hot loop stays one transfer per drive).
    # Derived purely from values the step already computes: no extra
    # randomness, no state writes — OFF compiles the exact pre-telemetry
    # program and the step's state evolution is bit-identical either
    # way (tested in tests/test_device_telemetry.py; A/B in PERF.md
    # round 8). The host side (device.* metrics, flight recorder,
    # invariant monitors) lives in models/telemetry.py.
    telemetry: bool = False


def pin_partitionable_rng() -> None:
    """Pin ``jax_threefry_partitionable`` ON before the step's RNG is
    traced. The legacy lowering materializes GLOBAL random bits and
    slices each shard's block, which on a group-sharded mesh compiles to
    collective-permutes + all-reduces per ``random.randint`` — the
    election-timer draws alone put 22 all-reduces into the step and
    broke the zero-collective contract (``parallel/scaling.py``) on jax
    builds that default the flag off; the partitionable form derives
    every shard's bits locally from the key.

    Invoked at THIS module's import (below), before any repo path can
    touch ``jax.random``: the flag changes ``PRNGKey``/``split`` values
    too, so a lazier pin (e.g. inside ``init_state`` alone) would make
    two same-seed engines built sequentially in one process diverge —
    the first one's key splits run pre-flag, the second's post-flag —
    and break every same-seed differential. The scope is already
    confined: neither the package root nor the client imports ``ops``,
    so host applications that merely import the client never see the
    flag; only engine users (who need it for the zero-collective
    contract) do. Random STREAMS differ from unflagged runs (timer
    draws change), but all in-repo determinism is
    same-process/same-flag — multihost lockstep holds because every
    process imports this module."""
    jax.config.update("jax_threefry_partitionable", True)


pin_partitionable_rng()


def init_state(num_groups: int, num_peers: int, log_slots: int,
               key: jax.Array, config: Config = Config(),
               members=None) -> RaftState:
    """``members`` (optional, needs ``config.dynamic_membership``): initial
    voter set as a ``[P]`` or ``[G,P]`` bool mask — every lane starts with
    the same view. Non-member lanes are cold standbys until an
    ``OP_CFG_ADD`` entry brings them in (e.g. 3 voters in a P=5 tensor)."""
    G, P, L = num_groups, num_peers, log_slots
    z2 = jnp.zeros((G, P), jnp.int32)
    z3 = jnp.zeros((G, P, P), jnp.int32)
    zl = jnp.zeros((G, P, L), jnp.int32)
    if members is None:
        mem = jnp.full((G, P), (1 << P) - 1, jnp.int32)
    else:
        m = jnp.broadcast_to(jnp.asarray(members, bool), (G, P))
        bits = jnp.sum(m * (1 << jnp.arange(P, dtype=jnp.int32))[None, :],
                       axis=1, dtype=jnp.int32)
        mem = jnp.broadcast_to(bits[:, None], (G, P))
    return RaftState(
        term=z2, voted_for=z2 - 1, role=z2 + FOLLOWER, leader_hint=z2 - 1,
        timer=jax.random.randint(key, (G, P), config.timer_min, config.timer_max),
        clock=z2,
        last_index=z2, commit_index=z2, applied_index=z2,
        next_index=z3 + 1, match_index=z3,
        log_term=zl, log_op=zl, log_a=zl, log_b=zl, log_c=zl,
        log_time=zl, log_tag=zl,
        resources=init_resources(G, P, config.resource),
        lease=jnp.zeros((G, P), bool),
        member=mem,
    )


def make_submits(num_groups: int, submit_slots: int) -> Submits:
    G, S = num_groups, submit_slots
    z = jnp.zeros((G, S), jnp.int32)
    return Submits(opcode=z, a=z, b=z, c=z, tag=z,
                   valid=jnp.zeros((G, S), bool))


def full_delivery(num_groups: int, num_peers: int) -> jnp.ndarray:
    return jnp.ones((num_groups, num_peers, num_peers), bool)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _peer_view(x: jnp.ndarray, lead: jnp.ndarray) -> jnp.ndarray:
    """Select x[g, lead[g], ...] → [G, ...] (lead clipped; mask separately).

    One-hot select-reduce over the tiny peer axis instead of
    ``take_along_axis``: XLA lowers these per-row gathers to element-wise
    DMA loops on TPU (measured ~70ns/element — it dominated the step),
    while the masked sum stays a fused VPU pass over x."""
    P = x.shape[1]
    oh = jnp.arange(P, dtype=jnp.int32)[None, :] == jnp.clip(lead, 0)[:, None]
    oh = oh.reshape(oh.shape + (1,) * (x.ndim - 2))
    return jnp.where(oh, x, 0).sum(axis=1).astype(x.dtype)


def _term_at_2d(log_term: jnp.ndarray, last: jnp.ndarray,
                idx: jnp.ndarray) -> jnp.ndarray:
    """Term lookup on a [G,L] ring at idx [G,P] (0 outside the live window)."""
    L = log_term.shape[-1]
    slot = (idx - 1) % L
    t = _gather3(jnp.broadcast_to(log_term[:, None, :],
                                  idx.shape + (L,)), slot)
    valid = (idx >= 1) & (idx <= last[:, None]) & (idx > last[:, None] - L)
    return jnp.where(valid, t, 0)


def _term_at_own(log_term: jnp.ndarray, last: jnp.ndarray,
                 idx: jnp.ndarray) -> jnp.ndarray:
    """Term lookup on each replica's own [G,P,L] ring at idx [G,P]."""
    L = log_term.shape[-1]
    t = _gather3(log_term, (idx - 1) % L)
    valid = (idx >= 1) & (idx <= last) & (idx > last - L)
    return jnp.where(valid, t, 0)


def _window_form(A: int, L: int, config: Config) -> str:
    """Which form :func:`_window_gather` takes: ``rotate`` where A one-hot
    compares a ring slot cost more than the ``ceil(log2(L))`` selects of a
    barrel shifter and the shifter's kernel can run (``config.use_pallas``,
    sizes it fits), else ``onehot``. Static sizes and the kernels' switch
    decide, nothing else."""
    from .pallas_kernels import ring_window_fits
    kernel = config.use_pallas and ring_window_fits(A, L)
    return "rotate" if kernel and A > (L - 1).bit_length() else "onehot"


def _window_gather(slot_all: jnp.ndarray, L: int, config: Config):
    """Reader of the committed window: ``ga(log)`` takes a ``[G,P,L]`` ring
    plane to its ``[G,P,A]`` entries at the ring slots ``slot_all``
    (``[G,P,A]``), which are a cyclic run: ``(slot_all[..., 0] + i) % L``.
    It returns whatever the slots hold; the caller masks the positions
    past the commit index.

    Taking a cyclic run out of a ring is a rotation of the L axis: a
    barrel shifter, one select for each bit of the first slot
    (pallas_kernels.ring_window_pallas: the raw and bulk shapes, A = 16
    of L = 32, kernels on). Where A is no more than the number of those
    bits (the served engines' A = 4 at L = 64), or the kernel is off (in
    jnp the shifter's static slices cost the TPU more than the one-hot
    does), the ``[G,P,A,L]`` one-hot select-reduce is kept, op for op as
    it was, its compare shared by the planes (:func:`_window_form`).
    Neither is ``take_along_axis``, which lowers to an element-wise DMA
    loop on TPU (700 ms for six planes of the raw cell)."""
    A = slot_all.shape[-1]
    if _window_form(A, L, config) == "onehot":
        win_oh = slot_all[..., None] == jnp.arange(L, dtype=jnp.int32)  # [G,P,A,L]
        return lambda log: jnp.where(win_oh, log[:, :, None, :], 0).sum(axis=-1)
    from .pallas_kernels import ring_window_pallas
    return partial(ring_window_pallas, s0=slot_all[..., 0], A=A,
                   interpret=config.pallas_interpret,
                   mesh=config.kernel_mesh)


def _scatter_lane(x: jnp.ndarray, lead: jnp.ndarray, active: jnp.ndarray,
                  new: jnp.ndarray) -> jnp.ndarray:
    """Write new[G,...] into x[G,P,...] at lane (g, lead[g]) where active."""
    P = x.shape[1]
    lane = (jnp.arange(P)[None, :] == lead[:, None]) & active[:, None]
    lane = lane.reshape(lane.shape + (1,) * (x.ndim - 2))
    return jnp.where(lane, jnp.expand_dims(new, 1), x)


def _slot_write(log: jnp.ndarray, slot: jnp.ndarray, mask: jnp.ndarray,
                value: jnp.ndarray) -> jnp.ndarray:
    """Masked scatter value[G,P] into log[G,P,L] at slot[G,P]."""
    L = log.shape[-1]
    hit = (jnp.arange(L)[None, None, :] == slot[..., None]) & mask[..., None]
    return jnp.where(hit, value[..., None], log)


def install_snapshots(state: RaftState, stale: jnp.ndarray,
                      leader: jnp.ndarray,
                      config: Config = Config()) -> RaftState:
    """Catch up followers flagged ``stale`` by copying the leader's lane.

    A follower lagging beyond the ring window can never be served by
    AppendEntries (``can_serve`` in :func:`step`); the reference would ship a
    compacted log segment here. Since live state = applied state + the ring
    (SURVEY.md §5.4), installing a snapshot is: copy the leader's log ring,
    indices and resource state into the stale lane and re-follow the leader.
    Vectorized over all flagged ``[G, P]`` lanes; jit-safe.
    """
    has = stale & (leader >= 0)[:, None]

    def cp(x: jnp.ndarray) -> jnp.ndarray:
        lv = _peer_view(x, leader)
        mask = has.reshape(has.shape + (1,) * (x.ndim - 2))
        return jnp.where(mask, jnp.expand_dims(lv, 1), x)

    return state._replace(
        term=cp(state.term),
        voted_for=jnp.where(has, leader[:, None], state.voted_for),
        role=jnp.where(has, FOLLOWER, state.role),
        leader_hint=jnp.where(has, leader[:, None], state.leader_hint),
        # Fresh full timeout so the caught-up follower doesn't immediately
        # depose the leader it just synced from.
        timer=jnp.where(has, config.timer_max, state.timer),
        last_index=cp(state.last_index), commit_index=cp(state.commit_index),
        applied_index=cp(state.applied_index),
        # next/match are as-owner state: unused until this lane wins an
        # election, which reinitializes them — leave untouched.
        log_term=cp(state.log_term), log_op=cp(state.log_op),
        log_a=cp(state.log_a), log_b=cp(state.log_b), log_c=cp(state.log_c),
        log_time=cp(state.log_time), log_tag=cp(state.log_tag),
        resources=jax.tree.map(cp, state.resources),
        # the applied-config mask is applied state like the pools: the
        # stale lane adopts the leader's (its applied_index jumps with
        # the snapshot; the log ring is copied too, so the derived
        # latest-in-log view matches as well)
        member=cp(state.member),
    )


def current_leader(state: RaftState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-group leader lane and whether one exists: ``(lead [G], active
    [G])``. The highest-term LEADER lane wins; a stale lower-term leader
    stays silent until it learns the higher term."""
    lead_term = jnp.where(state.role == LEADER, state.term, -1)
    lead = jnp.argmax(lead_term, axis=1).astype(jnp.int32)
    active = jnp.max(lead_term, axis=1) >= 0
    return jnp.where(active, lead, -1), active


def query_step(state: RaftState, queries: Submits,
               atomic: jnp.ndarray | None = None,
               config: Config = Config()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Serve read-only ops from the leader's applied state — no log append.

    The reference serves CAUSAL/SEQUENTIAL queries without consensus
    (``Consistency.java:45-126``); this is the device equivalent: a
    separate tiny program (no state output — nothing is written back)
    that evaluates query opcodes against the leader lane's resource
    pools. Serving is gated on the lane being a current leader that (a)
    has applied everything it committed AND (b) has committed an entry of
    its OWN term — a freshly elected leader's commit index can trail its
    predecessor's served state until its election no-op commits (Raft
    §8), and serving before that could hand a client state older than a
    read it already observed. With the gate, reads are sequential:
    leader-local and monotone per group.

    ``atomic`` ([G,S] bool, optional) marks slots needing
    BOUNDED_LINEARIZABLE freshness (the reference's ATOMIC read level,
    ``Consistency.java:157-176``): those are additionally gated on the
    leader LEASE (quorum-acked in the latest round — ``RaftState.lease``),
    which certifies no other leader could have committed anything, so the
    read linearizes at the lease round without a log append.

    Returns ``(results [G,S], served [G,S] bool)`` — unserved slots (no
    leader, fresh leader, applied < commit, or no lease for an atomic
    slot) must be retried or escalated to the command path by the caller
    (models/raft_groups.py does the latter).
    """
    G = state.term.shape[0]
    S = queries.valid.shape[1]
    lead, active = current_leader(state)
    l_applied = _peer_view(state.applied_index, lead)
    l_commit = _peer_view(state.commit_index, lead)
    l_term = _peer_view(state.term, lead)
    l_last = _peer_view(state.last_index, lead)
    l_log_term = _peer_view(state.log_term, lead)
    commit_term = _term_at_2d(l_log_term, l_last, l_commit[:, None])[:, 0]
    current = active & (l_applied >= l_commit) & (commit_term == l_term)
    served = queries.valid & current[:, None]
    if atomic is not None:
        leased = jnp.any(state.lease, axis=1)
        served = served & (~atomic | leased[:, None])

    # Leader-lane view of every pool, broadcast over the S query slots so
    # the shape-generic apply kernel evaluates ALL slots in one fused pass
    # (the broadcast is a view — reads never materialize [G,S,...] pools).
    # A map table of buckets is never viewed whole: the kernel fetches the
    # key's bucket from the leader's replica of the table as it is.
    res = state.resources
    bucketed = res.map_table.ndim == 5
    if bucketed:
        res = res._replace(map_table=res.map_count[..., :0])
    lres = jax.tree.map(
        lambda x: jnp.broadcast_to(
            _peer_view(x, lead)[:, None], (G, S) + x.shape[2:]),
        res)
    map_peer = None
    if bucketed:
        lres = lres._replace(map_table=state.resources.map_table)
        map_peer = jnp.broadcast_to(jnp.maximum(lead, 0)[:, None], (G, S))
    now = jnp.broadcast_to(_peer_view(state.clock, lead)[:, None], (G, S))

    # Read-only evaluation: the returned (possibly TTL-purged) state is
    # discarded, so the replicated pools are never perturbed.
    _, results = apply_entry(
        lres, queries.opcode, queries.a, queries.b, queries.c,
        jnp.zeros_like(queries.opcode), now, served, map_peer)
    return jnp.where(served, results, 0), served


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def step(state: RaftState, submits: Submits, deliver: jnp.ndarray,
         key: jax.Array, config: Config) -> tuple[RaftState, StepOutputs]:
    """Advance every group by one synchronous consensus round."""
    G, P = state.term.shape
    L = state.log_term.shape[-1]
    E = config.append_window
    A = config.applies_per_round
    quorum = P // 2 + 1
    peer_ids = jnp.arange(P)
    g_ids = jnp.arange(G)

    # Submit-leaf normalization: hosts shrink the bytes staged to the
    # device every round by passing COMPACT leaves —
    # a Python/numpy scalar for a burst-uniform opcode/payload (zero
    # transfer), or for ``tag`` a [G,1] column meaning "this base tag at
    # slot 0, consecutive at later slots" (the deep bulk plane's dense
    # per-group streams, models/bulk.py — 16x fewer tag bytes). ``valid``
    # is always a full [G,S] bool array and defines S. Full [G,S] arrays
    # pass through untouched, so every existing caller is unchanged.
    S_sub = submits.valid.shape[-1]

    def _norm(x):
        x = jnp.asarray(x, jnp.int32)
        return x if x.shape == (G, S_sub) \
            else jnp.broadcast_to(x, (G, S_sub))

    tag_n = jnp.asarray(submits.tag, jnp.int32)
    if tag_n.ndim == 2 and tag_n.shape == (G, 1) and S_sub != 1:
        tag_n = tag_n + jnp.arange(S_sub, dtype=jnp.int32)[None, :]
    else:
        tag_n = _norm(tag_n)
    submits = submits._replace(
        opcode=_norm(submits.opcode), a=_norm(submits.a),
        b=_norm(submits.b), c=_norm(submits.c), tag=tag_n)

    # Replicated logical clock: +1 per step in every lane, so entry
    # timestamps (and thus TTL/timeout evaluation) are identical on every
    # replica (SURVEY.md §7.3 #3 — never wall clock inside the kernel).
    clock1 = state.clock + 1

    # Self-delivery is always on (a node talks to itself).
    deliver = deliver | jnp.eye(P, dtype=bool)[None]

    lead, active = current_leader(state)

    # Dynamic membership views (compiled in only when configured; the
    # static path keeps the P-lane quorum and never reads state.member).
    dyn = config.dynamic_membership
    if dyn:
        from .pallas_kernels import kth_largest_masked
        # Each lane's ACTIVE config = the latest config entry in its log
        # — adopted at APPEND, reverted by truncation (Raft §4.1) — else
        # the applied-prefix mask. Entries in (applied, last] live at
        # ring slot (idx-1) % L, so slot s holds index
        # applied + 1 + ((s - applied) % L) when inside the window.
        s_ids_m = jnp.arange(L, dtype=jnp.int32)[None, None, :]
        off_m = (s_ids_m - state.applied_index[..., None]) % L
        win_m = off_m < (state.last_index - state.applied_index)[..., None]
        cfg_m = win_m & ((state.log_op == OP_CFG_ADD)
                         | (state.log_op == OP_CFG_REMOVE))     # [G,P,L]
        key_m = jnp.where(cfg_m, state.applied_index[..., None] + 1 + off_m,
                          0)
        best_m = jnp.max(key_m, axis=-1)                        # [G,P]
        latest_mask = jnp.sum(
            jnp.where(cfg_m & (key_m == best_m[..., None]), state.log_a, 0),
            axis=-1)
        view = jnp.where(best_m > 0, latest_mask, state.member)  # [G,P] i32
        self_member = ((view >> peer_ids[None, :]) & 1).astype(bool)
        view_quorum = jax.lax.population_count(view) // 2 + 1    # [G,P]
        cfg_inflight = _peer_view(best_m > 0, lead)              # [G]
        l_view = _peer_view(view, lead)                          # [G]
        l_quorum = _peer_view(view_quorum, lead)                 # [G]
        # which lanes the leader's active config counts
        l_member = ((l_view[:, None] >> peer_ids[None, :]) & 1) \
            .astype(bool)                                        # [G,P]

    l_term = _peer_view(state.term, lead)          # [G]
    l_last = _peer_view(state.last_index, lead)    # [G]
    l_commit = _peer_view(state.commit_index, lead)
    l_applied = _peer_view(state.applied_index, lead)
    l_next = _peer_view(state.next_index, lead)    # [G,P]
    l_match = _peer_view(state.match_index, lead)  # [G,P]
    l_log_term = _peer_view(state.log_term, lead)  # [G,L]
    l_log_op = _peer_view(state.log_op, lead)
    l_log_a = _peer_view(state.log_a, lead)
    l_log_b = _peer_view(state.log_b, lead)
    l_log_c = _peer_view(state.log_c, lead)
    l_log_time = _peer_view(state.log_time, lead)
    l_log_tag = _peer_view(state.log_tag, lead)
    l_clock = jnp.max(clock1, axis=1)              # [G] (identical per lane)

    # Quorum tallies = k-th largest over the peer axis; Pallas kernel on
    # the TPU hot path, closed-form jnp selection otherwise.
    if config.use_pallas:
        from .pallas_kernels import kth_largest_pallas
        _kth = partial(kth_largest_pallas,
                       interpret=config.pallas_interpret,
                       mesh=config.kernel_mesh)
    else:
        from .pallas_kernels import kth_largest as _kth

    # ---- phase 1: inject client submits into the leader log ----
    # Backpressure: never let the ring overwrite entries the leader itself or
    # a quorum-th replica still has to apply (laggards beyond the window go
    # stale and are snapshot-installed by the host).
    # Under dynamic membership, quorum tallies count only the leader's
    # member view — non-member lanes never receive entries, so an
    # unmasked tally would wedge backpressure/commit at their floor.
    if dyn:
        q_applied = kth_largest_masked(state.applied_index, l_member,
                                       l_quorum)
    else:
        q_applied = _kth(state.applied_index, quorum)
    allowed_last = jnp.minimum(l_applied, q_applied) + L

    accept_ok = active
    if config.lease_gated_accept:
        # last round's quorum-ack witness at the leader lane: no lease →
        # no new appends (host requeues; see Config.lease_gated_accept)
        accept_ok = active & (_peer_view(state.lease, lead) != 0)
    valid = submits.valid & accept_ok[:, None]
    if dyn:
        # Config-change append guard + full-config composition: ONE
        # change in flight at a time (adjacent single-server configs
        # always quorum-intersect; two concurrent ones need not — Raft
        # §4.2), so a config submit is rejected (the host requeues it)
        # while a config entry sits un-applied in the leader's log or
        # another rides earlier in the same window, and removing the
        # last member is refused outright. The leader composes the FULL
        # new config bitmask from its active view (Raft's C_new entries)
        # — that mask, not the submitted lane, is what the entry's ``a``
        # carries, so any lane can adopt a config from one entry.
        is_cfg = (submits.opcode == OP_CFG_ADD) \
            | (submits.opcode == OP_CFG_REMOVE)
        in_range = (submits.a >= 0) & (submits.a < P)
        bit = jnp.where(in_range, 1 << jnp.clip(submits.a, 0, P - 1), 0)
        new_mask = jnp.where(submits.opcode == OP_CFG_ADD,
                             l_view[:, None] | bit,
                             l_view[:, None] & ~bit)            # [G,S]
        first_cfg = (jnp.cumsum((is_cfg & valid).astype(jnp.int32),
                                axis=1) == 1) & is_cfg
        # Permanently impossible (would empty the group): FAIL fast via
        # the refused output — requeueing would livelock the whole queue
        # behind it (suffix rejects below keep FIFO hole-free).
        refused = is_cfg & valid & first_cfg & ~cfg_inflight[:, None] \
            & (new_mask == 0)
        cfg_rejected = is_cfg & valid & ~(first_cfg & ~cfg_inflight[:, None]
                                          & (new_mask != 0))
        # Reject the whole window SUFFIX from a rejected config submit:
        # rejections must stay hole-free (like backpressure's), or a
        # later op in the same window would append — and commit — ahead
        # of the requeued config change, breaking per-group FIFO
        # completion (the session program order _harvest preserves).
        valid = valid & (jnp.cumsum(cfg_rejected.astype(jnp.int32),
                                    axis=1) == 0)
    if config.monotone_tag_accept:
        # Max stream tag in the leader log's LIVE ring window. Slot j's
        # resident index is the unique idx in (last-L, last] with
        # (idx-1) % L == j; slots outside the window (never appended, or
        # beyond a truncated last) are masked out. Election no-ops carry
        # tag 0 and stream tags start at 1, so max==0 means "no stream
        # entry yet".
        j_ids = jnp.arange(L, dtype=jnp.int32)[None, :]
        idx_at = l_last[:, None] - ((l_last[:, None] - (j_ids + 1)) % L)
        in_log = (idx_at >= 1) & (idx_at <= l_last[:, None])
        last_stream = jnp.max(jnp.where(in_log, l_log_tag, 0), axis=1)
        vi = valid.astype(jnp.int32)
        rank = jnp.cumsum(vi, axis=1) - vi       # rank among valid slots
        gate_ok = submits.tag == last_stream[:, None] + 1 + rank
        # suffix-reject from the first gate failure keeps acceptance
        # hole-free (same discipline as backpressure/config rejects)
        gate_fail = valid & ~gate_ok
        valid = valid & gate_ok & (jnp.cumsum(
            gate_fail.astype(jnp.int32), axis=1) == 0)
    pos = l_last[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1)
    accepted = valid & (pos <= allowed_last[:, None])
    # One-hot scatter per log array: accepted slots are distinct within a
    # group (cumsum positions), so at most one submit hits each ring slot —
    # a masked sum over the S axis writes all slots in a single fused VPU
    # pass (XLA's scatter lowers to an element-wise DMA loop on TPU).
    slot_s = jnp.where(accepted, (pos - 1) % L, L)         # [G,S]; L = drop
    inj_hit = slot_s[:, :, None] == jnp.arange(L, dtype=jnp.int32)  # [G,S,L]
    inj_any = inj_hit.any(axis=1)                           # [G,L]

    def _inject(log: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
        filled = jnp.where(inj_hit, vals[:, :, None], 0).sum(axis=1)
        return jnp.where(inj_any, filled, log)

    l_log_term = _inject(l_log_term,
                         jnp.broadcast_to(l_term[:, None], slot_s.shape))
    l_log_op = _inject(l_log_op, submits.opcode)
    l_log_a = _inject(l_log_a,
                      jnp.where(is_cfg, new_mask, submits.a) if dyn
                      else submits.a)
    l_log_b = _inject(l_log_b, submits.b)
    l_log_c = _inject(l_log_c, submits.c)
    l_log_time = _inject(l_log_time,
                         jnp.broadcast_to(l_clock[:, None], slot_s.shape))
    l_log_tag = _inject(l_log_tag, submits.tag)
    l_last = l_last + accepted.sum(axis=1, dtype=jnp.int32)

    # ---- phase 2: AppendEntries leader → followers ----
    del_fwd = _peer_view(deliver, lead)                       # deliver[g,lead,f]
    del_back = _peer_view(jnp.swapaxes(deliver, 1, 2), lead)  # deliver[g,f,lead]
    recv = active[:, None] & (peer_ids[None, :] != lead[:, None]) & del_fwd
    if dyn:
        # leaders replicate only to members of their current config; a
        # re-added lane is behind and reconverges via rewind or the
        # stale→snapshot-install path
        recv = recv & l_member

    prev = l_next - 1                                         # [G,P]
    # The leader can only serve entries still in its ring: prev must sit
    # inside the window (prev == 0 qualifies only while the log hasn't
    # wrapped — a wrapped leader must snapshot-install a fresh follower,
    # never serve overwritten slots relabeled as old indices).
    can_serve = prev > l_last[:, None] - L
    stale = recv & ~can_serve
    recv = recv & can_serve
    prev_term = _term_at_2d(l_log_term, l_last, prev)
    # Follower-side flow control: index j lands in the ring slot of index
    # j-L, so a follower takes only the prefix that overwrites nothing it
    # has yet to apply (as if the message were shorter; the rest comes
    # next round). The leader's submit backpressure protects its own lane
    # and the quorum-th replica only — a follower behind those (commit
    # learned late across an election) had unapplied slots overwritten
    # and then applied the wrong entries: replicas diverged in silence.
    upto = jnp.minimum(jnp.minimum(prev + E, l_last[:, None]),
                       state.applied_index + L)

    msg_term = l_term[:, None]
    ok_term = recv & (msg_term >= state.term)
    reject_term = recv & (msg_term < state.term)

    term1 = jnp.where(ok_term, msg_term, state.term)
    voted1 = jnp.where(ok_term & (msg_term > state.term), -1, state.voted_for)
    role1 = jnp.where(ok_term, FOLLOWER, state.role)
    hint1 = jnp.where(ok_term, lead[:, None], state.leader_hint)
    heartbeat = ok_term

    f_prev_term = _term_at_own(state.log_term, state.last_index, prev)
    in_window = prev > state.last_index - L
    match = ok_term & (
        (prev == 0)
        | (prev <= state.commit_index)  # committed prefix always matches
        | ((prev <= state.last_index) & in_window & (f_prev_term == prev_term)))

    # Entry copy as ONE masked cyclic-window select per log array: the same
    # absolute index lives in the same ring slot on every replica, so
    # copying indices (prev+1 .. upto) is a broadcast of the leader's ring
    # masked to the window of slots {prev%L .. (upto-1)%L} (length ≤ E ≤ L,
    # so the window never self-overlaps). Replaces an E-unrolled
    # gather+scatter chain — the step's former bandwidth hog.
    count = jnp.where(match, jnp.clip(upto - prev, 0, E), 0)  # [G,P]
    s_ids = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    win = ((s_ids - prev[..., None]) % L) < count[..., None]  # [G,P,L]

    def _win_copy(follower: jnp.ndarray, leader_view: jnp.ndarray
                  ) -> jnp.ndarray:
        return jnp.where(win, leader_view[:, None, :], follower)

    log_term2 = _win_copy(state.log_term, l_log_term)
    log_op2 = _win_copy(state.log_op, l_log_op)
    log_a2 = _win_copy(state.log_a, l_log_a)
    log_b2 = _win_copy(state.log_b, l_log_b)
    log_c2 = _win_copy(state.log_c, l_log_c)
    log_time2 = _win_copy(state.log_time, l_log_time)
    log_tag2 = _win_copy(state.log_tag, l_log_tag)

    entries_sent = match & (upto >= prev + 1)
    last2 = jnp.where(entries_sent, upto, state.last_index)
    # Commit advance only after the consistency check passed, capped at the
    # last VERIFIED entry (prev + entries appended) — a follower's unverified
    # tail must never be committed by a leaderCommit heartbeat (Raft §5.3).
    verified = jnp.where(entries_sent, upto, prev)
    commit2 = jnp.where(
        match,
        jnp.maximum(state.commit_index,
                    jnp.minimum(l_commit[:, None], verified)),
        state.commit_index)

    # ---- phase 3: acks → matchIndex/nextIndex, quorum commit advance ----
    ack_seen = (recv | reject_term) & del_back
    leader_stale = active & jnp.any(ack_seen & (term1 > l_term[:, None]), axis=1)
    max_ack_term = jnp.max(jnp.where(ack_seen, term1, 0), axis=1)

    ack_success = match & del_back
    ack_match = jnp.where(entries_sent, upto, prev)
    l_match = jnp.where(ack_success, jnp.maximum(l_match, ack_match), l_match)
    l_next = jnp.where(ack_success, l_match + 1, l_next)
    ack_fail = ok_term & ~match & del_back
    hint = jnp.where(prev <= state.last_index, prev - 1, state.last_index)
    l_next = jnp.where(ack_fail,
                       jnp.clip(jnp.minimum(prev, hint + 1), 1, None), l_next)

    self_lane = peer_ids[None, :] == lead[:, None]
    # Leader lease: a quorum of same-term acks THIS round (self included)
    # with no higher term observed — see RaftState.lease for why this
    # certifies exclusive leadership through this round.
    match_full = jnp.where(self_lane, l_last[:, None], l_match)
    if dyn:
        acked = jnp.sum((ack_success | self_lane) & l_member, axis=1)
        lease_g = active & ~leader_stale & (acked >= l_quorum)
        cand_commit = kth_largest_masked(match_full, l_member, l_quorum)
    else:
        acked = jnp.sum(ack_success | self_lane, axis=1)
        lease_g = active & ~leader_stale & (acked >= quorum)
        cand_commit = _kth(match_full, quorum)
    cand_commit_term = _term_at_2d(l_log_term, l_last, cand_commit[:, None])[:, 0]
    advance = active & ~leader_stale & (cand_commit > l_commit) \
        & (cand_commit_term == l_term)
    l_commit = jnp.where(advance, cand_commit, l_commit)

    # Scatter the leader view back into replica lanes.
    sc = ~leader_stale & active
    term1 = jnp.where(self_lane & leader_stale[:, None],
                      jnp.maximum(l_term[:, None], max_ack_term[:, None]), term1)
    role1 = jnp.where(self_lane & leader_stale[:, None], FOLLOWER, role1)
    voted1 = jnp.where(self_lane & leader_stale[:, None], -1, voted1)
    last2 = _scatter_lane(last2, lead, active, l_last)
    commit2 = _scatter_lane(commit2, lead, sc, l_commit)
    next2 = _scatter_lane(state.next_index, lead, sc, l_next)
    match2 = _scatter_lane(state.match_index, lead, sc, l_match)
    log_term2 = _scatter_lane(log_term2, lead, active, l_log_term)
    log_op2 = _scatter_lane(log_op2, lead, active, l_log_op)
    log_a2 = _scatter_lane(log_a2, lead, active, l_log_a)
    log_b2 = _scatter_lane(log_b2, lead, active, l_log_b)
    log_c2 = _scatter_lane(log_c2, lead, active, l_log_c)
    log_time2 = _scatter_lane(log_time2, lead, active, l_log_time)
    log_tag2 = _scatter_lane(log_tag2, lead, active, l_log_tag)

    # ---- phase 4: election timers + RequestVote tally ----
    key_t, key_c = jax.random.split(key)
    fresh = jax.random.randint(key_t, (G, P), config.timer_min, config.timer_max)
    is_ldr = role1 == LEADER
    # CheckQuorum (Raft thesis §6.2, the standard companion to leader
    # stickiness below): a leader's timer is renewed only by an ack
    # QUORUM this round (lease_g; stale lower-term leaders never renew).
    # Without it, stickiness could wedge a group forever under a stable
    # asymmetric partition — a leader reaching some-but-not-quorum
    # followers keeps them sticky while never committing; here it steps
    # down after an election timeout and its followers become electable.
    renewed = self_lane & lease_g[:, None]
    timer1 = jnp.where(heartbeat | (is_ldr & renewed), fresh,
                       state.timer - 1)
    ldr_down = is_ldr & (timer1 <= 0)
    role1 = jnp.where(ldr_down, FOLLOWER, role1)
    is_ldr = is_ldr & ~ldr_down
    timer1 = jnp.where(ldr_down, fresh, timer1)
    timeout = ~is_ldr & ~heartbeat & ~ldr_down & (timer1 <= 0)
    if dyn:
        # lanes outside their own config view never campaign (a removed
        # server must not disrupt the cluster it left; a standby lane
        # must not elect itself before an ADD brings it in)
        timeout = timeout & self_member

    term_e = jnp.where(timeout, term1 + 1, term1)
    voted_e = jnp.where(timeout, peer_ids[None, :], voted1)
    role_e = jnp.where(timeout, CANDIDATE, role1)
    timer1 = jnp.where(
        timeout, jax.random.randint(key_c, (G, P), config.timer_min,
                                    config.timer_max), timer1)

    cand_mask = role_e == CANDIDATE
    # A vote needs request AND response delivery. Lanes that believe a
    # current leader exists — they received its AppendEntries THIS round,
    # or they ARE it — ignore RequestVote entirely (no term adoption, no
    # grant): Raft's leader-stickiness rule (thesis §4.2.3), which is
    # what stops a server that was removed from the config (and so
    # receives no appends, is never deposed via the ack path, and cannot
    # be caught up) from depose-looping a healthy group with ever-growing
    # terms. A genuinely partitioned MEMBER still deposes a stale leader
    # through its AppendEntries reject (leader_stale above), so real
    # failovers are unaffected.
    reach = cand_mask[:, :, None] & deliver & jnp.swapaxes(deliver, 1, 2) \
        & ~(heartbeat | is_ldr)[:, None, :]
    c_term_b = jnp.where(reach, term_e[:, :, None], 0)
    v_seen = c_term_b.max(axis=1)                                 # [G,V]
    higher = v_seen > term_e
    term_v = jnp.maximum(term_e, v_seen)
    voted_v = jnp.where(higher, -1, voted_e)
    role_v = jnp.where(higher, FOLLOWER, role_e)

    own_last_term = _term_at_own(log_term2, last2, last2)         # [G,P]
    c_pair = (own_last_term[:, :, None], last2[:, :, None])
    v_pair = (own_last_term[:, None, :], last2[:, None, :])
    up_to_date = (c_pair[0] > v_pair[0]) | (
        (c_pair[0] == v_pair[0]) & (c_pair[1] >= v_pair[1]))

    elig = reach & (term_e[:, :, None] == term_v[:, None, :]) & up_to_date \
        & ((voted_v[:, None, :] == -1) | (voted_v[:, None, :] == peer_ids[None, :, None]))
    choice = jnp.where(elig, peer_ids[None, :, None], P).min(axis=1)  # [G,V]
    voted_v = jnp.where(choice < P, choice, voted_v)
    grant = elig & (peer_ids[None, :, None] == choice[:, None, :])
    # role_v is the post-vote role on the candidate's own lane (it may have
    # stepped down to a higher-term candidate).
    if dyn:
        # a candidate counts only votes from lanes in ITS active config
        # view, against that view's quorum (any lane may still GRANT a
        # vote — standard Raft: servers answer RequestVote from/for
        # non-members for liveness during config changes)
        mem_cv = ((view[:, :, None] >> peer_ids[None, None, :]) & 1) \
            .astype(bool)                                         # [G,C,V]
        votes = jnp.sum(grant & mem_cv, axis=2)                   # [G,C]
        won = (role_v == CANDIDATE) & cand_mask & self_member \
            & (votes >= view_quorum)
    else:
        votes = grant.sum(axis=2)                                 # [G,C]
        won = (role_v == CANDIDATE) & cand_mask & (votes >= quorum)

    role_f = jnp.where(won, LEADER, role_v)
    hint_f = jnp.where(won, peer_ids[None, :], hint1)
    # Winner initializes nextIndex/matchIndex and appends a NoOp of its term.
    win_lane = won[:, :, None]
    next2 = jnp.where(win_lane, last2[:, :, None] + 2, next2)  # +1 entry +NoOp
    match2 = jnp.where(win_lane, 0, match2)
    noop_idx = last2 + 1
    noop_slot = (noop_idx - 1) % L
    log_term2 = _slot_write(log_term2, noop_slot, won, term_v)
    log_op2 = _slot_write(log_op2, noop_slot, won, jnp.zeros_like(term_v))
    log_time2 = _slot_write(log_time2, noop_slot, won, clock1)
    log_tag2 = _slot_write(log_tag2, noop_slot, won, jnp.zeros_like(term_v))
    last_f = jnp.where(won, noop_idx, last2)

    # ---- phase 5: apply committed entries (all replicas, A per round) ----
    # All A candidate entries (contiguous indices applied+1 .. applied+A,
    # capped at commit) are read out of each log array by _window_gather:
    # the ring rotated to the window's first slot by a Pallas kernel,
    # log2(L) selects a plane, where A > log2(L) and the kernels are on
    # (the raw and bulk cells, A = 16 of L = 32); the [A, L] one-hot
    # select-reduce where they are not (the served engines, A = 4 of
    # L = 64; every engine with use_pallas off). They are then applied
    # by the conflict-partitioned window kernel: each resource pool folds
    # only ITS entries, carrying only its own arrays (apply.py
    # apply_window).
    idx_all = state.applied_index[..., None] + 1 \
        + jnp.arange(A, dtype=jnp.int32)[None, None, :]       # [G,P,A]
    slot_all = (idx_all - 1) % L
    do_all = idx_all <= commit2[..., None]
    ga = _window_gather(slot_all, L, config)
    time_w = ga(log_time2)
    op_w = ga(log_op2)
    a_w = ga(log_a2)
    b_w = ga(log_b2)
    c_w = ga(log_c2)
    if config.pool_budgets is not None:
        if len(config.pool_budgets) != NUM_POOLS:
            raise ValueError(
                f"pool_budgets needs {NUM_POOLS} entries "
                f"(value,map,set,queue,lock,election), got "
                f"{config.pool_budgets!r}")
        budgets = tuple(max(1, min(int(x), A))
                        for x in config.pool_budgets)
        resources, res_w, admitted = apply_window(
            state.resources, op_w, a_w, b_w, c_w, idx_all, time_w,
            do_all, budgets)
    else:
        # No budgets → every entry in the window applies; the single
        # sequential scan over the composed kernel has fewer fusions than
        # six per-pool folds, which wins when the step is dispatch-bound
        # (small G / single-pool workloads). The partitioned path wins
        # when budgets shrink a heavy pool's HBM traffic (mixed configs).
        xs = jax.tree.map(
            lambda x: jnp.moveaxis(x, 2, 0),                  # [A,G,P]
            (op_w, a_w, b_w, c_w, time_w, idx_all, do_all))

        def _apply_one(resources, x):
            op_i, a_i, b_i, c_i, time_i, idx, do = x
            return apply_entry(resources, op_i, a_i, b_i, c_i, idx,
                               time_i, do)

        if state.resources.map_table.ndim == 5:
            # A bucketed map entry costs its lanes' gather and scatter
            # indices whether the lanes are live or not: take as many
            # turns as the busiest lane applies (a lane's entries are a
            # prefix of the window), the carry updated in place.
            def _turn(i, carry):
                resources, res_all = carry
                resources, r = _apply_one(
                    resources, jax.tree.map(lambda v: v[i], xs))
                return resources, res_all.at[i].set(r)

            resources, res_all = jax.lax.fori_loop(
                0, jnp.max(do_all.sum(axis=-1, dtype=jnp.int32)), _turn,
                (state.resources, jnp.zeros_like(xs[0])))
        else:
            resources, res_all = jax.lax.scan(
                _apply_one, state.resources, xs)
        res_w = jnp.moveaxis(res_all, 0, 2)                   # [G,P,A]
        admitted = do_all
    applied = state.applied_index \
        + admitted.sum(axis=-1, dtype=jnp.int32)

    # Config-change entries take effect on each lane AS IT APPLIES them:
    # an unrolled in-order fold over the ≤A window positions (config
    # changes are rare, so A tiny [G,P] selects per round are noise; the
    # one-in-flight append guard means ≥2 hits per window only when a
    # lane catches up on two serialized changes at once — the fold order
    # keeps even that correct).
    member2 = state.member
    if dyn:
        # config entries carry the full bitmask, so the applied config is
        # just the mask of the latest admitted config entry in the window
        cfg_w = (op_w == OP_CFG_ADD) | (op_w == OP_CFG_REMOVE)
        for i in range(A):
            hit = admitted[:, :, i] & cfg_w[:, :, i]              # [G,P]
            member2 = jnp.where(hit, a_w[:, :, i], member2)

    # Reporting lane: the lane with the highest applied_index AFTER this
    # round. In the first round the global max passes an entry, the argmax
    # lane applies it (all lanes started below it), so every result is
    # reported at least once — even when the group is leaderless (see
    # StepOutputs docstring). One fused pass each over [G,P,A].
    rep = jnp.argmax(applied, axis=1).astype(jnp.int32)       # [G]
    rep_oh = peer_ids[None, :] == rep[:, None]                # [G,P]
    rep3 = lambda x: jnp.where(rep_oh[:, :, None], x, 0).sum(axis=1)
    out_valid = rep3(admitted).astype(bool)                   # [G,A]
    out_tag = jnp.where(out_valid, rep3(ga(log_tag2)), 0)
    out_result = jnp.where(out_valid, rep3(res_w), 0)
    time_rep = rep3(time_w)
    out_latency = jnp.where(out_valid, l_clock[:, None] - time_rep, 0)

    # ---- phase 6: drain session events (leader lane → host) --------------
    # Gated on an active leader so events emitted during leaderless rounds
    # are not popped unseen.
    resources, (ev_seq, ev_code, ev_target, ev_arg, ev_ok) = drain_events(
        resources, config.events_per_round, active)
    lead_ev = active[:, None] & _peer_view(ev_ok, lead)

    if dyn:
        # A leader whose removal has been committed+applied steps down
        # (Raft thesis §4.2.2: it keeps leading while C_new-without-self
        # replicates, under the old config, then stops). Candidates are
        # judged by the ACTIVE view instead — a re-added lane may
        # campaign on its appended-but-uncommitted config, a removed
        # lane's view reverts to the applied mask and it stands down.
        self_m2 = ((member2 >> peer_ids[None, :]) & 1).astype(bool)
        # Step down only when BOTH the applied config and the active
        # view exclude the lane: a lane that won its election on an
        # appended-but-uncommitted re-ADD (view includes it, applied
        # does not) must keep leading until that entry applies, or it
        # would be demoted every round and churn terms forever.
        role_f = jnp.where((role_f == LEADER) & ~self_m2 & ~self_member,
                           FOLLOWER, role_f)
        role_f = jnp.where((role_f == CANDIDATE) & ~self_member, FOLLOWER,
                           role_f)

    new_state = RaftState(
        term=jnp.maximum(term_v, term_e), voted_for=voted_v, role=role_f,
        leader_hint=hint_f, timer=timer1, clock=clock1,
        last_index=last_f, commit_index=commit2, applied_index=applied,
        next_index=next2, match_index=match2,
        log_term=log_term2, log_op=log_op2, log_a=log_a2, log_b=log_b2,
        log_c=log_c2, log_time=log_time2,
        log_tag=log_tag2, resources=resources,
        lease=jnp.broadcast_to(lease_g[:, None], (G, P)),
        member=member2)

    # ---- telemetry block (compiled in only under Config.telemetry) -------
    # Pure reductions over values already computed above: no new RNG, no
    # state writes — the off path is the exact pre-telemetry program.
    # Every reduction stays per-group ([G]-leading) so a group-sharded
    # mesh compiles it without cross-device collectives.
    tel = None
    if config.telemetry:
        i32 = jnp.int32
        term_max = jnp.max(new_state.term, axis=1)
        commit_max = jnp.max(commit2, axis=1)
        post_lead_term = jnp.where(role_f == LEADER, new_state.term, -1)
        post_lead = jnp.argmax(post_lead_term, axis=1).astype(i32)
        post_term = jnp.max(post_lead_term, axis=1)
        rejected = submits.valid & ~accepted
        if dyn:
            rejected = rejected & ~refused
        # entries applied by the reporting lane, bucketed by pool (the
        # commit-stream view — counting all P lanes would overstate by P)
        pool_w = pool_of(op_w)                               # [G,P,A]
        pool_oh = pool_w[..., None] == jnp.arange(NUM_POOLS + 1,
                                                  dtype=i32)  # [G,P,A,K]
        rep_adm = (rep_oh[:, :, None] & admitted)[..., None]
        applies_by_pool = jnp.sum(pool_oh & rep_adm, axis=(1, 2),
                                  dtype=i32)                 # [G,K]
        # outbox accounting: heads advance by drain pops or drop-oldest
        # overwrites; lanes evolve in lockstep, so the max lane is the
        # group's truth
        pops = ev_ok.sum(axis=-1, dtype=i32)                 # [G,P]
        head_adv = resources.ev_head - state.resources.ev_head
        tel = DeviceTelemetry(
            elections_started=timeout.sum(axis=1, dtype=i32),
            leader_changes=jnp.sum(
                won & ((peer_ids[None, :] != lead[:, None])
                       | ~active[:, None]), axis=1, dtype=i32),
            term_bumps=term_max - jnp.max(state.term, axis=1),
            leaderless=(~active).astype(i32),
            commit_advance=commit_max
            - jnp.max(state.commit_index, axis=1),
            commit_max=commit_max,
            term_max=term_max,
            leader_lane=jnp.where(post_term >= 0, post_lead, -1),
            leader_term=post_term,
            applies=applies_by_pool,
            ring_occ_max=jnp.max(last_f - applied, axis=1),
            submit_rejections=rejected.sum(axis=1, dtype=i32),
            vote_splits=(jnp.any(cand_mask, axis=1)
                         & ~jnp.any(won, axis=1)).astype(i32),
            events_drained=lead_ev.sum(axis=1, dtype=i32),
            events_dropped=jnp.max(
                jnp.maximum(head_adv - pops, 0), axis=1),
        )

    outputs = StepOutputs(
        accepted=accepted, out_valid=out_valid, out_tag=out_tag,
        out_result=out_result, out_latency=out_latency, leader=lead,
        commit_index=jnp.where(active, l_commit, jnp.max(commit2, axis=1)),
        stale=stale, clock=l_clock,
        ev_seq=_peer_view(ev_seq, lead), ev_code=_peer_view(ev_code, lead),
        ev_target=_peer_view(ev_target, lead),
        ev_arg=_peer_view(ev_arg, lead), ev_valid=lead_ev,
        assigned=jnp.where(accepted, pos, 0),
        assigned_term=jnp.where(accepted, l_term[:, None], 0),
        out_index=jnp.where(out_valid, rep3(idx_all), 0),
        out_term=jnp.where(out_valid, rep3(ga(log_term2)), 0),
        leader_term=jnp.max(
            jnp.where(role_f == LEADER, new_state.term, -1), axis=1),
        refused=refused if dyn else jnp.zeros_like(submits.valid),
        telemetry=tel)
    return new_state, outputs


def deep_step(state: RaftState, resbuf: jnp.ndarray, valbuf: jnp.ndarray,
              rndbuf: jnp.ndarray, evflag: jnp.ndarray, base: jnp.ndarray,
              rnd: jnp.ndarray, submits: Submits, deliver: jnp.ndarray,
              key: jax.Array, config: Config, onehot: bool = False
              ) -> tuple[RaftState, jnp.ndarray, jnp.ndarray, jnp.ndarray,
                         jnp.ndarray, StepOutputs]:
    """One consensus round + ON-DEVICE result accumulation (deep bulk plane).

    The deep pipelined driver (``models/bulk.py``) commits dense
    per-group tag streams (``Config.monotone_tag_accept``), so an applied
    result's stream rank is ``out_tag - 1 - base[g]`` — this wrapper
    scatters each round's applied results/resolve-rounds into carried
    ``[G, B]`` buffers keyed by that rank. The host then fetches ONE
    buffer set per drive instead of per-round out arrays: one blocking
    device→host fetch per drive, not one per round.

    ``rndbuf`` keeps the EARLIEST resolve round per op (``.min`` scatter)
    so at-least-once re-reports never inflate client latency. ``evflag``
    carries "any session event drained so far" — the host checks one
    scalar and fetches per-round event leaves only on the rare path.
    Reports for tags outside [base+1, base+B] (earlier drives, election
    no-ops) fall on the ``mode="drop"`` sentinel column.
    """
    state, out = step(state, submits, deliver, key, config=config)
    G = out.out_tag.shape[0]
    B = resbuf.shape[1]
    return _deep_accumulate(state, resbuf, valbuf, rndbuf, evflag, base,
                            rnd, out, G, B, onehot)


def _deep_accumulate(state, resbuf, valbuf, rndbuf, evflag, base, rnd,
                     out, G, B, onehot):
    """Scatter one round's applied results into the deep accumulators
    (the body shared by :func:`deep_step` and :func:`deep_scan`)."""
    k = out.out_tag - 1 - base[:, None]
    ok = out.out_valid & (k >= 0) & (k < B)
    rnd_i = jnp.asarray(rnd, jnp.int32)
    if onehot:
        # One-hot select-reduce: ranks are distinct within a group-round,
        # so a masked sum over the A axis writes every hit in one fused
        # pass — and, unlike scatter, it stays SHARD-LOCAL on a
        # group-sharded mesh (the round-4 collective census caught the
        # scatter form compiling to all-gathers of the [G,B] buffers).
        # Cost is O(G*A*B) per round, so the unsharded path below keeps
        # the O(G*A) scatter instead.
        hit = jnp.where(ok, k, -1)[:, :, None] \
            == jnp.arange(B, dtype=jnp.int32)[None, None, :]   # [G,A,B]
        any_hit = hit.any(axis=1)                               # [G,B]
        resbuf = jnp.where(
            any_hit,
            jnp.where(hit, out.out_result[:, :, None], 0).sum(axis=1),
            resbuf)
        rndbuf = jnp.where(
            any_hit,
            jnp.minimum(rndbuf,
                        jnp.where(hit, rnd_i, jnp.int32(2**30)).min(axis=1)),
            rndbuf)
        valbuf = valbuf | any_hit
    else:
        kk = jnp.where(ok, k, B)  # B = drop sentinel (out of range)
        g_ids = jnp.arange(G, dtype=jnp.int32)[:, None]
        resbuf = resbuf.at[g_ids, kk].set(out.out_result, mode="drop")
        rndbuf = rndbuf.at[g_ids, kk].min(
            jnp.broadcast_to(rnd_i, kk.shape), mode="drop")
        valbuf = valbuf.at[g_ids, kk].set(True, mode="drop")
    # per-GROUP event flag (host ors it after the fetch): a scalar
    # .any() here would be the one cross-shard all-reduce in the whole
    # program on a group-sharded mesh (census-verified)
    evflag = evflag | out.ev_valid.any(axis=1)
    return state, resbuf, valbuf, rndbuf, evflag, out


def deep_scan(state: RaftState, resbuf: jnp.ndarray, valbuf: jnp.ndarray,
              rndbuf: jnp.ndarray, evflag: jnp.ndarray, base: jnp.ndarray,
              submits_w: Submits, deliver: jnp.ndarray, key: jax.Array,
              config: Config, onehot: bool = False):
    """The deep drive's ENTIRE blind phase as one compiled program.

    ``submits_w`` stacks W rounds of submit windows ([W, ...] leaves —
    the trailing windows are the empty settle rounds); a ``lax.scan``
    runs :func:`deep_step`'s round W times with the accumulators
    carried on device. The host uploads one stacked payload and
    dispatches ONCE instead of once per window — the per-drive
    host↔device interaction count drops from ~W to 1, on top of the
    round-4 design's zero blocking fetches (``models/bulk.py`` scan
    mode; events come back stacked [W, ...] for the rare
    session-event path).
    """
    W = submits_w.valid.shape[0]
    keys = jax.random.split(key, W)
    rnds = jnp.arange(W, dtype=jnp.int32)

    def body(carry, xs):
        st, rb, vb, nb, ev = carry
        sub, rnd, k = xs
        st, out = step(st, sub, deliver, k, config=config)
        st, rb, vb, nb, ev, out = _deep_accumulate(
            st, rb, vb, nb, ev, base, rnd, out,
            out.out_tag.shape[0], rb.shape[1], onehot)
        return (st, rb, vb, nb, ev), ((out.ev_seq, out.ev_code,
                                       out.ev_target, out.ev_arg,
                                       out.ev_valid), out.telemetry)

    (state, resbuf, valbuf, rndbuf, evflag), (evs, tels) = jax.lax.scan(
        body, (state, resbuf, valbuf, rndbuf, evflag),
        (submits_w, rnds, keys))
    # ``tels`` is the stacked [W, G] telemetry of the whole blind phase
    # (None when Config.telemetry is off) — fetched with the drive's one
    # accumulator harvest, never per round.
    return state, resbuf, valbuf, rndbuf, evflag, evs, tels
