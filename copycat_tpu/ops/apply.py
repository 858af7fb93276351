"""Vectorized state-machine apply kernels for every device resource type.

The reference applies one commit at a time through per-resource executors
(``ResourceManager.operateResource``, ``ResourceManager.java:56``; resource
state machines ``AtomicValueState.java:32``, ``MapState.java:32``,
``LockState.java:33``, ``LeaderElectionState.java:31``, ``QueueState.java:30``,
``SetState.java:32``). Here the same op semantics are data — an opcode plus
three int32 arguments — applied to ALL groups' replicas at once with
``jnp.where`` masking, so XLA vectorizes the apply across the
``[num_groups, num_peers]`` batch instead of dispatching per commit.

Design rules (SURVEY.md §7.3):

- **Fixed shapes**: maps/sets are fixed-slot probe tables, queues and wait
  lists are fixed-capacity rings. Overflow returns the ``FAIL`` sentinel —
  the host falls back to the CPU oracle path for oversized resources.
- **Pay only for hosted types**: every pool size in :class:`ResourceConfig`
  may be 0, which compiles the pool *out* of the kernel entirely (its ops
  then return ``FAIL``). A deployment whose groups host only counters
  carries no map/lock/event state through the step — pool traffic is the
  step's bandwidth bill, so this is the single biggest throughput lever
  (measured 600k → 1.6M committed ops/sec at 10k groups on one chip).
- **Deterministic time** (§7.3 #3): TTLs and lock timeouts are evaluated
  lazily against the *entry's* logical timestamp (the leader's replicated
  round clock at append), never wall clock — replica state stays a pure
  function of the applied log prefix, so all replicas converge bit-exactly.
  Client-observed timeouts are driven through the log (``OP_LOCK_CANCEL``),
  which totally orders grant-vs-timeout races (the reference instead runs
  replicated ``executor().schedule`` timers, ``ResourceStateMachineExecutor``).
- **Events** (§7.3 #4): session-push events (lock grant
  ``LockState.java:publish("lock",…)``, election ``publish("elect",…)``)
  go into a per-lane replicated event ring with absolute sequence numbers;
  the step drains the leader lane into ``StepOutputs`` and the host dedups
  by sequence across leader changes (at-least-once while a leader exists,
  with the authoritative ``OP_LOCK_HOLDER``/``OP_ELECT_LEADER`` queries as
  the overflow-proof fallback).

Only fixed-width state lives on device. Arbitrary Python payloads take the
CPU oracle path (``copycat_tpu.server``); the device path covers the hot,
fixed-shape resource kernels (BASELINE.md configs #1-#5).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INT_MIN = jnp.iinfo(jnp.int32).min
INT_MAX = jnp.iinfo(jnp.int32).max

#: Sentinel returned for failed/absent/overflow results. Device-path values
#: must avoid INT_MIN (the host facades enforce this).
FAIL = int(INT_MIN)

#: A keyed map op whose ``c`` is ``MAP_TELL`` tells an absent key from a
#: stored 0: where the reply is "previous value | 0" it says ``ABSENT``
#: for a key that was not there, and ``FULL`` where a put found no free
#: slot (``FAIL`` is the engine's own refusal, which the served path
#: reports as an error). The served path's values avoid all three.
ABSENT = FAIL + 1
FULL = FAIL + 2
MAP_TELL = -1

#: Slots of one map bucket. A bucket of the table is one tile of int32
#: lanes, ``MAP_TILE``: two rows of keys, two of values, two of deadlines
#: and two of live flags, which the chip keeps and moves as one piece
#: (a bucket laid out over several tiles costs an index a piece).
MAP_BUCKET = 256
MAP_TILE = (8, 128)

# --- opcodes (device-path operation catalog) -------------------------------
# Mirrors the reference's serializer-id catalogs as a dense opcode space:
# AtomicValueCommands ids 50-55, MapCommands ids 60-72, SetCommands 100-105,
# QueueCommands 90-99, LockCommands 115-116, LeaderElectionCommands 110-112.
OP_NOP = 0

# value / long (AtomicValueState.java:32, DistributedAtomicLong.java:29)
OP_VALUE_SET = 1          # a=value, c=ttl ticks (0 = none)
OP_VALUE_GET = 2
OP_VALUE_CAS = 3          # a=expect, b=update -> 1 if swapped else 0
OP_VALUE_GET_AND_SET = 4  # a=update -> previous value
OP_LONG_ADD = 5           # a=delta -> new value (addAndGet)

# map (MapState.java:32; hashed fixed keyspace per SURVEY.md §7.1). With
# c=MAP_TELL (no TTL) "| 0" reads "| ABSENT", a full table FULL, and
# PUT_IF_ABSENT answers the value it found | ABSENT when it put.
OP_MAP_PUT = 10           # a=key, b=value, c=ttl -> previous value | 0
OP_MAP_GET = 11           # a=key -> value | 0
OP_MAP_REMOVE = 12        # a=key -> previous value | 0
OP_MAP_PUT_IF_ABSENT = 13  # a=key, b=value, c=ttl -> 1 if put else 0
OP_MAP_GET_OR_DEFAULT = 14  # a=key, b=default
OP_MAP_REMOVE_IF = 15     # a=key, b=value -> 1 if removed
OP_MAP_REPLACE = 16       # a=key, b=value -> previous | FAIL if absent
OP_MAP_REPLACE_IF = 17    # a=key, b=expect, c=update -> 1 if replaced
OP_MAP_CONTAINS_KEY = 18  # a=key -> 0/1
OP_MAP_CONTAINS_VALUE = 19  # a=value -> 0/1
OP_MAP_SIZE = 20
OP_MAP_IS_EMPTY = 21
OP_MAP_CLEAR = 22

# set (SetState.java:32)
OP_SET_ADD = 30           # a=value, c=ttl -> 1 if added else 0
OP_SET_REMOVE = 31        # a=value -> 1 if removed
OP_SET_CONTAINS = 32      # a=value -> 0/1
OP_SET_SIZE = 33
OP_SET_CLEAR = 34

# queue (QueueState.java:30; device subset — remove(v)/contains take the
# CPU path, SURVEY.md §2.1 QueueState row)
OP_Q_OFFER = 40           # a=value -> 1 | 0 when full
OP_Q_POLL = 41            # -> value | FAIL when empty
OP_Q_PEEK = 42            # -> value | FAIL when empty
OP_Q_SIZE = 43
OP_Q_CLEAR = 44

# lock (LockState.java:33; grant delivered as an event, DistributedLock.java:58)
OP_LOCK_ACQUIRE = 50      # a=holder id, b=timeout ticks (-1 forever, 0 try)
OP_LOCK_RELEASE = 51      # a=holder id -> 1 if released
OP_LOCK_CANCEL = 52       # a=holder id -> 2 already-granted | 1 dequeued | 0 gone
OP_LOCK_HOLDER = 53       # -> current holder id | -1 (authoritative grant
#                           check — the facades' fallback if a grant event
#                           is lost to outbox-ring overflow)

# leader election (LeaderElectionState.java:31; epoch = entry log index)
OP_ELECT_LISTEN = 60      # a=candidate id -> epoch if elected now else 0
OP_ELECT_RESIGN = 61      # a=candidate id (resign / unlisten)
OP_ELECT_IS_LEADER = 62   # a=candidate id, b=epoch -> 0/1 (fencing check)
OP_ELECT_LEADER = 63      # -> current leader id | -1 (authoritative)
OP_ELECT_GET_EPOCH = 64   # -> current epoch

# multimap (MultiMapState.java:30; probe table keyed on the (key, value)
# PAIR — the device variant of the reference's nested map-of-maps)
OP_MM_PUT = 70            # a=key, b=value, c=ttl -> 1 if added, 0 if dup
OP_MM_REMOVE = 71         # a=key -> count of entries removed
OP_MM_REMOVE_ENTRY = 72   # a=key, b=value -> 1 if removed
OP_MM_CONTAINS_KEY = 73   # a=key -> 0/1
OP_MM_CONTAINS_ENTRY = 74  # a=key, b=value -> 0/1
OP_MM_CONTAINS_VALUE = 75  # a=value -> 0/1
OP_MM_COUNT = 76          # a=key -> entries under key (MultiMapState.java:169)
OP_MM_SIZE = 77           # -> total entries
OP_MM_IS_EMPTY = 78
OP_MM_CLEAR = 79

# topic pub/sub (TopicState.java:31; publish fans out through the event
# ring as ONE broadcast event per publish — subscribers filter by their
# replicated membership, which this kernel tracks)
OP_TOPIC_LISTEN = 85      # a=subscriber id -> 1 if added, 0 if already
OP_TOPIC_UNLISTEN = 86    # a=subscriber id -> 1 if removed
OP_TOPIC_PUB = 87         # a=message -> subscriber count at publish
OP_TOPIC_COUNT = 88       # -> current subscriber count

# Cluster membership change (consensus-layer, not a resource pool): a
# single-server Raft configuration change rides the log like any command
# and is applied by the consensus step itself — each replica lane updates
# its OWN membership view when it applies the entry (``ops/consensus.py``
# phase 5). Routed to POOL_NONE here (no resource work, result 0).
# Reference obligation: server join/leave
# (manager/src/test/java/io/atomix/AtomixServerTest.java
# testServerJoin/testServerLeave); safety requires ONE change in flight
# at a time (adjacent single-server configs always share a quorum
# intersection), which the step enforces at append.
OP_CFG_ADD = 90           # a=peer lane -> 0 (idempotent)
OP_CFG_REMOVE = 91        # a=peer lane -> 0 (idempotent; last member kept)

# Read-only opcodes servable on the fast query lane (query_step evaluates
# and DISCARDS state, so admitting a write there would silently drop the
# mutation while acking success — the host validates against this set).
QUERY_OPCODES = frozenset({
    OP_VALUE_GET,
    OP_MAP_GET, OP_MAP_GET_OR_DEFAULT, OP_MAP_CONTAINS_KEY,
    OP_MAP_CONTAINS_VALUE, OP_MAP_SIZE, OP_MAP_IS_EMPTY,
    OP_SET_CONTAINS, OP_SET_SIZE,
    OP_Q_PEEK, OP_Q_SIZE,
    OP_LOCK_HOLDER,
    OP_ELECT_IS_LEADER, OP_ELECT_LEADER, OP_ELECT_GET_EPOCH,
    OP_MM_CONTAINS_KEY, OP_MM_CONTAINS_ENTRY, OP_MM_CONTAINS_VALUE,
    OP_MM_COUNT, OP_MM_SIZE, OP_MM_IS_EMPTY,
    OP_TOPIC_COUNT,
})

# --- event codes (session push, harvested from the leader lane) ------------
EV_NONE = 0
EV_LOCK_GRANT = 1   # target=holder id, arg=1
EV_ELECT = 3        # target=new leader id, arg=epoch (fencing token)
EV_TOPIC_MSG = 4    # target=-1 (broadcast), arg=message


class ResourceConfig(NamedTuple):
    """Fixed device pool sizes (hashable — part of the jit-static Config).

    Any size may be 0: the pool is then compiled out of the kernel and its
    ops return ``FAIL``. Size the pools to the resource types the groups
    actually host — pool state is carried through every step, so unused
    pools cost real HBM bandwidth.
    """

    map_slots: int = 16
    set_slots: int = 16
    queue_slots: int = 16
    wait_slots: int = 8       # lock wait queue (0 = try-lock only)
    listener_slots: int = 8   # election listener queue (0 = no succession)
    event_slots: int = 32     # session-event outbox ring
    multimap_slots: int = 16  # (key, value)-pair probe table
    topic_slots: int = 8      # topic subscriber table

    @classmethod
    def counters_only(cls) -> "ResourceConfig":
        """Value/long registers only — the leanest (fastest) kernel."""
        return cls(map_slots=0, set_slots=0, queue_slots=0, wait_slots=0,
                   listener_slots=0, event_slots=0, multimap_slots=0,
                   topic_slots=0)


class ResourceState(NamedTuple):
    """Per-group, per-replica device-resident resource state.

    Every field is ``[num_groups, num_peers, ...]``: each replica applies the
    same committed ops in the same order, so replica states stay identical —
    exactly the reference's replicated-state-machine discipline, kept as a
    batch dimension so divergence is *testable* (see tests). The event ring
    (``ev_*``) is outbox infrastructure, not linearizable state: lanes drain
    it in lockstep, so its heads may differ across replicas. Disabled pools
    (size 0) are zero-width arrays — present in the tree, absent from the
    compiled program.
    """

    # value register + TTL deadline (0 = none)
    value: jnp.ndarray    # [G,P] i32
    val_dl: jnp.ndarray   # [G,P] i32

    # hashed map: fixed probe table
    map_key: jnp.ndarray   # [G,P,K] i32
    map_val: jnp.ndarray   # [G,P,K] i32
    map_live: jnp.ndarray  # [G,P,K] bool
    map_dl: jnp.ndarray    # [G,P,K] i32 (0 = no TTL)
    # The same map where ``map_buckets(map_slots)`` is more than one: the
    # four planes above are then zero-width and the table lives here, a
    # bucket's keys, values, deadlines and live flags in one ``MAP_TILE``,
    # so that one index fetches or stores a bucket whole. A key lives in
    # the bucket ``map_bucket`` names. ``map_count``: live slots, and those
    # of them with a deadline: what size/is_empty read. Both zero-width
    # otherwise.
    map_table: jnp.ndarray  # [G,P,NB,8,128] i32
    map_count: jnp.ndarray  # [G,P,2] i32

    # set: probe table without values
    set_key: jnp.ndarray   # [G,P,Ks] i32
    set_live: jnp.ndarray  # [G,P,Ks] bool
    set_dl: jnp.ndarray    # [G,P,Ks] i32

    # FIFO queue ring
    q_val: jnp.ndarray     # [G,P,Q] i32
    q_head: jnp.ndarray    # [G,P] i32 (absolute pops)
    q_size: jnp.ndarray    # [G,P] i32

    # lock: holder + wait-queue ring (id, deadline, live)
    lk_holder: jnp.ndarray   # [G,P] i32, -1 = free
    lk_wait_id: jnp.ndarray  # [G,P,W] i32
    lk_wait_dl: jnp.ndarray  # [G,P,W] i32 (INT_MAX = wait forever)
    lk_wait_live: jnp.ndarray  # [G,P,W] bool
    lk_head: jnp.ndarray     # [G,P] i32
    lk_size: jnp.ndarray     # [G,P] i32

    # leader election: leader + listener ring + epoch fencing token
    el_leader: jnp.ndarray   # [G,P] i32, -1 = none
    el_epoch: jnp.ndarray    # [G,P] i32 (log index of the winning listen)
    el_id: jnp.ndarray       # [G,P,Wl] i32
    el_live: jnp.ndarray     # [G,P,Wl] bool
    el_head: jnp.ndarray     # [G,P] i32
    el_size: jnp.ndarray     # [G,P] i32

    # session-event outbox ring (code/target/arg), absolute head/tail seqs
    ev_code: jnp.ndarray    # [G,P,E] i32
    ev_target: jnp.ndarray  # [G,P,E] i32
    ev_arg: jnp.ndarray     # [G,P,E] i32
    ev_head: jnp.ndarray    # [G,P] i32
    ev_tail: jnp.ndarray    # [G,P] i32

    # multimap: probe table keyed on the (key, value) PAIR
    mm_key: jnp.ndarray     # [G,P,M] i32
    mm_val: jnp.ndarray     # [G,P,M] i32
    mm_live: jnp.ndarray    # [G,P,M] bool
    mm_dl: jnp.ndarray      # [G,P,M] i32 (0 = no TTL)

    # topic: subscriber membership table
    tp_id: jnp.ndarray      # [G,P,T] i32
    tp_live: jnp.ndarray    # [G,P,T] bool


def map_buckets(map_slots: int) -> int:
    """Buckets of a map table of ``map_slots``: ``MAP_BUCKET`` slots each
    where the table is whole buckets and more than one, else the table
    is its one bucket (and a keyed op passes over all of it)."""
    if map_slots > MAP_BUCKET and map_slots % MAP_BUCKET == 0:
        return map_slots // MAP_BUCKET
    return 1


def map_bucket(key: jnp.ndarray, buckets: int) -> jnp.ndarray:
    """The bucket a key lives in: multiply-shift on the key's 32 bits
    (Knuth's 2^32/phi), the high 16 bits modulo ``buckets``. Part of the
    state's format (docs/DURABILITY.md): every member and every restore
    has to find a key where the put left it."""
    mixed = key.astype(jnp.uint32) * jnp.uint32(2654435761)
    return ((mixed >> 16) % jnp.uint32(buckets)).astype(jnp.int32)


def init_resources(num_groups: int, num_peers: int,
                   rc: ResourceConfig = ResourceConfig()) -> ResourceState:
    G, P = num_groups, num_peers
    z2 = jnp.zeros((G, P), jnp.int32)

    def zi(n):
        return jnp.zeros((G, P, n), jnp.int32)

    def zb(n):
        return jnp.zeros((G, P, n), bool)

    buckets = map_buckets(rc.map_slots)
    flat = rc.map_slots if buckets == 1 else 0
    return ResourceState(
        value=z2, val_dl=z2,
        map_key=zi(flat), map_val=zi(flat),
        map_live=zb(flat), map_dl=zi(flat),
        map_table=zi(0) if buckets == 1 else jnp.zeros(
            (G, P, buckets, *MAP_TILE), jnp.int32),
        map_count=zi(0 if buckets == 1 else 2),
        set_key=zi(rc.set_slots), set_live=zb(rc.set_slots),
        set_dl=zi(rc.set_slots),
        q_val=zi(rc.queue_slots), q_head=z2, q_size=z2,
        lk_holder=z2 - 1, lk_wait_id=zi(rc.wait_slots),
        lk_wait_dl=zi(rc.wait_slots), lk_wait_live=zb(rc.wait_slots),
        lk_head=z2, lk_size=z2,
        el_leader=z2 - 1, el_epoch=z2, el_id=zi(rc.listener_slots),
        el_live=zb(rc.listener_slots), el_head=z2, el_size=z2,
        ev_code=zi(rc.event_slots), ev_target=zi(rc.event_slots),
        ev_arg=zi(rc.event_slots), ev_head=z2, ev_tail=z2,
        mm_key=zi(rc.multimap_slots), mm_val=zi(rc.multimap_slots),
        mm_live=zb(rc.multimap_slots), mm_dl=zi(rc.multimap_slots),
        tp_id=zi(rc.topic_slots), tp_live=zb(rc.topic_slots),
    )


# ---------------------------------------------------------------------------
# small vectorized helpers over [G,P,N] pools
# ---------------------------------------------------------------------------

def _gather3(arr: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """arr[G,P,N] selected at slot[G,P] -> [G,P].

    One-hot select-reduce: take_along_axis lowers to an element-wise DMA
    loop on TPU; the masked sum is one fused vector pass over the pool."""
    N = arr.shape[-1]
    oh = slot[..., None] == jnp.arange(N, dtype=jnp.int32)
    return jnp.where(oh, arr, 0).sum(axis=-1).astype(arr.dtype)


def _scatter3(arr: jnp.ndarray, slot: jnp.ndarray, mask: jnp.ndarray,
              value: jnp.ndarray) -> jnp.ndarray:
    """Masked write of value[G,P] into arr[G,P,N] at slot[G,P]."""
    N = arr.shape[-1]
    hit = (jnp.arange(N)[None, None, :] == slot[..., None]) & mask[..., None]
    return jnp.where(hit, value[..., None], arr)


def _first_true(mask: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(index of first True along last axis, any True) for mask[G,P,N].

    One max-reduce instead of argmax + any (two reduces): score slot i as
    N-i where mask holds, 0 otherwise — the max is N-first_index, and 0
    means no hit. Profiled in the apply scan: argmax+reduce_or were ~25%
    of the mixed round (PERF.md)."""
    N = mask.shape[-1]
    score = jnp.where(mask, N - jnp.arange(N, dtype=jnp.int32), 0)
    best = jnp.max(score, axis=-1)
    found = best > 0
    return jnp.where(found, N - best, 0).astype(jnp.int32), found


def _ring_pos(head: jnp.ndarray, n: int) -> jnp.ndarray:
    """Position-in-queue of each ring slot: [G,P,N] given head[G,P]."""
    slots = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    return (slots - head[..., None]) % n


def _ring_compact(mask: jnp.ndarray, head, size, pos, live_arr, live_win,
                  *arrays):
    """Stable-compact ring slots where ``mask`` holds; returns
    (head, size, live, compacted arrays...). FIFO order of live entries is
    preserved (argsort key = pos for live, pos+N for dead). Lanes where
    ``mask`` is False keep every field untouched."""
    N = arrays[0].shape[-1]
    # Stable live-first order WITHOUT argsort: ring positions are a
    # permutation of 0..N-1, so the keys (pos for live, N+pos for dead) are
    # pairwise distinct and each slot's target rank is just how many keys
    # are smaller — O(N²) vector compares beat the sort network (PERF.md).
    key = jnp.where(live_win, pos, N + pos)
    rank = jnp.sum((key[..., None, :] < key[..., :, None]).astype(jnp.int32),
                   axis=-1)                                   # [G,P,N]
    count = jnp.sum(live_win, axis=-1).astype(jnp.int32)
    m3 = mask[..., None]
    # permutation as a one-hot [G,P,N,N] select-reduce (N is small); the
    # take_along_axis equivalent lowers to an element-wise DMA loop on TPU.
    # perm[i, j] == True iff the slot moving to position i is j, i.e.
    # rank[j] == i.
    perm = rank[..., None, :] == jnp.arange(N, dtype=jnp.int32)[:, None]
    pick = lambda arr: jnp.where(perm, arr[..., None, :], 0).sum(-1).astype(
        arr.dtype)
    out = [jnp.where(m3, pick(arr), arr) for arr in arrays]
    live = jnp.where(m3, jnp.arange(N)[None, None, :] < count[..., None],
                     live_arr)
    head = jnp.where(mask, 0, head)
    size = jnp.where(mask, count, size)
    return head, size, live, out


# ---------------------------------------------------------------------------
# pool classification (conflict partitioning)
# ---------------------------------------------------------------------------

#: Pool ids: entries in DIFFERENT pools commute (disjoint state), so the
#: step's apply phase folds each pool's entries independently, touching
#: only that pool's arrays (PERF.md "conflict-partitioned apply").
(POOL_VALUE, POOL_MAP, POOL_SET, POOL_QUEUE, POOL_LOCK, POOL_ELECT,
 POOL_MMAP, POOL_TOPIC) = range(8)
NUM_POOLS = 8
POOL_NONE = NUM_POOLS  # NoOps — applied (indices advance), no pool work


def pool_of(opcode: jnp.ndarray) -> jnp.ndarray:
    """Map opcodes to pool ids ([G,P] -> [G,P], POOL_NONE for NoOp)."""
    pool = jnp.full_like(opcode, POOL_NONE)
    pool = jnp.where((opcode >= OP_VALUE_SET) & (opcode <= OP_LONG_ADD),
                     POOL_VALUE, pool)
    pool = jnp.where((opcode >= OP_MAP_PUT) & (opcode <= OP_MAP_CLEAR),
                     POOL_MAP, pool)
    pool = jnp.where((opcode >= OP_SET_ADD) & (opcode <= OP_SET_CLEAR),
                     POOL_SET, pool)
    pool = jnp.where((opcode >= OP_Q_OFFER) & (opcode <= OP_Q_CLEAR),
                     POOL_QUEUE, pool)
    pool = jnp.where((opcode >= OP_LOCK_ACQUIRE) & (opcode <= OP_LOCK_HOLDER),
                     POOL_LOCK, pool)
    pool = jnp.where((opcode >= OP_ELECT_LISTEN) & (opcode <= OP_ELECT_GET_EPOCH),
                     POOL_ELECT, pool)
    pool = jnp.where((opcode >= OP_MM_PUT) & (opcode <= OP_MM_CLEAR),
                     POOL_MMAP, pool)
    pool = jnp.where((opcode >= OP_TOPIC_LISTEN) & (opcode <= OP_TOPIC_COUNT),
                     POOL_TOPIC, pool)
    return pool


# ---------------------------------------------------------------------------
# per-pool apply kernels
#
# Each kernel applies ONE entry per (group, replica) lane against ONLY its
# pool's arrays, so a scan over a pool's entries carries that pool's HBM
# and nothing else. ``apply_entry`` below composes all six for the
# single-entry case (query lane + CPU-oracle differential tests).
# ---------------------------------------------------------------------------

def apply_value(value, val_dl, opcode, a, b, c, now, live):
    """Value/long registers; returns ((value, val_dl), result)."""
    def op(code):
        return live & (opcode == code)

    expired = (val_dl > 0) & (val_dl <= now)
    eff = jnp.where(expired, 0, value)  # TTL'd value reads as unset

    is_set = op(OP_VALUE_SET)
    is_get = op(OP_VALUE_GET)
    is_cas = op(OP_VALUE_CAS)
    is_gas = op(OP_VALUE_GET_AND_SET)
    is_add = op(OP_LONG_ADD)
    cas_hit = is_cas & (eff == a)
    # Only ops that actually write may touch value/val_dl — a failed CAS
    # must leave an active TTL intact.
    wrote = is_set | cas_hit | is_gas | is_add
    purge = (is_get | is_cas) & expired  # observed expiry without writing

    new_value = eff
    new_value = jnp.where(is_set, a, new_value)
    new_value = jnp.where(cas_hit, b, new_value)
    new_value = jnp.where(is_gas, a, new_value)
    new_value = jnp.where(is_add, eff + a, new_value)
    out_value = jnp.where(wrote, new_value, jnp.where(purge, 0, value))
    new_dl = jnp.where(is_set & (c > 0), now + c, 0)
    out_dl = jnp.where(wrote, new_dl, jnp.where(purge, 0, val_dl))

    result = jnp.zeros_like(opcode)
    result = jnp.where(is_get, eff, result)
    result = jnp.where(is_cas, cas_hit.astype(jnp.int32), result)
    result = jnp.where(is_gas, eff, result)
    result = jnp.where(is_add, eff + a, result)
    return (out_value, out_dl), result


def _map_row(mk, mv, ml, mdl, opcode, a, b, c, now, live):
    """One entry a lane against one row of map slots ``[..., N]``: the
    whole table where it is one bucket, else the key's bucket. Returns
    ((mk, mv, ml, mdl), result); size, is_empty, contains_value and clear
    speak of the row alone."""
    def op(code):
        return live & (opcode == code)

    is_map = live & (opcode >= OP_MAP_PUT) & (opcode <= OP_MAP_CLEAR)
    result = jnp.zeros_like(opcode)
    tell = c == MAP_TELL
    m_alive = ml & ((mdl == 0) | (mdl > now[..., None]))
    hit = m_alive & (mk == a[..., None])
    hit_idx, hit_any = _first_true(hit)
    free_idx, free_any = _first_true(~m_alive)
    old = jnp.where(hit_any, _gather3(mv, hit_idx),
                    jnp.where(tell, ABSENT, 0))

    put = op(OP_MAP_PUT)
    pia = op(OP_MAP_PUT_IF_ABSENT)
    rep = op(OP_MAP_REPLACE)
    repif = op(OP_MAP_REPLACE_IF) & hit_any & (old == b)
    write_new = (put | pia) & ~hit_any           # needs a free slot
    write_over = (put & hit_any) | (rep & hit_any) | repif
    ins_ok = write_new & free_any
    w_idx = jnp.where(hit_any, hit_idx, free_idx)
    w_val = jnp.where(repif, c, b)
    w_dl = jnp.where((put | pia) & (c > 0), now + c, 0)
    do_write = ins_ok | write_over
    mk = _scatter3(mk, w_idx, do_write, a)
    mv = _scatter3(mv, w_idx, do_write, w_val)
    mdl = _scatter3(mdl, w_idx, do_write,
                    jnp.where(write_over & ~put, 0, w_dl))
    ml = _scatter3(ml, w_idx, do_write, jnp.ones_like(a, bool))

    rm = op(OP_MAP_REMOVE) | (op(OP_MAP_REMOVE_IF) & (old == b))
    ml = _scatter3(ml, hit_idx, rm & hit_any, jnp.zeros_like(a, bool))
    ml = jnp.where(op(OP_MAP_CLEAR)[..., None], False, ml)
    # drop expired slots whenever any map op touches the row (lazy
    # purge; just-written slots have dl == 0 or dl > now, so they
    # always survive)
    ml = jnp.where(is_map[..., None],
                   ml & ((mdl == 0) | (mdl > now[..., None])), ml)

    m_size = jnp.sum(m_alive, axis=-1).astype(jnp.int32)
    full = jnp.where(tell, FULL, INT_MIN)
    result = jnp.where(put, old, result)
    result = jnp.where(put & write_new & ~free_any, full, result)
    result = jnp.where(pia, jnp.where(
        hit_any, jnp.where(tell, old, 0),
        jnp.where(free_any, jnp.where(tell, ABSENT, 1), full)), result)
    result = jnp.where(op(OP_MAP_GET), old, result)
    result = jnp.where(op(OP_MAP_GET_OR_DEFAULT),
                       jnp.where(hit_any, old, b), result)
    result = jnp.where(op(OP_MAP_REMOVE), old, result)
    result = jnp.where(op(OP_MAP_REMOVE_IF),
                       (hit_any & (old == b)).astype(jnp.int32), result)
    result = jnp.where(rep, jnp.where(
        hit_any, old, jnp.where(tell, ABSENT, INT_MIN)), result)
    result = jnp.where(op(OP_MAP_REPLACE_IF), repif.astype(jnp.int32),
                       result)
    result = jnp.where(op(OP_MAP_CONTAINS_KEY),
                       hit_any.astype(jnp.int32), result)
    result = jnp.where(op(OP_MAP_CONTAINS_VALUE),
                       jnp.any(m_alive & (mv == a[..., None]),
                               axis=-1).astype(jnp.int32), result)
    result = jnp.where(op(OP_MAP_SIZE), m_size, result)
    result = jnp.where(op(OP_MAP_IS_EMPTY),
                       (m_size == 0).astype(jnp.int32), result)
    return (mk, mv, ml, mdl), result


def apply_map(mk, mv, ml, mdl, mt, mc, opcode, a, b, c, now, live,
              peer=None):
    """Hashed probe-table map; returns ((mk, mv, ml, mdl, mt, mc), result).

    A table of one bucket is the four planes, and every op passes over
    them. A table of more is ``mt``: an op fetches its key's bucket (one
    gather index a lane moves the bucket's four rows), works on that and
    stores it back (one scatter index); no term touches more unless the
    op is about the whole map. ``size``/``is_empty`` read ``mc``;
    ``contains_value`` and a size over slots with deadlines (an expired
    slot counts until its bucket is touched) pass over the table in an
    evaluation in which some lane holds one, ``clear`` writes it in a
    round in which some lane holds one.

    ``peer`` ([G,S], read-only callers): lane ``[g,s]`` reads replica
    ``peer[g,s]`` of ``mt [G,P,...]``; ``mc`` is already the lanes'.
    ``None``: the lanes are the replicas, and buckets are stored."""
    is_map = live & (opcode >= OP_MAP_PUT) & (opcode <= OP_MAP_CLEAR)
    if mt.ndim != 5:
        if mk.shape[-1] == 0:
            return (mk, mv, ml, mdl, mt, mc), jnp.where(
                is_map, INT_MIN, jnp.zeros_like(opcode))
        rows, result = _map_row(mk, mv, ml, mdl, opcode, a, b, c, now, live)
        return (*rows, mt, mc), result

    def op(code):
        return live & (opcode == code)

    G, P, buckets = mt.shape[:3]

    def planes(t):          # tiles [..., 8, 128] -> key, value, deadline, live
        k, v, dl, lv = jnp.moveaxis(
            t.reshape(*t.shape[:-2], 4, MAP_BUCKET), -2, 0)
        return k, v, dl, lv != 0

    at = (jnp.arange(G)[:, None],
          jnp.arange(P)[None, :] if peer is None else peer,
          map_bucket(a, buckets))
    with jax.named_scope("map_lookup"):
        ok, ov, odl, ol = planes(mt[at])                   # each [G,X,B]
        (rk, rv, rl, rdl), result = _map_row(
            ok, ov, ol, odl, opcode, a, b, c, now, live)
        if peer is None:
            mt = mt.at[at].set(
                jnp.stack([rk, rv, rdl, rl.astype(jnp.int32)],
                          axis=-2).reshape(*rk.shape[:-1], *MAP_TILE),
                indices_are_sorted=True, unique_indices=True)
            count = lambda live_, dl: jnp.stack(
                [live_.sum(-1, dtype=jnp.int32),
                 (live_ & (dl != 0)).sum(-1, dtype=jnp.int32)], axis=-1)
            mc = mc + count(rl, rdl) - count(ol, odl)

    has_value = op(OP_MAP_CONTAINS_VALUE)
    sized = op(OP_MAP_SIZE) | op(OP_MAP_IS_EMPTY)
    clear = op(OP_MAP_CLEAR)

    def whole(t):
        """What only a pass over a lane's whole table can say: does it
        hold the value, and how many slots are alive now."""
        # on the tiles as they lie (rows 2-3 values, 4-5 deadlines, 6-7
        # live): a reshape of the whole table would be a copy of it
        val, dl, lv = t[..., 2:4, :], t[..., 4:6, :], t[..., 6:8, :]
        at_ = lambda x: x[..., None, None, None]
        alive = (lv != 0) & ((dl == 0) | (dl > at_(now)))
        return (jnp.any(alive & (val == at_(a)), axis=(-3, -2, -1)),
                jnp.where(mc[..., 1] > 0,
                          alive.sum((-3, -2, -1), dtype=jnp.int32),
                          mc[..., 0]))

    asked = jnp.any(has_value | (sized & (mc[..., 1] > 0)))
    if peer is None:
        # One loop of no or one turn for the reads above and for clear: a
        # loop's carry is updated in place, where a conditional's
        # pass-through branch would copy the table every entry.
        _, mt, found, size = jax.lax.while_loop(
            lambda carry: carry[0],
            lambda carry: (False, jnp.where(
                clear[..., None, None, None], 0, carry[1]),
                *whole(carry[1])),
            (asked | jnp.any(clear), mt, jnp.zeros_like(live), mc[..., 0]))
        mc = jnp.where(clear[..., None], 0, mc)
    else:
        found, size = jax.lax.cond(
            asked,
            lambda: whole(jnp.take_along_axis(
                mt, peer[:, :, None, None, None], axis=1)),
            lambda: (jnp.zeros_like(live), mc[..., 0]))
    result = jnp.where(has_value, found.astype(jnp.int32), result)
    result = jnp.where(op(OP_MAP_SIZE), size, result)
    result = jnp.where(op(OP_MAP_IS_EMPTY), (size == 0).astype(jnp.int32),
                       result)
    return (mk, mv, ml, mdl, mt, mc), result


def apply_set(sk, sl, sdl, opcode, a, b, c, now, live):
    """Probe-table set; returns ((sk, sl, sdl), result)."""
    def op(code):
        return live & (opcode == code)

    is_setop = live & (opcode >= OP_SET_ADD) & (opcode <= OP_SET_CLEAR)
    result = jnp.zeros_like(opcode)
    if sk.shape[-1] == 0:
        return (sk, sl, sdl), jnp.where(is_setop, INT_MIN, result)

    s_alive = sl & ((sdl == 0) | (sdl > now[..., None]))
    s_hit = s_alive & (sk == a[..., None])
    s_hit_idx, s_hit_any = _first_true(s_hit)
    s_free_idx, s_free_any = _first_true(~s_alive)

    add = op(OP_SET_ADD) & ~s_hit_any & s_free_any
    sk = _scatter3(sk, s_free_idx, add, a)
    sdl = _scatter3(sdl, s_free_idx, add, jnp.where(c > 0, now + c, 0))
    sl = _scatter3(sl, s_free_idx, add, jnp.ones_like(a, bool))
    srm = op(OP_SET_REMOVE) & s_hit_any
    sl = _scatter3(sl, s_hit_idx, srm, jnp.zeros_like(a, bool))
    sl = jnp.where(op(OP_SET_CLEAR)[..., None], False, sl)
    sl = jnp.where(is_setop[..., None],
                   sl & ((sdl == 0) | (sdl > now[..., None])), sl)
    s_size = jnp.sum(s_alive, axis=-1).astype(jnp.int32)
    result = jnp.where(op(OP_SET_ADD),
                       jnp.where(s_hit_any, 0,
                                 jnp.where(s_free_any, 1, INT_MIN)),
                       result)
    result = jnp.where(op(OP_SET_REMOVE), s_hit_any.astype(jnp.int32),
                       result)
    result = jnp.where(op(OP_SET_CONTAINS), s_hit_any.astype(jnp.int32),
                       result)
    result = jnp.where(op(OP_SET_SIZE), s_size, result)
    return (sk, sl, sdl), result


def apply_queue(qv, qh, qs, opcode, a, b, c, now, live):
    """FIFO ring queue; returns ((qv, qh, qs), result)."""
    def op(code):
        return live & (opcode == code)

    is_q = live & (opcode >= OP_Q_OFFER) & (opcode <= OP_Q_CLEAR)
    result = jnp.zeros_like(opcode)
    if qv.shape[-1] == 0:
        return (qv, qh, qs), jnp.where(is_q, INT_MIN, result)

    Q = qv.shape[-1]
    offer = op(OP_Q_OFFER)
    can_push = offer & (qs < Q)
    qv = _scatter3(qv, (qh + qs) % Q, can_push, a)
    head_val = _gather3(qv, qh % Q)
    poll = op(OP_Q_POLL) & (qs > 0)
    qs = jnp.where(can_push, qs + 1, qs)
    qh = jnp.where(poll, qh + 1, qh)
    qs = jnp.where(poll, qs - 1, qs)
    qs = jnp.where(op(OP_Q_CLEAR), 0, qs)
    result = jnp.where(offer, can_push.astype(jnp.int32), result)
    result = jnp.where(op(OP_Q_POLL),
                       jnp.where(poll, head_val, INT_MIN), result)
    result = jnp.where(op(OP_Q_PEEK),
                       jnp.where(qs > 0, head_val, INT_MIN), result)
    result = jnp.where(op(OP_Q_SIZE), qs, result)
    return (qv, qh, qs), result


def apply_lock(holder, wid, wdl, wlv, lh, ls, opcode, a, b, now, live):
    """Lock kernel; returns ((holder, wid, wdl, wlv, lh, ls), result,
    (ev_mask, ev_code, ev_target, ev_arg))."""
    def op(code):
        return live & (opcode == code)

    is_lock = live & (opcode >= OP_LOCK_ACQUIRE) & (opcode <= OP_LOCK_HOLDER)
    result = jnp.zeros_like(opcode)
    ev_mask = jnp.zeros_like(live)
    ev_code = jnp.zeros_like(opcode)
    ev_target = jnp.zeros_like(opcode)
    ev_arg = jnp.zeros_like(opcode)

    acq = op(OP_LOCK_ACQUIRE)
    rel = op(OP_LOCK_RELEASE)
    cxl = op(OP_LOCK_CANCEL)
    held_by_me = holder == a
    grant_now = acq & (holder == -1)
    holder = jnp.where(grant_now, a, holder)
    idem = acq & held_by_me          # retried acquire we already won
    do_rel = rel & held_by_me
    W = wid.shape[-1]
    if W > 0:
        # Lazily expire timed-out waiters, then compact the ring: dead
        # slots (cancelled or expired anywhere in the window) must never
        # wedge capacity. Stable compaction keeps FIFO order.
        pos = _ring_pos(lh, W)
        in_win = pos < ls[..., None]
        wlv = wlv & ~(is_lock[..., None] & in_win & (wdl <= now[..., None]))
        live_win = wlv & in_win
        any_dead = is_lock & jnp.any(in_win & ~wlv, axis=-1)
        lh, ls, wlv, (wid, wdl) = _ring_compact(
            any_dead, lh, ls, pos, wlv, live_win, wid, wdl)

        pos2 = _ring_pos(lh, W)
        in_win2 = pos2 < ls[..., None]
        queued_me = jnp.any(wlv & in_win2 & (wid == a[..., None]), axis=-1)

        want_q = acq & ~grant_now & ~idem & ~queued_me & (b != 0)
        q_ok = want_q & (ls < W)
        q_dl = jnp.where(b < 0, INT_MAX, now + b)
        wid = _scatter3(wid, (lh + ls) % W, q_ok, a)
        wdl = _scatter3(wdl, (lh + ls) % W, q_ok, q_dl)
        wlv = _scatter3(wlv, (lh + ls) % W, q_ok, jnp.ones_like(a, bool))
        ls = jnp.where(q_ok, ls + 1, ls)

        # release: hand to the first waiter (ring is compacted: head live)
        next_id = _gather3(wid, lh % W)
        has_next = do_rel & (ls > 0)
        holder = jnp.where(do_rel,
                           jnp.where(has_next, next_id, -1), holder)
        lh = jnp.where(has_next, lh + 1, lh)
        ls = jnp.where(has_next, ls - 1, ls)

        # cancel: totally ordered with grants through the log, so the
        # client's timeout decision is race-free (2 = won before cancel)
        already = cxl & held_by_me
        cxl_hit = wlv & in_win2 & (wid == a[..., None])
        cxl_idx, cxl_found = _first_true(cxl_hit)
        wlv = _scatter3(wlv, cxl_idx, cxl & ~already & cxl_found,
                        jnp.zeros_like(a, bool))

        result = jnp.where(acq, jnp.where(
            grant_now | idem, 1,
            jnp.where(q_ok | queued_me, 2, 0)), result)
        result = jnp.where(cxl, jnp.where(already, 2,
                           jnp.where(cxl_found, 1, 0)), result)
        # Only queued-waiter grants are asynchronous; an immediate grant
        # or failure reaches the client as the command's own result
        ev_mask = ev_mask | has_next
        ev_code = jnp.where(has_next, EV_LOCK_GRANT, ev_code)
        ev_target = jnp.where(has_next, next_id, ev_target)
        ev_arg = jnp.where(has_next, 1, ev_arg)
    else:
        holder = jnp.where(do_rel, -1, holder)
        result = jnp.where(acq,
                           jnp.where(grant_now | idem, 1, 0), result)
        result = jnp.where(cxl, jnp.where(held_by_me, 2, 0), result)
    result = jnp.where(rel, do_rel.astype(jnp.int32), result)
    result = jnp.where(op(OP_LOCK_HOLDER), holder, result)
    return (holder, wid, wdl, wlv, lh, ls), result, \
        (ev_mask, ev_code, ev_target, ev_arg)


def apply_elect(el, ep, eid, elv, eh, es, opcode, a, b, index, live):
    """Leader-election kernel; returns ((el, ep, eid, elv, eh, es),
    result, (ev_mask, ev_code, ev_target, ev_arg))."""
    def op(code):
        return live & (opcode == code)

    is_el = live & (opcode >= OP_ELECT_LISTEN) & (opcode <= OP_ELECT_GET_EPOCH)
    result = jnp.zeros_like(opcode)
    ev_mask = jnp.zeros_like(live)
    ev_code = jnp.zeros_like(opcode)
    ev_target = jnp.zeros_like(opcode)
    ev_arg = jnp.zeros_like(opcode)

    listen = op(OP_ELECT_LISTEN)
    resign = op(OP_ELECT_RESIGN)
    am_leader = el == a
    vacant = el == -1
    win_now = listen & vacant
    el = jnp.where(win_now, a, el)
    ep = jnp.where(win_now, index, ep)
    do_res = resign & am_leader
    Wl = eid.shape[-1]
    if Wl > 0:
        # compact out unlisted waiters (same discipline as the lock ring)
        e_pos = _ring_pos(eh, Wl)
        e_in = e_pos < es[..., None]
        e_live_win = elv & e_in
        e_dead = is_el & jnp.any(e_in & ~elv, axis=-1)
        eh, es, elv, (eid,) = _ring_compact(
            e_dead, eh, es, e_pos, elv, e_live_win, eid)

        e_pos2 = _ring_pos(eh, Wl)
        e_in2 = e_pos2 < es[..., None]
        listed = jnp.any(elv & e_in2 & (eid == a[..., None]), axis=-1)

        # a retried listen by the sitting leader or a queued waiter is
        # idempotent — no duplicate ring entry
        el_q = listen & ~vacant & ~am_leader & ~listed & (es < Wl)
        eid = _scatter3(eid, (eh + es) % Wl, el_q, a)
        elv = _scatter3(elv, (eh + es) % Wl, el_q, jnp.ones_like(a, bool))
        es = jnp.where(el_q, es + 1, es)
        el_full = listen & ~vacant & ~am_leader & ~listed & ~el_q

        # resign by the leader promotes the next listener (FIFO
        # succession, LeaderElectionState.close:36-49); by a waiter unlists
        succ_id = _gather3(eid, eh % Wl)
        has_succ = do_res & (es > 0)
        el = jnp.where(do_res, jnp.where(has_succ, succ_id, -1), el)
        ep = jnp.where(has_succ, index, ep)
        eh = jnp.where(has_succ, eh + 1, eh)
        es = jnp.where(has_succ, es - 1, es)
        e_hit = elv & e_in2 & (eid == a[..., None])
        e_idx, e_found = _first_true(e_hit)
        elv = _scatter3(elv, e_idx, resign & ~do_res & e_found,
                        jnp.zeros_like(a, bool))

        result = jnp.where(listen, jnp.where(win_now, index,
                           jnp.where(am_leader, ep,
                           jnp.where(el_full, INT_MIN, 0))), result)
        ev_mask = ev_mask | has_succ
        ev_code = jnp.where(has_succ, EV_ELECT, ev_code)
        ev_target = jnp.where(has_succ, succ_id, ev_target)
        ev_arg = jnp.where(has_succ, index, ev_arg)
    else:
        el = jnp.where(do_res, -1, el)
        result = jnp.where(listen, jnp.where(win_now, index,
                           jnp.where(am_leader, ep, INT_MIN)), result)
    result = jnp.where(resign, do_res.astype(jnp.int32), result)
    result = jnp.where(op(OP_ELECT_IS_LEADER),
                       (am_leader & (ep == b)).astype(jnp.int32), result)
    result = jnp.where(op(OP_ELECT_LEADER), el, result)
    result = jnp.where(op(OP_ELECT_GET_EPOCH), ep, result)
    return (el, ep, eid, elv, eh, es), result, \
        (ev_mask, ev_code, ev_target, ev_arg)


def apply_multimap(mk, mv, ml, mdl, opcode, a, b, c, now, live):
    """(key, value)-pair probe table; returns ((mk, mv, ml, mdl), result).

    The reference's nested ``Map<Object, Map<Object, Commit>>``
    (``MultiMapState.java:30``) flattened to pairs: membership is per
    (key, value), removal by key drops every pair under it.
    """
    def op(code):
        return live & (opcode == code)

    is_mm = live & (opcode >= OP_MM_PUT) & (opcode <= OP_MM_CLEAR)
    result = jnp.zeros_like(opcode)
    if mk.shape[-1] == 0:
        return (mk, mv, ml, mdl), jnp.where(is_mm, INT_MIN, result)

    alive = ml & ((mdl == 0) | (mdl > now[..., None]))
    key_hit = alive & (mk == a[..., None])
    pair_hit = key_hit & (mv == b[..., None])
    pair_idx, pair_any = _first_true(pair_hit)
    free_idx, free_any = _first_true(~alive)
    key_count = jnp.sum(key_hit, axis=-1).astype(jnp.int32)
    total = jnp.sum(alive, axis=-1).astype(jnp.int32)

    put = op(OP_MM_PUT) & ~pair_any & free_any
    mk = _scatter3(mk, free_idx, put, a)
    mv = _scatter3(mv, free_idx, put, b)
    mdl = _scatter3(mdl, free_idx, put, jnp.where(c > 0, now + c, 0))
    ml = _scatter3(ml, free_idx, put, jnp.ones_like(a, bool))

    # remove-by-key drops EVERY live pair under the key in one pass
    rm_key = op(OP_MM_REMOVE)
    ml = jnp.where(rm_key[..., None] & key_hit, False, ml)
    rm_pair = op(OP_MM_REMOVE_ENTRY) & pair_any
    ml = _scatter3(ml, pair_idx, rm_pair, jnp.zeros_like(a, bool))
    ml = jnp.where(op(OP_MM_CLEAR)[..., None], False, ml)
    # lazy TTL purge on any touch, like the map kernel
    ml = jnp.where(is_mm[..., None],
                   ml & ((mdl == 0) | (mdl > now[..., None])), ml)

    result = jnp.where(op(OP_MM_PUT),
                       jnp.where(pair_any, 0,
                                 jnp.where(free_any, 1, INT_MIN)), result)
    result = jnp.where(rm_key, key_count, result)
    result = jnp.where(op(OP_MM_REMOVE_ENTRY), pair_any.astype(jnp.int32),
                       result)
    result = jnp.where(op(OP_MM_CONTAINS_KEY),
                       (key_count > 0).astype(jnp.int32), result)
    result = jnp.where(op(OP_MM_CONTAINS_ENTRY), pair_any.astype(jnp.int32),
                       result)
    result = jnp.where(op(OP_MM_CONTAINS_VALUE),
                       jnp.any(alive & (mv == a[..., None]),
                               axis=-1).astype(jnp.int32), result)
    result = jnp.where(op(OP_MM_COUNT), key_count, result)
    result = jnp.where(op(OP_MM_SIZE), total, result)
    result = jnp.where(op(OP_MM_IS_EMPTY), (total == 0).astype(jnp.int32),
                       result)
    return (mk, mv, ml, mdl), result


def apply_topic(tid, tlive, opcode, a, b, now, live):
    """Topic subscriber table + publish fan-out; returns
    ((tid, tlive), result, (ev_mask, ev_code, ev_target, ev_arg)).

    Publish emits ONE broadcast event carrying the message
    (``EV_TOPIC_MSG``, target = -1); subscribers consume the group's
    event stream and filter client-side — the reference instead pushes a
    per-session event from ``TopicState.publish`` (``TopicState.java:31``);
    the SPI path preserves that exact semantic via the CPU machine, this
    kernel is the batch-scale fan-out.
    """
    def op(code):
        return live & (opcode == code)

    is_tp = live & (opcode >= OP_TOPIC_LISTEN) & (opcode <= OP_TOPIC_COUNT)
    result = jnp.zeros_like(opcode)
    ev_mask = jnp.zeros_like(live)
    ev_code = jnp.zeros_like(opcode)
    ev_target = jnp.zeros_like(opcode)
    ev_arg = jnp.zeros_like(opcode)
    if tid.shape[-1] == 0:
        return (tid, tlive), jnp.where(is_tp, INT_MIN, result), \
            (ev_mask, ev_code, ev_target, ev_arg)

    hit = tlive & (tid == a[..., None])
    hit_idx, hit_any = _first_true(hit)
    free_idx, free_any = _first_true(~tlive)
    count = jnp.sum(tlive, axis=-1).astype(jnp.int32)

    sub = op(OP_TOPIC_LISTEN) & ~hit_any & free_any
    tid = _scatter3(tid, free_idx, sub, a)
    tlive = _scatter3(tlive, free_idx, sub, jnp.ones_like(a, bool))
    unsub = op(OP_TOPIC_UNLISTEN) & hit_any
    tlive = _scatter3(tlive, hit_idx, unsub, jnp.zeros_like(a, bool))

    pub = op(OP_TOPIC_PUB)
    result = jnp.where(op(OP_TOPIC_LISTEN),
                       jnp.where(hit_any, 0,
                                 jnp.where(free_any, 1, INT_MIN)), result)
    result = jnp.where(op(OP_TOPIC_UNLISTEN), hit_any.astype(jnp.int32),
                       result)
    result = jnp.where(pub, count, result)
    result = jnp.where(op(OP_TOPIC_COUNT), count, result)

    fan = pub & (count > 0)
    ev_mask = ev_mask | fan
    ev_code = jnp.where(fan, EV_TOPIC_MSG, ev_code)
    ev_target = jnp.where(fan, -1, ev_target)
    ev_arg = jnp.where(fan, a, ev_arg)
    return (tid, tlive), result, (ev_mask, ev_code, ev_target, ev_arg)


def push_events(res: ResourceState, ev_mask, ev_code, ev_target, ev_arg,
                ) -> ResourceState:
    """Push one event per lane (where ``ev_mask``) into the outbox ring,
    dropping the oldest on overflow."""
    E = res.ev_code.shape[-1]
    if E == 0:
        return res
    evc, evt, eva = res.ev_code, res.ev_target, res.ev_arg
    evh, evtl = res.ev_head, res.ev_tail
    overflow = ev_mask & ((evtl - evh) >= E)
    evh = jnp.where(overflow, evh + 1, evh)  # drop oldest
    slot = evtl % E
    evc = _scatter3(evc, slot, ev_mask, ev_code)
    evt = _scatter3(evt, slot, ev_mask, ev_target)
    eva = _scatter3(eva, slot, ev_mask, ev_arg)
    evtl = jnp.where(ev_mask, evtl + 1, evtl)
    return res._replace(ev_code=evc, ev_target=evt, ev_arg=eva,
                        ev_head=evh, ev_tail=evtl)


# ---------------------------------------------------------------------------
# the apply kernel
# ---------------------------------------------------------------------------

def apply_entry(
    res: ResourceState,
    opcode: jnp.ndarray,  # [G,P] i32
    a: jnp.ndarray,       # [G,P] i32
    b: jnp.ndarray,       # [G,P] i32
    c: jnp.ndarray,       # [G,P] i32
    index: jnp.ndarray,   # [G,P] i32 — absolute log index of this entry
    now: jnp.ndarray,     # [G,P] i32 — entry's logical timestamp
    live: jnp.ndarray,    # [G,P] bool — entry exists and is being applied
    map_peer: jnp.ndarray | None = None,  # apply_map's ``peer``
) -> tuple[ResourceState, jnp.ndarray]:
    """Apply one committed entry per (group, replica) lane.

    Composition of the six per-pool kernels (an entry belongs to exactly
    one pool, so the untouched pools pass through unchanged — XLA elides
    them). The step's hot path instead folds each pool separately
    (:func:`apply_window`); this composed form serves the query lane,
    single-entry callers and the differential tests.

    Returns ``(new_state, result)`` where ``result`` is the int32 command
    response for the lane (meaningful only where ``live``). Session events
    are pushed into the state's event ring.
    """
    (value, val_dl), r_val = apply_value(
        res.value, res.val_dl, opcode, a, b, c, now, live)
    (mk, mv, ml, mdl, mt, mc), r_map = apply_map(
        res.map_key, res.map_val, res.map_live, res.map_dl, res.map_table,
        res.map_count, opcode, a, b, c, now, live, map_peer)
    (sk, sl, sdl), r_set = apply_set(
        res.set_key, res.set_live, res.set_dl, opcode, a, b, c, now, live)
    (qv, qh, qs), r_q = apply_queue(
        res.q_val, res.q_head, res.q_size, opcode, a, b, c, now, live)
    (holder, wid, wdl, wlv, lh, ls), r_lock, ev_lock = apply_lock(
        res.lk_holder, res.lk_wait_id, res.lk_wait_dl, res.lk_wait_live,
        res.lk_head, res.lk_size, opcode, a, b, now, live)
    (el, ep, eid, elv, eh, es), r_el, ev_el = apply_elect(
        res.el_leader, res.el_epoch, res.el_id, res.el_live,
        res.el_head, res.el_size, opcode, a, b, index, live)
    (mmk, mmv, mml, mmdl), r_mm = apply_multimap(
        res.mm_key, res.mm_val, res.mm_live, res.mm_dl,
        opcode, a, b, c, now, live)
    (tid, tlv), r_tp, ev_tp = apply_topic(
        res.tp_id, res.tp_live, opcode, a, b, now, live)

    # exactly one pool claims each opcode, so results merge by sum of the
    # disjoint contributions
    result = r_val + r_map + r_set + r_q + r_lock + r_el + r_mm + r_tp

    res = res._replace(
        value=value, val_dl=val_dl,
        map_key=mk, map_val=mv, map_live=ml, map_dl=mdl, map_table=mt,
        map_count=mc,
        set_key=sk, set_live=sl, set_dl=sdl,
        q_val=qv, q_head=qh, q_size=qs,
        lk_holder=holder, lk_wait_id=wid, lk_wait_dl=wdl, lk_wait_live=wlv,
        lk_head=lh, lk_size=ls,
        el_leader=el, el_epoch=ep, el_id=eid, el_live=elv, el_head=eh,
        el_size=es,
        mm_key=mmk, mm_val=mmv, mm_live=mml, mm_dl=mmdl,
        tp_id=tid, tp_live=tlv)

    # grant/elect/topic are mutually exclusive across opcodes: ≤1 event
    ev_mask = ev_lock[0] | ev_el[0] | ev_tp[0]
    pick = lambda i: jnp.where(ev_lock[0], ev_lock[i],
                               jnp.where(ev_el[0], ev_el[i], ev_tp[i]))
    return push_events(res, ev_mask, pick(1), pick(2), pick(3)), result


def push_events_window(res: ResourceState, mask: jnp.ndarray,
                       code: jnp.ndarray, target: jnp.ndarray,
                       arg: jnp.ndarray) -> ResourceState:
    """Push a window of per-lane event candidates (``[G,P,A]``, ≤1 event
    per window position, ordered by position = log order) into the outbox
    ring in ONE fused pass per ring array, dropping the oldest entries on
    overflow — bit-identical ring evolution to pushing the events one
    entry at a time in log order."""
    E = res.ev_code.shape[-1]
    if E == 0 or mask.shape[-1] == 0:
        return res
    evh, evtl = res.ev_head, res.ev_tail
    count = mask.sum(axis=-1, dtype=jnp.int32)             # [G,P]
    off = jnp.cumsum(mask, axis=-1, dtype=jnp.int32) - mask  # exclusive
    # If the window somehow carries more events than the ring holds, only
    # the LAST E survive (same drop-oldest outcome as sequential pushes)
    # — also guarantees distinct slots below, so the one-hot sum is exact.
    mask = mask & (off >= count[..., None] - E)
    slot = (evtl[..., None] + off) % E                     # [G,P,A]
    hit = (slot[..., None] == jnp.arange(E, dtype=jnp.int32)) \
        & mask[..., None]                                  # [G,P,A,E]
    any_hit = hit.any(axis=2)                              # [G,P,E]

    def write(ring, vals):
        filled = jnp.where(hit, vals[..., None], 0).sum(axis=2)
        return jnp.where(any_hit, filled.astype(ring.dtype), ring)

    new_tail = evtl + count
    new_head = jnp.maximum(evh, new_tail - E)              # drop-oldest
    return res._replace(
        ev_code=write(res.ev_code, code),
        ev_target=write(res.ev_target, target),
        ev_arg=write(res.ev_arg, arg),
        ev_head=new_head, ev_tail=new_tail)


def apply_window(
    res: ResourceState,
    opcode: jnp.ndarray,  # [G,P,A] window-position-major entry fields
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    index: jnp.ndarray,   # [G,P,A] absolute log indexes (contiguous)
    now: jnp.ndarray,     # [G,P,A] entry timestamps
    do: jnp.ndarray,      # [G,P,A] bool — within this round's commit budget
    budgets: tuple,       # per-pool applies admitted per round (len 6, ≥1)
) -> tuple[ResourceState, jnp.ndarray, jnp.ndarray]:
    """Conflict-partitioned apply of a contiguous window of ≤A entries.

    The legacy formulation scanned ``apply_entry`` A times, dragging EVERY
    pool's state through HBM per iteration — ~95% of the mixed-scenario
    round (PERF.md "Known next bottleneck"). Entries in different pools
    commute (disjoint state), so here each pool folds only ITS entries —
    compacted to ``budgets[k]`` scan iterations over only that pool's
    arrays. Log order is preserved within each pool (the only order that
    matters); the admitted window is the longest prefix in which no pool
    exceeds its budget, so a lane never applies entry j before j-1.

    Returns ``(new_res, result [G,P,A], admitted [G,P,A])`` — results are
    positioned at their window slots; non-admitted entries stay pending
    for the next round (exactly like the existing per-round A budget).

    Events are scattered back to their window positions and pushed in log
    order (``push_events_window``), so the outbox ring evolves
    bit-identically to the sequential formulation.
    """
    A = opcode.shape[-1]
    pool = pool_of(jnp.where(do, opcode, -1))  # !do → POOL_NONE (opcode -1)
    is_pool = [(pool == k) for k in range(NUM_POOLS)]

    # Longest prefix in which every pool stays within budget.
    admitted = do
    rank = []
    for k in range(NUM_POOLS):
        cum = jnp.cumsum(is_pool[k].astype(jnp.int32), axis=-1)
        rank.append(jnp.where(is_pool[k], cum - 1, A))
        if budgets[k] < A:
            admitted = admitted & jnp.where(is_pool[k],
                                            cum <= budgets[k], True)
    admitted = jnp.cumprod(admitted.astype(jnp.int32), axis=-1).astype(bool)

    result = jnp.zeros_like(opcode)

    def fold(kernel, state_arrays, k, n_out):
        """Scan ``kernel`` over pool k's ≤budgets[k] compacted entries,
        carrying only ``state_arrays``. Returns (state, result
        contribution [G,P,A], events scattered to window positions —
        (mask, code, target, arg) each [G,P,A], or None).

        When the budget covers the whole window (B >= A), compaction
        would be the identity up to padding — skip it and iterate the
        window positions directly (zero overhead vs the legacy scan)."""
        B = min(budgets[k], A)
        sel = admitted & is_pool[k]
        if B >= A:
            oh = None
            live_b = sel
            fields = (opcode, a, b, c, index, now)
        else:
            oh = (rank[k][..., None] == jnp.arange(B, dtype=jnp.int32)) \
                & sel[..., None]                              # [G,P,A,B]
            pick = lambda arr: jnp.where(oh, arr[..., None], 0).sum(axis=2)
            live_b = jnp.any(oh, axis=2)                      # [G,P,B]
            fields = tuple(pick(f) for f in (opcode, a, b, c, index, now))
        xs = jax.tree.map(lambda x: jnp.moveaxis(x, -1, 0),   # [B,G,P]
                          fields + (live_b,))

        def body(st, x):
            op_i, a_i, b_i, c_i, idx_i, now_i, live_i = x
            out = kernel(*st, op_i, a_i, b_i, c_i, idx_i, now_i, live_i)
            return out[0], out[1:]

        def unpick(stacked):  # [B,G,P] -> [G,P,A] at window positions
            by_slot = jnp.moveaxis(stacked, 0, -1)            # [G,P,B]
            if oh is None:
                return by_slot
            return jnp.where(oh, by_slot[..., None, :], 0).sum(axis=-1)
        if k == POOL_MAP and res.map_table.ndim == 5:
            # A bucketed map entry costs its lanes' gather and scatter
            # indices whether the lanes are live or not: stop after the
            # last position any lane holds, the carry updated in place.
            def turn(i, carry):
                st, out = carry
                st, (r,) = body(st, jax.tree.map(lambda v: v[i], xs))
                return st, out.at[i].set(r)

            last = jnp.max(jnp.where(
                jnp.any(live_b, axis=(0, 1)), 1 + jnp.arange(B), 0))
            state, out = jax.lax.fori_loop(
                0, last, turn, (state_arrays, jnp.zeros_like(xs[0])))
            return state, unpick(out), None
        # Full unroll: lax.scan blocks cross-iteration fusion, and with
        # only ONE pool's arrays in the carry, XLA fuses the unrolled
        # iterations into far fewer passes over that pool's HBM.
        state, outs = jax.lax.scan(body, state_arrays, xs, unroll=True)

        contribution = unpick(outs[0])
        events = None
        if n_out > 1:
            events = tuple(unpick(x) for x in outs[1])
        return state, contribution, events

    # adapters: uniform (state..., op, a, b, c, index, now, live) signature
    k_val = lambda v, dl, op_, a_, b_, c_, i_, n_, lv: \
        apply_value(v, dl, op_, a_, b_, c_, n_, lv)
    k_map = lambda mk, mv, ml, mdl, mt, mc, op_, a_, b_, c_, i_, n_, lv: \
        apply_map(mk, mv, ml, mdl, mt, mc, op_, a_, b_, c_, n_, lv)
    k_set = lambda sk, sl, sdl, op_, a_, b_, c_, i_, n_, lv: \
        apply_set(sk, sl, sdl, op_, a_, b_, c_, n_, lv)
    k_q = lambda qv, qh, qs, op_, a_, b_, c_, i_, n_, lv: \
        apply_queue(qv, qh, qs, op_, a_, b_, c_, n_, lv)
    k_lock = lambda h, wi, wd, wl, lh, ls, op_, a_, b_, c_, i_, n_, lv: \
        apply_lock(h, wi, wd, wl, lh, ls, op_, a_, b_, n_, lv)
    k_el = lambda el, ep, ei, el_, eh, es, op_, a_, b_, c_, i_, n_, lv: \
        apply_elect(el, ep, ei, el_, eh, es, op_, a_, b_, i_, lv)
    k_mm = lambda mk_, mv_, ml_, md_, op_, a_, b_, c_, i_, n_, lv: \
        apply_multimap(mk_, mv_, ml_, md_, op_, a_, b_, c_, n_, lv)
    k_tp = lambda ti, tl, op_, a_, b_, c_, i_, n_, lv: \
        apply_topic(ti, tl, op_, a_, b_, n_, lv)

    (value, val_dl), r, _ = fold(
        k_val, (res.value, res.val_dl), POOL_VALUE, 1)
    result = result + r
    (mk, mv, ml, mdl, mt, mc), r, _ = fold(
        k_map, (res.map_key, res.map_val, res.map_live, res.map_dl,
                res.map_table, res.map_count), POOL_MAP, 1)
    result = result + r
    (sk, sl, sdl), r, _ = fold(
        k_set, (res.set_key, res.set_live, res.set_dl), POOL_SET, 1)
    result = result + r
    (qv, qh, qs), r, _ = fold(
        k_q, (res.q_val, res.q_head, res.q_size), POOL_QUEUE, 1)
    result = result + r
    (holder, wid, wdl, wlv, lh, ls), r, ev_lock = fold(
        k_lock, (res.lk_holder, res.lk_wait_id, res.lk_wait_dl,
                 res.lk_wait_live, res.lk_head, res.lk_size),
        POOL_LOCK, 2)
    result = result + r
    (el, ep, eid, elv, eh, es), r, ev_el = fold(
        k_el, (res.el_leader, res.el_epoch, res.el_id, res.el_live,
               res.el_head, res.el_size), POOL_ELECT, 2)
    result = result + r
    (mmk, mmv, mml, mmdl), r, _ = fold(
        k_mm, (res.mm_key, res.mm_val, res.mm_live, res.mm_dl),
        POOL_MMAP, 1)
    result = result + r
    (tid, tlv), r, ev_tp = fold(
        k_tp, (res.tp_id, res.tp_live), POOL_TOPIC, 2)
    result = result + r

    res = res._replace(
        value=value, val_dl=val_dl,
        map_key=mk, map_val=mv, map_live=ml, map_dl=mdl, map_table=mt,
        map_count=mc,
        set_key=sk, set_live=sl, set_dl=sdl,
        q_val=qv, q_head=qh, q_size=qs,
        lk_holder=holder, lk_wait_id=wid, lk_wait_dl=wdl, lk_wait_live=wlv,
        lk_head=lh, lk_size=ls,
        el_leader=el, el_epoch=ep, el_id=eid, el_live=elv, el_head=eh,
        el_size=es,
        mm_key=mmk, mm_val=mmv, mm_live=mml, mm_dl=mmdl,
        tp_id=tid, tp_live=tlv)
    # Merge the event-producing pools by window position (disjoint — an
    # entry belongs to one pool) and push in log order.
    ev_mask = ev_lock[0].astype(bool) | ev_el[0].astype(bool) \
        | ev_tp[0].astype(bool)
    res = push_events_window(res, ev_mask,
                             ev_lock[1] + ev_el[1] + ev_tp[1],
                             ev_lock[2] + ev_el[2] + ev_tp[2],
                             ev_lock[3] + ev_el[3] + ev_tp[3])
    return res, result, admitted


def drain_events(res: ResourceState, n: int, mask: jnp.ndarray
                 ) -> tuple[ResourceState, tuple[jnp.ndarray, ...]]:
    """Pop up to ``n`` oldest events from each lane's outbox ring where
    ``mask`` ([G] bool — group has an active leader) holds.

    Returns ``(new_state, (seq, code, target, arg, valid))``, each
    ``[G,P,n]``. Lanes of a group pop in lockstep (deterministic); the
    caller harvests the leader lane and dedups by absolute ``seq``. Gating
    on an active leader means events emitted during leaderless rounds stay
    queued until someone can deliver them (at-least-once).
    """
    E = res.ev_code.shape[-1]
    G, P = res.ev_head.shape
    if E == 0 or n == 0:
        z = jnp.zeros((G, P, n), jnp.int32)
        return res, (z, z, z, z, jnp.zeros((G, P, n), bool))
    evh, evtl = res.ev_head, res.ev_tail
    lane_mask = mask[:, None]
    seqs, codes, targets, args, valids = [], [], [], [], []
    for i in range(n):
        seq = evh + i
        ok = lane_mask & (seq < evtl)
        slot = seq % E
        seqs.append(seq)
        codes.append(jnp.where(ok, _gather3(res.ev_code, slot), 0))
        targets.append(jnp.where(ok, _gather3(res.ev_target, slot), 0))
        args.append(jnp.where(ok, _gather3(res.ev_arg, slot), 0))
        valids.append(ok)
    new_head = jnp.where(lane_mask, jnp.minimum(evh + n, evtl), evh)
    out = tuple(jnp.stack(x, axis=-1) for x in
                (seqs, codes, targets, args, valids))
    return res._replace(ev_head=new_head), out
