"""Device resource kernel tests: map/set/queue/lock/election + TTL + events.

Drives the full batched consensus path (RaftGroups) so every assertion
exercises replicated, quorum-committed apply — the reference's
"real consensus, fake network" strategy (SURVEY.md §4) on device.
Reference semantics: MapState.java:32, SetState.java:32, QueueState.java:30,
LockState.java:33, LeaderElectionState.java:31.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.apply import FAIL  # noqa: E402

from engines import G, device_plane  # noqa: E402


def make(config=None):
    rg = device_plane(config)
    rg.wait_for_leaders()
    return rg


def run_ops(rg, ops, group=0):
    """Submit (opcode, a, b, c) tuples in order; return list of results."""
    tags = [rg.submit(group, *op) for op in ops]
    rg.run_until(tags)
    return [rg.results[t] for t in tags]


def events(rg, group=0, code=None):
    evs = rg.events.get(group, [])
    if code is None:
        return evs
    return [e for e in evs if e[1] == code]


# ---------------------------------------------------------------------------
# map
# ---------------------------------------------------------------------------

def test_map_put_get_remove_semantics():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_MAP_PUT, 7, 100),          # -> 0 (no previous)
        (ap.OP_MAP_PUT, 7, 200),          # -> 100
        (ap.OP_MAP_GET, 7),               # -> 200
        (ap.OP_MAP_CONTAINS_KEY, 7),      # -> 1
        (ap.OP_MAP_CONTAINS_KEY, 8),      # -> 0
        (ap.OP_MAP_CONTAINS_VALUE, 200),  # -> 1
        (ap.OP_MAP_SIZE,),                # -> 1
        (ap.OP_MAP_REMOVE, 7),            # -> 200
        (ap.OP_MAP_GET, 7),               # -> 0
        (ap.OP_MAP_IS_EMPTY,),            # -> 1
    ])
    assert res == [0, 100, 200, 1, 0, 1, 1, 200, 0, 1]


def test_map_conditional_ops():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_MAP_PUT_IF_ABSENT, 1, 10),   # -> 1 (put)
        (ap.OP_MAP_PUT_IF_ABSENT, 1, 99),   # -> 0 (present)
        (ap.OP_MAP_GET, 1),                 # -> 10
        (ap.OP_MAP_REPLACE, 1, 20),         # -> 10
        (ap.OP_MAP_REPLACE, 2, 5),          # -> FAIL (absent)
        (ap.OP_MAP_REPLACE_IF, 1, 20, 30),  # -> 1
        (ap.OP_MAP_REPLACE_IF, 1, 99, 40),  # -> 0
        (ap.OP_MAP_GET, 1),                 # -> 30
        (ap.OP_MAP_REMOVE_IF, 1, 99),       # -> 0
        (ap.OP_MAP_REMOVE_IF, 1, 30),       # -> 1
        (ap.OP_MAP_GET_OR_DEFAULT, 1, 77),  # -> 77
    ])
    assert res == [1, 0, 10, 10, FAIL, 1, 0, 30, 0, 1, 77]


def test_map_ttl_expiry_is_deterministic_log_time():
    rg = make()
    r1 = run_ops(rg, [(ap.OP_MAP_PUT, 5, 42, 3),   # ttl = 3 ticks
                      (ap.OP_MAP_GET, 5)])
    assert r1 == [0, 42]
    rg.run(10)  # advance the logical clock past the deadline
    r2 = run_ops(rg, [(ap.OP_MAP_GET, 5), (ap.OP_MAP_SIZE,),
                      (ap.OP_MAP_CONTAINS_KEY, 5)])
    assert r2 == [0, 0, 0]


def test_map_clear_and_overflow():
    rg = make()
    K = rg.config.resource.map_slots
    res = run_ops(rg, [(ap.OP_MAP_PUT, k, k * 10) for k in range(1, K + 1)])
    assert res == [0] * K
    over = run_ops(rg, [(ap.OP_MAP_PUT, 999, 1)])  # table full
    assert over == [FAIL]
    res = run_ops(rg, [(ap.OP_MAP_SIZE,), (ap.OP_MAP_CLEAR,),
                       (ap.OP_MAP_SIZE,), (ap.OP_MAP_PUT, 999, 1)])
    assert res[0] == K and res[2] == 0 and res[3] == 0


def test_map_groups_are_isolated():
    rg = make()
    t1 = rg.submit(0, ap.OP_MAP_PUT, 1, 111)
    t2 = rg.submit(1, ap.OP_MAP_PUT, 1, 222)
    rg.run_until([t1, t2])
    g0 = run_ops(rg, [(ap.OP_MAP_GET, 1)], group=0)
    g1 = run_ops(rg, [(ap.OP_MAP_GET, 1)], group=1)
    g2 = run_ops(rg, [(ap.OP_MAP_GET, 1)], group=2)
    assert (g0, g1, g2) == ([111], [222], [0])


# ---------------------------------------------------------------------------
# set
# ---------------------------------------------------------------------------

def test_set_semantics():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_SET_ADD, 5), (ap.OP_SET_ADD, 5), (ap.OP_SET_ADD, 9),
        (ap.OP_SET_CONTAINS, 5), (ap.OP_SET_CONTAINS, 6),
        (ap.OP_SET_SIZE,), (ap.OP_SET_REMOVE, 5), (ap.OP_SET_REMOVE, 5),
        (ap.OP_SET_SIZE,), (ap.OP_SET_CLEAR,), (ap.OP_SET_SIZE,),
    ])
    assert res == [1, 0, 1, 1, 0, 2, 1, 0, 1, 0, 0]


def test_set_ttl():
    rg = make()
    assert run_ops(rg, [(ap.OP_SET_ADD, 3, 0, 2)]) == [1]
    rg.run(8)
    assert run_ops(rg, [(ap.OP_SET_CONTAINS, 3), (ap.OP_SET_SIZE,)]) == [0, 0]


# ---------------------------------------------------------------------------
# queue
# ---------------------------------------------------------------------------

def test_queue_fifo():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_Q_POLL,),                       # empty -> FAIL
        (ap.OP_Q_OFFER, 11), (ap.OP_Q_OFFER, 22), (ap.OP_Q_OFFER, 33),
        (ap.OP_Q_PEEK,), (ap.OP_Q_SIZE,),
        (ap.OP_Q_POLL,), (ap.OP_Q_POLL,), (ap.OP_Q_POLL,), (ap.OP_Q_POLL,),
    ])
    assert res == [FAIL, 1, 1, 1, 11, 3, 11, 22, 33, FAIL]


def test_queue_full_and_clear():
    rg = make()
    Q = rg.config.resource.queue_slots
    res = run_ops(rg, [(ap.OP_Q_OFFER, i) for i in range(Q + 2)])
    assert res == [1] * Q + [0, 0]
    res = run_ops(rg, [(ap.OP_Q_CLEAR,), (ap.OP_Q_SIZE,), (ap.OP_Q_OFFER, 7),
                       (ap.OP_Q_POLL,)])
    assert res == [0, 0, 1, 7]


# ---------------------------------------------------------------------------
# lock (grant delivered as a session event — DistributedLock.java:58)
# ---------------------------------------------------------------------------

def test_lock_grant_queue_release():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 101, -1),  # free -> granted (1)
        (ap.OP_LOCK_ACQUIRE, 102, -1),  # held -> queued (2)
        (ap.OP_LOCK_ACQUIRE, 103, 0),   # try-lock -> fail (0)
        (ap.OP_LOCK_RELEASE, 101),      # -> 1, grants 102
        (ap.OP_LOCK_RELEASE, 102),      # -> 1, queue empty
        (ap.OP_LOCK_RELEASE, 999),      # not holder -> 0
    ])
    assert res == [1, 2, 0, 1, 1, 0]
    # only the queued waiter's grant is an event; immediate grant (101) and
    # immediate try-lock failure (103) are synchronous command results
    grants = events(rg, code=ap.EV_LOCK_GRANT)
    assert [e[2] for e in grants] == [102]
    assert events(rg, code=ap.EV_NONE) == []


def test_lock_timeout_waiter_never_granted():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 1, -1),   # granted
        (ap.OP_LOCK_ACQUIRE, 2, 3),    # queued with 3-tick deadline
    ])
    assert res == [1, 2]
    rg.run(10)  # deadline passes in log time
    res = run_ops(rg, [(ap.OP_LOCK_RELEASE, 1)])
    assert res == [1]
    rg.run(10)  # let followers apply
    # expired waiter was dropped: lock is free, no grant event to 2
    holder = np.asarray(rg.state.resources.lk_holder)[0]
    assert (holder == -1).all()
    assert events(rg, code=ap.EV_LOCK_GRANT) == []


def test_lock_cancel_orders_with_grant():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 1, -1),
        (ap.OP_LOCK_ACQUIRE, 2, -1),
        (ap.OP_LOCK_CANCEL, 2),        # still queued -> 1 (dequeued)
        (ap.OP_LOCK_RELEASE, 1),       # queue empty after cancel
        (ap.OP_LOCK_CANCEL, 3),        # never queued -> 0
    ])
    assert res == [1, 2, 1, 1, 0]
    rg.run(10)  # let followers apply
    holder = np.asarray(rg.state.resources.lk_holder)[0]
    assert (holder == -1).all()
    # cancel AFTER the grant already happened reports "you won" (2)
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 5, -1),
        (ap.OP_LOCK_CANCEL, 5),
    ])
    assert res == [1, 2]


def test_lock_contention_fifo_order():
    rg = make()
    res = run_ops(rg, [(ap.OP_LOCK_ACQUIRE, 10, -1)]
                  + [(ap.OP_LOCK_ACQUIRE, 10 + i, -1) for i in range(1, 5)]
                  + [(ap.OP_LOCK_RELEASE, 10 + i) for i in range(5)])
    assert res == [1, 2, 2, 2, 2] + [1] * 5
    grants = [e[2] for e in events(rg, code=ap.EV_LOCK_GRANT)]
    assert grants == [11, 12, 13, 14]  # strict FIFO succession (10 = sync)


# ---------------------------------------------------------------------------
# leader election resource (epoch = log index fencing token)
# ---------------------------------------------------------------------------

def test_election_listen_promote_fencing():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_ELECT_LISTEN, 7),  # vacant -> elected, result = epoch
        (ap.OP_ELECT_LISTEN, 8),  # queued
        (ap.OP_ELECT_LISTEN, 9),  # queued
    ])
    epoch7 = res[0]
    assert epoch7 > 0 and res[1:] == [0, 0]
    assert run_ops(rg, [(ap.OP_ELECT_IS_LEADER, 7, epoch7)]) == [1]
    assert run_ops(rg, [(ap.OP_ELECT_IS_LEADER, 8, epoch7)]) == [0]

    # resign promotes FIFO successor with a fresh epoch (7's immediate win
    # was its listen result — only the promotion is an event)
    assert run_ops(rg, [(ap.OP_ELECT_RESIGN, 7)]) == [1]
    elects = events(rg, code=ap.EV_ELECT)
    assert [e[2] for e in elects] == [8]
    epoch8 = elects[-1][3]
    assert epoch8 > epoch7
    assert run_ops(rg, [(ap.OP_ELECT_IS_LEADER, 8, epoch8)]) == [1]
    # stale fencing token from the old leadership is rejected
    assert run_ops(rg, [(ap.OP_ELECT_IS_LEADER, 7, epoch7)]) == [0]

    # a queued waiter can unlisten without affecting the leader
    assert run_ops(rg, [(ap.OP_ELECT_RESIGN, 9)]) == [0]
    assert run_ops(rg, [(ap.OP_ELECT_RESIGN, 8)]) == [1]
    rg.run(10)  # let followers apply
    leader = np.asarray(rg.state.resources.el_leader)[0]
    assert (leader == -1).all()


def test_lock_cancelled_waiters_free_capacity():
    rg = make()
    W = rg.config.resource.wait_slots
    assert run_ops(rg, [(ap.OP_LOCK_ACQUIRE, 1, -1)]) == [1]
    waiters = list(range(10, 10 + W))
    assert run_ops(rg, [(ap.OP_LOCK_ACQUIRE, w, -1) for w in waiters]) \
        == [2] * W
    # queue is full; a fresh waiter is rejected
    assert run_ops(rg, [(ap.OP_LOCK_ACQUIRE, 99, -1)]) == [0]
    # cancel every waiter: the ring must compact, reclaiming capacity
    assert run_ops(rg, [(ap.OP_LOCK_CANCEL, w) for w in waiters]) == [1] * W
    assert run_ops(rg, [(ap.OP_LOCK_ACQUIRE, 99, -1)]) == [2]
    assert run_ops(rg, [(ap.OP_LOCK_RELEASE, 1)]) == [1]
    assert [e[2] for e in events(rg, code=ap.EV_LOCK_GRANT)] == [99]


def test_lock_acquire_idempotent_and_holder_query():
    rg = make()
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 1, -1),  # granted
        (ap.OP_LOCK_ACQUIRE, 1, -1),  # retry by holder -> still 1, no dup
        (ap.OP_LOCK_ACQUIRE, 2, -1),  # queued
        (ap.OP_LOCK_ACQUIRE, 2, -1),  # retry by waiter -> 2, no dup entry
        (ap.OP_LOCK_HOLDER,),         # -> 1
        (ap.OP_LOCK_RELEASE, 1),
        (ap.OP_LOCK_HOLDER,),         # -> 2
        (ap.OP_LOCK_RELEASE, 2),
        (ap.OP_LOCK_HOLDER,),         # -> -1 (queue held no duplicates)
    ])
    assert res == [1, 1, 2, 2, 1, 1, 2, 1, -1]


def test_election_duplicate_listen_idempotent():
    rg = make()
    res = run_ops(rg, [(ap.OP_ELECT_LISTEN, 7)])
    epoch7 = res[0]
    assert epoch7 > 0
    res = run_ops(rg, [
        (ap.OP_ELECT_LISTEN, 7),   # leader re-listen -> current epoch
        (ap.OP_ELECT_LISTEN, 8),   # queued
        (ap.OP_ELECT_LISTEN, 8),   # retry -> idempotent, no dup
        (ap.OP_ELECT_LEADER,),     # -> 7
        (ap.OP_ELECT_RESIGN, 7),   # promotes 8
        (ap.OP_ELECT_LEADER,),     # -> 8
        (ap.OP_ELECT_RESIGN, 8),
        (ap.OP_ELECT_LEADER,),     # -> -1: no stale duplicate of 8 promoted
    ])
    assert res == [epoch7, 0, 0, 7, 1, 8, 1, -1]


def test_value_ttl_survives_failed_cas():
    rg = make()
    res = run_ops(rg, [(ap.OP_VALUE_SET, 5, 0, 5),  # ttl = 5 ticks
                       (ap.OP_VALUE_CAS, 7, 9)])    # miss — must not clear TTL
    assert res == [0, 0]
    rg.run(15)
    assert run_ops(rg, [(ap.OP_VALUE_GET,)]) == [0]  # expired as scheduled


# ---------------------------------------------------------------------------
# zero-size pools: compiled out, ops fail cleanly (ResourceConfig)
# ---------------------------------------------------------------------------

def test_counters_only_config():
    from copycat_tpu.ops.apply import ResourceConfig
    from copycat_tpu.ops.consensus import Config

    rg = make(Config(resource=ResourceConfig.counters_only()))
    # counters fully work
    res = run_ops(rg, [(ap.OP_LONG_ADD, 5), (ap.OP_LONG_ADD, 5),
                       (ap.OP_VALUE_GET,)])
    assert res == [5, 10, 10]
    # disabled pools fail cleanly with the sentinel
    res = run_ops(rg, [(ap.OP_MAP_PUT, 1, 2), (ap.OP_SET_ADD, 1),
                       (ap.OP_Q_OFFER, 1)])
    assert res == [FAIL, FAIL, FAIL]
    # lock still works in try-lock-only mode (no wait ring)
    res = run_ops(rg, [
        (ap.OP_LOCK_ACQUIRE, 7, 0),   # grant
        (ap.OP_LOCK_ACQUIRE, 8, -1),  # would queue; no ring -> fail (0)
        (ap.OP_LOCK_HOLDER,),
        (ap.OP_LOCK_RELEASE, 7),
        (ap.OP_LOCK_HOLDER,),
    ])
    assert res == [1, 0, 7, 1, -1]
    # election works leader-only (no succession ring)
    res = run_ops(rg, [(ap.OP_ELECT_LISTEN, 5)])
    epoch = res[0]
    assert epoch > 0
    res = run_ops(rg, [
        (ap.OP_ELECT_LISTEN, 6),      # no ring -> FAIL
        (ap.OP_ELECT_IS_LEADER, 5, epoch),
        (ap.OP_ELECT_RESIGN, 5),
        (ap.OP_ELECT_LEADER,),
    ])
    assert res == [FAIL, 1, 1, -1]


# ---------------------------------------------------------------------------
# convergence: replicated pools stay identical across replicas
# ---------------------------------------------------------------------------

def test_all_pools_converge_under_partitions():
    rg = make()
    P = rg.num_peers
    rng = np.random.default_rng(3)
    import jax.numpy as jnp
    ops = [
        (ap.OP_MAP_PUT, 1, 10), (ap.OP_SET_ADD, 2), (ap.OP_Q_OFFER, 3),
        (ap.OP_LOCK_ACQUIRE, 4, -1), (ap.OP_ELECT_LISTEN, 5),
        (ap.OP_MAP_PUT, 6, 60, 4), (ap.OP_LOCK_RELEASE, 4),
        (ap.OP_VALUE_SET, 8), (ap.OP_Q_POLL,), (ap.OP_MAP_REMOVE, 1),
    ]
    for i, op in enumerate(ops):
        for g in range(G):
            rg.submit(g, *op)
        if i % 3 == 0:
            rg.deliver = jnp.asarray(rng.random((G, P, P)) > 0.3)
        rg.run(4)
    rg.deliver = jnp.ones((G, P, P), bool)
    rg.run(40)  # heal + converge

    res = rg.state.resources
    applied = np.asarray(rg.state.applied_index)
    for g in range(G):
        assert len(set(applied[g].tolist())) == 1, applied[g]
    # every linearizable pool field is bit-identical across replicas
    for name in res._fields:
        if name.startswith("ev_"):
            continue  # outbox ring drains in lockstep, not compared
        arr = np.asarray(getattr(res, name))
        for g in range(G):
            first = arr[g, 0]
            for p in range(1, P):
                assert (arr[g, p] == first).all(), (name, g, arr[g])


# ---- outbox-ring overflow: event loss + authoritative fallback -------------
#
# VERDICT weak-#4 / next-#8: the outbox is a drop-oldest ring (apply.py,
# "drop oldest" at the event push); an evicted grant/elect event is gone for
# good, and the facades' documented recovery is the authoritative replicated
# register (OP_LOCK_HOLDER / OP_ELECT_LEADER).  These tests force the loss
# deterministically — event_slots=1 and two event-producing commits applied
# in the same round, so the second push evicts the first — then assert the
# facade recovers through the fallback, not the event.

def _overflow_groups():
    from copycat_tpu.models import DeviceElection, DeviceLock
    from copycat_tpu.ops.consensus import Config
    from copycat_tpu.ops.apply import ResourceConfig
    cfg = Config(resource=ResourceConfig(
        map_slots=0, set_slots=0, queue_slots=0,
        wait_slots=4, listener_slots=4, event_slots=1))
    rg = make(cfg)
    a, b = DeviceLock(rg, 0, 1), DeviceLock(rg, 0, 2)
    e1, e2 = DeviceElection(rg, 0, 11), DeviceElection(rg, 0, 12)
    a.lock()
    assert e1.listen() is not None      # elected immediately, no event
    assert e2.listen() is None          # queued successor
    # B queues behind A; the grant will arrive by event (or not, below)
    acquire = rg.submit(0, ap.OP_LOCK_ACQUIRE, 2, -1)
    rg.run_until([acquire])
    assert rg.results.pop(acquire) not in (0, 1)  # queued, not granted/full
    return rg, a, b, e1, e2


def _same_round_commits(rg, ops_):
    tags = [rg.submit(0, *op) for op in ops_]
    rg.run_until(tags)
    return [rg.results.pop(t) for t in tags]


def test_lost_lock_grant_recovered_via_holder_register():
    rg, a, b, e1, e2 = _overflow_groups()
    # release(A) grants B (event #1); resign(e1) elects e2 (event #2).
    # Both commit in one submit batch -> both apply in one round -> the
    # 1-slot ring drops the grant, keeps the elect.
    res = _same_round_commits(
        rg, [(ap.OP_LOCK_RELEASE, 1), (ap.OP_ELECT_RESIGN, 11)])
    assert res == [1, 1]
    rg.run(8)  # drain whatever survived in the ring
    evs = rg.events.get(0, [])
    assert any(c == ap.EV_ELECT and t == 12 for _, c, t, _a in evs)
    assert not any(c == ap.EV_LOCK_GRANT for _, c, t, _a in evs), evs
    # the facade must still converge, via the authoritative holder register
    assert b._await_grant(None) is True
    assert b._call(ap.OP_LOCK_HOLDER) == 2
    # and the election facade sees its (surviving) event the normal way
    assert e2.poll_elected() is not None
    assert e2.is_leader()


def test_lost_elect_event_recovered_via_leader_register():
    rg, a, b, e1, e2 = _overflow_groups()
    # reversed order: the elect event is pushed first and evicted by the
    # lock grant
    res = _same_round_commits(
        rg, [(ap.OP_ELECT_RESIGN, 11), (ap.OP_LOCK_RELEASE, 1)])
    assert res == [1, 1]
    rg.run(8)
    evs = rg.events.get(0, [])
    assert any(c == ap.EV_LOCK_GRANT and t == 2 for _, c, t, _a in evs)
    assert not any(c == ap.EV_ELECT for _, c, t, _a in evs), evs
    # poll_elected never sees the event; the every-20-polls fallback must
    # consult OP_ELECT_LEADER and recover the epoch + fencing token
    epoch = None
    for _ in range(25):
        epoch = e2.poll_elected()
        if epoch is not None:
            break
    assert epoch is not None
    assert e2.is_leader(epoch)
    # the lock side converges on its surviving event
    assert b._await_grant(None) is True
