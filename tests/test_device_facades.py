"""Typed device facade tests (models/device_resources.py).

Facades mirror the reference's client resource classes; each test drives
real quorum commitment through the batched step.
"""

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.models import (  # noqa: E402
    DeviceElection,
    DeviceLock,
    DeviceLong,
    DeviceMap,
    DeviceQueue,
    DeviceSet,
    DeviceValue,
)

from engines import device_plane  # noqa: E402


@pytest.fixture(scope="module")
def rg():
    groups = device_plane()
    groups.wait_for_leaders()
    return groups


def test_value_and_long(rg):
    v = DeviceValue(rg, 0)
    v.set(10)
    assert v.get() == 10
    assert v.compare_and_set(10, 20)
    assert not v.compare_and_set(10, 30)
    assert v.get_and_set(5) == 20

    n = DeviceLong(rg, 1)
    assert n.increment_and_get() == 1
    assert n.add_and_get(9) == 10
    assert n.get_and_add(5) == 10
    assert n.decrement_and_get() == 14
    assert n.get() == 14


def test_map_facade(rg):
    m = DeviceMap(rg, 2)
    assert m.put(1, 100) == 0
    assert m.get(1) == 100
    assert m.put_if_absent(1, 999) is False
    assert m.put_if_absent(2, 200) is True
    assert m.contains_key(2) and not m.contains_key(3)
    assert m.contains_value(200)
    assert m.size() == 2
    assert m.replace(1, 111) == 100
    assert m.replace(42, 1) is None
    assert m.replace_if(1, 111, 112)
    assert m.remove(1) == 112
    assert m.get_or_default(1, 7) == 7
    m.clear()
    assert m.is_empty()


def test_set_queue_facades(rg):
    s = DeviceSet(rg, 3)
    assert s.add(5) and not s.add(5)
    assert s.contains(5) and s.size() == 1
    assert s.remove(5) and s.is_empty()

    q = DeviceQueue(rg, 3)
    assert q.poll() is None
    q.add(1)
    assert q.offer(2)
    assert q.peek() == 1 and q.size() == 2
    assert q.poll() == 1 and q.poll() == 2 and q.poll() is None


def test_lock_facade_two_clients():
    rg = device_plane()
    rg.wait_for_leaders()
    a = DeviceLock(rg, 0, holder_id=101)
    b = DeviceLock(rg, 0, holder_id=102)
    a.lock()
    assert not b.try_lock()          # immediate try fails while held
    assert not b.try_lock(timeout=3)  # expires in log time, race-free cancel
    a.unlock()
    assert b.try_lock()
    b.unlock()


def test_lock_blocking_handoff():
    rg = device_plane()
    rg.wait_for_leaders()
    a = DeviceLock(rg, 0, holder_id=1)
    b = DeviceLock(rg, 0, holder_id=2)
    a.lock()
    # queue b, then release a: the grant event must complete b's lock()
    tag = rg.submit(0, __import__("copycat_tpu.ops.apply", fromlist=["x"])
                    .OP_LOCK_ACQUIRE, 2, -1)
    rg.run_until([tag])
    a.unlock()
    assert b._await_grant(None)
    b.unlock()


def test_no_stale_grant_after_immediate_grant():
    """An immediate grant is synchronous-only; a later queued try_lock must
    not be satisfied by any stale event (mutual exclusion regression)."""
    rg = device_plane()
    rg.wait_for_leaders()
    a = DeviceLock(rg, 0, holder_id=1)
    b = DeviceLock(rg, 0, holder_id=2)
    assert a.try_lock()
    a.unlock()
    b.lock()
    assert not a.try_lock(timeout=5)
    b.unlock()


def test_election_facade():
    rg = device_plane()
    rg.wait_for_leaders()
    e1 = DeviceElection(rg, 0, candidate_id=11)
    e2 = DeviceElection(rg, 0, candidate_id=22)
    epoch1 = e1.listen()
    assert epoch1 and e1.is_leader()
    assert e2.listen() is None
    assert not e2.is_leader()
    e1.resign()
    rg.run(10)
    assert e2.poll_elected() is not None
    assert e2.is_leader()
    assert not e1.is_leader(epoch1)  # stale fencing token rejected


def test_sequential_reads_via_query_lane():
    """SEQUENTIAL reads are served from the leader's applied state (no log
    append): committed writes are visible and the log does not grow."""
    import numpy as np
    groups = device_plane()
    groups.wait_for_leaders()
    m = DeviceMap(groups, 0).with_consistency("sequential")
    v = DeviceValue(groups, 1).with_consistency("sequential")
    m.put(3, 33)
    v.set(77)
    last_before = int(np.asarray(groups.state.last_index[0]).max())
    assert m.get(3) == 33
    assert m.get_or_default(9, 42) == 42
    assert m.contains_key(3) and not m.contains_key(9)
    assert m.size() == 1
    assert v.get() == 77
    last_after = int(np.asarray(groups.state.last_index[0]).max())
    assert last_after == last_before  # reads appended nothing
    assert groups.metrics.counter("queries_served").value >= 5


def test_query_lane_escalates_without_leader():
    """A query submitted before any leader exists cannot be served from
    applied state; it falls back to the command path and resolves through
    the log once a leader is elected (queries are never silently
    dropped — reference routes every query to a leader)."""
    groups = device_plane()
    assert groups.leader(0) == -1  # pre-election: genuinely leaderless
    tag = groups.submit_query(0, ap.OP_VALUE_GET)
    groups.step_round()  # query lane attempts + escalates
    assert groups.metrics.counter("queries_escalated").value >= 1
    groups.run_until([tag])  # election happens, command path serves it
    assert groups.results[tag] == 0


def test_sequential_reads_are_monotone():
    """Mixed read/write history: query-lane reads of a counter never go
    backwards (sequential consistency on one session)."""
    groups = device_plane()
    groups.wait_for_leaders()
    counter = DeviceLong(groups, 0)
    reader = DeviceLong(groups, 0).with_consistency("sequential")
    seen = 0
    for _ in range(10):
        counter.add_and_get(1)
        got = reader.get()
        assert got >= seen, f"read went backwards: {got} < {seen}"
        seen = got
    assert seen == 10  # quiesced: all committed increments visible


def test_query_lane_rejects_write_opcodes():
    """The query lane discards state, so writes must be rejected up front
    (a put 'served' there would be silently dropped with a success ack)."""
    groups = device_plane()
    with pytest.raises(ValueError, match="not read-only"):
        groups.submit_query(0, ap.OP_MAP_PUT, 1, 2)
