"""Device-path sessions: keep-alives + deterministic expiry fan-out.

Round-2 VERDICT directive #3: a crashed device-path client must not wedge
a lock or a leadership slot — session death must release through the log,
totally ordered with concurrent grants (the reference's session story,
``ResourceManager.java:238-266``, ``LeaderElectionState.close:36-49``;
the CPU path's release-on-death fix, ``coordination/state.py``).
"""

import pytest

from copycat_tpu.models.device_resources import DeviceElection, DeviceLock
from copycat_tpu.models.raft_groups import RaftGroups
from copycat_tpu.models.sessions import SessionExpiredError
from copycat_tpu.ops.apply import OP_LOCK_ACQUIRE

from engines import device_plane


def _groups(timeout_rounds: int = 25) -> RaftGroups:
    groups = device_plane(seed=7)
    groups.sessions.timeout_rounds = timeout_rounds
    groups.wait_for_leaders()
    return groups


def test_crashed_holder_releases_lock_to_next_waiter():
    groups = _groups()
    s1 = groups.sessions.open_session()
    s2 = groups.sessions.open_session()
    holder = DeviceLock(groups, 0, session=s1)
    waiter = DeviceLock(groups, 0, session=s2)

    holder.lock()
    assert not waiter.try_lock()  # held

    # s1 "crashes": it never keep-alives again. waiter.lock() drives the
    # batch; s1 expires mid-wait, the registry fans OP_LOCK_CANCEL +
    # OP_LOCK_RELEASE through the log, and the queued waiter is granted.
    waiter.lock()
    assert s1.expired

    # the zombie's facade is fenced off
    with pytest.raises(SessionExpiredError):
        holder.unlock()
    waiter.unlock()


def test_crashed_queued_waiter_is_dequeued():
    groups = _groups()
    s1 = groups.sessions.open_session()
    s2 = groups.sessions.open_session()
    s3 = groups.sessions.open_session()
    holder = DeviceLock(groups, 1, session=s1)
    dead_waiter = DeviceLock(groups, 1, session=s2)
    live_waiter = DeviceLock(groups, 1, session=s3)

    holder.lock()
    # queue s2 without blocking (raw acquire: 2 = queued on device)
    assert dead_waiter._call(OP_LOCK_ACQUIRE, s2.id, -1) == 2
    # s2 crashes while queued; s1 and s3 stay alive through their calls.
    for _ in range(30):
        holder._touch()
        groups.step_round()
        s3.keep_alive()
    assert s2.expired
    # release: the grant must skip the dead waiter and reach s3
    holder.unlock()
    live_waiter.lock()
    live_waiter.unlock()


def test_crashed_leader_promotes_next_listener():
    groups = _groups()
    s1 = groups.sessions.open_session()
    s2 = groups.sessions.open_session()
    e1 = DeviceElection(groups, 2, session=s1)
    e2 = DeviceElection(groups, 2, session=s2)

    epoch1 = e1.listen()
    assert epoch1 is not None and epoch1 > 0  # immediate leadership
    assert e2.listen() is None                # queued behind s1

    # s1 crashes; drive rounds keeping s2 alive until succession lands
    epoch2 = None
    for _ in range(120):
        groups.step_round()
        s2.keep_alive()
        epoch2 = e2.poll_elected()
        if epoch2:
            break
    assert s1.expired
    assert epoch2 and epoch2 != epoch1, "successor not promoted"
    assert e2.is_leader(epoch2)
    # the dead leader's epoch no longer fences
    assert not e2.is_leader(epoch1)


def test_graceful_close_releases_immediately():
    groups = _groups(timeout_rounds=10_000)  # expiry can't be the cause
    s1 = groups.sessions.open_session()
    s2 = groups.sessions.open_session()
    holder = DeviceLock(groups, 3, session=s1)
    waiter = DeviceLock(groups, 3, session=s2)

    holder.lock()
    assert not waiter.try_lock()
    s1.close()           # graceful: same fan-out, no timeout needed
    waiter.lock()
    waiter.unlock()
    with pytest.raises(SessionExpiredError):
        holder.lock()
