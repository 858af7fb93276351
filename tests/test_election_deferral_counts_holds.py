"""An election timer tells its leader's silence from its own deafness
(``server/raft_group.py: _start_election``, ``utils/scheduled.py: LoopWatch``).

Found on the chip by ``cluster-3x1k-crash.kill-rejoin`` (PR 49): a killed
leader's restart held the one loop its two live neighbours share for 332 ms
(its boot recovery) and, 24 ms later, for 246 ms (its first image's restore);
nothing from the healthy leader reached the other follower between the two,
that follower's timer came due inside the second hold and ran 84 ms late,
under the heartbeat interval the deferral asked of ONE hold, and it deposed
a healthy leader. The timeout now counts the seconds a member listened: the
deferral reads what the loop was held for since the timer was armed, however
many holds that was, and listens that long again."""

import asyncio
import time

from helpers import async_test
from raft_fixtures import create_cluster

from copycat_tpu.server.raft import FOLLOWER
from copycat_tpu.utils.scheduled import LoopWatch


def _hold_until(instant: float) -> None:
    """Stand the loop still until ``instant`` of the monotonic clock."""
    time.sleep(max(0.0, instant - time.monotonic()))


async def _deaf_follower():
    """The deployment's timers, a follower cut off from its leader, and its
    election timer armed as the leader's last message arms it."""
    cluster = await create_cluster(3, election_timeout=0.5,
                                   heartbeat_interval=0.1)
    nem = cluster.registry.attach_nemesis()
    leader = await cluster.await_leader()
    follower = next(s for s in cluster.servers if s is not leader)
    group = follower.groups[0]
    for _ in range(100):
        if group.leader_address == leader.address:
            break
        await asyncio.sleep(0.01)
    assert group.leader_address == leader.address
    others = [s.address for s in cluster.servers if s is not follower]
    nem.partition([follower.address], others)
    await asyncio.sleep(0.01)           # what was on the wire has landed
    group._reset_election_timer()
    await asyncio.sleep(0)              # the timer's task begins its sleep
    return cluster, nem, leader, group


def _two_holds_24_ms_apart(group):
    """The chip's sequence on the timer just armed: held to 200 ms before
    the timer is due, let go for 24 ms, held again until 50 ms past it:
    the callback runs half a heartbeat late."""
    due = group._election_due
    held_from = time.monotonic()

    async def holds() -> float:
        _hold_until(due - 0.2)
        await asyncio.sleep(0.024)
        _hold_until(due + 0.05)
        held = time.monotonic() - held_from - 0.024
        await asyncio.sleep(0.03)       # the timer's callback runs
        return held
    return holds()


def _read(group, name: str) -> int:
    return group.metrics.counter(name).value


@async_test(timeout=60)
async def test_two_holds_of_the_loop_defer_the_election_and_keep_the_leader():
    cluster, nem, leader, group = await _deaf_follower()
    try:
        term = leader.groups[0].term
        started = _read(group, "raft_elections_started")
        deferred = _read(group, "raft_elections_deferred")
        held = await _two_holds_24_ms_apart(group)
        assert held > group.heartbeat_interval
        # the timer fired UNDER a heartbeat late, over a loop that stood
        # still for most of the timeout: deferred, not stood
        assert _read(group, "raft_elections_deferred") == deferred + 1
        assert _read(group, "raft_elections_started") == started
        assert group.role == FOLLOWER and group.term == term
        # it listens for as long again as it was deaf, a timeout at most
        due = group._election_due
        assert 0 < due - time.monotonic() <= held
        # the leader's next message ends the silence: a fresh timeout
        nem.heal()
        for _ in range(100):
            if group._election_due != due:
                break
            await asyncio.sleep(0.01)
        assert group._election_due != due
        assert _read(group, "raft_elections_started") == started
        assert cluster.leader is leader and leader.groups[0].term == term
        assert group.leader_address == leader.address
    finally:
        await cluster.close()


@async_test(timeout=60)
async def test_a_leader_that_is_gone_is_replaced_after_one_deferral():
    cluster, nem, leader, group = await _deaf_follower()
    try:
        started = _read(group, "raft_elections_started")
        deferred = _read(group, "raft_elections_deferred")
        await _two_holds_24_ms_apart(group)
        assert _read(group, "raft_elections_deferred") == deferred + 1
        assert _read(group, "raft_elections_started") == started
        # nothing comes (the partition stands) and the loop runs: having
        # listened the timeout through, it stands
        for _ in range(300):
            if _read(group, "raft_elections_started") > started:
                break
            await asyncio.sleep(0.01)
        assert _read(group, "raft_elections_started") > started
    finally:
        await cluster.close()


@async_test(timeout=60)
async def test_a_timer_superseded_after_it_fired_starts_no_election():
    """Behind a held loop the timer that came due and the leader's message
    are queued in one turn: the timer's callback is already spawned when the
    message re-arms the timer, and a cancel does not reach it."""
    cluster, nem, leader, group = await _deaf_follower()
    try:
        term = leader.groups[0].term
        started = _read(group, "raft_elections_started")
        group._election_timer._invoke()     # the timer fires: callback spawned
        group._reset_election_timer()       # the leader's message, same turn
        await asyncio.sleep(0.05)
        assert _read(group, "raft_elections_started") == started
        assert group.role == FOLLOWER and group.term == term
        nem.heal()
        await asyncio.sleep(2 * group.heartbeat_interval)
        assert cluster.leader is leader and leader.groups[0].term == term
    finally:
        await cluster.close()


@async_test(timeout=30)
async def test_the_loop_watch_counts_holds_and_not_a_loop_that_runs():
    period = 0.025
    watch = LoopWatch(2 * period)
    try:
        before = watch.held()
        time.sleep(0.15)
        # the hold the caller is still inside is counted before any tick
        # has run behind it
        assert 0.15 - period <= watch.held() - before <= 0.3
        await asyncio.sleep(2 * period)  # the late tick runs: counted once
        assert 0.15 - period <= watch.held() - before <= 0.3
        time.sleep(0.1)
        await asyncio.sleep(0.024)
        time.sleep(0.1)
        assert watch.held() - before >= 0.35 - 3 * period
        # a tick late by less than its floor is a busy loop, not a held one
        await asyncio.sleep(2 * period)
        watch.cancel()
        mark = watch.held()
        watch._due = time.monotonic() - 1.5 * period
        watch._tick()
        assert watch.held() == mark
        watch._due = time.monotonic() - 0.2
        watch._tick()
        assert watch.held() - mark >= 0.2
    finally:
        watch.cancel()
    # cancelled, it ticks no more
    assert watch._handle is None
