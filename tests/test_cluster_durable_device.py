"""The replicated, durable deployment on the CPU, against the plain reference.

Three ``AtomixServer(executor="tpu")`` members over ``Storage(DISK)`` with the
default ``fsync="commit"`` and a wire delay, a few dozen counters beside locks
and elections, closed-loop traffic across several snapshot captures on every
member; then a crash of all three with adds in flight, every log cut back to its
last sync, a fresh cluster over the same directories. Replies, every member's
own device values and the recovered values equal the model's
(``benchmarks/reference_cluster.PlainCounters``: a dict of ints).

And the two hooks that put such a cluster on the snapshot lane:
``DeviceLockState`` and ``DeviceLeaderElectionState`` round-trip their host
bookkeeping, idle and held, and a lock with an armed acquire timeout opts out.
"""

import asyncio
import os
import sys

import pytest

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import reference_cluster  # noqa: E402
from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.coordination import (  # noqa: E402
    DistributedLeaderElection, DistributedLock)
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.manager.device_executor import (  # noqa: E402
    DeviceLeaderElectionState, DeviceLockState, _UnboundSession)
from copycat_tpu.ops import apply as ops  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402
from copycat_tpu.server.log import Storage, StorageLevel  # noqa: E402
from copycat_tpu.testing.nemesis import crash_server  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED, SERVED_WIDE  # noqa: E402


def cadence(monkeypatch, entries):
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", str(entries))
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")


def machines(server, kind):
    """The member's device machines of one kind, by resource key."""
    manager = server.server.state_machine
    return {h.key: h.state_machine for h in manager.resources.values()
            if isinstance(h.state_machine, kind)}


# -- the hooks ---------------------------------------------------------------

@pytest.mark.parametrize("held", [False, True], ids=["idle", "held"])
@async_test(timeout=180)
async def test_lock_and_election_state_round_trip_a_snapshot(
        tmp_path, monkeypatch, held):
    """One member crashes after a capture and restores from the image: the
    lock's holder and waiter, the election's leader, epoch and listener are
    what they were, their sessions are bound again, and the queue moves on:
    the restored waiter is granted the lock, the listener is elected."""
    cadence(monkeypatch, 8)
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)

    def build():
        return AtomixServer(
            addr, [addr], LocalTransport(registry, local_address=addr),
            storage=Storage(StorageLevel.DISK, str(tmp_path / "m0")),
            election_timeout=0.2, heartbeat_interval=0.04,
            session_timeout=60.0, executor="tpu",
            engine_config=SERVED)

    server = build()
    await server.open()
    # 20 s, so a keep-alive every 5: after the crash the waiter's grant
    # reaches b when b's next keep-alive has found the reborn member
    a = AtomixClient([addr], LocalTransport(registry), session_timeout=20.0)
    b = AtomixClient([addr], LocalTransport(registry), session_timeout=20.0)
    await a.open()
    await b.open()
    reborn = None
    try:
        lock_a = await a.get("lock", DistributedLock)
        lock_b = await b.get("lock", DistributedLock)
        elect_a = await a.get("elect", DistributedLeaderElection)
        elect_b = await b.get("elect", DistributedLeaderElection)
        ctr = await a.get("ctr", DistributedAtomicLong)
        won_a, won_b = [], []
        waiting = None
        if held:
            await lock_a.lock()
            waiting = asyncio.ensure_future(lock_b.lock())   # no timeout
            await elect_a.on_election(won_a.append)
            await elect_b.on_election(won_b.append)
            assert len(won_a) == 1 and not won_b
        for _ in range(20):
            await ctr.increment_and_get()
        raft = server.server
        await raft.snapshots_settled()
        assert raft._snap_index > 0 and raft.groups[0]._snap_supported
        assert raft.groups[0].metrics.gauge("snap.lane").value == 1
        before_lock = machines(server, DeviceLockState)["lock"]
        before_elect = machines(server, DeviceLeaderElectionState)["elect"]
        image = (before_lock._holder_id, list(before_lock._waiters),
                 before_elect._leader, before_elect._epoch,
                 list(before_elect._listens))
        if held:
            assert image[0] is not None and len(image[1]) == 2
            assert image[2] is not None and image[3] == won_a[0]
            assert len(image[4]) == 2
        else:
            assert image == (None, [], None, None, [])
        await crash_server(raft)

        reborn = build()
        # restored from the image, not replayed from index 1
        assert reborn.server.last_applied >= raft._snap_index
        restores = reborn.server.groups[0].metrics.counter("snap.restores")
        assert restores.value == 1
        lock = machines(reborn, DeviceLockState)["lock"]
        elect = machines(reborn, DeviceLeaderElectionState)["elect"]
        assert lock is not before_lock
        assert (lock._holder_id, list(lock._waiters), elect._leader,
                elect._epoch, list(elect._listens)) == image
        # the manager re-registered the instances: no stand-in session is left
        commits = list(lock._waiters.values()) + list(elect._listens.values())
        assert not any(type(c.session) is _UnboundSession for c in commits)
        assert all(c.session.is_open for c in commits)
        await reborn.open()
        assert await asyncio.wait_for(ctr.increment_and_get(), 30) == 21
        if held:
            # the device ring and the host mirror agree: the queue moves on
            await asyncio.wait_for(lock_a.unlock(), 30)
            await asyncio.wait_for(waiting, 30)
            await asyncio.wait_for(elect_a.resign(), 30)
            for _ in range(200):
                if won_b:
                    break
                await asyncio.sleep(0.02)
            assert len(won_b) == 1 and won_b[0] > won_a[0]
            assert await elect_b.is_leader(won_b[0])
            await asyncio.wait_for(lock_b.unlock(), 30)
        assert await asyncio.wait_for(lock_a.try_lock(), 30)
    finally:
        for node in (a, b, reborn):
            if node is not None:
                try:
                    await asyncio.wait_for(node.close(), 10)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass


@async_test(timeout=120)
async def test_a_lock_with_an_armed_timeout_opts_out(tmp_path, monkeypatch):
    """An acquire that waits with a timeout arms a timer closed over its
    commit: the state says ``NotImplemented`` and the member stays on the
    replay-only lane, saying so on its gauge."""
    cadence(monkeypatch, 8)
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)
    server = AtomixServer(
        addr, [addr], LocalTransport(registry, local_address=addr),
        storage=Storage(StorageLevel.DISK, str(tmp_path / "m0")),
        election_timeout=0.2, heartbeat_interval=0.04, session_timeout=60.0,
        executor="tpu", engine_config=SERVED)
    await server.open()
    a = AtomixClient([addr], LocalTransport(registry), session_timeout=60.0)
    b = AtomixClient([addr], LocalTransport(registry), session_timeout=60.0)
    await a.open()
    await b.open()
    try:
        lock_a = await a.get("lock", DistributedLock)
        lock_b = await b.get("lock", DistributedLock)
        await lock_a.lock()
        waiting = asyncio.ensure_future(lock_b.try_lock(timeout=3600))
        ctr = await a.get("ctr", DistributedAtomicLong)
        for _ in range(20):
            await ctr.increment_and_get()
        state = machines(server, DeviceLockState)["lock"]
        assert state._timers and state.snapshot_state() is NotImplemented
        raft = server.server
        await raft.snapshots_settled()
        assert raft._snap_index == 0 and not raft.groups[0]._snap_supported
        assert raft.groups[0].metrics.gauge("snap.lane").value == 0
        assert raft.groups[0].metrics.counter("snap.snapshots_taken").value == 0
        await lock_a.unlock()
        assert await asyncio.wait_for(waiting, 30) is True
        assert not state._timers
        assert state.snapshot_state()["holder"] == state._holder_id
    finally:
        for node in (a, b, server):
            try:
                await asyncio.wait_for(node.close(), 10)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass


# -- the deployment against the plain reference ------------------------------

COUNTERS, ROUNDS = 24, 12


class Cluster:
    def __init__(self, root, addrs):
        self.root, self.addrs = root, addrs
        self.registry = LocalServerRegistry()
        self.registry.attach_nemesis().set_delay(0.0005)
        self.servers = [AtomixServer(
            addr, addrs, LocalTransport(self.registry),
            storage=Storage(StorageLevel.DISK, str(root / f"m{i}")),
            election_timeout=0.5, heartbeat_interval=0.1,
            session_timeout=60.0, executor="tpu", engine_config=SERVED_WIDE)
            for i, addr in enumerate(addrs)]

    @property
    def groups(self):
        return [s.server.groups[0] for s in self.servers]

    async def open(self):
        await asyncio.gather(*(s.open() for s in self.servers))
        client = AtomixClient(self.addrs, LocalTransport(self.registry),
                              session_timeout=60.0)
        await client.open()
        return client

    async def caught_up(self):
        for _ in range(1500):
            leaders = [g for g in self.groups if g.role == "leader"]
            if len(leaders) == 1 and all(
                    g.last_applied >= leaders[0].commit_index
                    for g in self.groups):
                break
            await asyncio.sleep(0.02)
        else:
            raise AssertionError("members did not catch up")
        await asyncio.sleep(0.1)         # a parked fused run rides a turn

    def device_values(self, names):
        out = []
        for server in self.servers:
            held = machines(server, object)
            rows = [held[n]._group for n in names]
            zeros = [0] * len(rows)
            out.append(server.server.state_machine.device_engine
                       .run_query_vector(rows, [ops.OP_VALUE_GET] * len(rows),
                                         zeros, zeros, zeros))
        return out

    async def crash_and_cut(self):
        """Every member killed without its graceful close, then everything
        it wrote after its last sync taken away."""
        for server in self.servers:
            await crash_server(server.server)
        lost = []
        for group in self.groups:
            path, length = group.log.synced_tail
            lost.append(os.path.getsize(path) - length)
            os.truncate(path, length)
        return lost


@pytest.mark.parametrize("lane", ["snapshot", "replay-only"])
@async_test(timeout=300)
async def test_three_durable_device_members_against_the_plain_reference(
        tmp_path, monkeypatch, lane):
    cadence(monkeypatch, 64)
    addrs = next_ports(3)
    cluster = Cluster(tmp_path, addrs)
    client = await cluster.open()
    other = reopened = client2 = None
    names = [f"ctr{i}" for i in range(COUNTERS)]
    model = reference_cluster.PlainCounters()
    try:
        ctrs = [await client.get(n, DistributedAtomicLong) for n in names]
        for c in ctrs:
            c.with_consistency(Consistency.ATOMIC)
        await client.get("map0", DistributedMap)
        locks = [await client.get(f"lock{i}", DistributedLock)
                 for i in range(2)]
        for i in range(2):
            await client.get(f"elect{i}", DistributedLeaderElection)
        if lane == "replay-only":
            # a held lock with a bounded waiter behind it: its state opts out
            other = AtomixClient(addrs, LocalTransport(cluster.registry),
                                 session_timeout=60.0)
            await other.open()
            await locks[0].lock()
            behind = await other.get("lock0", DistributedLock)
            asyncio.ensure_future(behind.try_lock(timeout=3600))
            await asyncio.sleep(0.05)

        wrong = []

        async def one(i, rounds):
            for k in range(rounds):
                d = 1 + (i * 31 + k * 7) % 999
                got = await ctrs[i].add_and_get(d)
                if got != model.add(names[i], d):
                    wrong.append((names[i], got, model.get(names[i])))

        await asyncio.wait_for(asyncio.gather(*(
            one(i, ROUNDS) for i in range(COUNTERS))), 120)
        assert not wrong
        assert sum(model.values.values()) > 0 and len(model.values) == COUNTERS
        assert await asyncio.gather(*(c.get() for c in ctrs)) == [
            model.get(n) for n in names]
        await cluster.caught_up()
        assert cluster.device_values(names) == [
            [model.get(n) for n in names]] * 3
        for g in cluster.groups:
            await g.snapshot_settled()
        taken = [g.metrics.counter("snap.snapshots_taken").value
                 for g in cluster.groups]
        lanes = [g.metrics.gauge("snap.lane").value for g in cluster.groups]
        firsts = [g.log.first_index for g in cluster.groups]
        syncs = [g.metrics.counter("log.syncs").value for g in cluster.groups]
        appended = [g.metrics.counter("log.bytes_appended").value
                    for g in cluster.groups]
        assert min(syncs) >= ROUNDS and min(appended) > 60 * COUNTERS * ROUNDS
        if lane == "snapshot":
            # 288 adds and the creates: at least two captures on every member
            assert min(taken) >= 2 and lanes == [1, 1, 1] and min(firsts) > 1
        else:
            assert taken == [0, 0, 0] and lanes == [0, 0, 0]
            assert firsts == [1, 1, 1]

        # adds in flight when every member dies: each was acknowledged or not
        pending = {}

        async def last(i):
            pending[names[i]] = d = 500 + i
            got = await ctrs[i].add_and_get(d)
            del pending[names[i]]
            assert got == model.add(names[i], d)

        tasks = [asyncio.ensure_future(last(i)) for i in range(COUNTERS)]
        await asyncio.sleep(0.004)
        lost = await cluster.crash_and_cut()
        await asyncio.sleep(0.05)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        assert all(n >= 0 for n in lost)
        for node in (client, other):
            if node is not None:
                try:
                    await asyncio.wait_for(node.close(), 1)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass
        client = other = None

        reopened = Cluster(tmp_path, addrs)
        restores = [g.metrics.counter("snap.restores").value
                    for g in reopened.groups]
        assert restores == ([1, 1, 1] if lane == "snapshot" else [0, 0, 0])
        client2 = await reopened.open()
        ctrs2 = [await client2.get(n, DistributedAtomicLong) for n in names]
        for c in ctrs2:
            c.with_consistency(Consistency.ATOMIC)
        recovered = await asyncio.wait_for(asyncio.gather(*(
            c.get() for c in ctrs2)), 60)
        assert reference_cluster.differences(
            model, names, recovered, pending) == (0, "")
        # what was in flight is now decided: all three members agree on it
        for name, value in zip(names, recovered):
            model.values[name] = value
        await reopened.caught_up()
        assert reopened.device_values(names) == [recovered] * 3
    finally:
        for node in (client2, client, other):
            if node is not None:
                try:
                    await asyncio.wait_for(node.close(), 5)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass
        for group in (reopened, cluster):
            for server in (group.servers if group is not None else ()):
                try:
                    await asyncio.wait_for(server.close(), 10)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass
