"""Adversarial interleavings inside one device apply window.

Single-member clusters defer commit advance to the end of the event-loop
turn, so same-turn submits apply as ONE DeviceWindow batch — these tests
force the trickiest orderings deterministically: deletes barriering
in-flight chains of the same group, lock handoff with both commands in
one window, listener registration ordered against a concurrent set, and
a batched mixed-resource storm.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicValue  # noqa: E402
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.coordination import DistributedLock  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED_WIDE  # noqa: E402



async def _node(n_clients: int = 1):
    registry = LocalServerRegistry()
    addrs = next_ports(1)
    server = AtomixServer(addrs[0], addrs, LocalTransport(registry),
                          election_timeout=0.2, heartbeat_interval=0.04,
                          session_timeout=10.0, executor="tpu",
                          engine_config=SERVED_WIDE)
    await server.open()
    clients = []
    for _ in range(n_clients):
        c = AtomixClient(addrs, LocalTransport(registry),
                         session_timeout=10.0)
        await c.open()
        clients.append(c)
    return server, clients


async def _teardown(nodes):
    for node in nodes:
        try:
            await asyncio.wait_for(node.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass


@async_test(timeout=180)
async def test_delete_mid_burst_barriers_then_group_reuses_clean(deleted="m1"):
    server, (client,) = await _node()
    try:
        m = await client.get("m1", DistributedMap)
        await asyncio.gather(*(m.put(i, i * 10) for i in range(6)))
        # same-turn: more puts racing the delete — the delete's run_excl
        # barriers the window so in-flight chains settle first
        results = await asyncio.gather(
            m.put(100, 1), m.put(101, 2), m.delete(),
            return_exceptions=True)
        # recreate under the same key: the recycled device group must be
        # clean (delete reset the device table before release)
        m2 = await client.get("m1", DistributedMap)
        assert await m2.size() == 0
        await m2.put(7, 70)
        assert await m2.get(7) == 70
    finally:
        await _teardown([client, server])


@async_test(timeout=180)
async def test_lock_handoff_within_one_window():
    server, (c1, c2) = await _node(2)
    try:
        l1 = await c1.get("lk", DistributedLock)
        l2 = await c2.get("lk", DistributedLock)
        await l1.lock()
        waiter = asyncio.ensure_future(l2.lock())
        await asyncio.sleep(0.2)
        assert not waiter.done()
        # unlock and a fresh contender race in the same turn: the grant
        # event (buffered during chain drive, replayed in log order) must
        # reach the FIFO-first waiter
        await l1.unlock()
        await asyncio.wait_for(waiter, 15)
        await l2.unlock()
        # lock still functional afterwards
        await l1.lock()
        await l1.unlock()
    finally:
        await _teardown([c1, c2, server])


@async_test(timeout=180)
async def test_listener_ordered_against_same_window_set():
    server, (c1, c2) = await _node(2)
    try:
        v1 = await c1.get("val", DistributedAtomicValue)
        v2 = await c2.get("val", DistributedAtomicValue)
        seen: list = []
        # listen (c1) lands in the log BEFORE the set (c2) or after — the
        # window must keep whichever order the log chose for host state
        # AND event delivery alike; after settling, a second set must
        # always notify
        await v1.on_change(seen.append)
        await v2.set(1)
        for _ in range(50):
            if seen:
                break
            await asyncio.sleep(0.05)
        assert seen and seen[-1] == 1, seen
        await v2.set(2)
        for _ in range(50):
            if seen[-1] == 2:
                break
            await asyncio.sleep(0.05)
        assert seen[-1] == 2, seen
    finally:
        await _teardown([c1, c2, server])


@async_test(timeout=240)
async def test_mixed_resource_storm_in_shared_windows():
    """Many resource types, many concurrent ops per turn, several turns:
    everything must commit with per-resource FIFO results intact."""
    server, (client,) = await _node()
    try:
        from copycat_tpu.atomic import DistributedAtomicLong
        from copycat_tpu.collections import DistributedQueue, DistributedSet

        counters = await asyncio.gather(
            *(client.get(f"n{i}", DistributedAtomicLong) for i in range(8)))
        maps = await asyncio.gather(
            *(client.get(f"mp{i}", DistributedMap) for i in range(4)))
        sets_ = await asyncio.gather(
            *(client.get(f"st{i}", DistributedSet) for i in range(4)))
        queues = await asyncio.gather(
            *(client.get(f"q{i}", DistributedQueue) for i in range(4)))

        for rep in range(3):
            ops = []
            ops += [c.increment_and_get() for c in counters]
            ops += [m.put(rep, rep * 7) for m in maps]
            ops += [s.add(rep) for s in sets_]
            ops += [q.offer(rep) for q in queues]
            await asyncio.wait_for(asyncio.gather(*ops), 60)

        got = await asyncio.gather(*(c.get() for c in counters))
        assert got == [3] * 8
        for m in maps:
            assert await m.size() == 3
        for s in sets_:
            assert await s.size() == 3
        for q in queues:
            assert [await q.poll() for _ in range(3)] == [0, 1, 2]  # FIFO
    finally:
        await _teardown([client, server])
