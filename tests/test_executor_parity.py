"""Executor parity: the SAME resource API against the CPU state machines
and the TPU device engine (``AtomixServer(..., executor="tpu")``).

This is the SPI obligation of SURVEY.md §7.1 — the device engine selectable
at replica build time, mirroring ``withStateMachine(new ResourceManager())``
(``AtomixReplica.java:374``) — and it subsumes the differential harness:
every test runs once per executor with identical assertions, and
``test_differential_map_sequences`` drives one randomized op stream through
both executors and compares every result.

Engine pools are deliberately tiny (map_slots=16 etc., DeviceEngineConfig
defaults) so the overflow tests genuinely spill device pools into the host
shadow (SURVEY.md §7.3 #1 "eviction-to-host for overflow").
"""

import asyncio
import random

import pytest

from copycat_tpu.atomic import DistributedAtomicLong, DistributedAtomicValue
from copycat_tpu.collections import (
    DistributedMap,
    DistributedQueue,
    DistributedSet,
)
from copycat_tpu.coordination import DistributedLeaderElection, DistributedLock
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer

from helpers import async_test
from raft_fixtures import next_ports

from engines import SERVED

EXECUTORS = ("cpu", "tpu")

# one small engine shape for every parity test → one jit compile per process


async def _cluster(executor: str, n: int = 3, n_clients: int = 1):
    registry = LocalServerRegistry()
    addrs = next_ports(n)
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry),
                     election_timeout=0.2, heartbeat_interval=0.04,
                     session_timeout=10.0, executor=executor,
                     engine_config=SERVED)
        for a in addrs
    ]
    await asyncio.gather(*(s.open() for s in servers))
    clients = []
    for _ in range(n_clients):
        client = AtomixClient(addrs, LocalTransport(registry),
                              session_timeout=10.0)
        await client.open()
        clients.append(client)
    return servers, clients


async def _teardown(nodes):
    for node in nodes:
        try:
            await asyncio.wait_for(node.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_value_and_long(executor):
    servers, (client,) = await _cluster(executor)
    try:
        value = await client.get("val", DistributedAtomicValue)
        assert await value.get() is None
        await value.set(41)
        assert await value.get() == 41
        assert await value.compare_and_set(41, 42)
        assert not await value.compare_and_set(41, 43)
        assert await value.get_and_set(7) == 42
        # non-int32 payloads transparently take the host shadow
        await value.set("a string")
        assert await value.get() == "a string"
        assert await value.compare_and_set("a string", 99)
        assert await value.get() == 99
        await value.set(None)
        assert await value.get() is None

        counter = await client.get("ctr", DistributedAtomicLong)
        assert await counter.increment_and_get() == 1
        assert await counter.add_and_get(9) == 10
        assert await counter.get_and_add(5) == 10
        assert await counter.get() == 15
        assert await counter.decrement_and_get() == 14
    finally:
        await _teardown([client] + servers)


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_map_overflow_and_mixed_payloads(executor):
    """Puts far past the device pool capacity (map_slots=16) and with
    non-int32 keys/values must succeed transparently — the overflow story
    (reference ``MapState.java:32`` has no capacity bound)."""
    servers, (client,) = await _cluster(executor)
    try:
        m = await client.get("m", DistributedMap)
        n = 40  # device pool holds 16: >half the entries spill to host
        for k in range(n):
            assert await m.put(k, k * 10) is None
        assert await m.size() == n
        for k in range(n):
            assert await m.get(k) == k * 10
        # mixed payload types
        await m.put("skey", [1, 2, 3])
        assert await m.get("skey") == [1, 2, 3]
        assert await m.put(5, "now a string") == 50
        assert await m.get(5) == "now a string"
        assert await m.contains_value("now a string")
        assert await m.contains_value(70)
        assert not await m.contains_value(50)
        # conditional ops across the device/shadow boundary
        assert await m.put_if_absent(5, 1) == "now a string"
        assert await m.replace_if_present(5, "now a string", 500)
        assert await m.get(5) == 500
        assert await m.remove(5) == 500
        assert await m.get(5) is None
        assert await m.remove_if_present(7, 70)
        assert await m.size() == n - 1  # removed 5, removed 7, added skey
        await m.clear()
        assert await m.is_empty()
    finally:
        await _teardown([client] + servers)


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_set_and_queue_overflow(executor):
    servers, (client,) = await _cluster(executor)
    try:
        s = await client.get("s", DistributedSet)
        for v in range(30):  # past set_slots=16
            assert await s.add(v)
        assert not await s.add(3)
        assert await s.size() == 30
        assert await s.contains(29)
        assert await s.remove(29)
        assert not await s.contains(29)
        assert await s.add("str-member")
        assert await s.contains("str-member")
        assert await s.size() == 30

        q = await client.get("q", DistributedQueue)
        for v in range(25):  # past queue_slots=16
            assert await q.offer(v)
        await q.offer("tail-str")
        assert await q.size() == 26
        assert await q.peek() == 0
        for v in range(25):
            assert await q.poll() == v
        assert await q.poll() == "tail-str"
        assert await q.poll() is None
        # remove-by-value from the middle
        for v in (1, 2, 3, 4):
            await q.offer(v)
        assert await q.remove(3) is True
        assert await q.contains(2)
        assert not await q.contains(3)
        assert [await q.poll() for _ in range(3)] == [1, 2, 4]
    finally:
        await _teardown([client] + servers)


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_lock_contention_and_session_release(executor):
    servers, (c1, c2) = await _cluster(executor, n_clients=2)
    try:
        l1 = await c1.get("lk", DistributedLock)
        l2 = await c2.get("lk", DistributedLock)
        await l1.lock()
        assert not await l2.try_lock()          # immediate attempt fails
        waiter = asyncio.ensure_future(l2.lock())  # queue behind holder
        await asyncio.sleep(0.3)
        assert not waiter.done()
        await l1.unlock()
        await asyncio.wait_for(waiter, 15)       # grant via session event
        await l2.unlock()

        # session death releases the lock (the capability fix over the
        # reference, preserved on the device path)
        await l1.lock()
        waiter2 = asyncio.ensure_future(l2.lock())
        await asyncio.sleep(0.3)
        await c1.close()                          # holder's client dies
        await asyncio.wait_for(waiter2, 15)
        await l2.unlock()
    finally:
        await _teardown([c1, c2] + servers)


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_election_succession_and_fencing(executor):
    servers, (c1, c2) = await _cluster(executor, n_clients=2)
    try:
        e1 = await c1.get("el", DistributedLeaderElection)
        e2 = await c2.get("el", DistributedLeaderElection)
        epochs1: list[int] = []
        epochs2: list[int] = []
        await e1.on_election(epochs1.append)
        await e2.on_election(epochs2.append)
        for _ in range(100):
            if epochs1:
                break
            await asyncio.sleep(0.05)
        assert epochs1, "first listener was not elected"
        # is_leader(epoch) is a pure fencing-token check: it validates the
        # epoch against the CURRENT leadership (reference
        # LeaderElectionState.isLeader:96), regardless of who asks.
        assert await e1.is_leader(epochs1[0])
        assert not await e1.is_leader(epochs1[0] + 999)
        # leader's client dies -> succession to the second listener
        await c1.close()
        for _ in range(200):
            if epochs2:
                break
            await asyncio.sleep(0.05)
        assert epochs2, "successor was not promoted"
        assert await e2.is_leader(epochs2[0])
        # the old epoch no longer fences
        assert not await e2.is_leader(epochs1[0])
    finally:
        await _teardown([c1, c2] + servers)


@async_test(timeout=300)
async def test_differential_map_sequences():
    """One randomized op stream through BOTH executors; every result must
    match — the differential harness collapsed into the SPI
    parametrization (round-2 VERDICT directive #2)."""
    rng = random.Random(1234)
    script = []
    for _ in range(60):
        op = rng.choice(["put", "get", "remove", "pia", "rip", "size"])
        k = rng.randrange(24)            # > map_slots → guaranteed overflow
        v = rng.randrange(100)
        script.append((op, k, v))

    async def run(executor):
        servers, (client,) = await _cluster(executor)
        try:
            m = await client.get("diff", DistributedMap)
            out = []
            for op, k, v in script:
                if op == "put":
                    out.append(await m.put(k, v))
                elif op == "get":
                    out.append(await m.get(k))
                elif op == "remove":
                    out.append(await m.remove(k))
                elif op == "pia":
                    out.append(await m.put_if_absent(k, v))
                elif op == "rip":
                    out.append(await m.remove_if_present(k, v))
                elif op == "size":
                    out.append(await m.size())
            return out
        finally:
            await _teardown([client] + servers)

    cpu = await run("cpu")
    tpu = await run("tpu")
    assert cpu == tpu


@async_test(timeout=180)
async def test_device_group_reuse_after_delete():
    """Deleting a device-backed resource resets and frees its group, so the
    engine can host capacity-many LIVE resources regardless of history —
    and a recycled group must not leak its predecessor's state."""
    servers, (client,) = await _cluster("tpu")
    try:
        first = await client.get("reuse-seed", DistributedMap)
        await first.put(1, 111)
        await first.delete()
        # capacity is 8: with the freed group back in the pool, all 8 new
        # resources get device placement (no CPU fallback anywhere)
        maps = []
        for i in range(8):
            m = await client.get(f"reuse-{i}", DistributedMap)
            await m.put(i + 100, i)
            maps.append(m)
        sm = servers[0].server.state_machine
        kinds = sorted(type(h.state_machine).__name__
                       for h in sm.resources.values())
        assert kinds == ["DeviceMapState"] * 8, kinds
        # the recycled group starts clean: the predecessor's key is gone
        for m in maps:
            assert await m.get(1) is None
        for i, m in enumerate(maps):
            assert await m.get(i + 100) == i
    finally:
        await _teardown([client] + servers)


@pytest.mark.parametrize("executor", EXECUTORS)
@async_test(timeout=180)
async def test_multimap_overflow_and_mixed_payloads(executor):
    from copycat_tpu.collections import DistributedMultiMap

    servers, (client,) = await _cluster(executor)
    try:
        mm = await client.get("mm", DistributedMultiMap)
        # past the device pair-table capacity (multimap_slots=16)
        for k in range(5):
            for v in range(5):
                assert await mm.put(k, v * 10)
        assert not await mm.put(0, 0)            # duplicate pair
        assert await mm.size() == 25
        assert await mm.size(2) == 5
        assert sorted(await mm.get(3)) == [0, 10, 20, 30, 40]
        # non-int32 payloads (hashable, as the reference requires)
        assert await mm.put("sk", "sv")
        assert await mm.contains_entry("sk", "sv")
        assert await mm.contains_value("sv")
        # remove-entry and remove-key across the device/shadow boundary
        assert await mm.remove(1, 10)            # remove one entry
        assert not await mm.contains_entry(1, 10)
        removed = await mm.remove(4)             # remove whole key
        assert sorted(removed) == [0, 10, 20, 30, 40]
        assert not await mm.contains_key(4)
        assert await mm.size() == 20             # 25 - 1 - 5 + 1(sk)
        await mm.clear()
        assert await mm.is_empty()
    finally:
        await _teardown([client] + servers)
