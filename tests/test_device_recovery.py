"""Restart-from-disk recovery with the device executor.

The two-plane design's recovery claim (manager/device_executor.py): the
engine's visible state is a pure function of the committed device-op
sequence, which is derived from the CPU log in apply order — so a server
restarted from its on-disk log rebuilds a FRESH device engine to exactly
the pre-crash resource state by replay. Reference obligation: recovery =
replay the un-compacted log (SURVEY.md §5.4).
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong, DistributedAtomicValue  # noqa: E402
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.server.log import Storage, StorageLevel  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED  # noqa: E402



@pytest.mark.parametrize("level", [StorageLevel.DISK, StorageLevel.MAPPED])
@async_test(timeout=300)
async def test_restart_replays_log_into_fresh_device_engine(tmp_path, level):
    registry = LocalServerRegistry()
    addrs = next_ports(1)
    storage = Storage(level, str(tmp_path), max_entries_per_segment=16)

    server = AtomixServer(addrs[0], addrs, LocalTransport(registry),
                          election_timeout=0.2, heartbeat_interval=0.04,
                          session_timeout=10.0, executor="tpu",
                          engine_config=SERVED, storage=storage)
    await server.open()
    client = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=10.0)
    await client.open()

    ctr = await client.get("ctr", DistributedAtomicLong)
    for _ in range(5):
        await ctr.increment_and_get()
    m = await client.get("m", DistributedMap)
    await m.put(1, 11)
    await m.put(2, 22)
    await m.remove(1)
    v = await client.get("v", DistributedAtomicValue)
    await v.set(99)
    engine = server.server.state_machine.device_engine
    assert engine._next_group >= 3  # all three landed on-device

    await asyncio.wait_for(client.close(), 5)
    await asyncio.wait_for(server.close(), 5)

    # Fresh process-equivalent: new registry/server over the SAME log dir;
    # a brand-new device engine must be rebuilt purely by replay.
    registry2 = LocalServerRegistry()
    storage2 = Storage(level, str(tmp_path), max_entries_per_segment=16)
    server2 = AtomixServer(addrs[0], addrs, LocalTransport(registry2),
                           election_timeout=0.2, heartbeat_interval=0.04,
                           session_timeout=10.0, executor="tpu",
                           engine_config=SERVED, storage=storage2)
    await server2.open()
    client2 = AtomixClient(addrs, LocalTransport(registry2),
                           session_timeout=10.0)
    await client2.open()
    try:
        ctr2 = await client2.get("ctr", DistributedAtomicLong)
        assert await ctr2.get() == 5
        assert await ctr2.increment_and_get() == 6  # still writable
        m2 = await client2.get("m", DistributedMap)
        assert await m2.get(2) == 22
        assert await m2.get(1) is None
        assert await m2.size() == 1
        v2 = await client2.get("v", DistributedAtomicValue)
        assert await v2.get() == 99
        engine2 = server2.server.state_machine.device_engine
        assert engine2 is not engine  # genuinely rebuilt
    finally:
        await asyncio.wait_for(client2.close(), 5)
        await asyncio.wait_for(server2.close(), 5)
