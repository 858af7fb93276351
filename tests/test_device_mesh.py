"""The SPI device plane over a sharded mesh.

``DeviceEngineConfig.mesh`` shards each server's engine group axis
across its local devices (`parallel/mesh.py` placement specs). This
drives the FULL public stack — AtomixServers with ``executor="tpu"``,
real client sessions — on an engine sharded over the suite's 8 virtual
CPU devices, and asserts both the results and the placement (the state
really is distributed). Sharding is a local placement choice: a sharded
and an unsharded engine replicate identically (same shapes, same seed),
which the mixed-mesh cluster test exercises directly.

Reference obligation: the public API is the data path
(``Atomix.java:205``); scale axes ride the mesh (SURVEY §2.2).
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.manager.device_executor import (  # noqa: E402
    DeviceEngine,
    DeviceEngineConfig,
)
from copycat_tpu.parallel import make_mesh  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED  # noqa: E402


def _mesh_or_skip():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh (conftest)")
    return make_mesh(groups=8)


def test_capacity_must_divide_mesh():
    mesh = _mesh_or_skip()
    engine = DeviceEngine(DeviceEngineConfig(capacity=12, mesh=mesh))
    with pytest.raises(ValueError, match="not divisible"):
        engine._ensure()


def test_engine_state_sharded_over_mesh():
    mesh = _mesh_or_skip()
    engine = DeviceEngine(SERVED._replace(mesh=mesh))
    rg = engine._ensure()
    shardings = {str(rg.state.term.sharding.spec),
                 str(rg.state.log_term.sharding.spec)}
    assert all("groups" in s for s in shardings), shardings
    # 8 groups over 8 devices: each device holds a [1, ...] slice
    assert len(rg.state.term.devices()) == 8


@async_test
async def test_public_api_through_sharded_engine():
    mesh = _mesh_or_skip()
    registry = LocalServerRegistry()
    addrs = next_ports(3)
    cfg = SERVED._replace(mesh=mesh)
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry),
                     election_timeout=0.2, heartbeat_interval=0.04,
                     executor="tpu", engine_config=cfg)
        for a in addrs
    ]
    await asyncio.gather(*(s.open() for s in servers))
    client = AtomixClient(addrs, LocalTransport(registry))
    await client.open()
    try:
        counters = [
            await client.get(f"c{i}", DistributedAtomicLong)
            for i in range(4)
        ]
        for rep in range(3):
            for i, c in enumerate(counters):
                got = await asyncio.wait_for(c.add_and_get(i + 1), 30)
                assert got == (i + 1) * (rep + 1)
    finally:
        await client.close()
        for s in servers:
            await s.close()


@async_test
async def test_mixed_mesh_cluster_replicates_identically():
    """A sharded server and unsharded servers form one cluster: the mesh
    is placement-only, so their replicated engine histories agree."""
    mesh = _mesh_or_skip()
    registry = LocalServerRegistry()
    addrs = next_ports(3)
    configs = [SERVED._replace(mesh=mesh), SERVED, SERVED]
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry),
                     election_timeout=0.2, heartbeat_interval=0.04,
                     executor="tpu", engine_config=c)
        for a, c in zip(addrs, configs)
    ]
    await asyncio.gather(*(s.open() for s in servers))
    client = AtomixClient(addrs, LocalTransport(registry))
    await client.open()
    try:
        c = await client.get("n", DistributedAtomicLong)
        for i in range(1, 6):
            assert await asyncio.wait_for(c.increment_and_get(), 30) == i
    finally:
        await client.close()
        for s in servers:
            await s.close()
