"""End-to-end exactly-once through the public API under leader churn.

Jepsen's counter invariant at the SPI level: with batched concurrent
increments racing a mid-storm leader kill, every acknowledged increment
applied exactly once and every failed one at most once — the final
counter value must land in [acked, acked + unknown]. Exercises the
batch RPC failover promotion, session-seq dedup across re-routes, and
the windowed device executor, all at once.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED  # noqa: E402



@async_test(timeout=300)
async def test_acked_increments_apply_exactly_once_across_leader_kills():
    registry = LocalServerRegistry()
    addrs = next_ports(3)
    servers = [AtomixServer(a, addrs, LocalTransport(registry),
                            election_timeout=0.2, heartbeat_interval=0.04,
                            session_timeout=20.0, executor="tpu",
                            engine_config=SERVED) for a in addrs]
    await asyncio.gather(*(s.open() for s in servers))
    client = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=20.0)
    await client.open()
    live = list(servers)
    try:
        counters = await asyncio.gather(
            *(client.get(f"x{i}", DistributedAtomicLong) for i in range(6)))

        acked = [0] * len(counters)
        unknown = [0] * len(counters)

        async def one(i) -> None:
            try:
                await asyncio.wait_for(counters[i].increment_and_get(), 30)
                acked[i] += 1
            except Exception:
                unknown[i] += 1

        async def storm(rounds: int) -> None:
            for _ in range(rounds):
                await asyncio.gather(
                    *(one(i) for i in range(len(counters))))

        # phase 1: steady state
        await storm(4)
        # phase 2: kill the leader mid-storm ONCE — on a 3-server
        # cluster a second kill would drop below quorum, so the storm
        # races exactly one failover (2 of 3 survive and re-elect)
        task = asyncio.ensure_future(storm(6))
        await asyncio.sleep(0.15)
        leader = next((s for s in live
                       if s.server.role == "leader"), None)
        if leader is not None:
            live.remove(leader)
            await asyncio.wait_for(leader.close(), 10)
        await asyncio.wait_for(task, 120)

        # settle: a final storm must fully succeed on the surviving quorum
        await storm(3)

        got = await asyncio.gather(*(c.get() for c in counters))
        for i, value in enumerate(got):
            assert acked[i] <= value <= acked[i] + unknown[i], (
                f"counter {i}: value {value} outside exactly-once window "
                f"[{acked[i]}, {acked[i] + unknown[i]}]")
        assert sum(acked) >= 6 * 7  # the storms genuinely committed work
    finally:
        try:
            await asyncio.wait_for(client.close(), 5)
        except Exception:
            pass
        for s in live:
            try:
                await asyncio.wait_for(s.close(), 5)
            except Exception:
                pass
