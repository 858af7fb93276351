"""A follower that fell under the leader's log while windows to it are still in
flight: the replication loop waits for them and YIELDS (``server/raft_group.py``,
``_replicate_pipelined``). Found on the chip by ``cluster-3x1k-crash.kill-rejoin``:
the wait was on an event that every append and every ack sets and that branch
never cleared, so the loop span without a suspension on the one thread its
windows needed to come home on, and the process never ended."""

import asyncio

from helpers import async_test
from raft_fixtures import create_cluster


@async_test(timeout=60)
async def test_the_loop_yields_while_windows_are_in_flight_under_the_prefix():
    cluster = await create_cluster(3)
    try:
        leader = await cluster.await_leader()
        group = leader.groups[0]
        peer = group.peers[0]
        for _ in range(100):                  # the peer's stream is up
            if peer in group._peer_streams:
                break
            await asyncio.sleep(0.01)
        stream, event = group._peer_streams[peer], \
            group._replication_events[peer]
        calls = {"n": 0}
        real = group._peer_connection

        async def counted(address):
            calls["n"] += 1
            if calls["n"] > 400:              # a spin ends here, not never
                raise RuntimeError("the replication loop does not yield")
            return await real(address)

        group._peer_connection = counted
        # the state a rejoin under load leaves for a moment: the peer's
        # cursor under the log's first index, a window still out, the
        # event set by the append that came meanwhile
        was = group.next_index[peer]
        stream.inflight_windows += 1
        group.next_index[peer] = group.log.prefix_index
        event.set()
        await asyncio.sleep(4 * group.heartbeat_interval)
        spun = calls["n"]
        stream.inflight_windows -= 1
        group.next_index[peer] = was
        event.set()
        del group._peer_connection
        # it looked again about once a heartbeat, and the loop ran on
        assert 1 <= spun <= 40, spun
        assert leader.role == "leader" and peer in group._replication_tasks
        assert not group._replication_tasks[peer].done()
    finally:
        await cluster.close()
