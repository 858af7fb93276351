"""Replication-plane tests: the pipelined leader->follower stream
(server/raft_group.py `_replicate_pipelined`) at its default depth and
at its edge of ONE window in flight (COPYCAT_REPL_DEPTH=1, the shape
stop-and-wait replication has), the log-rewind path (conflicting
suffix -> truncate -> last_index hint rewind -> reconverge), the
no-progress backoff branch, backpressure caps, the COPYCAT_REPL_WINDOW
knob, and the transport-level pending-correlation leak fix.
"""

import asyncio

import pytest

from helpers import async_test
from raft_fixtures import REPL_DEPTHS, Get, Put, create_cluster

from copycat_tpu.client.client import RaftClient
from copycat_tpu.io.local import LocalTransport
from copycat_tpu.io.serializer import Serializer
from copycat_tpu.io.transport import Address
from copycat_tpu.protocol import messages as msg
from copycat_tpu.server.log import NoOpEntry
from copycat_tpu.server.raft import FOLLOWER, LEADER, _PeerStream


async def _await_leader_among(servers, timeout=15.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        for s in servers:
            if s.is_open and s.role == LEADER:
                return s
        await asyncio.sleep(0.02)
    raise TimeoutError("no leader among the given servers")


def _assert_logs_converged(servers, up_to=None):
    """Committed logs are bit-identical across members: every index both
    members still hold (compaction timing may differ) serializes to the
    same bytes — replicated entries carry the leader's term/timestamp."""
    ser = Serializer()
    base = servers[0]
    limit = up_to or min(s.commit_index for s in servers)
    compared = 0
    for other in servers[1:]:
        for i in range(1, limit + 1):
            a, b = base.log.get(i), other.log.get(i)
            if a is None or b is None:
                continue
            assert ser.write(a) == ser.write(b), \
                f"log divergence at {i}: {a!r} != {b!r}"
            compared += 1
    assert compared > 0, "nothing compared: logs fully compacted?"


# ---------------------------------------------------------------------------
# divergence -> truncate -> hint rewind -> reconverge (both depths)
# ---------------------------------------------------------------------------


@REPL_DEPTHS
def test_follower_divergence_truncates_and_reconverges(depth, monkeypatch):
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)

    @async_test(timeout=120)
    async def run():
        cluster = await create_cluster(3, session_timeout=30.0)
        try:
            old = await cluster.await_leader()
            client = await cluster.client(session_timeout=30.0)
            await client.submit(Put(key="base", value=1))

            # isolate the leader and grow an uncommitted CONFLICTING
            # suffix on it (its own term; a quorum never sees it)
            nem = cluster.registry.attach_nemesis()
            others = [s for s in cluster.servers if s is not old]
            nem.partition([old.address], [s.address for s in others])
            for _ in range(5):
                old._append(NoOpEntry())
            diverged_at = old.log.last_index

            # the majority elects and commits PAST the divergence point
            new = await _await_leader_among(others, timeout=20)
            assert new.term > old.term
            maj = RaftClient([s.address for s in others],
                             LocalTransport(cluster.registry),
                             session_timeout=30.0)
            await maj.open()
            cluster.clients.append(maj)
            for i in range(10):
                await asyncio.wait_for(
                    maj.submit(Put(key="post", value=i)), 30)

            # heal: the old leader's suffix must truncate (conflict scan)
            # and the stream rewind via the last_index hint, then converge
            nem.heal()
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                if (old.role == FOLLOWER
                        and old.state_machine.data.get("post") == 9
                        and old.commit_index >= new.commit_index):
                    break
                await asyncio.sleep(0.05)
            assert old.role == FOLLOWER
            assert old.state_machine.data.get("post") == 9
            # the conflicting suffix is gone: whatever occupies those
            # indices now carries the NEW leader's term
            for i in range(diverged_at - 4, diverged_at + 1):
                e = old.log.get(i)
                if e is not None:
                    assert e.term >= new.term or e.term < old.term, (i, e)
            _assert_logs_converged(cluster.servers)
        finally:
            await cluster.close()

    run()


@REPL_DEPTHS
def test_lagging_follower_last_index_hint_rewind(depth, monkeypatch):
    """A fresh leader starts every peer at next_index = last+1; a
    follower that missed a burst refuses the first append (prev past its
    tail) with its last_index as the hint, and the stream must rewind to
    it in ONE step and re-stream the gap (repl.rewinds counts it)."""
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)

    @async_test(timeout=120)
    async def run():
        cluster = await create_cluster(3, session_timeout=30.0)
        try:
            old = await cluster.await_leader()
            client = await cluster.client(session_timeout=30.0)
            # isolate one FOLLOWER, commit a burst past it
            lagging = next(s for s in cluster.servers if s is not old)
            rest = [s for s in cluster.servers if s is not lagging]
            nem = cluster.registry.attach_nemesis()
            nem.partition([lagging.address], [s.address for s in rest])
            futs = [client.submit_command_nowait(Put(key="k", value=i))
                    for i in range(80)]
            await asyncio.gather(*futs)
            behind_by = old.log.last_index - lagging.log.last_index
            assert behind_by > 0

            # depose the old leader and heal: the surviving up-to-date
            # member elects, starts the lagging peer at ITS last+1, and
            # must hint-rewind to the peer's tail
            await old.close()
            nem.heal()
            survivor = next(s for s in rest if s is not old)
            new = await _await_leader_among([survivor, lagging], timeout=30)
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                if lagging.state_machine.data.get("k") == 79:
                    break
                await asyncio.sleep(0.05)
            assert lagging.state_machine.data.get("k") == 79
            assert new.metrics.counter("repl.rewinds").value >= 1
            _assert_logs_converged([new, lagging])
        finally:
            await cluster.close()

    run()


@REPL_DEPTHS
def test_no_progress_backoff_branch(depth, monkeypatch):
    """A follower that refuses every append without a usable hint drives
    the leader's rewind to the log base; the leader must back off (stall
    counter) instead of hot-spinning, stay leader via the healthy
    follower, and reconverge once the refusal clears."""
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)

    @async_test(timeout=120)
    async def run():
        cluster = await create_cluster(3, session_timeout=30.0)
        try:
            leader = await cluster.await_leader()
            client = await cluster.client(session_timeout=30.0)
            await client.submit(Put(key="a", value=1))
            victim = next(s for s in cluster.servers if s is not leader)

            async def reject(request):
                return msg.AppendResponse(term=victim.term, success=False,
                                          last_index=0)

            victim._on_append = reject  # new connections pick this up
            conn = leader._peer_connections.get(victim.address)
            if conn is not None:
                await conn.close()  # force a re-dial onto the patched handler

            stalls0 = leader.metrics.counter("repl.stalls").value
            for i in range(5):
                await asyncio.wait_for(
                    client.submit(Put(key="b", value=i)), 30)
            deadline = asyncio.get_running_loop().time() + 10
            while asyncio.get_running_loop().time() < deadline:
                if leader.metrics.counter("repl.stalls").value > stalls0:
                    break
                await asyncio.sleep(0.05)
            assert leader.role == LEADER
            assert leader.metrics.counter("repl.stalls").value > stalls0

            # clear the fault: the class handler serves again
            del victim.__dict__["_on_append"]
            conn = leader._peer_connections.get(victim.address)
            if conn is not None:
                await conn.close()
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                if victim.state_machine.data.get("b") == 4:
                    break
                await asyncio.sleep(0.05)
            assert victim.state_machine.data.get("b") == 4
        finally:
            await cluster.close()

    run()


# ---------------------------------------------------------------------------
# knobs, backpressure, adaptive window
# ---------------------------------------------------------------------------


def test_repl_window_knob_reaches_the_stream(monkeypatch):
    monkeypatch.setenv("COPYCAT_REPL_WINDOW", "16")

    @async_test(timeout=60)
    async def run():
        cluster = await create_cluster(3, session_timeout=30.0)
        try:
            leader = await cluster.await_leader()
            assert leader._repl_window == 16
            client = await cluster.client(session_timeout=30.0)
            futs = [client.submit_command_nowait(Put(key="k", value=i))
                    for i in range(100)]
            await asyncio.gather(*futs)
            hist = leader.metrics.histogram("repl.window_entries")
            assert hist.count > 0
            assert hist.max_value <= 16, hist.max_value
            # the whole repl.* family rides the leader's snapshot
            snap = leader.metrics.snapshot()
            assert snap["repl.windows_sent"] > 0, sorted(snap)
            assert snap["repl.entries_sent"] >= 100
            assert snap["repl.ack_ms"]["count"] > 0
            for key in ("repl.rewinds", "repl.stalls",
                        "repl.backpressure_waits", "repl.windows_inflight",
                        "repl.entries_inflight"):
                assert key in snap, (key, sorted(snap))
        finally:
            await cluster.close()

    run()


def test_backpressure_caps_inflight_entries(monkeypatch):
    """A tiny in-flight budget + wire latency: the pump must hold the
    stream at the cap (backpressure counter moves) and still commit
    everything; the gauges return to zero once the stream drains."""
    monkeypatch.setenv("COPYCAT_REPL_WINDOW", "8")
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", "1")
    monkeypatch.setenv("COPYCAT_REPL_MAX_INFLIGHT", "8")

    @async_test(timeout=120)
    async def run():
        cluster = await create_cluster(3, session_timeout=30.0)
        try:
            leader = await cluster.await_leader()
            client = await cluster.client(session_timeout=30.0)
            nem = cluster.registry.attach_nemesis()
            nem.set_delay(0.002)
            futs = [client.submit_command_nowait(Put(key="k", value=i))
                    for i in range(150)]
            await asyncio.gather(*futs)
            assert leader.metrics.counter(
                "repl.backpressure_waits").value > 0
            nem.heal()
            # poll for the drain — an in-flight heartbeat window may
            # legitimately show at any instant
            deadline = asyncio.get_running_loop().time() + 5
            while asyncio.get_running_loop().time() < deadline:
                if (leader.metrics.gauge("repl.windows_inflight").value == 0
                        and leader.metrics.gauge(
                            "repl.entries_inflight").value == 0):
                    break
                await asyncio.sleep(0.02)
            assert leader.metrics.gauge("repl.windows_inflight").value == 0
            assert leader.metrics.gauge("repl.entries_inflight").value == 0
            assert await client.submit(Get(key="k")) == 149
        finally:
            await cluster.close()

    run()


def test_peer_stream_adaptive_window():
    ps = _PeerStream(64)
    assert ps.window == 64 and ps.floor == 8
    ps.observe_ack(1.0)          # baseline
    ps.observe_ack(50.0)         # spike vs baseline: shrink
    assert ps.window < ps.ceiling
    # escalating congestion outruns the EWMA every ack: collapse to floor
    for lat in (100.0, 1000.0, 10000.0):
        ps.observe_ack(lat)
    assert ps.window == ps.floor
    # a PERSISTENT latency shift re-baselines (EWMA, not all-time best)
    # and the window regrows to the ceiling instead of reading the new
    # RTT as congestion forever
    for _ in range(60):
        ps.observe_ack(10000.0)
    assert ps.window == ps.ceiling
    for _ in range(200):         # never leaves [floor, ceiling]
        ps.observe_ack(0.1)
        assert ps.floor <= ps.window <= ps.ceiling


# ---------------------------------------------------------------------------
# satellite regressions: pending-correlation leak, stale-term metrics
# ---------------------------------------------------------------------------


@async_test
async def test_tcp_send_timeout_pops_pending_correlation():
    """A timed-out correlated send (the replication/ping pattern:
    asyncio.wait_for around conn.send) must not strand its future in the
    connection's _pending map until the connection closes."""
    from copycat_tpu.io.tcp import TcpTransport

    transport = TcpTransport()
    server = transport.server()
    release = asyncio.Event()

    def on_connect(conn):
        async def slow(m):
            await release.wait()
            return m.value

        conn.handler(Put, slow)

    await server.listen(Address("127.0.0.1", 0), on_connect)
    port = server._server.sockets[0].getsockname()[1]
    client = transport.client()
    conn = await client.connect(Address("127.0.0.1", port))
    try:
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(conn.send(Put(key="k", value=1)), 0.05)
        assert conn._pending == {}, "timed-out correlation leaked"
        # the connection is still usable after the leak-free timeout
        release.set()
        assert await asyncio.wait_for(
            conn.send(Put(key="k", value=2)), 5) == 2
        assert conn._pending == {}
    finally:
        await client.close()
        await server.close()


@async_test
async def test_stale_term_append_not_recorded(monkeypatch):
    """Appends from deposed leaders are rejected BEFORE touching the
    append-size histogram / heartbeat counter."""
    from copycat_tpu.io.local import LocalServerRegistry
    from copycat_tpu.server.raft import RaftServer
    from raft_fixtures import KVStateMachine, next_ports

    registry = LocalServerRegistry()
    addr, peer = next_ports(2)
    server = RaftServer(addr, [addr, peer], LocalTransport(registry),
                        KVStateMachine())
    server.term = 5
    entry = NoOpEntry(term=3, timestamp=0.0)
    entry.index = 1
    stale = msg.AppendRequest(term=3, leader=peer, prev_index=0,
                              prev_term=0, entries=[entry], commit_index=0)
    response = await server._on_append(stale)
    assert response.success is False and response.term == 5
    assert server.metrics.histogram("append_batch_entries").count == 0
    response = await server._on_append(msg.AppendRequest(
        term=3, leader=peer, prev_index=0, prev_term=0, entries=[],
        commit_index=0))
    assert response.success is False
    assert server.metrics.counter("append_heartbeats").value == 0

    # a CURRENT-term append still records (and a heartbeat still counts)
    fresh_entry = NoOpEntry(term=5, timestamp=0.0)
    fresh_entry.index = 1
    await server._on_append(msg.AppendRequest(
        term=5, leader=peer, prev_index=0, prev_term=0,
        entries=[fresh_entry], commit_index=0))
    assert server.metrics.histogram("append_batch_entries").count == 1
    await server._on_append(msg.AppendRequest(
        term=5, leader=peer, prev_index=1, prev_term=5, entries=[],
        commit_index=0))
    assert server.metrics.counter("append_heartbeats").value == 1
    if server._election_timer is not None:
        server._election_timer.cancel()
