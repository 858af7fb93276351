"""Host-stack fault injection: partitions, asymmetric blocks, message
loss over the asyncio Raft (VERDICT r4 #3).

The device plane has first-class ``deliver`` masks; until round 5 the
HOST stack (``server/raft.py`` + SPI) was only ever killed cleanly. The
reference's pyramid runs real consensus over a controllable fake network
(``AbstractServerTest.java:53-57``) and claims Jepsen testing
(``README.md:8``) — these tests drive the same envelope through
``io/local.NetworkNemesis``: the stale-leader lease-read hunt the round-4
verdict called the weakest correctness evidence in the tree, plus a
partition/loss soak asserting convergence and exactly-once apply.
"""

import asyncio

import pytest

from helpers import async_test
from raft_fixtures import (
    BoundedGet,
    Cluster,
    Get,
    KVStateMachine,
    Put,
    create_cluster,
)

from copycat_tpu.client.client import RaftClient
from copycat_tpu.protocol.operations import QueryConsistency
from copycat_tpu.io.local import (
    LocalServerRegistry,
    LocalTransport,
    NetworkNemesis,
)
from copycat_tpu.io.transport import Address, TransportError
from copycat_tpu.server.raft import FOLLOWER, LEADER


# ---------------------------------------------------------------------------
# transport-level semantics
# ---------------------------------------------------------------------------


@async_test
async def test_transport_fault_semantics():
    """Partition blocks both ways; block() is one-directional; response
    loss runs the handler; heal restores everything."""
    registry = LocalServerRegistry()
    nem = registry.attach_nemesis()
    a, b = Address("local", 1), Address("local", 2)
    handled = []

    async def serve(addr):
        server = LocalTransport(registry).server()

        def on_connect(conn):
            async def handle(m):
                handled.append((addr.port, m.key))
                return m.value

            conn.handler(Put, handle)

        await server.listen(addr, on_connect)
        return server

    sa, sb = await serve(a), await serve(b)
    ca = await LocalTransport(registry, local_address=a).client().connect(b)
    cb = await LocalTransport(registry, local_address=b).client().connect(a)
    assert await ca.send(Put(key="x", value=1)) == 1

    nem.partition([a], [b])
    with pytest.raises(TransportError):
        await ca.send(Put(key="y", value=2))
    with pytest.raises(TransportError):
        await cb.send(Put(key="z", value=3))
    # a partitioned dial is refused too
    with pytest.raises(TransportError):
        await LocalTransport(registry, local_address=a).client().connect(b)
    # anonymous clients reach every side (Jepsen client model)
    anon = await LocalTransport(registry).client().connect(b)
    assert await anon.send(Put(key="w", value=4)) == 4
    nem.heal()
    assert await ca.send(Put(key="y", value=2)) == 2

    # asymmetric: cut only the b -> a response direction; a's REQUESTS
    # still run b's handler but a never learns the outcome
    n_handled = len(handled)
    nem.block(b, a)
    with pytest.raises(TransportError, match="response"):
        await ca.send(Put(key="q", value=5))
    assert len(handled) == n_handled + 1  # handler ran; reply was lost
    with pytest.raises(TransportError, match="request"):
        await cb.send(Put(key="r", value=6))  # b -> a request leg is cut
    nem.heal()

    # probabilistic loss: with request loss 1.0 nothing gets through
    nem.set_loss(request=1.0)
    with pytest.raises(TransportError):
        await ca.send(Put(key="s", value=7))
    nem.heal()
    assert await ca.send(Put(key="s", value=7)) == 7

    # delay: a fixed floor is actually paid per message, and
    # set_delay(x) means "exactly x" (the round-5 review fixed the
    # min-without-max silent-zero footgun)
    nem.set_delay(0.02)
    t0 = asyncio.get_running_loop().time()
    await ca.send(Put(key="t", value=8))
    assert asyncio.get_running_loop().time() - t0 >= 0.02
    with pytest.raises(ValueError):
        nem.set_delay(0.01, 0.005)   # reversed bounds refuse loudly
    nem.heal()
    assert nem.delivered > 0
    await sa.close()
    await sb.close()


# ---------------------------------------------------------------------------
# stale-leader lease reads (the round-4 hunt target)
# ---------------------------------------------------------------------------


#: generous sessions throughout this module: partitions deliberately
#: starve keep-alives, and a session expiring mid-choreography turns a
#: lease/soak check into a SessionExpiredError timing flake
SESSION_T = 30.0


async def _nemesis_cluster(n=3, **kwargs) -> tuple[Cluster, NetworkNemesis]:
    kwargs.setdefault("session_timeout", SESSION_T)
    cluster = await create_cluster(n, **kwargs)
    nem = cluster.registry.attach_nemesis()
    return cluster, nem


@async_test(timeout=120)
async def test_stale_leader_refuses_lease_read_under_asymmetric_partition():
    """The nastiest lease trap: the leader can still SEND heartbeats
    (followers stay followers — no new election) but the ack direction
    is cut, so its lease silently expires. A BOUNDED_LINEARIZABLE read
    at that leader MUST be refused, not served from stale lease state
    (``server/raft.py`` ``_lease_valid``/``_gate_query``)."""
    cluster, nem = await _nemesis_cluster()
    try:
        leader = await cluster.await_leader()
        client = await cluster.client(session_timeout=SESSION_T)
        assert await client.submit(Put(key="k", value=1)) is None
        # lease-read sanity while healthy
        assert await client.submit(BoundedGet(key="k")) == 1

        # cut every ack path TO the leader (peer->leader direction only)
        for s in cluster.servers:
            if s is not leader:
                nem.block(s.address, leader.address)
        # wait out the lease window: no successful quorum round-trips
        await asyncio.sleep(leader.election_timeout * 2.5)
        assert leader.role == LEADER, "one-way heartbeats should keep peers"
        assert not leader._lease_valid(), "lease must expire without acks"
        # a lease read at the stale leader must REFUSE (NOT_LEADER path
        # after the failed leadership confirmation), never serve stale
        refused = await leader._gate_query(
            QueryConsistency.BOUNDED_LINEARIZABLE, 0)
        assert refused is not None, \
            "stale leader served a lease read with an expired lease"
        nem.heal()
        # after heal the lease re-arms and lease reads serve again
        deadline = asyncio.get_running_loop().time() + 10
        while asyncio.get_running_loop().time() < deadline:
            if (await leader._gate_query(
                    QueryConsistency.BOUNDED_LINEARIZABLE, 0)) is None:
                break
            await asyncio.sleep(0.05)
        assert await client.submit(BoundedGet(key="k")) == 1
    finally:
        await cluster.close()


@async_test(timeout=120)
async def test_majority_progress_and_stale_leader_refusal_symmetric():
    """Symmetric partition: {leader} | {majority}. The majority elects,
    commits NEW writes; the old leader still in its lease window must
    not serve a lease read with the OLD value once its lease lapses."""
    cluster, nem = await _nemesis_cluster()
    try:
        old = await cluster.await_leader()
        client = await cluster.client(session_timeout=SESSION_T)
        assert await client.submit(Put(key="k", value=1)) is None

        minority = [old.address]
        majority = [s.address for s in cluster.servers if s is not old]
        nem.partition(minority, majority)

        # majority side elects (awaited, its follower's word of it too: a
        # client that opens sooner is hinted to the old leader, which
        # takes the register, never answers, and holds it for a whole
        # SESSION_T) and commits a NEWER value
        for _ in range(500):
            if all(s.leader_address in majority
                   for s in cluster.servers if s is not old):
                break
            await asyncio.sleep(0.02)
        maj_client = RaftClient(majority, LocalTransport(cluster.registry),
                                session_timeout=SESSION_T)
        await maj_client.open()
        cluster.clients.append(maj_client)
        assert await asyncio.wait_for(
            maj_client.submit(Put(key="k", value=2)), 30) == 1
        new_leader = next(s for s in cluster.servers
                          if s is not old and s.role == LEADER)
        assert new_leader.term > old.term

        # the deposed leader's lease is stale; once it lapses a lease
        # read must refuse rather than return k=1
        await asyncio.sleep(old.election_timeout * 2.5)
        if old.role == LEADER:  # it can't learn of the new term yet
            refused = await old._gate_query(
                QueryConsistency.BOUNDED_LINEARIZABLE, 0)
            assert refused is not None, \
                "deposed leader served a stale lease read"

        nem.heal()
        # healed: old leader steps down and converges to k=2
        deadline = asyncio.get_running_loop().time() + 15
        while asyncio.get_running_loop().time() < deadline:
            if old.role == FOLLOWER and \
                    old.state_machine.data.get("k") == 2:
                break
            await asyncio.sleep(0.05)
        assert old.role == FOLLOWER
        assert old.state_machine.data.get("k") == 2
        assert await client.submit(BoundedGet(key="k")) == 2
    finally:
        await cluster.close()


@async_test(timeout=120)
async def test_command_at_a_partitioned_leader_reroutes_after_one_timeout(
        monkeypatch):
    """A partitioned leader keeps its role and its connections: it
    takes the command, cannot commit it and never answers, and
    ``LocalConnection.close`` fails no send in flight, so the command
    waits out one per-try timeout (the session timeout). The keep-alive
    gives up sooner and finds the new leader. The command's retry must
    ride that connection, neither drop it nor dial the dead member
    first: it did both, one time in three (here every time: the shuffle
    is held still with the old leader in front), and stalled a second
    timeout (ROADMAP Queue 3 item 1, the edge-reads nemesis test's red)."""
    import random

    T = 4.0
    cluster, nem = await _nemesis_cluster()
    try:
        old = await cluster.await_leader()
        others = [s.address for s in cluster.servers if s is not old]
        client = RaftClient([old.address, *others],
                            LocalTransport(cluster.registry),
                            session_timeout=T)
        monkeypatch.setattr(random, "shuffle", lambda members: None)
        await client.open()
        cluster.clients.append(client)
        assert await client.submit(Put(key="k", value=1)) is None
        assert client._connected_to == old.address
        nem.partition([old.address], others)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        assert await asyncio.wait_for(
            client.submit(Put(key="k", value=2)), 3 * T) == 1
        assert loop.time() - t0 < T + 2.0, "more than one per-try timeout"
        assert client._connected_to not in (None, old.address)
        assert client._failed_last == old.address
    finally:
        nem.heal()
        await cluster.close()


# ---------------------------------------------------------------------------
# partition + loss soak: convergence and exactly-once apply
# ---------------------------------------------------------------------------


# A command in flight at a leader that is cut off or deposed is never
# answered (ROADMAP Queue 3 item 1) and waits out SESSION_T, so the soak
# takes 30 s a disturbance that catches one: 5 to 278 s over thirty runs
# of the 30 writes (three partitions), at the parent as now. Tier-1 runs
# one partition's worth, the same schedule cut at its first heal; the
# three rotations stay behind ``-m slow``.
@pytest.mark.parametrize(
    "n_puts", [10, pytest.param(30, marks=pytest.mark.slow)])
@async_test(timeout=480)
async def test_soak_partitions_and_loss_exactly_once(n_puts):
    """``n_puts`` acked writes through rolling partitions + 15%/10%
    message loss + 0-3ms delays. After heal: every server applied each
    committed command EXACTLY once (the session dedup surviving lost
    responses) and all logs converge to the same final state."""
    # generous session timeout: under full-suite load the event loop can
    # starve keep-alives for seconds, and an expiry mid-soak fails the
    # run with SessionExpiredError — a timing artifact, not a finding
    cluster, nem = await _nemesis_cluster(
        session_timeout=SESSION_T)
    try:
        await cluster.await_leader()
        client = await cluster.client(session_timeout=SESSION_T)
        nem.set_loss(request=0.15, response=0.10)
        nem.set_delay(0.0, 0.003)

        addrs = [s.address for s in cluster.servers]
        for i in range(n_puts):
            if i % 10 == 3:
                # rotate a symmetric minority partition mid-stream
                loner = addrs[(i // 10) % len(addrs)]
                nem.partition([loner], [a for a in addrs if a != loner])
            elif i % 10 == 8:
                nem.partition()  # heal partition, keep loss+delay
            # generous per-op cap: under rotating partitions + 15% loss,
            # elections can thrash for tens of seconds (split votes with
            # lost RequestVotes) before a commit lands — slowness here is
            # the nemesis working, not a failure
            await asyncio.wait_for(
                client.submit(Put(key="n", value=i)), 150)

        nem.heal()
        # convergence: all servers apply all n_puts puts exactly once
        deadline = asyncio.get_running_loop().time() + 30
        while asyncio.get_running_loop().time() < deadline:
            if all(s.state_machine.applied_ops >= n_puts
                   and s.state_machine.data.get("n") == n_puts - 1
                   for s in cluster.servers):
                break
            await asyncio.sleep(0.1)
        for s in cluster.servers:
            assert s.state_machine.data.get("n") == n_puts - 1, \
                f"{s.address} did not converge"
            assert s.state_machine.applied_ops == n_puts, \
                (f"{s.address} applied {s.state_machine.applied_ops} != "
                 f"{n_puts}: double- or missed apply under loss")
        # the nemesis actually did something
        assert nem.dropped_requests + nem.dropped_responses > 0
        assert await client.submit(Get(key="n")) == n_puts - 1
    finally:
        await cluster.close()
