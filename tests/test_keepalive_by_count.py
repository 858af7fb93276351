"""A session acknowledges its replies by count as well as by time
(``client/client.py``: ``_KEEPALIVE_REPLIES``, ``_replies_resolved``): the
server's response cache, which every snapshot image carries, holds that many
replies and what is in flight whatever the session's timeout; the timer's
keep-alive stays; a command above the acknowledged prefix is still answered
from the cache after an early keep-alive pruned the ones below it."""

import asyncio

import pytest

from helpers import async_test
from raft_fixtures import KVStateMachine, Put, create_cluster

from copycat_tpu.client import client as client_mod
from copycat_tpu.protocol import messages as msg
from copycat_tpu.server.log import Storage, StorageLevel

N = 64          # _KEEPALIVE_REPLIES for these tests
BATCH = 16      # commands in flight at a time


@pytest.fixture(autouse=True)
def _small_n(monkeypatch):
    monkeypatch.setattr(client_mod, "_KEEPALIVE_REPLIES", N)


async def member(storage=None):
    """One member and one session with a 120 s timeout: the timer's
    keep-alive comes every 30 s, so never inside a test."""
    cluster = await create_cluster(1, KVStateMachine, session_timeout=120.0,
                                   storage=storage)
    client = await cluster.client(session_timeout=120.0)
    (session,) = cluster.servers[0].sessions.values()
    return cluster, client, session


async def complete(client, count, start=0):
    """``count`` commands, ``BATCH`` in flight at a time; their replies."""
    replies = []
    for at in range(start, start + count, BATCH):
        replies += await asyncio.gather(*(
            client.submit(Put(key=i % 8, value=i))
            for i in range(at, min(at + BATCH, start + count))))
    return replies


async def settled(client):
    if client._early_keepalive is not None:
        await client._early_keepalive


def early(client):
    return client.metrics.counter("keepalives_early").value


def hold_keepalives(client):
    """Keep-alives wait for the returned event; what they were is listed."""
    gate, sent, real = asyncio.Event(), [], client._request

    async def request(request, *args, **kwargs):
        if isinstance(request, msg.KeepAliveRequest):
            sent.append(request)
            await gate.wait()
        return await real(request, *args, **kwargs)

    client._request = request
    return gate, sent


@async_test(timeout=30)
async def test_three_n_commands_leave_n_and_what_is_in_flight_cached():
    cluster, client, session = await member()
    try:
        peak = 0
        for at in range(0, 3 * N, BATCH):
            await complete(client, BATCH, at)
            peak = max(peak, len(session.responses))
        await settled(client)
        assert early(client) in (2, 3)
        # a keep-alive is one committed entry behind the replies that
        # tripped it: the batch in flight beside it is cached too
        assert peak <= N + 2 * BATCH
        assert len(session.responses) <= N + BATCH
        assert client._kept_alive_seq >= 2 * N
        gauge = cluster.servers[0].groups[0].metrics.gauge(
            "session.responses_cached").value
        assert 0 < gauge <= N + 2 * BATCH     # before the last prune
    finally:
        await cluster.close()


@async_test(timeout=30)
async def test_n_minus_one_commands_send_no_early_keepalive():
    cluster, client, session = await member()
    try:
        await complete(client, N - 1)
        assert early(client) == 0 and client._early_keepalive is None
        assert len(session.responses) == N - 1
        await complete(client, 1, N - 1)      # the Nth reply trips it
        await settled(client)
        assert early(client) == 1 and len(session.responses) == 0
    finally:
        await cluster.close()


@async_test(timeout=30)
async def test_no_second_keepalive_starts_while_one_is_in_flight():
    cluster, client, session = await member()
    try:
        gate, sent = hold_keepalives(client)
        await complete(client, 3 * N)
        await asyncio.sleep(0)
        assert early(client) == 1 and len(sent) == 1
        assert sent[0].command_seq == N
        assert len(session.responses) == 3 * N    # nothing said yet
        gate.set()
        await settled(client)
        assert client._kept_alive_seq == N
        assert len(session.responses) == 2 * N
        await complete(client, BATCH, 3 * N)      # the next frame asks again
        await settled(client)
        assert early(client) == 2 and len(sent) == 2
        assert sent[1].command_seq == 3 * N + BATCH
        assert len(session.responses) == 0
    finally:
        await cluster.close()


@async_test(timeout=30)
async def test_the_timer_still_keeps_an_idle_session_alive():
    cluster = await create_cluster(1, KVStateMachine, session_timeout=2.0)
    try:
        client = await cluster.client(session_timeout=2.0)
        (session,) = cluster.servers[0].sessions.values()
        gate, sent = hold_keepalives(client)
        gate.set()                          # listed, not held
        await asyncio.sleep(1.3)            # two ticks of timeout / 4
        assert len(sent) >= 2 and early(client) == 0
        assert session.is_open and client.session().is_open
    finally:
        await cluster.close()


@async_test(timeout=30)
async def test_a_resubmitted_command_above_the_prefix_is_answered_from_the_cache():
    cluster, client, session = await member()
    try:
        replies = await complete(client, N + BATCH)
        await settled(client)
        # the early keep-alive went with the Nth reply's frame
        assert early(client) == 1 and client._kept_alive_seq == N
        assert sorted(session.responses) == list(range(N + 1, N + BATCH + 1))
        machine = cluster.servers[0].state_machine
        applied = machine.applied_ops
        seq = N + 3                                # completed, not yet said
        again = await client._request(msg.CommandRequest(
            session_id=client.session().id, seq=seq,
            operation=Put(key=0, value="twice")))
        assert again.error is None and again.result == replies[seq - 1]
        assert machine.applied_ops == applied      # exactly once
        pruned = await client._request(msg.CommandRequest(
            session_id=client.session().id, seq=N,
            operation=Put(key=0, value="twice")))
        assert pruned.error == msg.INTERNAL and machine.applied_ops == applied
    finally:
        await cluster.close()


#: between what an image of this traffic measures with N replies cached (928
#: bytes after 1 x N commands, 1,132 after 4 x N, whose indices and values
#: are wider) and with every one of 4 x N + BATCH (3,424)
IMAGE_LIMIT = 2048


@pytest.mark.parametrize("times, counted", [(1, True), (4, True), (4, False)],
                         ids=["1xN", "4xN", "4xN-by-time-alone"])
@async_test(timeout=30)
async def test_the_serialised_image_does_not_grow_with_the_commands_completed(
        tmp_path, monkeypatch, times, counted):
    """A durable member's capture after 1 x N and after 4 x N completed
    commands is the same few replies long; acknowledged by time alone (the
    count out of reach) the second holds every reply and passes the limit."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", str(BATCH))
    if not counted:
        monkeypatch.setattr(client_mod, "_KEEPALIVE_REPLIES", 1 << 30)
    cluster, client, session = await member(
        Storage(StorageLevel.DISK, str(tmp_path / "m0")))
    try:
        await complete(client, times * N)
        await settled(client)
        await complete(client, BATCH, times * N)   # a capture after the prune
        server = cluster.servers[0]
        await server.snapshots_settled()
        group = server.groups[0]
        # the newest capture: within two cadences of the last entry (one
        # in flight, and the one that fell due meanwhile waits for the
        # next apply)
        index, payload = group._snapshots.newest()
        assert index > times * N - 2 * BATCH
        image = group._snap_serializer.read(payload)
        (cached,) = [len(s["responses"]) for s in image["sessions"]]
        if counted:
            assert cached <= N + 2 * BATCH and len(payload) < IMAGE_LIMIT
        else:
            assert cached > 3 * N and len(payload) > IMAGE_LIMIT
    finally:
        await cluster.close()


@async_test(timeout=120)
async def test_an_early_keepalive_costs_the_served_path_no_round_and_no_lane():
    """``AtomixServer(executor="tpu")``: a cohort of eight counters, one add
    each a turn, across several early keep-alives. Every block still goes as
    one append block (the fast lane) and rides ONE engine round: the
    ``KeepAliveEntry`` lies between two blocks in the log, so it bounds no
    staged run and splits none."""
    pytest.importorskip("jax")
    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from engines import G, SERVED
    from raft_fixtures import next_ports

    registry = LocalServerRegistry()
    (addr,) = next_ports(1)
    server = AtomixServer(addr, [addr],
                          LocalTransport(registry, local_address=addr),
                          session_timeout=120.0, executor="tpu",
                          engine_config=SERVED)
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=120.0)
    await client.open()
    try:
        ctrs = await asyncio.gather(*(
            client.get(f"ctr{i}", DistributedAtomicLong) for i in range(G)))
        raft = server.server
        rounds = raft.state_machine.device_engine._groups.metrics.counter(
            "rounds")
        fast = raft.metrics.counter("commands_fast_lane")
        general = raft.metrics.counter("commands_general_lane")
        await asyncio.gather(*(c.add_and_get(1) for c in ctrs))   # warm
        turns = 3 * N // G
        was = rounds.value, fast.value, general.value
        early_was = early(client.client)
        for turn in range(turns):
            got = await asyncio.gather(*(c.add_and_get(1) for c in ctrs))
            assert got == [turn + 2] * G
        await settled(client.client)
        assert early(client.client) - early_was in (2, 3)
        assert rounds.value - was[0] == turns
        assert fast.value - was[1] == turns * G
        assert general.value == was[2]
        (session,) = raft.sessions.values()
        assert len(session.responses) <= N + G
    finally:
        await client.close()
        await server.close()
