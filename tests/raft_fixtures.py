"""Shared Raft test fixtures: a tiny KV state machine + cluster builders.

Mirrors the reference's test strategy (SURVEY.md §4): real N-server consensus
over the in-memory transport, tiny inline state machines, no mocks.
"""

from __future__ import annotations

import asyncio
from typing import Any

import pytest

from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
from copycat_tpu.io.transport import Address
from copycat_tpu.io.serializer import serialize_with
from copycat_tpu.protocol.messages import Message
from copycat_tpu.protocol.operations import Command, Query
from copycat_tpu.server.log import Storage, StorageLevel
from copycat_tpu.server.raft import LEADER, RaftServer

#: the replication stream at its default depth and at its edge of ONE
#: window in flight (the shape stop-and-wait replication has); the test
#: sets ``COPYCAT_REPL_DEPTH`` to ``depth`` before it builds its cluster
REPL_DEPTHS = pytest.mark.parametrize("depth", ("8", "1"),
                                      ids=("depth8", "depth1"))
from copycat_tpu.server.state_machine import (
    Commit,
    SnapshotCut,
    StateMachine,
)
from copycat_tpu.client.client import RaftClient


@serialize_with(910)
class Put(Message, Command):
    _fields = ("key", "value")


@serialize_with(911)
class Get(Message, Query):
    _fields = ("key",)


@serialize_with(916)
class SeqGet(Get):
    def consistency(self):
        from copycat_tpu.protocol.operations import QueryConsistency

        return QueryConsistency.SEQUENTIAL


@serialize_with(917)
class BoundedGet(Get):
    def consistency(self):
        from copycat_tpu.protocol.operations import QueryConsistency

        return QueryConsistency.BOUNDED_LINEARIZABLE


@serialize_with(912)
class Notify(Message, Command):
    """Publishes an event back to the submitting session."""

    _fields = ("payload",)


@serialize_with(913)
class Fail(Message, Command):
    """Always raises inside the state machine."""

    _fields = ()


@serialize_with(914)
class PutTtl(Message, Command):
    _fields = ("key", "value", "ttl")


@serialize_with(915)
class Count(Message, Query):
    _fields = ()


class KVStateMachine(StateMachine):
    """Inline test machine exercising auto-registration, events, timers,
    and the crash-recovery plane's snapshot hooks (docs/DURABILITY.md):
    pending TTL deadlines are part of the snapshot image and re-scheduled
    on restore, so a recovered member expires keys at the same log time a
    never-crashed member does."""

    def __init__(self) -> None:
        super().__init__()
        self.data: dict[Any, Any] = {}
        self.applied_ops = 0
        self.expired_sessions: list[int] = []
        self.closed_sessions: list[int] = []
        self.ttl_deadlines: dict[Any, float] = {}  # key -> log-clock deadline

    def put(self, commit: Commit[Put]) -> Any:
        self.applied_ops += 1
        old = self.data.get(commit.operation.key)
        self.data[commit.operation.key] = commit.operation.value
        return old

    def put_ttl(self, commit: Commit[PutTtl]) -> Any:
        self.applied_ops += 1
        op = commit.operation
        old = self.data.get(op.key)
        self.data[op.key] = op.value
        key = op.key
        self.ttl_deadlines[key] = commit.time + op.ttl

        def expire() -> None:
            self.data.pop(key, None)
            self.ttl_deadlines.pop(key, None)
            commit.clean()

        self.executor.schedule(op.ttl, expire)
        return old

    # -- snapshot hooks ----------------------------------------------------

    def snapshot_state(self) -> Any:
        return {"data": dict(self.data),
                "applied_ops": self.applied_ops,
                "expired": list(self.expired_sessions),
                "closed": list(self.closed_sessions),
                "ttl": dict(self.ttl_deadlines)}

    def restore_state(self, data: Any, sessions: dict) -> None:
        self.data = dict(data["data"])
        self.applied_ops = data["applied_ops"]
        self.expired_sessions = list(data["expired"])
        self.closed_sessions = list(data["closed"])
        self.ttl_deadlines = dict(data["ttl"])
        clock = self.executor.context.clock
        for key, deadline in list(self.ttl_deadlines.items()):
            def expire(_key=key) -> None:
                # the creating commit is behind the snapshot boundary —
                # its log entry is already released, nothing to clean()
                self.data.pop(_key, None)
                self.ttl_deadlines.pop(_key, None)

            self.executor.schedule(max(0.0, deadline - clock), expire)

    def get(self, commit: Commit[Get]) -> Any:
        return self.data.get(commit.operation.key)

    def count(self, commit: Commit[Count]) -> int:
        return len(self.data)

    def notify(self, commit: Commit[Notify]) -> str:
        commit.session.publish("poked", commit.operation.payload)
        commit.clean()
        return "notified"

    def fail(self, commit: Commit[Fail]) -> None:
        commit.clean()
        raise ValueError("deliberate failure")

    def expire(self, session: Any) -> None:
        self.expired_sessions.append(session.id)

    def close(self, session: Any) -> None:
        self.closed_sessions.append(session.id)


def _norm(obj: Any) -> Any:
    """Order-insensitive canonical form for dict-shaped state (dict
    insertion order is an implementation detail, not replicated state)."""
    if isinstance(obj, dict):
        return tuple(sorted((repr(k), _norm(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_norm(x) for x in obj)
    if isinstance(obj, set):
        return tuple(sorted(repr(x) for x in obj))
    return repr(obj)


def server_fingerprint(server: RaftServer, from_index: int | None = None):
    """Bit-comparable image of a server's replicated state — the
    recovery differential's equality subject: serialized log entries
    (from ``from_index``, so a prefix-truncated recovered member compares
    over the shared range), the state machine's snapshot image, and the
    session table's replicated halves."""
    from copycat_tpu.io.serializer import Serializer

    ser = Serializer()
    log = server.log
    start = log.first_index if from_index is None else max(
        log.first_index, from_index)
    entries = []
    for i in range(start, log.last_index + 1):
        e = log.get(i)
        entries.append(None if e is None else ser.write(e))
    machine = server.state_machine.snapshot_state()
    if isinstance(machine, SnapshotCut):
        machine = machine.finish()
    sessions = sorted(
        (sid, _norm(s.snapshot_dict())) for sid, s in server.sessions.items())
    return {
        "log_start": start,
        "log_last": log.last_index,
        "log": entries,
        "machine": None if machine is NotImplemented else _norm(machine),
        "sessions": sessions,
        "last_applied": server.last_applied,
        "clock": server.context.clock,
    }


_port_counter = [6000]


def next_ports(n: int) -> list[Address]:
    base = _port_counter[0]
    _port_counter[0] += n
    return [Address("local", base + i) for i in range(n)]


class Cluster:
    def __init__(self, servers: list[RaftServer], registry: LocalServerRegistry):
        self.servers = servers
        self.registry = registry
        self.clients: list[RaftClient] = []

    @property
    def leader(self) -> RaftServer | None:
        for server in self.servers:
            if server.is_open and server.role == LEADER:
                return server
        return None

    async def await_leader(self, timeout: float = 10.0) -> RaftServer:
        deadline = asyncio.get_running_loop().time() + timeout
        while asyncio.get_running_loop().time() < deadline:
            leader = self.leader
            # Require a stable leader whose term is seen by a quorum
            if leader is not None:
                return leader
            await asyncio.sleep(0.02)
        raise TimeoutError("no leader elected")

    async def client(self, session_timeout: float = 2.0) -> RaftClient:
        client = RaftClient(
            [s.address for s in self.servers],
            LocalTransport(self.registry),
            session_timeout=session_timeout,
        )
        await client.open()
        self.clients.append(client)
        return client

    async def close(self) -> None:
        for client in self.clients:
            try:
                await asyncio.wait_for(client.close(), 5)
            except (Exception, asyncio.TimeoutError):
                pass
        for server in self.servers:
            try:
                await asyncio.wait_for(server.close(), 5)
            except (Exception, asyncio.TimeoutError):
                pass


async def create_cluster(
    n: int = 3,
    machine_factory=KVStateMachine,
    election_timeout: float = 0.2,
    heartbeat_interval: float = 0.04,
    session_timeout: float = 2.0,
    storage: Storage | None = None,
    storage_factory=None,
) -> Cluster:
    registry = LocalServerRegistry()
    addresses = next_ports(n)
    servers = []
    for i, addr in enumerate(addresses):
        store = storage_factory(i) if storage_factory else (storage or Storage(StorageLevel.MEMORY))
        servers.append(
            RaftServer(
                addr,
                addresses,
                # local_address identifies this server's DIALS to the
                # nemesis (partition membership for peer connections)
                LocalTransport(registry, local_address=addr),
                machine_factory(),
                storage=store,
                election_timeout=election_timeout,
                heartbeat_interval=heartbeat_interval,
                session_timeout=session_timeout,
            )
        )
    await asyncio.gather(*(s.open() for s in servers))
    cluster = Cluster(servers, registry)
    await cluster.await_leader()
    return cluster
