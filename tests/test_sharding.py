"""Multi-raft keyspace sharding (docs/SHARDING.md): hash-routing
stability, leadership spread, the proxy ingress, per-group event
channels, and the single-group differential.

The load-bearing contracts:

- resource→group assignment is a pure function of (key, group count) —
  deterministic across restarts and IDENTICAL on every member (a member
  disagreeing about ownership would apply a command to the wrong shard);
- session events route back from the OWNING group's replicated apply on
  the ingress member, each group numbering its own event channel;
- ``--groups 1`` / ``COPYCAT_MULTI_GROUP=0`` IS the pre-refactor
  single-group plane: same logs, same command stream, same responses.
"""

from __future__ import annotations

import asyncio
import zlib

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.client.client import PinnedConnectionStrategy, RaftClient  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.io.serializer import serialize_with  # noqa: E402
from copycat_tpu.io.transport import Address  # noqa: E402
from copycat_tpu.manager.operations import (  # noqa: E402
    GetResource,
    InstanceCommand,
)
from copycat_tpu.manager.state import ResourceManager  # noqa: E402
from copycat_tpu.protocol.messages import Message  # noqa: E402
from copycat_tpu.protocol.operations import Command  # noqa: E402
from copycat_tpu.server.raft import LEADER, RaftServer  # noqa: E402
from copycat_tpu.server.state_machine import Commit  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import Get, KVStateMachine, Put, SeqGet, next_ports  # noqa: E402


@serialize_with(930)
class NotifyKey(Message, Command):
    """Publishes an event from the group OWNING ``key``."""

    _fields = ("key", "payload")


class ShardedKV(KVStateMachine):
    """KV fixture with stable hash routing (the bench/test shard shape)."""

    def configure(self, executor) -> None:
        super().configure(executor)
        executor.register(NotifyKey, self.notify_key)

    def notify_key(self, commit: "Commit") -> str:
        commit.session.publish(
            "poked", (commit.operation.key, commit.operation.payload))
        return commit.operation.key

    @classmethod
    def route_group(cls, operation, groups: int) -> int:
        key = getattr(operation, "key", None)
        if isinstance(key, str):
            return zlib.crc32(key.encode()) % groups
        return 0


async def sharded_cluster(n: int = 3, groups: int = 4,
                          machine_cls=ShardedKV,
                          session_timeout: float = 30.0):
    registry = LocalServerRegistry()
    addresses = next_ports(n)
    servers = [
        RaftServer(addr, addresses,
                   LocalTransport(registry, local_address=addr),
                   (lambda g: machine_cls()), groups=groups,
                   election_timeout=0.2, heartbeat_interval=0.04,
                   session_timeout=session_timeout)
        for addr in addresses]
    await asyncio.gather(*(s.open() for s in servers))
    deadline = asyncio.get_running_loop().time() + 15
    while asyncio.get_running_loop().time() < deadline:
        led = {g.group_id for s in servers for g in s.groups
               if g.role == LEADER}
        if len(led) == groups:
            return registry, servers
        await asyncio.sleep(0.02)
    raise TimeoutError("not every group elected a leader")


async def close_all(servers, *clients) -> None:
    for c in clients:
        try:
            await asyncio.wait_for(c.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass
    for s in servers:
        try:
            await asyncio.wait_for(s.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass


# ---------------------------------------------------------------------------
# hash-routing stability
# ---------------------------------------------------------------------------


def test_route_group_is_deterministic_and_restart_stable():
    """The routing function is pure: same (operation, groups) -> same
    group on every call, every instance, every 'process' — it must never
    depend on object identity, dict order, or PYTHONHASHSEED (which is
    why it is crc32, not hash())."""
    keys = [f"resource-{i}" for i in range(100)]
    for groups in (1, 2, 4, 7):
        first = [ResourceManager.route_group(GetResource(k, None), groups)
                 for k in keys]
        again = [ResourceManager.route_group(GetResource(k, None), groups)
                 for k in keys]
        assert first == again
        expected = [zlib.crc32(k.encode()) % groups for k in keys]
        assert first == expected
        assert all(0 <= g < groups for g in first)
    # instance ops are self-routing: ids carry their group residue
    for groups in (2, 4):
        for raw_index in (3, 10, 57):
            for g in range(groups):
                iid = raw_index * groups + g
                assert ResourceManager.route_group(
                    InstanceCommand(resource=iid, operation=None),
                    groups) == g


def test_manager_ids_are_group_stamped_and_unsharded_identity():
    mgr = ResourceManager(group_id=3, num_groups=4)
    assert mgr.num_groups == 4 and mgr.group_id == 3
    # the id a commit at index 7 would mint: 7*4+3 — residue = group
    assert (7 * 4 + 3) % 4 == 3
    # single-group managers mint raw indices (the pre-sharding ids)
    plain = ResourceManager()
    assert plain.num_groups == 1 and plain.group_id == 0


@async_test(timeout=120)
async def test_resource_placement_identical_on_every_member():
    """Create resources across the keyspace through the public API, then
    assert every member placed every key in the SAME group — the group
    the routing function names — including followers (placement is
    replicated state, not an ingress-local choice)."""
    from copycat_tpu.atomic import DistributedAtomicLong

    registry = LocalServerRegistry()
    addresses = next_ports(3)
    groups = 4
    servers = [
        RaftServer(addr, addresses,
                   LocalTransport(registry, local_address=addr),
                   (lambda g: ResourceManager(group_id=g,
                                              num_groups=groups)),
                   groups=groups,
                   election_timeout=0.2, heartbeat_interval=0.04,
                   session_timeout=30.0)
        for addr in addresses]
    await asyncio.gather(*(s.open() for s in servers))
    client = RaftClient(addresses, LocalTransport(registry),
                        session_timeout=30.0)
    keys = [f"counter-{i}" for i in range(12)]
    try:
        await client.open()
        from copycat_tpu.resource.resource import resource_state_machine_of
        machine = resource_state_machine_of(DistributedAtomicLong)
        for k in keys:
            iid = await client.submit(GetResource(k, machine))
            # id residue IS the owning group, and it matches the hash
            assert iid % groups == zlib.crc32(k.encode()) % groups
        # wait until every member applied every group's catalog writes
        deadline = asyncio.get_running_loop().time() + 20
        while asyncio.get_running_loop().time() < deadline:
            placements = [
                {k: g.group_id
                 for s in [srv] for g in s.groups
                 for k in g.state_machine.keys}
                for srv in servers]
            if all(len(p) == len(keys) for p in placements):
                break
            await asyncio.sleep(0.05)
        assert all(len(p) == len(keys) for p in placements), \
            [len(p) for p in placements]
        # identical on every member, and equal to the routing function
        assert placements[0] == placements[1] == placements[2]
        for k, g in placements[0].items():
            assert g == zlib.crc32(k.encode()) % groups, (k, g)
    finally:
        await close_all(servers, client)


# ---------------------------------------------------------------------------
# leadership spread
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_leadership_spreads_across_members_at_boot():
    registry, servers = await sharded_cluster(n=3, groups=6)
    try:
        led = {str(s.address): sum(1 for g in s.groups
                                   if g.role == LEADER)
               for s in servers}
        assert sum(led.values()) == 6
        # seed-spread: every member leads exactly G/N groups at boot
        assert sorted(led.values()) == [2, 2, 2], led
        # the stats surface says the same: the shard family and one
        # commit series a group ride every member's ``raft`` section
        for s in servers:
            raft = s.stats_snapshot()["raft"]
            assert raft["shard.groups"] == 6, sorted(raft)
            assert raft["shard.groups_led"] == led[str(s.address)]
            for g in range(6):
                assert f"raft_commit_index{{group={g}}}" in raft, g
        # and the preference is the deterministic one: group g's leader
        # is members[g % N] over the sorted member list
        ranked = sorted((s.address for s in servers),
                        key=lambda a: (a.host, a.port))
        for s in servers:
            for g in s.groups:
                if g.role == LEADER:
                    assert ranked[g.group_id % 3] == s.address
    finally:
        await close_all(servers)


# ---------------------------------------------------------------------------
# the proxy ingress + per-group event channels
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_commands_route_and_apply_exactly_once_via_any_ingress():
    """Pin a client to each member in turn: every member is a full
    ingress (local staging for groups it leads, proxy for the rest), and
    a key's increments land exactly once wherever they entered."""
    registry, servers = await sharded_cluster(n=3, groups=4)
    clients = []
    try:
        keys = [f"k{i}" for i in range(24)]
        for i, s in enumerate(servers):
            client = RaftClient(
                [x.address for x in servers], LocalTransport(registry),
                session_timeout=30.0,
                connection_strategy=PinnedConnectionStrategy(s.address))
            await client.open()
            clients.append(client)
            await asyncio.gather(*(
                client.submit_command_nowait(Put(key=k, value=(i, k)))
                for k in keys))
        # last writer wins per key: client 2's values
        got = await asyncio.gather(*(clients[0].submit(Get(key=k))
                                     for k in keys))
        assert [tuple(v) for v in got] == [(2, k) for k in keys], got
        # sequential reads agree (per-group client indices)
        seq = await asyncio.gather(*(clients[1].submit(SeqGet(key=k))
                                     for k in keys))
        assert [tuple(v) for v in seq] == [(2, k) for k in keys], seq
        # the proxy lane actually ran: with 4 groups over 3 members at
        # least one pinned ingress forwarded sub-blocks
        proxied = sum(s._metrics.counter("shard.commands_proxied").value
                      for s in servers)
        local = sum(s._metrics.counter("shard.commands_local").value
                    for s in servers)
        assert proxied > 0 and local > 0, (proxied, local)
    finally:
        await close_all(servers, *clients)


@async_test(timeout=120)
async def test_session_events_route_back_from_the_owning_group():
    """Events published by a group's apply reach the client through the
    ingress member's replica of THAT group — one independently numbered
    channel per group (the PublishRequest ``group`` field)."""
    registry, servers = await sharded_cluster(n=3, groups=4)
    client = RaftClient([s.address for s in servers],
                        LocalTransport(registry), session_timeout=30.0)
    try:
        await client.open()
        got: list = []
        client.session().on_event("poked", got.append)
        # pick keys covering EVERY group
        cover: dict[int, str] = {}
        i = 0
        while len(cover) < 4:
            k = f"evt{i}"
            cover.setdefault(zlib.crc32(k.encode()) % 4, k)
            i += 1
        for g, k in sorted(cover.items()):
            await client.submit(NotifyKey(key=k, payload=f"p{g}"))
        deadline = asyncio.get_running_loop().time() + 10
        while asyncio.get_running_loop().time() < deadline \
                and len(got) < 4:
            await asyncio.sleep(0.02)
        assert sorted(tuple(e) for e in got) == sorted(
            (k, f"p{g}") for g, k in cover.items()), got
        # each owning group advanced ITS channel exactly once
        idx = client.session()._event_indices
        assert {g: idx.get(g) for g in cover} == {g: 1 for g in cover}, idx
    finally:
        await close_all(servers, client)


# ---------------------------------------------------------------------------
# the single-group differential (the sharding A/B): COPYCAT_MULTI_GROUP=0
# / --groups 1 IS the pre-refactor plane
# ---------------------------------------------------------------------------


def _command_stream(server) -> list:
    """The applied command stream: (session_id, seq, op identity) in log
    order — the deterministic core the A/B compares (terms/timestamps
    are election-timing artifacts, deliberately excluded)."""
    from copycat_tpu.server.log import CommandEntry

    out = []
    log = server.log
    for index in range(max(1, log.first_index), log.last_index + 1):
        entry = log.get(index)
        if type(entry) is CommandEntry:
            op = entry.operation
            out.append((entry.session_id, entry.seq, type(op).__name__,
                        getattr(op, "key", None),
                        getattr(op, "value", None)))
    return out


def _entry_stream(server) -> list:
    """Full log identity including layout (entry types in order)."""
    log = server.log
    return [(type(log.get(i)).__name__ if log.get(i) is not None else None)
            for i in range(max(1, log.first_index), log.last_index + 1)]


async def _drive_single_plane(n_keys: int = 20):
    """One seeded sequential workload against a fresh 3-member cluster
    built from the CURRENT env (the caller pins the knobs); returns the
    (logs, state, stream) triple for comparison."""
    registry = LocalServerRegistry()
    addresses = next_ports(3)
    servers = [
        RaftServer(addr, addresses,
                   LocalTransport(registry, local_address=addr),
                   ShardedKV(),
                   election_timeout=0.2, heartbeat_interval=0.04,
                   session_timeout=60.0)
        for addr in addresses]
    await asyncio.gather(*(s.open() for s in servers))
    client = RaftClient(addresses, LocalTransport(registry),
                        session_timeout=60.0)
    try:
        await client.open()
        for i in range(n_keys):
            await client.submit(Put(key=f"d{i}", value=i))
        # convergence: every member applied everything
        leader = next(s for s in servers if s.role == LEADER)
        deadline = asyncio.get_running_loop().time() + 15
        while asyncio.get_running_loop().time() < deadline:
            if all(s.last_applied >= leader.commit_index
                   and s.log.last_index == leader.log.last_index
                   for s in servers):
                break
            await asyncio.sleep(0.02)
        return ([_command_stream(s) for s in servers],
                [dict(s.state_machine.data) for s in servers],
                [s.num_groups for s in servers],
                [s.log.name if hasattr(s.log, "name") else "" for s in servers])
    finally:
        await close_all(servers, client)


def test_multi_group_knob_off_is_the_single_group_plane(monkeypatch):
    """COPYCAT_GROUPS=4 + COPYCAT_MULTI_GROUP=0 builds EXACTLY the
    single-group plane: one group, unsuffixed log names, and the same
    command stream + applied state as an explicit groups=1 server for
    the same seeded workload."""

    @async_test(timeout=120)
    async def run_baseline():
        global _BASE
        _BASE = await _drive_single_plane()

    @async_test(timeout=120)
    async def run_knob_off():
        global _OFF
        _OFF = await _drive_single_plane()

    monkeypatch.delenv("COPYCAT_GROUPS", raising=False)
    monkeypatch.delenv("COPYCAT_MULTI_GROUP", raising=False)
    run_baseline()
    monkeypatch.setenv("COPYCAT_GROUPS", "4")
    monkeypatch.setenv("COPYCAT_MULTI_GROUP", "0")
    run_knob_off()
    base_streams, base_states, base_groups, _ = _BASE
    off_streams, off_states, off_groups, _ = _OFF
    assert off_groups == [1, 1, 1]  # the knob FORCED the single plane
    assert base_groups == [1, 1, 1]
    # cross-member identity within each run, and identity ACROSS runs
    assert base_streams[0] == base_streams[1] == base_streams[2]
    assert off_streams[0] == off_streams[1] == off_streams[2]
    assert base_streams[0] == off_streams[0]
    assert base_states == off_states


def test_single_plane_differential_under_nemesis_strict(monkeypatch):
    """The acceptance differential: the knob-forced single-group plane
    under nemesis (partition + leader deposition) with
    COPYCAT_INVARIANTS=strict — all members' logs converge
    bit-identically (serialized bytes), the applied command stream is
    exactly-once, and the strict commit-quorum tripwire never fired."""
    monkeypatch.setenv("COPYCAT_GROUPS", "4")
    monkeypatch.setenv("COPYCAT_MULTI_GROUP", "0")
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=240)
    async def run():
        from copycat_tpu.io.serializer import Serializer

        registry = LocalServerRegistry()
        addresses = next_ports(3)
        servers = [
            RaftServer(addr, addresses,
                       LocalTransport(registry, local_address=addr),
                       ShardedKV(),
                       election_timeout=0.2, heartbeat_interval=0.04,
                       session_timeout=60.0)
            for addr in addresses]
        await asyncio.gather(*(s.open() for s in servers))
        client = RaftClient(addresses, LocalTransport(registry),
                            session_timeout=60.0)
        try:
            await client.open()
            assert all(s.single and s.num_groups == 1 for s in servers)
            submitted = []
            for i in range(15):
                await client.submit(Put(key=f"n{i}", value=i))
                submitted.append((f"n{i}", i))
            # clean unregister, then depose the leader: partition it from
            # the other two; the majority elects and keeps committing
            # through a majority-scoped client (the nemesis idiom —
            # tests/test_nemesis_raft.py)
            await client.close()
            nem = registry.attach_nemesis()
            old_leader = next(s for s in servers if s.role == LEADER)
            majority = [s.address for s in servers if s is not old_leader]
            nem.partition([old_leader.address], majority)
            # wait for the majority to elect, and for its follower to
            # have heard of it, before registering: a follower still
            # hinting the OLD leader would route the register to an
            # uncommittable append (clients bypass the partition by
            # design), burning a whole per-try timeout (60 s, one run
            # in a few when only the election was awaited)
            deadline = asyncio.get_running_loop().time() + 15
            while asyncio.get_running_loop().time() < deadline:
                if all(s.leader_address in majority
                       for s in servers if s is not old_leader):
                    break
                await asyncio.sleep(0.05)
            assert any(s.role == LEADER and s is not old_leader
                       for s in servers), "majority never elected"
            maj_client = RaftClient(majority, LocalTransport(registry),
                                    session_timeout=60.0)
            await maj_client.open()
            try:
                for i in range(15, 30):
                    await asyncio.wait_for(
                        maj_client.submit(Put(key=f"n{i}", value=i)), 30)
                    submitted.append((f"n{i}", i))
            finally:
                nem.heal()
                await asyncio.wait_for(maj_client.close(), 10)
            # the deposed leader rejoins and truncates/reconverges
            deadline = asyncio.get_running_loop().time() + 20
            while asyncio.get_running_loop().time() < deadline:
                leader = next((s for s in servers if s.role == LEADER),
                              None)
                if leader is not None and all(
                        s.log.last_index == leader.log.last_index
                        and s.last_applied == leader.last_applied
                        for s in servers):
                    break
                await asyncio.sleep(0.05)
            # 1) bit-identical logs: serialized entry bytes per slot.
            # Compaction is member-LOCAL GC (cleaned noop/keepalive
            # slots release at each member's own pace), so a slot may
            # read None on one member and bytes on another — every
            # SURVIVING copy of a slot must be byte-identical, and the
            # tails must agree.
            ser = Serializer()
            last = servers[0].log.last_index
            assert all(s.log.last_index == last for s in servers)
            for i in range(1, last + 1):
                copies = {ser.write(e) for e in
                          (s.log.get(i) for s in servers)
                          if e is not None}
                assert len(copies) <= 1, f"slot {i} diverged"
            # 2) exactly-once command stream covering every submit
            streams = [_command_stream(s) for s in servers]
            assert streams[0] == streams[1] == streams[2]
            applied = [(k, v) for _sid, _seq, name, k, v in streams[0]
                       if name == "Put"]
            assert applied == submitted
            # 3) the strict tripwire stayed silent on every member
            for s in servers:
                assert s.metrics.counter(
                    "repl.invariant_violations").value == 0
            # 4) final state agrees everywhere
            states = [dict(s.state_machine.data) for s in servers]
            assert states[0] == states[1] == states[2]
            assert states[0] == {k: v for k, v in submitted}
        finally:
            await close_all(servers, client)

    run()
