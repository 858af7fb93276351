"""Crash-recovery plane (docs/DURABILITY.md): snapshot capture/restore,
restart-recovery differentials under the crash/torn-write nemesis family,
snapshot-install streaming, and boot-time edge cases.

The headline differential: a member killed mid-append and rebooted from
snapshot + log tail must be bit-identical (log, state machine state,
session table) to a never-crashed member — with COPYCAT_SNAPSHOTS=0
restoring the replay-only path bit-identically (the recovery A/B knob).
"""

import asyncio
import os
import shutil

import pytest

from copycat_tpu.io.local import LocalTransport
from copycat_tpu.server.log import Storage, StorageLevel
from copycat_tpu.server.raft import LEADER, RaftServer
from copycat_tpu.server.snapshot import SnapshotStore, frame, unframe
from copycat_tpu.testing.nemesis import StorageNemesis, crash_server

from raft_fixtures import (
    REPL_DEPTHS,
    Get,
    KVStateMachine,
    Put,
    PutTtl,
    create_cluster,
    server_fingerprint,
)

from engines import SERVED

LEVELS = [StorageLevel.MAPPED, StorageLevel.DISK]


def _storage(level, directory):
    return Storage(level, str(directory), max_entries_per_segment=16)


def _reboot(cluster, index, level, directory, *, env=None,
            members=None) -> RaftServer:
    """A fresh RaftServer on a crashed member's storage + address."""
    old = cluster.servers[index]
    server = RaftServer(
        old.address,
        members or [s.address for s in cluster.servers],
        LocalTransport(cluster.registry, local_address=old.address),
        KVStateMachine(),
        storage=_storage(level, directory),
        election_timeout=old.election_timeout,
        heartbeat_interval=old.heartbeat_interval,
        session_timeout=old.session_timeout,
    )
    cluster.servers[index] = server
    return server


async def _converged(cluster, timeout: float = 10.0):
    """Wait until every open member applied the leader's full log."""
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        leader = cluster.leader
        if leader is not None:
            lagging = [
                s for s in cluster.servers
                if s.is_open and s.last_applied < leader.last_applied]
            if not lagging and leader.commit_index == leader.log.last_index:
                return leader
        await asyncio.sleep(0.02)
    raise TimeoutError("cluster did not converge")


def _assert_bit_identical(a: RaftServer, b: RaftServer) -> None:
    from copycat_tpu.io.serializer import Serializer
    from copycat_tpu.server.log import KeepAliveEntry, NoOpEntry

    start = max(a.log.first_index, b.log.first_index)
    fa = server_fingerprint(a, from_index=start)
    fb = server_fingerprint(b, from_index=start)
    # Log: bit-identical entry bytes, EXCEPT that a slot compacted on one
    # side may hold a cleaned/superseded entry on the other (a leader
    # legitimately omits compacted entries when re-replicating; their
    # effects are replicated via machine + session state, compared
    # strictly below).
    ser = Serializer()
    assert a.log.last_index == b.log.last_index
    for i in range(start, a.log.last_index + 1):
        ea, eb = a.log.get(i), b.log.get(i)
        if ea is None and eb is None:
            continue
        if ea is None or eb is None:
            present, holder = (eb, b) if ea is None else (ea, a)
            assert holder.log.is_cleaned(i) or isinstance(
                present, (KeepAliveEntry, NoOpEntry)), (
                i, type(present).__name__)
            continue
        assert ser.write(ea) == ser.write(eb), i
    assert fa["machine"] == fb["machine"]
    assert fa["sessions"] == fb["sessions"]
    assert fa["last_applied"] == fb["last_applied"]


# ---------------------------------------------------------------------------
# the restart-recovery differential (snapshots ON and OFF, both levels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("level", LEVELS, ids=lambda lv: lv.value)
@pytest.mark.parametrize("snapshots", ["1", "0"], ids=["snap", "replay"])
@pytest.mark.parametrize(
    "fault", [None, "torn_tail", "partial_frame", "dropped_fsync"],
    ids=["clean", "torn_tail", "partial_frame", "dropped_fsync"])
def test_restart_recovery_differential(tmp_path, monkeypatch, level,
                                       snapshots, fault):
    """Kill a follower mid-append, tear what the crash left behind,
    reboot it from snapshot + log tail (or full replay with
    COPYCAT_SNAPSHOTS=0): once re-converged it must be bit-identical to a
    member that never crashed."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", snapshots)
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "20")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "4")
    dirs = [tmp_path / f"m{i}" for i in range(3)]

    async def run() -> None:
        cluster = await create_cluster(
            3, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            for i in range(30):
                await client.submit(Put(key=f"k{i % 7}", value=i))
            leader = cluster.leader
            victim = next(s for s in cluster.servers if s is not leader)
            vic = cluster.servers.index(victim)
            await victim.snapshots_settled()

            # kill mid-append: a burst is in flight when the process dies
            burst = [
                asyncio.ensure_future(
                    client.submit(Put(key=f"burst{i}", value=i)))
                for i in range(8)]
            await asyncio.sleep(0)
            await crash_server(victim)
            await asyncio.gather(*burst)  # quorum of 2 still commits

            if fault is not None:
                StorageNemesis(str(dirs[vic])).inject(fault)

            for i in range(20):
                await client.submit(Put(key=f"post{i % 5}", value=i))

            reborn = _reboot(cluster, vic, level, dirs[vic])
            if snapshots == "1":
                # boot must start from the snapshot, not index 1
                assert reborn.last_applied > 0
            await reborn.open()
            leader = await _converged(cluster)
            healthy = next(
                s for s in cluster.servers
                if s is not reborn and s is not leader)
            _assert_bit_identical(reborn, healthy)
            _assert_bit_identical(reborn, leader)
            # and the recovered member still serves reads through the API
            v = await client.submit(Get(key="post4"))
            assert v == 19
        finally:
            await cluster.close()

    asyncio.run(run())


@pytest.mark.parametrize("level", LEVELS, ids=lambda lv: lv.value)
def test_recovery_with_ttl_timers(tmp_path, monkeypatch, level):
    """Pending log-time TTLs ride the snapshot image: a recovered member
    expires keys at the same log time a never-crashed member does."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "10")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")
    dirs = [tmp_path / f"m{i}" for i in range(3)]

    async def run() -> None:
        cluster = await create_cluster(
            3, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            await client.submit(PutTtl(key="ephemeral", value=1, ttl=0.6))
            for i in range(15):
                await client.submit(Put(key=f"k{i}", value=i))
            leader = cluster.leader
            victim = next(s for s in cluster.servers if s is not leader)
            vic = cluster.servers.index(victim)
            await victim.snapshots_settled()
            assert victim._snap_index > 0
            # the snapshot image carries the pending deadline
            await crash_server(victim)
            reborn = _reboot(cluster, vic, level, dirs[vic])
            assert "ephemeral" in reborn.state_machine.data
            assert "ephemeral" in reborn.state_machine.ttl_deadlines
            await reborn.open()
            await _converged(cluster)
            await asyncio.sleep(0.8)
            for _ in range(100):
                if "ephemeral" not in reborn.state_machine.data:
                    break
                await asyncio.sleep(0.05)
            healthy = next(
                s for s in cluster.servers if s is not reborn)
            assert "ephemeral" not in healthy.state_machine.data
            assert "ephemeral" not in reborn.state_machine.data
        finally:
            await cluster.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# snapshot-install streaming
# ---------------------------------------------------------------------------


@REPL_DEPTHS
def test_install_streaming_catches_up_wiped_follower(tmp_path, monkeypatch,
                                                     depth):
    """A follower with total data loss reboots empty while the leader's
    log is prefix-truncated: the append stream cannot serve it, so the
    leader streams the snapshot (chunked, through the replication plane)
    and resumes appends where the snapshot ends — with eight chunks in
    flight and with one."""
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "25")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "2")
    monkeypatch.setenv("COPYCAT_SNAP_CHUNK", "4096")  # force several chunks
    dirs = [tmp_path / f"m{i}" for i in range(3)]
    level = StorageLevel.MAPPED

    async def run() -> None:
        cluster = await create_cluster(
            3, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            leader = cluster.leader
            victim = next(s for s in cluster.servers if s is not leader)
            vic = cluster.servers.index(victim)
            await crash_server(victim)
            # big values so the snapshot spans multiple install chunks
            for i in range(120):
                await client.submit(
                    Put(key=f"k{i % 9}", value="v" * 200 + str(i)))
            leader = cluster.leader
            await leader.snapshots_settled()
            assert leader.log.prefix_index > 0
            shutil.rmtree(dirs[vic])
            os.makedirs(dirs[vic])
            reborn = _reboot(cluster, vic, level, dirs[vic])
            await reborn.open()
            await _converged(cluster)
            _assert_bit_identical(reborn, leader)
            snap = leader.metrics.snapshot()
            assert snap["snap.installs_sent"] >= 1
            assert snap["snap.install_chunks_sent"] >= 2
            assert snap["snap.snapshots_taken"] > 0
            assert snap["snap.snapshot_bytes"] > 0
            assert snap["snap.truncated_entries"] > 0
            assert snap["snap.install_failures"] == 0
            rsnap = reborn.metrics.snapshot()
            assert rsnap["snap.installs_received"] >= 1
            assert rsnap["snap.restores"] >= 1
            assert rsnap["snap.install_chunks_received"] >= 2
        finally:
            await cluster.close()

    asyncio.run(run())


def test_snapshots_off_keeps_full_log_no_installs(tmp_path, monkeypatch):
    """COPYCAT_SNAPSHOTS=0 restores the replay-only plane bit-identically:
    no snapshot files, no prefix truncation, recovery replays from the
    log alone, and no install traffic ever flows."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "0")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "10")
    dirs = [tmp_path / f"m{i}" for i in range(3)]
    level = StorageLevel.DISK

    async def run() -> None:
        cluster = await create_cluster(
            3, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            for i in range(60):
                await client.submit(Put(key=f"k{i % 5}", value=i))
            leader = cluster.leader
            assert leader.log.prefix_index == 0
            assert leader.log.first_index == 1
            assert not [f for f in os.listdir(dirs[0]) if f.endswith(".snap")]
            victim = next(s for s in cluster.servers if s is not leader)
            vic = cluster.servers.index(victim)
            await crash_server(victim)
            reborn = _reboot(cluster, vic, level, dirs[vic])
            assert reborn.last_applied == 0  # full replay, by design
            await reborn.open()
            leader = await _converged(cluster)
            _assert_bit_identical(reborn, leader)
            snap = leader.metrics.snapshot()
            assert snap.get("snap.installs_sent", 0) == 0
            assert snap.get("snap.snapshots_taken", 0) == 0
        finally:
            await cluster.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# boot-time recovery edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("level", LEVELS, ids=lambda lv: lv.value)
def test_corrupt_meta_falls_back_to_zero_state(tmp_path, monkeypatch, level):
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    dirs = [tmp_path / "m0"]

    async def run() -> None:
        cluster = await create_cluster(
            1, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            await client.submit(Put(key="a", value=1))
            server = cluster.servers[0]
            assert server.term > 0
            await crash_server(server)
            assert StorageNemesis(str(dirs[0])).torn_meta() is not None
            reborn = _reboot(cluster, 0, level, dirs[0])
            # boot survived; vote state fell back to zero, loudly counted
            assert reborn.term == 0
            assert reborn.voted_for is None
            assert reborn.metrics.snapshot()["snap.meta_fallbacks"] == 1
            await reborn.open()
            await _converged(cluster)
            assert reborn.state_machine.data["a"] == 1
        finally:
            await cluster.close()

    asyncio.run(run())


def test_corrupt_snapshot_falls_back_to_older_then_replay(tmp_path,
                                                          monkeypatch):
    """A bad-CRC newest snapshot is skipped (never restored, never fatal):
    recovery uses the previous snapshot; with every snapshot corrupt it
    falls back to full replay."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "10")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "1000")  # keep the log
    d = tmp_path / "m0"
    level = StorageLevel.DISK

    async def run() -> None:
        cluster = await create_cluster(
            1, storage_factory=lambda i: _storage(level, d))
        try:
            client = await cluster.client(session_timeout=30)
            for i in range(25):
                await client.submit(Put(key=f"k{i % 3}", value=i))
            server = cluster.servers[0]
            await server.snapshots_settled()
            store = server._snapshots
            assert len(store.indexes()) == 2
            newest = store.indexes()[-1]
            await crash_server(server)

            nem = StorageNemesis(str(d))
            assert nem.corrupt_snapshot() is not None
            reborn = _reboot(cluster, 0, level, d)
            # restored from the OLDER snapshot (newest skipped on CRC)
            assert 0 < reborn.last_applied < newest
            assert reborn._snapshots.bad_skipped == 1
            reborn.log.close()

            # corrupt EVERY snapshot: full replay is the final fallback
            for fname in os.listdir(d):
                if fname.endswith(".snap"):
                    path = os.path.join(str(d), fname)
                    with open(path, "r+b") as f:
                        f.seek(24)
                        chunk = f.read(8)
                        f.seek(24)
                        f.write(bytes(b ^ 0xFF for b in chunk))
            reborn2 = _reboot(cluster, 0, level, d)
            assert reborn2.last_applied == 0
            await reborn2.open()
            await _converged(cluster)
            assert reborn2.state_machine.data["k0"] == 24
        finally:
            await cluster.close()

    asyncio.run(run())


@pytest.mark.parametrize("level", LEVELS, ids=lambda lv: lv.value)
def test_torn_tail_past_snapshot_index(tmp_path, monkeypatch, level):
    """A torn log tail PAST the snapshot boundary: recovery restores the
    snapshot, replays the surviving tail frames, and drops only the torn
    ones — then re-fetches them from the leader."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "15")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")
    dirs = [tmp_path / f"m{i}" for i in range(3)]

    async def run() -> None:
        cluster = await create_cluster(
            3, storage_factory=lambda i: _storage(level, dirs[i]))
        try:
            client = await cluster.client(session_timeout=30)
            for i in range(40):
                await client.submit(Put(key=f"k{i % 7}", value=i))
            leader = cluster.leader
            victim = next(s for s in cluster.servers if s is not leader)
            vic = cluster.servers.index(victim)
            await victim.snapshots_settled()
            snap_index = victim._snap_index
            assert snap_index > 0
            await crash_server(victim)
            StorageNemesis(str(dirs[vic])).partial_frame()
            reborn = _reboot(cluster, vic, level, dirs[vic])
            assert reborn.last_applied >= snap_index
            assert reborn.log.last_index >= snap_index
            await reborn.open()
            leader = await _converged(cluster)
            _assert_bit_identical(reborn, leader)
        finally:
            await cluster.close()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# device-backed manager: snapshot via the checkpoint pytree format
# ---------------------------------------------------------------------------


def test_manager_tpu_snapshot_restores_device_values(tmp_path, monkeypatch):
    """A ResourceManager on the TPU executor snapshots its whole catalog:
    device-resident registers ride one ``models/checkpoint.py`` field-path
    blob, and a rebooted server serves the same values without replaying
    history."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "8")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")
    from copycat_tpu.atomic import DistributedAtomicValue
    from copycat_tpu.io.local import LocalServerRegistry
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer

    from raft_fixtures import next_ports

    d = tmp_path / "m0"

    async def run() -> None:
        registry = LocalServerRegistry()
        (addr,) = next_ports(1)

        def build_server() -> AtomixServer:
            return AtomixServer(
                addr, [addr], LocalTransport(registry, local_address=addr),
                storage=_storage(StorageLevel.DISK, d),
                election_timeout=0.2, heartbeat_interval=0.04,
                session_timeout=10.0, executor="tpu",
                engine_config=SERVED)

        server = build_server()
        await server.open()
        client = AtomixClient([addr], LocalTransport(registry),
                              session_timeout=10.0)
        await client.open()
        try:
            value = await client.get("reg", DistributedAtomicValue)
            for i in range(12):
                await value.set(100 + i)
            raft = server.server
            await raft.snapshots_settled()
            assert raft._snap_index > 0  # the manager snapshot happened
            await client.close()
            await crash_server(raft)

            reborn = build_server()
            # restored from the snapshot image, not from index 1
            assert reborn.server.last_applied >= raft._snap_index
            manager = reborn.server.state_machine
            assert manager.keys == {"reg": min(manager.keys.values())} \
                or "reg" in manager.keys
            await reborn.open()
            client2 = AtomixClient([addr], LocalTransport(registry),
                                   session_timeout=10.0)
            await client2.open()
            try:
                value2 = await client2.get("reg", DistributedAtomicValue)
                assert await value2.get() == 111
                await value2.set(7)
                assert await value2.get() == 7
            finally:
                await client2.close()
            await reborn.close()
        finally:
            try:
                await server.close()
            except Exception:
                pass

    asyncio.run(run())


def test_manager_tpu_snapshot_restores_device_map_and_set(tmp_path,
                                                          monkeypatch):
    """The device map and set machines' ``snapshot_state``/
    ``restore_state`` hooks (docs/DURABILITY.md): a manager hosting them
    no longer opts the whole server into replay-only recovery. The
    differential: a server that crashed after the snapshot serves the
    SAME answers as the never-crashed one for device-resident int
    entries, host-shadowed string entries, sizes and membership — and
    it provably restored from the image (``last_applied`` at or past
    the snapshot index before any replay)."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "8")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")
    from copycat_tpu.collections import DistributedMap, DistributedSet
    from copycat_tpu.io.local import LocalServerRegistry
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer

    from raft_fixtures import next_ports

    d = tmp_path / "m0"

    async def probe(client) -> dict:
        m = await client.get("m", DistributedMap)
        s = await client.get("s", DistributedSet)
        return {
            "dev_keys": [await m.get(k) for k in range(1, 7)],
            "shadow": await m.get("name"),
            "absent": await m.get(99),
            "m_size": await m.size(),
            "s_members": [await s.contains(v) for v in (5, 6, 7, "x")],
            "s_size": await s.size(),
        }

    async def run() -> None:
        registry = LocalServerRegistry()
        (addr,) = next_ports(1)

        def build_server() -> AtomixServer:
            return AtomixServer(
                addr, [addr], LocalTransport(registry, local_address=addr),
                storage=_storage(StorageLevel.DISK, d),
                election_timeout=0.2, heartbeat_interval=0.04,
                session_timeout=10.0, executor="tpu",
                engine_config=SERVED)

        server = build_server()
        await server.open()
        client = AtomixClient([addr], LocalTransport(registry),
                              session_timeout=10.0)
        await client.open()
        try:
            m = await client.get("m", DistributedMap)
            s = await client.get("s", DistributedSet)
            for k in range(1, 7):
                await m.put(k, k * 10)          # device probe table
            await m.put("name", "shadowed")     # host shadow
            await m.remove(3)
            for v in (5, 6, 7):
                await s.add(v)                  # device probe table
            await s.add("x")                    # host shadow
            await s.remove(6)
            raft = server.server
            await raft.snapshots_settled()
            assert raft._snap_index > 0, \
                "map/set hooks must not opt the manager out of snapshots"
            before = await probe(client)
            await client.close()
            await crash_server(raft)

            reborn = build_server()
            assert reborn.server.last_applied >= raft._snap_index
            await reborn.open()
            client2 = AtomixClient([addr], LocalTransport(registry),
                                   session_timeout=10.0)
            await client2.open()
            try:
                assert await probe(client2) == before
                # the restored machines keep working (device + shadow)
                m2 = await client2.get("m", DistributedMap)
                assert await m2.put(1, 11) == 10
                assert await m2.get(1) == 11
                s2 = await client2.get("s", DistributedSet)
                assert await s2.add(7) is False  # still a member
            finally:
                await client2.close()
            await reborn.close()
        finally:
            try:
                await server.close()
            except Exception:
                pass

    asyncio.run(run())


def test_device_map_set_ttl_still_opts_out(monkeypatch):
    """An armed per-key TTL timer holds commit references that cannot
    round-trip a snapshot: the map/set machines must keep opting out
    (NotImplemented) exactly like the value machine's documented rule."""
    from copycat_tpu.manager.device_executor import (
        DeviceMapState,
        DeviceSetState,
        _Held,
    )
    from copycat_tpu.server.state_machine import Commit

    for cls in (DeviceMapState, DeviceSetState):
        machine = cls.__new__(cls)  # no engine needed for the hook
        machine._held = {}
        assert machine.snapshot_state() == {"held": []}
        held = _Held(Commit(0, None, 0.0, None, None), value=1)
        machine._held[1] = held
        assert machine.snapshot_state() is not NotImplemented
        held.timer = object()  # armed TTL
        assert machine.snapshot_state() is NotImplemented


# ---------------------------------------------------------------------------
# snapshot store + log prefix units
# ---------------------------------------------------------------------------


def test_snapshot_store_frame_roundtrip_and_bad_crc(tmp_path):
    store = SnapshotStore(str(tmp_path), "s")
    store.save(10, b"ten")
    store.save(20, b"twenty")
    assert store.indexes() == [10, 20]
    assert store.newest() == (20, b"twenty")
    # corrupt the newest: falls back to 10, counts the skip
    path = os.path.join(str(tmp_path), "s-%016d.snap" % 20)
    with open(path, "r+b") as f:
        f.seek(-2, os.SEEK_END)
        f.write(b"\xff\xff")
    assert store.newest() == (10, b"ten")
    assert store.bad_skipped == 1
    # an all-zero file must not validate (seeded CRC)
    with open(path, "wb") as f:
        f.write(b"\x00" * 64)
    assert store.newest() == (10, b"ten")
    assert store.gc(keep=1) == 1
    assert store.indexes() == [20]  # gc keeps newest by name; it's corrupt
    assert store.newest() is None


def test_snapshot_frame_unframe():
    assert unframe(frame(b"payload")) == b"payload"
    assert unframe(frame(b"")) == b""
    assert unframe(b"") is None
    assert unframe(b"CCSNAP1\n") is None
    data = bytearray(frame(b"payload"))
    data[-1] ^= 0x01
    assert unframe(bytes(data)) is None


def test_meta_write_is_atomic(tmp_path):
    """_persist_meta must leave either the old or the new complete file —
    interrupting the write path never yields a half-written meta."""

    async def run() -> None:
        cluster = await create_cluster(
            1, storage_factory=lambda i: _storage(
                StorageLevel.DISK, tmp_path / "m0"))
        try:
            server = cluster.servers[0]
            meta = server._meta_path
            assert os.path.exists(meta)
            # no .tmp sibling survives a completed write
            assert not os.path.exists(meta + ".tmp")
            import json
            with open(meta) as f:
                parsed = json.load(f)
            assert parsed["term"] == server.term
        finally:
            await cluster.close()

    asyncio.run(run())
