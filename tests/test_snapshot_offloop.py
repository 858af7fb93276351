"""The snapshot capture in two phases (docs/DURABILITY.md).

Phase 1, the cut, runs on the member's event loop inside the apply path and
copies whatever a later entry can change. Phase 2 (the device fetch,
compression, the serializer, the file with its fsync and rename) runs on the
server's snapshot worker; its completion comes back to the loop, and only
there do ``_snap_index`` and the log's prefix move. These tests hold the
worker inside ``SnapshotStore.save`` and look at the member meanwhile.

CounterMachine members everywhere but for the device half of the image test,
which shares ``tests/test_cluster_durable_device.py``'s engine sizes.
"""

import asyncio
import os
import shutil
import sys
import threading
import time

import pytest

from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
from copycat_tpu.io.serializer import Serializer
from copycat_tpu.server.log import Storage, StorageLevel
from copycat_tpu.server.raft import RaftServer
from copycat_tpu.server.snapshot import SnapshotStore
from copycat_tpu.testing.counter_machine import ClusterAdd, CounterMachine
from copycat_tpu.testing.nemesis import crash_server
from copycat_tpu.utils import tracing
from copycat_tpu.utils.tracing import TRACER

from helpers import async_test
from raft_fixtures import create_cluster, next_ports

from engines import SERVED

EVERY = 8


@pytest.fixture(autouse=True)
def cadence(monkeypatch):
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", str(EVERY))
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")


class Gate:
    """Stands in for a store's ``save``: says when the worker has reached
    it and holds the worker there until released (or fails it)."""

    def __init__(self, store, fail: Exception | None = None) -> None:
        self.entered, self.release = threading.Event(), threading.Event()
        self.fail, self.store, self.save = fail, store, store.save
        store.save = self

    def __call__(self, index, payload):
        self.entered.set()
        assert self.release.wait(30), "the test never released the worker"
        if self.fail is not None:
            raise self.fail
        return self.save(index, payload)

    def open(self) -> None:
        """Let this and every later save through."""
        self.store.save = self.save
        self.release.set()


async def until(condition, timeout: float = 10.0) -> None:
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, "condition never held"
        await asyncio.sleep(0.002)


async def one_member(directory, machine=CounterMachine):
    cluster = await create_cluster(
        1, machine_factory=machine, session_timeout=30.0,
        storage_factory=lambda i: Storage(
            StorageLevel.DISK, str(directory), max_entries_per_segment=4))
    return cluster, cluster.servers[0], await cluster.client(30.0)


async def add_until_cut(client, group, key="k") -> int:
    """Adds, one at a time, until one's apply has cut an image: returns
    the counter's value in that image."""
    taken = group._snap_index
    for _ in range(4 * EVERY):
        value = await client.submit(ClusterAdd(key=key, delta=1))
        if group._snap_inflight is not None or group._snap_index > taken:
            return value
    raise AssertionError("no capture fell due")


def counter(group, name: str) -> int:
    return group.metrics.counter(name).value


def reboot(server, directory) -> RaftServer:
    return RaftServer(
        server.address, [server.address],
        LocalTransport(LocalServerRegistry(), local_address=server.address),
        CounterMachine(),
        storage=Storage(StorageLevel.DISK, str(directory),
                        max_entries_per_segment=4),
        election_timeout=0.2, heartbeat_interval=0.04, session_timeout=30.0)


def only_the_snapshot(directory, target, index: int) -> None:
    """A directory that holds a member's snapshot at ``index`` and no log:
    what boots over it holds the image and nothing replayed on top."""
    os.makedirs(target)
    (name,) = [f for f in os.listdir(directory)
               if f.endswith(f"{index:016d}.snap")]
    shutil.copy(os.path.join(directory, name), target)


# -- (a) the apply path does not wait for the file ---------------------------

@async_test(timeout=60)
async def test_apply_returns_and_later_entries_are_acknowledged(tmp_path):
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    gate = Gate(group._snapshots)
    TRACER.clear()
    tracing.enable()
    try:
        cut_value = await add_until_cut(client, group)
        cut_index = group._snap_inflight.index
        await until(gate.entered.is_set)
        # the worker stands inside save; the loop has moved on
        spans = TRACER.report()["spans"]
        assert spans["snapshot.capture"]["n"] == 1
        assert "snapshot.finish" not in spans and "snapshot.write" not in spans
        for k in range(EVERY // 2):
            assert await client.submit(
                ClusterAdd(key="k", delta=1)) == cut_value + k + 1
        assert server.last_applied > cut_index
        assert group._snap_index == 0 and counter(
            group, "snap.snapshots_taken") == 0
        gate.open()
        await group.snapshot_settled()
        assert group._snap_index == cut_index
        assert counter(group, "snap.captures_finished") == 1
        assert counter(group, "snap.snapshots_taken") == 1
        assert group.metrics.gauge("snap.capture_lag_entries").value \
            == server.last_applied - cut_index > 0
        spans = TRACER.report()["spans"]
        assert spans["snapshot.finish"]["n"] == 1
        assert spans["snapshot.write"]["n"] == 1
        assert "snapshot.fetch" not in spans    # no device part here
        by_name = {s.name: s for t in TRACER.traces().values() for s in t
                   if s.name.startswith("snapshot.")}
        capture, finish, write = (by_name[n] for n in (
            "snapshot.capture", "snapshot.finish", "snapshot.write"))
        assert capture.trace_id == finish.trace_id == write.trace_id
        assert write.parent == "snapshot.finish" and finish.parent is None
        # the cut had closed before the file was begun, and it is short
        assert capture.end <= write.start <= write.end <= finish.end
        assert finish.meta["lag"] == server.last_applied - cut_index
    finally:
        tracing.disable()
        TRACER.clear()
        gate.open()
        await cluster.close()


# -- (b) the image is the state at the cut -----------------------------------

@async_test(timeout=60)
async def test_image_is_the_cut_cpu_machine(tmp_path):
    cluster, server, client = await one_member(tmp_path / "m")
    group = server.groups[0]
    gate = Gate(group._snapshots)
    try:
        cut_value = await add_until_cut(client, group)
        cut_index = group._snap_inflight.index
        (session,) = server.sessions.values()
        cut_seqs = set(session.responses)
        await until(gate.entered.is_set)
        for _ in range(3):
            await client.submit(ClusterAdd(key="k", delta=1))
        await client.submit(ClusterAdd(key="later", delta=5))
        assert set(session.responses) > cut_seqs
        gate.open()
        await group.snapshot_settled()
        only_the_snapshot(tmp_path / "m", tmp_path / "image", cut_index)
        restored = reboot(server, tmp_path / "image")
        assert restored.last_applied == cut_index
        assert restored.state_machine.data == {"k": cut_value}
        (again,) = restored.sessions.values()
        assert set(again.responses) == cut_seqs
        assert all(type(r) is tuple for r in again.responses.values())
        assert server.state_machine.data == {"k": cut_value + 3, "later": 5}
        restored.log.close()
    finally:
        gate.open()
        await cluster.close()


@async_test(timeout=180)
async def test_image_is_the_cut_device_machine(tmp_path):
    pytest.importorskip("jax")
    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.resource.consistency import Consistency

    registry = LocalServerRegistry()
    (addr,) = next_ports(1)

    def build(directory):
        return AtomixServer(
            addr, [addr], LocalTransport(registry, local_address=addr),
            storage=Storage(StorageLevel.DISK, str(directory)),
            election_timeout=0.2, heartbeat_interval=0.04,
            session_timeout=60.0, executor="tpu",
            engine_config=SERVED)

    server = build(tmp_path / "m")
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=60.0)
    await client.open()
    group = server.server.groups[0]
    gate = Gate(group._snapshots)
    restored = reader = None
    try:
        ctr = await client.get("ctr", DistributedAtomicLong)
        for _ in range(4 * EVERY):
            cut_value = await ctr.increment_and_get()
            if group._snap_inflight is not None:
                break
        cut_index = group._snap_inflight.index
        await until(gate.entered.is_set)
        # rounds after the cut donate the state the cut was taken from
        for k in range(5):
            assert await ctr.increment_and_get() == cut_value + k + 1
        gate.open()
        await group.snapshot_settled()
        assert group._snap_index == cut_index
        assert counter(group, "snap.captures_finished") == counter(
            group, "snap.snapshots_taken") >= 1
        await client.close()
        await server.close()
        client = server = None

        only_the_snapshot(tmp_path / "m", tmp_path / "image", cut_index)
        restored = build(tmp_path / "image")
        assert restored.server.last_applied == cut_index
        await restored.open()
        reader = AtomixClient([addr], LocalTransport(registry),
                              session_timeout=60.0)
        await reader.open()
        seen = await reader.get("ctr", DistributedAtomicLong)
        seen.with_consistency(Consistency.ATOMIC)
        assert await seen.get() == cut_value
    finally:
        gate.open()
        for closing in (client, reader, server, restored):
            if closing is not None:
                await closing.close()


# -- (c) the log's prefix goes only after the file ---------------------------

@async_test(timeout=60)
async def test_prefix_released_after_save_and_kept_when_save_fails(tmp_path):
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    order = []
    save, truncate = group._snapshots.save, group.log.truncate_prefix

    def saved(index, payload):
        path = save(index, payload)
        order.append(("saved", index))
        return path

    def truncated(to_index):
        order.append(("truncate", to_index))
        return truncate(to_index)

    group._snapshots.save, group.log.truncate_prefix = saved, truncated
    gate = Gate(group._snapshots, fail=OSError("disk full"))
    try:
        await add_until_cut(client, group)
        failed_index = group._snap_inflight.index
        await until(gate.entered.is_set)
        await client.submit(ClusterAdd(key="k", delta=1))
        gate.open()                 # this save raises; later ones are real
        await group.snapshot_settled()
        assert order == [] and group.log.first_index == 1
        assert group._snap_index == 0
        assert counter(group, "snap.capture_failures") == 1
        assert counter(group, "snap.captures_finished") == 0
        assert not [f for f in os.listdir(tmp_path) if ".snap" in f]
        # the next apply tries again, and that one lands
        await client.submit(ClusterAdd(key="k", delta=1))
        await group.snapshot_settled()
        landed = group._snap_index
        assert landed > failed_index
        assert counter(group, "snap.snapshots_taken") == 1
        assert order == [("saved", landed), ("truncate", landed)]
        assert group.log.first_index == landed + 1
    finally:
        gate.open()
        await cluster.close()


# -- (d) a member stopped with a capture in flight ---------------------------

@async_test(timeout=60)
async def test_crash_with_the_worker_blocked_leaves_log_and_index(tmp_path):
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    gate = None
    try:
        await add_until_cut(client, group)
        await group.snapshot_settled()
        first_snap = group._snap_index
        assert first_snap > 0
        gate = Gate(group._snapshots)
        await add_until_cut(client, group)
        await until(gate.entered.is_set)
        value = await client.submit(ClusterAdd(key="k", delta=1))
        first_index, last_index = group.log.first_index, group.log.last_index
        inflight = group._snap_inflight
        await crash_server(server)

        # the file was never finished: the previous snapshot and the tail
        reborn = reboot(server, tmp_path)
        cluster.servers[0] = reborn
        assert reborn.groups[0].metrics.counter("snap.restores").value == 1
        assert reborn.last_applied == first_snap
        await reborn.open()
        await until(lambda: reborn.last_applied >= last_index)
        assert reborn.state_machine.data == {"k": value}

        # it lands late and whole, and the stopped member takes no notice
        gate.open()
        await asyncio.wait_for(inflight.settled, 10)
        assert group._snap_index == first_snap
        assert (group.log.first_index, group.log.last_index) == (
            first_index, last_index)
        assert counter(group, "snap.snapshots_taken") == 1
        store = SnapshotStore(str(tmp_path), group._snapshots.name)
        assert inflight.index in store.indexes()
        assert store.newest() is not None and store.bad_skipped == 0
        assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    finally:
        if gate is not None:
            gate.open()
        await cluster.close()


# -- (e) one capture in flight, none queued ----------------------------------

@async_test(timeout=60)
async def test_one_in_flight_and_the_deferred_one_is_taken_next(tmp_path):
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    gate = Gate(group._snapshots)
    try:
        await add_until_cut(client, group)
        first = group._snap_inflight
        await until(gate.entered.is_set)
        for _ in range(2 * EVERY + 2):     # two more cadences fall due
            await client.submit(ClusterAdd(key="k", delta=1))
        assert group._snap_inflight is first
        assert counter(group, "snap.captures_deferred") == 1
        gate.open()
        await group.snapshot_settled()
        assert group._snap_index == first.index
        # the first apply after the completion takes the deferred one
        gate = Gate(group._snapshots)
        await client.submit(ClusterAdd(key="k", delta=1))
        await until(gate.entered.is_set)
        second = group._snap_inflight
        assert second is not first and second.index == server.last_applied
        gate.open()
        await group.snapshot_settled()
        assert group._snap_index == second.index
        assert counter(group, "snap.snapshots_taken") == 2
        assert counter(group, "snap.captures_deferred") == 1
        # the log held two cadences and a little at most
        assert group.log.first_index == second.index + 1
    finally:
        gate.open()
        await cluster.close()


# -- (f) the graceful close ---------------------------------------------------

@async_test(timeout=60)
async def test_close_joins_the_worker_and_leaves_no_tmp(tmp_path):
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    save = group._snapshots.save
    began = threading.Event()

    def slow(index, payload):
        began.set()
        time.sleep(0.3)
        return save(index, payload)

    group._snapshots.save = slow
    await add_until_cut(client, group)
    inflight = group._snap_inflight
    await until(began.is_set)
    log_first = group.log.first_index
    await client.close()
    cluster.clients.clear()
    threads = list(server._snap_worker._threads)
    await server.close()
    assert server._snap_worker is None
    assert threads and not any(t.is_alive() for t in threads)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    store = SnapshotStore(str(tmp_path), group._snapshots.name)
    assert store.newest()[0] == inflight.index and store.bad_skipped == 0
    # its completion found the member closed: nothing moved
    await asyncio.wait_for(inflight.settled, 10)
    assert group._snap_index == 0 and group.log.first_index == log_first


# -- the worker beside a busy loop -------------------------------------------

@async_test(timeout=120)
async def test_every_file_is_an_exact_image_under_traffic(tmp_path):
    """Captures back to back under a client that never pauses, with the
    interpreter switching threads as often as it can: every file that
    lands is the counters at its own index, whatever ran beside it."""
    cluster, server, client = await one_member(tmp_path)
    group = server.groups[0]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    at_index: dict[int, dict] = {}
    seen: dict[int, dict] = {}
    save = group._snapshots.save
    serializer = Serializer()

    def keep(index, payload):
        seen[index] = serializer.read(payload)["machine"]["data"]
        return save(index, payload)

    group._snapshots.save = keep
    try:
        deadline = time.monotonic() + 3.0
        n = 0
        while time.monotonic() < deadline and n < 600:
            key = f"k{n % 5}"
            await client.submit(ClusterAdd(key=key, delta=n))
            at_index[server.last_applied] = dict(server.state_machine.data)
            n += 1
        await group.snapshot_settled()
    finally:
        sys.setswitchinterval(interval)
        await cluster.close()
    assert len(seen) >= 5, seen.keys()
    for index, data in seen.items():
        # a keep-alive may sit between an add and the cut: the newest add
        # at or before the image's index
        assert data == at_index[max(i for i in at_index if i <= index)], index
    taken = counter(group, "snap.snapshots_taken")
    assert taken == counter(group, "snap.captures_finished") == len(seen)
