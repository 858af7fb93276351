"""Differential proof for the batched device-native read plane.

The read pump (``RaftServer._run_read_window`` + ``RaftGroups.
drive_query_vector``, or its two halves around a parked vector run's
round) coalesces reads arriving across sessions into
per-consistency windows, pays each window's consistency gate ONCE, and
evaluates device-eligible reads as tensors through one ``query_step``
engine round. Its contract is BIT-IDENTICAL observable behavior to the
host state machines (``executor="cpu"``, whose every read runs its
query handler): same results, same observed indices, same error
surfaces — proven here by running the same seeded mixed read/write
script through a device server and a CPU server and comparing
everything the client can see, plus gate-amortization accounting
(≤1 leadership-confirm round per linearizable window, witnessed by the
``query_gate_rounds_saved`` counter) and the engine-level vector read
drive against per-op ``serve_query``.
"""

import asyncio
import os
import random

import pytest

jax = pytest.importorskip("jax")

import numpy as np  # noqa: E402

from copycat_tpu.atomic import DistributedAtomicValue  # noqa: E402
from copycat_tpu.io.local import (  # noqa: E402
    LocalServerRegistry, LocalTransport)
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED, device_plane  # noqa: E402



async def _spi_cluster(registry, executor: str = "tpu"):
    """One standalone server + client on the given executor."""
    (addr,) = next_ports(1)
    server = AtomixServer(addr, [addr], LocalTransport(registry),
                          election_timeout=0.5, heartbeat_interval=0.1,
                          session_timeout=20.0, executor=executor,
                          engine_config=SERVED)
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=20.0)
    await client.open()
    return server, client


def _script(seed: int, n_rounds: int, wave: int):
    """Seeded read-dominated script over 4 values: each round is a
    write phase (set/cas/gas bursts) followed by a read phase of
    ``wave`` gets. Phases are awaited separately so every read phase
    observes a settled state — the histories of both servers are then
    comparable value-for-value (concurrent read/write races have many
    valid linearizations and would compare noise, not the executors).
    Value 3 carries a change listener (its writes take the generator
    path — the read window still serves its gets from the device)."""
    rng = random.Random(seed)
    rounds = []
    for _ in range(n_rounds):
        writes = [(rng.randrange(4), rng.randrange(3), rng.randrange(5),
                   rng.randrange(5)) for _ in range(wave // 4)]
        reads = [rng.randrange(4) for _ in range(wave)]
        rounds.append((writes, reads))
    return rounds


async def _run_script(client, rounds):
    """Execute the script; returns (results, indices, finals, events) —
    the client-observable history including the per-round high-water
    index the reads advanced."""
    values = [await client.get(f"v{i}", DistributedAtomicValue)
              for i in range(4)]
    # exercise every consistency routing: bounded (default), sequential,
    # full-quorum linearizable, bounded+listener
    values[1].with_consistency(Consistency.SEQUENTIAL)
    values[2]._read_cl = "linearizable"
    events: list = []
    listener = await values[3].on_change(lambda v: events.append(v))
    for i, v in enumerate(values):
        await v.set(i)  # deterministic non-None base; lands on device
    results = []
    indices = []
    for writes, reads in rounds:
        async def one_write(target, kind, a, b):
            v = values[target]
            try:
                if kind == 0:
                    await v.set(a)
                    return ("set", None)
                if kind == 1:
                    return ("cas", await v.compare_and_set(a, b))
                return ("gas", await v.get_and_set(a))
            except Exception as e:  # noqa: BLE001 — error surfaces compare
                return ("err", type(e).__name__, str(e))

        async def one_read(target):
            try:
                return ("get", await values[target].get())
            except Exception as e:  # noqa: BLE001
                return ("err", type(e).__name__, str(e))

        results.append(await asyncio.gather(
            *(one_write(*w) for w in writes)))
        results.append(await asyncio.gather(
            *(one_read(t) for t in reads)))
        indices.append(client.client.index)
    finals = [await v.get() for v in values]
    listener.close()
    await asyncio.sleep(0.05)
    return results, indices, finals, events


@async_test(timeout=300)
async def test_read_pump_bit_identical_to_per_op_path():
    """Same seeded script, the device server against the host state
    machines: results, observed indices, event order and final state
    must be identical."""
    waves = _script(seed=7, n_rounds=5, wave=32)
    histories = []
    metrics = []
    for executor in ("tpu", "cpu"):
        registry = LocalServerRegistry()
        server, client = await _spi_cluster(registry, executor)
        try:
            histories.append(await _run_script(client, waves))
            snap = server.server.metrics.snapshot()
            metrics.append(snap)
        finally:
            await asyncio.wait_for(client.close(), 5)
            await asyncio.wait_for(server.close(), 5)
    device, host = histories
    assert device[0] == host[0], "read pump diverged from host results"
    assert device[1] == host[1], "read pump diverged in observed indices"
    assert device[2] == host[2], "read pump diverged in final state"
    assert device[3] == host[3], "read pump diverged in event order"
    # the script genuinely exercised the batched lane: windows flushed
    # and device rows evaluated, while the reference answered every read
    # from its query handlers
    snap_dev, snap_host = metrics
    assert snap_dev["query_windows"] > 0
    assert snap_dev["query_ops_device_lane"] > 0
    assert snap_host.get("query_ops_device_lane", 0) == 0
    assert snap_host["query_ops_per_op_lane"] > 0


@async_test(timeout=300)
async def test_linearizable_window_pays_one_confirm_round():
    """N same-turn linearizable reads across sessions form ONE window:
    exactly one leadership-confirm round runs, and the
    query_gate_rounds_saved counter records the N-1 amortized rounds."""
    registry = LocalServerRegistry()
    server, client = await _spi_cluster(registry)
    try:
        raft = server.server
        values = [await client.get(f"v{i}", DistributedAtomicValue)
                  for i in range(4)]
        for v in values:
            v._read_cl = "linearizable"
            await v.set(9)
        confirms = [0]
        real_confirm = raft._confirm_leadership

        async def counting_confirm():
            confirms[0] += 1
            return await real_confirm()

        raft._confirm_leadership = counting_confirm
        saved0 = raft.metrics.counter("query_gate_rounds_saved").value
        windows0 = raft.metrics.counter("query_windows").value
        n = 24
        got = await asyncio.gather(
            *(values[i % 4].get() for i in range(n)))
        assert got == [9] * n
        # client-side the 24 gets coalesce into one QueryBatchRequest,
        # server-side into one window: ≤1 confirm round for all of them
        assert confirms[0] == 1, f"window paid {confirms[0]} confirm rounds"
        assert raft.metrics.counter("query_windows").value == windows0 + 1
        assert raft.metrics.counter(
            "query_gate_rounds_saved").value - saved0 == n - 1
    finally:
        await asyncio.wait_for(client.close(), 5)
        await asyncio.wait_for(server.close(), 5)


@async_test(timeout=300)
async def test_cross_session_reads_share_one_window():
    """Reads from DIFFERENT client sessions arriving in one event-loop
    turn share a single read window (the pump's advantage over the
    per-request QueryBatch gate)."""
    registry = LocalServerRegistry()
    server, client_a = await _spi_cluster(registry)
    client_b = AtomixClient([server.server.address],
                            LocalTransport(registry), session_timeout=20.0)
    await client_b.open()
    try:
        raft = server.server
        va = await client_a.get("shared", DistributedAtomicValue)
        vb = await client_b.get("shared", DistributedAtomicValue)
        await va.set(5)
        windows0 = raft.metrics.counter("query_windows").value
        got = await asyncio.gather(va.get(), vb.get(),
                                   va.get(), vb.get())
        assert got == [5, 5, 5, 5]
        flushed = raft.metrics.counter("query_windows").value - windows0
        assert flushed <= 2, (
            f"4 same-turn reads from 2 sessions flushed {flushed} windows")
    finally:
        await asyncio.wait_for(client_b.close(), 5)
        await asyncio.wait_for(client_a.close(), 5)
        await asyncio.wait_for(server.close(), 5)


def test_drive_query_vector_matches_per_op_serve():
    """Engine level: one vectorized query_step round returns exactly what
    per-op serve_query returns, for mixed groups and uneven per-group
    read counts (slot packing + pow2 width padding)."""
    rg = device_plane(seed=3)
    rg.wait_for_leaders()
    for g in range(8):
        rg.run_until([rg.submit(g, ap.OP_LONG_ADD, g + 1)])
    # uneven read multiplicity per group: group g read g+1 times
    groups = np.concatenate([np.full(g + 1, g) for g in range(8)])
    got = rg.drive_query_vector(groups, ap.OP_VALUE_GET)
    want = np.array([rg.serve_query(int(g), ap.OP_VALUE_GET)
                     for g in groups])
    assert (got == want).all(), (got, want)
    # atomic (lease-gated) rows serve too on a healthy engine
    got_atomic = rg.drive_query_vector(groups, ap.OP_VALUE_GET,
                                       atomic=True)
    assert (got_atomic == want).all()


@pytest.mark.parametrize("atomic", [False, True],
                         ids=["applied", "lease"])
def test_the_two_halves_of_the_vector_read_match_per_op_serve(atomic):
    """``stage_query_vector`` then ``finish_query_vector`` is
    ``drive_query_vector``; and offered to a vector run between them
    (``drive_vector(query=)``: the read window that finds a run parked,
    tests/test_read_joins_round.py) the same rows answer from the state
    that run left, in one device round trip with it, as per-op
    ``serve_query`` answers after it."""
    rg = device_plane(seed=3)
    rg.wait_for_leaders()
    rg.run(3)
    groups = np.concatenate([np.full(g + 1, g) for g in range(8)])
    query = rg.stage_query_vector(groups, ap.OP_VALUE_GET, atomic=atomic)
    assert query.slots == 8 and query.n == groups.size
    got = rg.finish_query_vector(query)
    assert (got == 0).all() and query.evaluations == 1
    fetches = rg.metrics.counter("fetches")
    joined = rg.metrics.counter("query_joined_drives")
    before = (fetches.value, joined.value)
    query = rg.stage_query_vector(groups, ap.OP_VALUE_GET, atomic=atomic)
    adds = np.arange(8, dtype=np.int64)
    rg.drive_vector(adds, np.full(8, ap.OP_LONG_ADD, np.int64), adds + 1,
                    adds * 0, adds * 0, query=query)
    got = rg.finish_query_vector(query)
    assert (fetches.value, joined.value) == (before[0] + 1, before[1] + 1)
    want = np.array([rg.serve_query(int(g), ap.OP_VALUE_GET)
                     for g in groups])
    assert (got == want).all() and (got == groups + 1).all()


def test_drive_query_vector_refuses_writes():
    rg = device_plane(seed=4)
    rg.wait_for_leaders()
    with pytest.raises(ValueError, match="not read-only"):
        rg.drive_query_vector([0], ap.OP_LONG_ADD, 1)
    with pytest.raises(ValueError, match="not read-only"):
        rg.stage_query_vector([0], ap.OP_LONG_ADD, 1)


@async_test(timeout=300)
async def test_follower_reads_round_robin(monkeypatch):
    """SEQUENTIAL reads round-robin across the cluster (follower read
    scale-out) and still return the committed value — the server-side
    client-index wait keeps them at-or-after the client's own writes;
    lagging servers refuse and the client falls back to the leader.
    Edge reads are pinned OFF: this test exercises the server read
    lane the edge tier exists to bypass (docs/EDGE_READS.md)."""
    monkeypatch.setenv("COPYCAT_EDGE_READS", "0")
    registry = LocalServerRegistry()
    addrs = next_ports(3)
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry, local_address=a),
                     election_timeout=0.3, heartbeat_interval=0.05,
                     session_timeout=20.0)
        for a in addrs
    ]
    await asyncio.gather(*(s.open() for s in servers))
    client = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=20.0)
    await client.open()
    try:
        assert client.client._follower_reads is True
        v = await client.get("v", DistributedAtomicValue)
        v.with_consistency(Consistency.SEQUENTIAL)
        await v.set(7)
        for _ in range(9):  # sequential singles: each advances the RR
            assert await v.get() == 7
        snap = client.client.metrics.snapshot()
        assert snap.get("client_reads_follower_lane", 0) >= 3, snap
        # every server saw read traffic (round-robin actually rotated)
        served = [s.server.metrics.counter(
            "query_reads", consistency="sequential").value
            for s in servers]
        assert sum(1 for n in served if n > 0) >= 2, served
    finally:
        await asyncio.wait_for(client.close(), 5)
        for s in servers:
            await asyncio.wait_for(s.close(), 10)


@async_test(timeout=120)
async def test_follower_reads_env_knob(monkeypatch):
    """COPYCAT_CLIENT_FOLLOWER_READS=0 restores leader-pinned reads."""
    from copycat_tpu.client.client import RaftClient
    from copycat_tpu.io.transport import Address

    monkeypatch.setenv("COPYCAT_CLIENT_FOLLOWER_READS", "0")
    registry = LocalServerRegistry()
    c = RaftClient([Address("127.0.0.1", 1)], LocalTransport(registry))
    assert c._follower_reads is False
    monkeypatch.delenv("COPYCAT_CLIENT_FOLLOWER_READS")
    c2 = RaftClient([Address("127.0.0.1", 1)], LocalTransport(registry))
    assert c2._follower_reads is True


@async_test(timeout=300)
async def test_read_pump_error_surfaces_match():
    """A read against a deleted resource raises the same ApplicationError
    on the device server and on the host state machines (the window's
    per-row error path)."""
    outcomes = []
    for executor in ("tpu", "cpu"):
        registry = LocalServerRegistry()
        server, client = await _spi_cluster(registry, executor)
        try:
            v = await client.get("doomed", DistributedAtomicValue)
            await v.set(1)
            instance_id = v.client.instance_id
            await v.delete()
            from copycat_tpu.atomic import commands as vc
            from copycat_tpu.manager.operations import InstanceQuery
            from copycat_tpu.resource.operations import ResourceQuery
            try:
                await client.client.submit(InstanceQuery(
                    instance_id, ResourceQuery(vc.Get(), "sequential")))
                outcomes.append(("ok",))
            except Exception as e:  # noqa: BLE001 — the surface under test
                outcomes.append((type(e).__name__, str(e)))
        finally:
            await asyncio.wait_for(client.close(), 5)
            await asyncio.wait_for(server.close(), 5)
    assert outcomes[0] == outcomes[1], outcomes
    assert outcomes[0][0] == "ApplicationError"
