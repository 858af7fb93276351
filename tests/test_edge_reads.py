"""Edge read tier tests (docs/EDGE_READS.md).

Covers the tentpole contracts:

- local serving + delta convergence on the live stack (reads stop
  touching the cluster once seeded; writes propagate via deltas);
- the knob-off differential: ``COPYCAT_EDGE_READS=0`` produces the
  same observable results with ZERO edge machinery (no subscriptions,
  no deltas, no extra wire fields — byte-identity of the unsubscribed
  frames is locked by the PR 9 goldens in test_trace_plane.py);
- merge safety: duplicated / reordered / re-delivered deltas converge
  (join-semilattice, max-version-wins);
- session guarantees under the delta-plane nemesis (partition,
  reconnect, leader failover) under ``COPYCAT_INVARIANTS=strict``:
  no cache-served read ever violates monotone-reads or
  read-your-writes against a linearizable witness read;
- the staleness gate, the LRU bound + keep-alive unsubscribe, and
  retirement on resource delete.
"""

import asyncio
import os

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong  # noqa: E402
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.io.local import (  # noqa: E402
    LocalServerRegistry, LocalTransport, NetworkNemesis)
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402
from copycat_tpu.server.raft import LEADER  # noqa: E402
from copycat_tpu.utils import tracing  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402


async def _stack(registry, members: int = 1, session_timeout: float = 20.0):
    addrs = next_ports(members)
    servers = [AtomixServer(a, addrs,
                            LocalTransport(registry, local_address=a),
                            election_timeout=0.3, heartbeat_interval=0.05,
                            session_timeout=session_timeout)
               for a in addrs]
    await asyncio.gather(*(s.open() for s in servers))
    return servers


async def _close_all(clients, servers):
    for c in clients:
        try:
            await asyncio.wait_for(c.close(), 5)
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    for s in servers:
        await asyncio.wait_for(s.close(), 10)


def _edge_snap(client) -> dict:
    return {k: v for k, v in client.client.metrics.snapshot().items()
            if str(k).startswith("edge.")}


# ---------------------------------------------------------------------------
# local serving + delta propagation
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_warm_reads_never_touch_the_server():
    """After the subscribing first read, SEQUENTIAL reads serve from
    the client replica: the server's read counters stop moving while
    local serves accumulate, and a write propagates via the delta."""
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    writer = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    reader = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await writer.open()
    await reader.open()
    try:
        w = await writer.get("ctr", DistributedAtomicLong)
        r = await reader.get("ctr", DistributedAtomicLong)
        r.with_consistency(Consistency.SEQUENTIAL)
        await w.add_and_get(3)
        assert await r.get() == 3  # subscribing read (server, seeds)

        def server_reads() -> int:
            snap = server.server.metrics.snapshot()
            return sum(v for k, v in snap.items()
                       if str(k).startswith("query_reads"))

        before = server_reads()
        for _ in range(20):
            assert await r.get() == 3
        assert server_reads() == before, "warm reads must stay local"
        snap = _edge_snap(reader)
        assert snap["edge.local_serves"] >= 20, snap
        assert snap["edge.seeds"] >= 1, snap
        for key in ("edge.server_fallbacks", "edge.merges",
                    "edge.evictions", "edge.stale_rejections"):
            assert key in snap, (key, sorted(snap))
        # a cache-served read's trace is client-side only: one
        # client.edge_serve span, no proxy.hop, no quorum.wait
        tracing.enable()
        try:
            assert await r.get() == 3
            served = [spans for spans in tracing.TRACER.traces().values()
                      if any(s.name == "client.edge_serve" for s in spans)]
        finally:
            tracing.disable()
        assert served, "the local serve recorded no span"
        assert {s.name for s in served[-1]} == {"client.edge_serve"}

        await w.add_and_get(4)
        # the delta flush rides the apply turn; give the push a beat
        for _ in range(50):
            if await r.get() == 7:
                break
            await asyncio.sleep(0.01)
        assert await r.get() == 7
        assert _edge_snap(reader)["edge.deltas_in"] >= 1
        ssnap = server.server.metrics.snapshot()
        assert ssnap["edge.subscribes"] >= 1
        assert ssnap["edge.deltas_sent"] >= 1
        assert ssnap["edge.delta_flushes"] >= 1
        assert ssnap["edge.subscriptions"] >= 1
    finally:
        await _close_all([writer, reader], [server])


@async_test(timeout=120)
async def test_map_reads_serve_locally():
    """Map gets/sizes/membership evaluate client-side from the tagged
    full-state replica with the CPU machine's exact semantics."""
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        m = await client.get("m", DistributedMap)
        m.with_consistency(Consistency.SEQUENTIAL)
        await m.put("a", 1)
        await m.put("b", None)
        assert await m.get("a") == 1  # seeds
        serves0 = _edge_snap(client)["edge.local_serves"]
        assert await m.get("a") == 1
        assert await m.get("b") is None
        assert await m.get("missing") is None
        assert await m.get_or_default("b", 9) is None  # present-but-None
        assert await m.get_or_default("missing", 9) == 9
        assert await m.contains_key("a") is True
        assert await m.size() == 2
        assert await m.is_empty() is False
        assert _edge_snap(client)["edge.local_serves"] > serves0
    finally:
        await _close_all([client], [server])


# ---------------------------------------------------------------------------
# the knob-off differential
# ---------------------------------------------------------------------------


@async_test(timeout=240)
async def test_knob_off_differential(monkeypatch):
    """A SAME-session write/read script — the strongest sequence the
    CAUSAL/SEQUENTIAL contract promises determinism for (every read
    must reflect the session's own completed writes) — produces
    identical results on both planes, and a cross-client phase
    converges to the same final value. With the knob off there is NO
    edge machinery — the client has no tier, requests carry no
    subscribe field, the server registers nothing and pushes nothing
    (the unsubscribed wire frames are byte-identical to the PR 9
    goldens — locked on both codecs by tests/test_trace_plane.py)."""
    outcomes = []
    for edge_on in (True, False):
        monkeypatch.setenv("COPYCAT_EDGE_READS", "1" if edge_on else "0")
        registry = LocalServerRegistry()
        (server,) = await _stack(registry)
        writer = AtomixClient([server.server.address],
                              LocalTransport(registry),
                              session_timeout=20.0)
        reader = AtomixClient([server.server.address],
                              LocalTransport(registry),
                              session_timeout=20.0)
        await writer.open()
        await reader.open()
        try:
            c = await reader.get("own", DistributedAtomicLong)
            c.with_consistency(Consistency.SEQUENTIAL)
            seen = []
            for i in range(6):  # same-session: deterministic via RYW
                await c.add_and_get(i + 1)
                seen.append(await c.get())
                seen.append(await c.get())
            # cross-client phase: eventual convergence (per-read
            # freshness against ANOTHER session's writes is exactly
            # what CAUSAL/SEQUENTIAL do not promise)
            w = await writer.get("shared", DistributedAtomicLong)
            r = await reader.get("shared", DistributedAtomicLong)
            r.with_consistency(Consistency.SEQUENTIAL)
            for _ in range(5):
                await w.add_and_get(2)
            final = None
            for _ in range(200):
                final = await r.get()
                if final == 10:
                    break
                await asyncio.sleep(0.01)
            seen.append(final)
            outcomes.append(seen)
            if edge_on:
                assert reader.client._edge is not None
            else:
                assert reader.client._edge is None
                assert _edge_snap(reader) == {}
                ssnap = server.server.metrics.snapshot()
                assert ssnap["edge.subscribes"] == 0
                assert ssnap["edge.deltas_sent"] == 0
        finally:
            await _close_all([writer, reader], [server])
    assert outcomes[0] == outcomes[1]


# ---------------------------------------------------------------------------
# merge safety: duplicated / reordered / re-delivered deltas
# ---------------------------------------------------------------------------


def test_merge_is_idempotent_commutative_associative():
    """max-version-wins over log-ordered versions is a join-semilattice:
    any delivery order, duplication, or re-delivery of the same delta
    set converges to the same replica state."""
    import itertools
    import random

    from copycat_tpu.client.edge import EdgeReadTier

    class _FakeClient:
        _num_groups = 1
        _indices: dict = {}

        def _note_index(self, value):
            pass

    from copycat_tpu.utils.metrics import MetricsRegistry

    deltas = [(7, 3, ("val", 30)), (7, 5, ("val", 50)),
              (7, 4, ("val", 40)), (7, 5, ("val", 50)),
              (7, 6, ("r", None))]

    states = set()
    orders = list(itertools.permutations(deltas))
    random.Random(5).shuffle(orders)
    for order in orders[:40]:
        fake = _FakeClient()
        fake.metrics = MetricsRegistry()
        tier = EdgeReadTier(fake)
        tier.seed([(7, 1, ("val", 10))])
        for d in order:
            tier.ingest([d])
            tier.ingest([d])  # duplicated delivery
        entry = tier._replica[7]
        states.add((entry.version, entry.state))
    assert states == {(6, 50)}


def test_retire_delta_drops_the_entry():
    from copycat_tpu.client.edge import EdgeReadTier
    from copycat_tpu.utils.metrics import MetricsRegistry

    class _FakeClient:
        _num_groups = 1
        _indices: dict = {}
        metrics = MetricsRegistry()

        def _note_index(self, value):
            pass

    tier = EdgeReadTier(_FakeClient())
    tier.seed([(7, 1, ("val", 10))])
    assert 7 in tier._replica
    tier.ingest([(7, 9, None)])
    assert 7 not in tier._replica
    # unknown-instance deltas are never adopted
    tier.ingest([(8, 1, ("val", 5))])
    assert 8 not in tier._replica


# ---------------------------------------------------------------------------
# staleness gate, LRU bound, unsubscribe, delete retirement
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_staleness_gate_re_seeds(monkeypatch):
    monkeypatch.setenv("COPYCAT_EDGE_TTL_S", "0.05")
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        c = await client.get("ctr", DistributedAtomicLong)
        c.with_consistency(Consistency.SEQUENTIAL)
        await c.add_and_get(1)
        assert await c.get() == 1  # seeds
        assert await c.get() == 1  # local
        await asyncio.sleep(0.1)   # TTL expires with no delta traffic
        assert await c.get() == 1  # falls back + re-seeds
        snap = _edge_snap(client)
        assert snap["edge.stale_rejections"] >= 1, snap
        assert snap["edge.seeds"] >= 2, snap
    finally:
        await _close_all([client], [server])


@async_test(timeout=120)
async def test_lru_bound_and_keepalive_unsubscribe(monkeypatch):
    monkeypatch.setenv("COPYCAT_EDGE_MAX_RESOURCES", "2")
    registry = LocalServerRegistry()
    (server,) = await _stack(registry, session_timeout=1.2)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=1.2)
    await client.open()
    try:
        ctrs = []
        for i in range(4):
            c = await client.get(f"c{i}", DistributedAtomicLong)
            c.with_consistency(Consistency.SEQUENTIAL)
            await c.add_and_get(1)
            assert await c.get() == 1
            ctrs.append(c)
        snap = _edge_snap(client)
        assert snap["edge.replica_entries"] <= 2, snap
        assert snap["edge.evictions"] >= 2, snap
        # the keep-alive carries the staged unsubscribes (interval =
        # session_timeout / 4 = 0.3 s)
        for _ in range(40):
            if server.server.metrics.snapshot()["edge.unsubscribes"] >= 2:
                break
            await asyncio.sleep(0.05)
        ssnap = server.server.metrics.snapshot()
        assert ssnap["edge.unsubscribes"] >= 2, ssnap
        assert ssnap["edge.subscriptions"] <= 2, ssnap
    finally:
        await _close_all([client], [server])


@async_test(timeout=120)
async def test_ttl_state_never_seeds_and_declines_negative_cache():
    """A value with an armed TTL is not edge-servable (the expiry fires
    outside the apply path, invisible to the delta plane): subscribing
    reads come back seedless, the instance negative-caches so later
    reads stop asking, and every read keeps hitting the server — which
    serves the post-expiry truth."""
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        c = await client.get("ttl", DistributedAtomicLong)
        c.with_consistency(Consistency.SEQUENTIAL)
        await c.set(5, ttl=0.2)
        assert await c.get() == 5          # server read, no seed
        assert await c.get() == 5          # still server (negative-cached)
        snap = _edge_snap(client)
        assert snap["edge.seeds"] == 0, snap
        assert snap["edge.replica_entries"] == 0, snap
        assert server.server.metrics.snapshot()["edge.subscribes"] == 0
        assert client.client._edge._no_seed, "seedless decline not cached"
        await asyncio.sleep(0.4)           # device/host TTL fires
        assert await c.get() == 0          # post-expiry truth, via server
    finally:
        await _close_all([client], [server])


def test_seed_response_negative_cache_unit():
    """Declined seeds stop subscribe attempts for one TTL interval and
    clear the moment a seed arrives."""
    from copycat_tpu.client.edge import EdgeReadTier
    from copycat_tpu.manager.operations import InstanceQuery
    from copycat_tpu.resource.operations import ResourceQuery
    from copycat_tpu.atomic import commands as vc
    from copycat_tpu.utils.metrics import MetricsRegistry

    class _FakeClient:
        _num_groups = 1
        _indices: dict = {}
        metrics = MetricsRegistry()

        def _note_index(self, value):
            pass

    tier = EdgeReadTier(_FakeClient())
    op = InstanceQuery(7, ResourceQuery(vc.Get(), "sequential"))
    items = [(op, None)]
    assert tier.wants_subscribe(items) is True
    tier.seed_response(items, None)        # server declined
    assert tier.wants_subscribe(items) is False
    tier.seed_response(items, [(7, 3, ("val", 9))])  # later seed clears
    assert 7 not in tier._no_seed
    assert 7 in tier._replica


@async_test(timeout=120)
async def test_delete_retires_the_replica():
    """Deleting a subscribed resource pushes retire deltas: the replica
    entry drops and the next read surfaces the server's error instead
    of a cached ghost value."""
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        c = await client.get("doomed", DistributedAtomicLong)
        c.with_consistency(Consistency.SEQUENTIAL)
        await c.set(5)
        assert await c.get() == 5
        assert await c.get() == 5  # local
        assert _edge_snap(client)["edge.replica_entries"] >= 1
        await c.delete()
        for _ in range(50):
            if _edge_snap(client)["edge.replica_entries"] == 0:
                break
            await asyncio.sleep(0.01)
        assert _edge_snap(client)["edge.replica_entries"] == 0
        assert server.server.metrics.snapshot()["edge.entries_retired"] >= 1
    finally:
        await _close_all([client], [server])


# ---------------------------------------------------------------------------
# delta-plane nemesis: partition, reconnect, failover — session
# guarantees against a linearizable witness, strict invariants
# ---------------------------------------------------------------------------


@async_test(timeout=420)
async def test_nemesis_monotone_and_ryw_against_linearizable_witness(
        monkeypatch):
    """A reader serving from its edge replica through a leader
    partition + failover + heal never observes the counter going
    BACKWARDS (monotone reads) and never observes a value the
    linearizable witness hasn't admitted yet (the counter only grows,
    so any served v must satisfy last_seen <= v <= witness-now).
    Per-read freshness against the WRITER's session is deliberately
    not asserted — CAUSAL/SEQUENTIAL permit bounded staleness — but
    the run must converge to the full total."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    registry = LocalServerRegistry()
    nem = NetworkNemesis(seed=3)
    registry.attach_nemesis(nem)
    # session_timeout is a harness parameter, not what's under test: it
    # only needs to outlive any slow moment (cold jit compiles, a
    # saturated CI host) so keep-alives never starve mid-nemesis —
    # 8 s flaked as SessionExpiredError deep in the full suite
    servers = await _stack(registry, members=3, session_timeout=20.0)
    addrs = [s.server.address for s in servers]
    # the writer's is also its commands' per-try timeout: the first write
    # after the partition is taken by the old leader, which keeps its role
    # and never answers, and LocalConnection fails no send in flight, so
    # that write waits these 10 s before it rides the connection the
    # keep-alive has found by then (ROADMAP Queue 3 item 1)
    writer = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=10.0)
    reader = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=20.0)
    await writer.open()
    await reader.open()
    try:
        w = await writer.get("ctr", DistributedAtomicLong)
        r = await reader.get("ctr", DistributedAtomicLong)
        r.with_consistency(Consistency.SEQUENTIAL)
        # the witness reads linearizably through its own client
        witness = await writer.get("ctr", DistributedAtomicLong)

        total = 0
        last_seen = 0

        async def check_read() -> None:
            nonlocal last_seen
            v = await asyncio.wait_for(r.get(), 10.0)
            assert v >= last_seen, (v, last_seen, "monotone violation")
            wit = await asyncio.wait_for(witness.get(), 10.0)
            assert v <= wit, (v, wit, "read ahead of linearizable state")
            last_seen = v

        for i in range(4):
            total += 1
            await asyncio.wait_for(w.add_and_get(1), 10.0)
            await check_read()
        # partition the current leader away; the majority elects
        leader = next(s.server for s in servers
                      if s.server.role == LEADER)
        minority = [leader.address]
        majority = [a for a in addrs if a != leader.address]
        nem.partition(minority, majority)
        # reads during the partition keep serving (stale-but-monotone
        # from the replica, or via a reachable member once re-routed)
        for _ in range(3):
            await check_read()
        # writes re-route to the new leader; reads must catch up
        for _ in range(4):
            total += 1
            await asyncio.wait_for(w.add_and_get(1), 30.0)
            await check_read()
        nem.heal()
        for _ in range(3):
            total += 1
            await asyncio.wait_for(w.add_and_get(1), 30.0)
            await check_read()
        # convergence: the reader eventually serves the full total
        for _ in range(200):
            if await asyncio.wait_for(r.get(), 10.0) == total:
                break
            await asyncio.sleep(0.05)
        assert await r.get() == total
    finally:
        nem.heal()
        await _close_all([writer, reader], servers)


@async_test(timeout=300)
async def test_ryw_through_own_writes(monkeypatch):
    """Read-your-writes via the client seq space: a client that writes
    then reads through the edge tier sees its own write — the write's
    response index raises the read floor past any stale replica entry
    (stale-reject + re-seed, never a stale serve)."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        c = await client.get("ctr", DistributedAtomicLong)
        c.with_consistency(Consistency.SEQUENTIAL)
        v = 0
        for i in range(12):
            v = await c.add_and_get(1)
            got = await c.get()
            assert got == v, (got, v, "read-your-writes violation")
    finally:
        await _close_all([client], [server])


@async_test(timeout=300)
async def test_reconnect_re_seeds_instead_of_serving_blind():
    """When the session connection moves (server restart of the event
    channel's holder is approximated by bouncing the connection), the
    server retires the undeliverable subscriptions; the client's TTL +
    re-seed path takes over — reads still return correct values."""
    registry = LocalServerRegistry()
    (server,) = await _stack(registry)
    client = AtomixClient([server.server.address],
                          LocalTransport(registry), session_timeout=20.0)
    await client.open()
    try:
        c = await client.get("ctr", DistributedAtomicLong)
        c.with_consistency(Consistency.SEQUENTIAL)
        await c.add_and_get(1)
        assert await c.get() == 1
        # bounce the session connection: deltas in the gap are lost and
        # the flush-side dead-connection rule drops the subscriptions
        client.client._drop_connection()
        await c.add_and_get(1)  # reconnects, commits
        for _ in range(100):
            if await c.get() == 2:
                break
            await asyncio.sleep(0.02)
        assert await c.get() == 2
    finally:
        await _close_all([client], [server])
