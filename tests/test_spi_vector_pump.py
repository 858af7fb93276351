"""Differential proof for the batched server-side session pump.

The vector lane (``RaftServer.flush_fused`` + ``DeviceEngine.
run_vector``) commits whole runs of device-eligible commands as tensors
through one shared engine round instead of per-op generator chains. Its
contract is BIT-IDENTICAL observable behavior to the host state
machines (``executor="cpu"``, the independent reference): same results,
same per-session event order, same exactly-once dedup under duplicate
delivery and faults. These tests prove it by running the same seeded op
script through both executors and comparing everything the client can
see, then racing the batched path against a response-dropping /
lossy-partition nemesis.

The flush-error split (ADVICE r5 #1: pre-dispatch failures restore
``_pending`` and re-raise, only abandoned drives mark INDETERMINATE)
and the deliver-until-close event contract (ADVICE r5 #2) are covered
at the BulkSessionClient layer below.
"""

import asyncio
import random

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong, DistributedAtomicValue  # noqa: E402
from copycat_tpu.io.local import (  # noqa: E402
    LocalServerRegistry, LocalTransport, NetworkNemesis)
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.models import BulkSessionClient  # noqa: E402
from copycat_tpu.models.session_client import (  # noqa: E402
    CommandIndeterminateError)
from copycat_tpu.ops import apply as ap  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import MONOTONE, SERVED, device_plane  # noqa: E402



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


def _script(seed: int, n_waves: int, wave: int):
    """Seeded op script over 3 plain values (vector-eligible steady
    state) + 1 listened value (listener forces the generator path, so
    every wave mixes eligible and ineligible entries and the pump's
    run-bounding is exercised)."""
    rng = random.Random(seed)
    waves = []
    for _ in range(n_waves):
        ops = []
        for _ in range(wave):
            target = rng.randrange(4)
            kind = rng.randrange(4)
            ops.append((target, kind, rng.randrange(5), rng.randrange(5)))
        waves.append(ops)
    return waves


async def _run_script(client, waves):
    """Execute the script; returns (results, events, finals) — the full
    client-observable history."""
    values = [await client.get(f"v{i}", DistributedAtomicValue)
              for i in range(4)]
    events: list[tuple[int, int]] = []
    listener = await values[3].on_change(
        lambda v: events.append((3, v)))
    for i, v in enumerate(values):
        await v.set(i)  # deterministic non-None base; lands on device
    results = []
    for ops in waves:
        async def one(target, kind, a, b):
            v = values[target]
            if kind == 0:
                await v.set(a)
                return ("set", None)
            if kind == 1:
                return ("cas", await v.compare_and_set(a, b))
            if kind == 2:
                return ("gas", await v.get_and_set(a))
            return ("get", await v.get())
        results.append(await asyncio.gather(
            *(one(*op) for op in ops)))
    finals = [await v.get() for v in values]
    listener.close()
    await asyncio.sleep(0.05)  # drain in-flight publishes
    return results, events, finals


async def _history(executor: str, waves):
    """The script's client-observable history on one executor, plus the
    server's vector-lane counters."""
    registry = LocalServerRegistry()
    server, client = await _spi_cluster(registry, executor)
    try:
        history = await _run_script(client, waves)
        snap = server.server.metrics.snapshot()
        return history, (snap.get("vector_runs", 0), snap.get("vector_ops", 0))
    finally:
        await asyncio.wait_for(client.close(), 5)
        await asyncio.wait_for(server.close(), 5)


@async_test(timeout=300)
async def test_vector_pump_bit_identical_to_per_op_path():
    """Same seeded script, the device server against the host state
    machines: results, per-session event order, and final state must be
    identical."""
    waves = _script(seed=42, n_waves=6, wave=32)
    (res_dev, ev_dev, fin_dev), (_, vops) = await _history("tpu", waves)
    (res_cpu, ev_cpu, fin_cpu), cpu_lane = await _history("cpu", waves)
    assert res_dev == res_cpu, "vector pump diverged from host results"
    assert ev_dev == ev_cpu, "vector pump diverged in event order"
    assert fin_dev == fin_cpu, "vector pump diverged in final state"
    # the script genuinely exercised the vector lane on the device server
    # and never on the reference, and CAS outcomes of both kinds appeared
    # (device CAS success + failure finalize arms)
    assert vops > 0 and cpu_lane == (0, 0)
    cas = [r[1] for wave in res_dev for r in wave if r[0] == "cas"]
    assert True in cas and False in cas


@async_test(timeout=300)
async def test_commit_window_of_one_eligible_command_matches_cpu():
    """The vector lane's smallest shape: each commit window holds ONE
    eligible command (a client that waits for every reply), so each run
    is one row and the fused flush carries one group. It must finalize
    as the host state machines do."""
    waves = _script(seed=7, n_waves=24, wave=1)
    (res_dev, ev_dev, fin_dev), (runs, vops) = await _history("tpu", waves)
    (res_cpu, ev_cpu, fin_cpu), _ = await _history("cpu", waves)
    assert (res_dev, ev_dev, fin_dev) == (res_cpu, ev_cpu, fin_cpu)
    # single-row runs: as many runs as rows
    assert runs == vops > 0


@async_test(timeout=300)
async def test_vector_pump_exactly_once_under_duplicate_delivery():
    """Response-leg loss makes the client resend whole committed batches
    (duplicate delivery of every entry): the server's session-seq dedup
    must serve cached responses, never re-apply. The final counter
    equals the exact number of acked increments."""
    registry = LocalServerRegistry()
    nemesis = registry.attach_nemesis(NetworkNemesis(seed=7))
    server, client = await _spi_cluster(registry)
    try:
        counter = await client.get("c", DistributedAtomicLong)
        await counter.increment_and_get()  # settle to steady state
        nemesis.set_loss(response=0.3)
        acked = 0
        for _ in range(40):
            await counter.increment_and_get()
            acked += 1
        nemesis.heal()
        value = await counter.get()
        assert value == acked + 1, (
            f"duplicate delivery broke exactly-once: {value} != {acked + 1}")
        assert nemesis.dropped_responses > 0, "nemesis never fired"
    finally:
        nemesis.heal()
        await asyncio.wait_for(client.close(), 5)
        await asyncio.wait_for(server.close(), 5)


@async_test(timeout=300)
async def test_vector_pump_partition_mid_batch_no_duplicate_applies():
    """A lossy partition (both legs) opens mid-storm and heals: every
    increment is eventually acked exactly once — a dropped request never
    applied, a dropped response applied once and deduped on resend."""
    registry = LocalServerRegistry()
    nemesis = registry.attach_nemesis(NetworkNemesis(seed=11))
    server, client = await _spi_cluster(registry)
    try:
        counter = await client.get("c", DistributedAtomicLong)
        await counter.increment_and_get()
        acked = 0

        async def storm(n):
            nonlocal acked
            for _ in range(n):
                await asyncio.wait_for(counter.increment_and_get(), 60)
                acked += 1

        task = asyncio.ensure_future(storm(30))
        await asyncio.sleep(0.02)
        nemesis.set_loss(request=0.4, response=0.4)  # partition opens
        await asyncio.sleep(0.3)
        nemesis.heal()
        await asyncio.wait_for(task, 120)
        value = await counter.get()
        assert value == acked + 1, (
            f"partition mid-batch broke exactly-once: {value} != "
            f"{acked + 1}")
    finally:
        nemesis.heal()
        await asyncio.wait_for(client.close(), 5)
        await asyncio.wait_for(server.close(), 5)


# ---------------------------------------------------------------------------
# BulkSessionClient flush-error split + deliver-until-close (ADVICE r5)


@pytest.fixture()
def deep_rg():
    rg = device_plane(MONOTONE, seed=13)
    rg.wait_for_leaders()
    return rg


def test_flush_pre_dispatch_error_restores_pending(deep_rg):
    """A failure raised BEFORE any device dispatch (no tags consumed)
    must restore the chunks to the sessions' _pending and re-raise —
    the commands definitely did not apply, so INDETERMINATE (which
    forces the correlate-a-read recovery path) would discard that."""
    client = BulkSessionClient(deep_rg)
    s = client.open_session()
    seqs = s.submit_batch([0] * 4, ap.OP_LONG_ADD, 1)
    real_drive = client._driver.drive
    client._driver.drive = lambda *a, **k: (_ for _ in ()).throw(
        ValueError("accumulators too skewed"))
    with pytest.raises(ValueError):
        client.flush()
    assert len(s._pending) == 1, "pre-dispatch failure must restore chunks"
    for q in seqs:
        assert int(q) not in s._results, "no result may be recorded"
    # the restored chunk commits exactly once on the next (healthy) flush
    client._driver.drive = real_drive
    assert client.flush() == 4
    assert list(s.results_window(int(seqs[0]), 4)) == [1, 2, 3, 4]


def test_flush_timeout_marks_indeterminate(deep_rg):
    """An abandoned drive (TimeoutError: the command MAY have applied)
    keeps the indeterminate marking."""
    client = BulkSessionClient(deep_rg)
    s = client.open_session()
    seqs = s.submit_batch([1] * 3, ap.OP_LONG_ADD, 1)
    client._driver.drive = lambda *a, **k: (_ for _ in ()).throw(
        TimeoutError("drive abandoned"))
    with pytest.raises(TimeoutError):
        client.flush()
    assert not s._pending, "abandoned commands must not be re-staged"
    with pytest.raises(CommandIndeterminateError):
        s.result(int(seqs[0]))


def test_events_delivered_until_close(deep_rg):
    """A gracefully closed session's listeners still receive the events
    committed by the flush that commits its close (the reference's
    deliver-until-close session event contract)."""
    client = BulkSessionClient(deep_rg)
    watcher = client.open_session()
    worker = client.open_session()
    group = 2
    got: list = []
    watcher.on_event(group, got.append)
    # the worker's topic publish emits a broadcast event on the group;
    # the watcher closes in the SAME flush that commits the event
    worker.submit(group, ap.OP_TOPIC_LISTEN, worker.id)
    worker.submit(group, ap.OP_TOPIC_PUB, 41)
    watcher.close()
    client.flush()
    assert [e.arg for e in got] == [41], (
        "closing session missed events committed by its own flush")
    assert watcher.id not in client._sessions, "closed session must leave"


# ---------------------------------------------------------------------------
# DistributedLock on the vector lane: Lock(-1), Lock(0) and Unlock as one
# device op each, the grant a release causes published inside its entry


async def _lock_history(executor: str):
    """Two sessions over three locks, contended and not, a try-lock that
    is refused and one that is taken, and a bounded wait that arms a timer
    (the lane falls back to the chain while it is armed). Every wave is
    one session's, so the log's order is the script's on both executors.
    Returns what the clients saw, with every log index (waiter ids,
    instance ids) as its rank among them, and what the server kept."""
    from copycat_tpu.coordination import DistributedLock
    from copycat_tpu.server.log import CommandEntry

    registry = LocalServerRegistry()
    server, a = await _spi_cluster(registry, executor)
    b = AtomixClient(a.client.members, LocalTransport(registry),
                     session_timeout=20.0)
    await b.open()
    try:
        la = [await a.get(f"l{i}", DistributedLock) for i in range(3)]
        lb = [await b.get(f"l{i}", DistributedLock) for i in range(3)]
        seen: dict[str, list] = {"a": [], "b": []}
        for name, client in (("a", a), ("b", b)):
            client.client.session().on_event(
                "lock", lambda m, _n=name: seen[_n].append(
                    (m.resource, m.message["id"], m.message["acquired"])))
        replies: list = []

        async def call(lock, what, *args):
            got = await getattr(lock, what)(*args)
            replies.append((what, got))
            return got

        wave = asyncio.gather
        await wave(*(call(l, "lock") for l in la))           # free: granted
        waits = [asyncio.ensure_future(call(l, "lock")) for l in lb[:2]]
        assert await call(lb[2], "try_lock") is False         # held: refused
        await asyncio.sleep(0.05)
        await wave(*(call(l, "unlock") for l in la[:2]))      # grants cross
        await wave(*waits)
        assert await call(la[0], "try_lock") is False
        # a holder's unlock and the next contender's lock in ONE batch
        again = [asyncio.ensure_future(call(l, "lock")) for l in la[:2]]
        await asyncio.sleep(0.05)
        await wave(*(call(l, "unlock") for l in lb[:2]))
        await wave(*again)
        # a bounded wait arms a timer: this lock is on the chain until the
        # waiter is granted
        timed = asyncio.ensure_future(call(lb[2], "try_lock", 30.0))
        await asyncio.sleep(0.05)
        await call(la[2], "unlock")
        assert await timed is True
        await call(lb[2], "unlock")
        assert await call(lb[2], "try_lock") is True          # free: taken
        await call(lb[2], "unlock")
        await wave(*(call(l, "unlock") for l in la[:2]))
        await asyncio.sleep(0.05)  # drain in-flight publishes

        raft = server.server
        log = raft.log
        commands = [e for e in (log.get(i) for i in range(
            1, log.last_index + 1)) if type(e) is CommandEntry]
        ids = {x for ev in seen.values() for r, i, _ in ev for x in (r, i)}
        ordinal = {x: k for k, x in enumerate(sorted(ids))}
        sessions = sorted(raft.sessions.values(), key=lambda s: s.id)
        manager = raft.state_machine
        engine = getattr(manager, "device_engine", None)
        counters = (0, 0)
        if executor == "tpu":
            c = engine._groups.metrics.counter
            counters = (c("lock_vector_ops").value, c("lock_chain_ops").value)
        return {
            "replies": replies,
            "events": {n: [(ordinal[r], ordinal[i], ok) for r, i, ok in ev]
                       for n, ev in seen.items()},
            "event_index": [(s.event_index, s.event_ack_index)
                            for s in sessions],
            "client_index": [c.client.session().event_index for c in (a, b)],
            # the commands still retained (a cleaned entry may be
            # compacted away already): the six that opened the locks
            "retained": [type(e.operation).__name__ for e in commands
                         if not log.is_cleaned(e.index)],
        }, counters
    finally:
        for node in (a, b, server):
            await asyncio.wait_for(node.close(), 5)


@async_test(timeout=300)
async def test_lock_on_the_vector_lane_is_the_cpu_lock_state():
    """The same script on the device executor and on the CPU ``LockState``:
    every reply, each session's events in order and content, the sessions'
    ``event_index`` (a batch an entry) and which retained commits were
    cleaned are equal; the device run took the vector lane for all but
    the lock with the armed timer."""
    dev, (vector, chain) = await _lock_history("tpu")
    cpu, _ = await _lock_history("cpu")
    assert dev == cpu
    assert len(dev["events"]["a"]) >= 5 and len(dev["events"]["b"]) >= 5
    assert vector >= 18 and 1 <= chain <= 4


@async_test(timeout=300)
async def test_lock_vector_lane_resumes_from_a_snapshot_mid_queue():
    """A holder and two waiters, the machine's host record taken as a
    snapshot and restored over it: the look-ahead reads the stand-in
    commits, and the queue moves on down the vector lane in order."""
    from copycat_tpu.coordination import DistributedLock
    from copycat_tpu.manager.device_executor import DeviceLockState

    registry = LocalServerRegistry()
    server, a = await _spi_cluster(registry)
    others = []
    try:
        for _ in range(2):
            c = AtomixClient(a.client.members, LocalTransport(registry),
                             session_timeout=20.0)
            await c.open()
            others.append(c)
        locks = [await c.get("lock", DistributedLock)
                 for c in (a, *others)]
        await locks[0].lock()
        waits = [asyncio.ensure_future(l.lock()) for l in locks[1:]]
        await asyncio.sleep(0.1)
        manager = server.server.state_machine
        (machine,) = [h.state_machine for h in manager.resources.values()
                      if isinstance(h.state_machine, DeviceLockState)]
        image = machine.snapshot_state()
        assert image["holder"] is not None and len(image["waiters"]) == 3
        sessions = {c.session.id: c.session
                    for c in machine._waiters.values()}
        machine._waiters.clear()
        machine._holder_id = None
        machine.restore_state(image, {})
        for session in sessions.values():
            machine.register(session)
        counter = manager.device_engine._groups.metrics.counter
        chain0 = counter("lock_chain_ops").value
        order = []
        for k, lock in enumerate(locks):
            if k:
                await asyncio.wait_for(waits[k - 1], 10)
            order.append(k)
            await lock.unlock()
        assert order == [0, 1, 2]
        assert counter("lock_chain_ops").value == chain0
        assert machine._holder_id is None and not machine._waiters
        assert await locks[0].try_lock()
    finally:
        for node in (a, *others, server):
            await asyncio.wait_for(node.close(), 5)


def test_plain_locks_and_the_cpu_lock_state_agree():
    """``benchmarks/reference_lock.PlainLocks`` (the benchmark's plain
    reference) against the CPU ``LockState`` on a seeded sequence of
    10,000 acquires, try-locks and releases over 16 locks: who holds each
    lock and who waits, after every step."""
    import importlib.util
    import os

    from copycat_tpu.coordination import commands as oc
    from copycat_tpu.coordination.state import LockState
    from copycat_tpu.server.state_machine import Commit

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference_lock.py")
    spec = importlib.util.spec_from_file_location("reference_lock", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    class Session:
        is_open = True

        def __init__(self, sid):
            self.id, self.events = sid, []

        def publish(self, event, message):
            self.events.append((message["id"], message["acquired"]))

    class Log:
        def clean(self, index):
            pass

    n_locks, rng = 16, random.Random(40)
    plain = ref.PlainLocks(n_locks)
    machines = [LockState() for _ in range(n_locks)]
    sessions = [Session(s) for s in range(6)]
    owner: dict[int, int] = {}            # waiter id -> session
    granted: list[tuple[int, int]] = []   # (lock, id) by the plain locks
    for index in range(1, 10_001):
        i = rng.randrange(n_locks)
        holder = plain.holder(i)
        if holder is not None and rng.random() < 0.45:
            passed = plain.release(i, holder)
            if passed is not None:
                granted.append((i, passed))
            machines[i].unlock(Commit(index, sessions[owner[holder]], 0.0,
                                      oc.Unlock(), Log()))
        else:
            s = rng.randrange(len(sessions))
            wait = rng.random() < 0.8
            owner[index] = s
            if plain.acquire(i, index, wait=wait):
                granted.append((i, index))
            machines[i].lock(Commit(index, sessions[s], 0.0,
                                    oc.Lock(timeout=-1 if wait else 0),
                                    Log()))
        state = machines[i]
        assert (state._holder.index if state._holder else None) \
            == plain.holder(i)
        assert [c.index for c in state._queue] == plain.waiting(i)
    told = sorted((wid, ok) for s in sessions for wid, ok in s.events)
    assert [wid for wid, ok in told if ok] == sorted(w for _, w in granted)
    assert len(granted) > 3000


# ---------------------------------------------------------------------------
# The event plane: one push loop a session, coalesced sends, batches sealed
# an entry


@async_test(timeout=300)
async def test_events_once_and_in_order_when_a_publish_response_is_lost():
    """Grants cross from one session's unlocks to another's event stream
    in coalesced PublishRequests. The response to one of them is lost
    after the client has taken its events: the server sends them again
    with what was sealed meanwhile, the client answers with its position,
    and the listener still sees every grant exactly once and in order."""
    from copycat_tpu.coordination import DistributedLock
    from copycat_tpu.io.transport import TransportError
    from copycat_tpu.protocol import messages as msg

    registry = LocalServerRegistry()
    server, a = await _spi_cluster(registry)
    b = AtomixClient(a.client.members, LocalTransport(registry),
                     session_timeout=20.0)
    await b.open()
    try:
        n = 6
        la = [await a.get(f"l{i}", DistributedLock) for i in range(n)]
        lb = [await b.get(f"l{i}", DistributedLock) for i in range(n)]
        seen: list = []
        b.client.session().on_event(
            "lock", lambda m: seen.append(m.message["id"]))
        raft = server.server
        session_b = raft.sessions[b.client.session().id]
        conn = session_b.connection
        real_send, sent, dropped = conn.send, [], []

        async def lossy(message):
            if type(message) is not msg.PublishRequest:
                return await real_send(message)
            batches = 1 + len(message.more or ())
            sent.append(batches)
            response = await real_send(message)
            if batches > 1 and not dropped:
                dropped.append(batches)
                raise TransportError("PublishResponse lost")
            return response

        conn.send = lossy
        for rounds in range(3):
            await asyncio.gather(*(l.lock() for l in la))
            waits = [asyncio.ensure_future(l.lock()) for l in lb]
            await asyncio.sleep(0.05)
            # one batch of unlocks: n grants to b, sealed an entry each,
            # leave in coalesced sends
            await asyncio.gather(*(l.unlock() for l in la))
            await asyncio.wait_for(asyncio.gather(*waits), 30)
            await asyncio.gather(*(l.unlock() for l in lb))
        assert dropped, f"no coalesced send to drop: {sent}"
        for _ in range(100):
            if session_b.event_ack_index == session_b.event_index:
                break
            await asyncio.sleep(0.05)
        assert len(seen) == 3 * n and seen == sorted(set(seen))
        assert session_b.event_index == 3 * n       # a batch an entry
        assert session_b.event_ack_index == 3 * n and \
            not session_b.event_queue
        assert b.client.session().event_index == 3 * n
        assert sum(sent) > 3 * n > len(sent)        # resent, and coalesced
    finally:
        for node in (a, b, server):
            await asyncio.wait_for(node.close(), 5)


@async_test(timeout=300)
async def test_members_that_cut_batches_differently_seal_equal_event_indexes(
        tmp_path, monkeypatch):
    """The live member applies the log a few entries a batch as the
    clients call; a member reborn over the same log replays it in one
    batch, many rows of a lock to a vector run. Each session's
    ``event_index`` (a batch an entry that published, whatever the cut)
    and every lock's record are equal."""
    from copycat_tpu.coordination import DistributedLock
    from copycat_tpu.server.log import Storage, StorageLevel
    from copycat_tpu.manager.device_executor import DeviceLockState
    from copycat_tpu.testing.nemesis import crash_server

    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "0")
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)

    def build():
        return AtomixServer(
            addr, [addr], LocalTransport(registry, local_address=addr),
            storage=Storage(StorageLevel.DISK, str(tmp_path / "m0")),
            election_timeout=0.2, heartbeat_interval=0.04,
            session_timeout=60.0, executor="tpu", engine_config=SERVED)

    def image(server):
        raft = server.server
        manager = raft.state_machine
        locks = {h.key: (h.state_machine._holder_id,
                         list(h.state_machine._waiters))
                 for h in manager.resources.values()
                 if isinstance(h.state_machine, DeviceLockState)}
        return ({sid: s.event_index for sid, s in raft.sessions.items()},
                locks)

    server = build()
    await server.open()
    clients = []
    reborn = None
    try:
        for _ in range(3):
            c = AtomixClient([addr], LocalTransport(registry),
                             session_timeout=60.0)
            await c.open()
            clients.append(c)
        locks = [[await c.get(f"l{i}", DistributedLock) for i in range(4)]
                 for c in clients]

        async def contend(mine):
            for _ in range(5):
                for lock in mine:
                    await lock.lock()
                    await lock.unlock()

        await asyncio.wait_for(asyncio.gather(
            *(contend(mine) for mine in locks)), 120)
        # leave a queue standing: a holder and two waiters on every lock
        await asyncio.gather(*(lock.lock() for lock in locks[0]))
        waits = [asyncio.ensure_future(lock.lock())
                 for mine in locks[1:] for lock in mine]
        await asyncio.sleep(0.2)
        live = image(server)
        assert all(h is not None and len(w) == 3
                   for h, w in live[1].values())
        assert sum(live[0].values()) >= 60
        for w in waits:
            w.cancel()
        await crash_server(server.server)

        reborn = build()
        await reborn.open()
        for _ in range(100):
            if reborn.server.last_applied >= server.server.last_applied:
                break
            await asyncio.sleep(0.05)
        counter = reborn.server.state_machine.device_engine._groups \
            .metrics.counter
        assert counter("lock_vector_ops").value >= 100
        assert image(reborn) == live
    finally:
        for node in (*clients, reborn or server):
            try:
                await asyncio.wait_for(node.close(), 10)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass


# ---------------------------------------------------------------------------
# DistributedLeaderElection on the vector lane: ElectionListen and
# ElectionUnlisten as one device op each, the elect a resign causes published
# inside its entry, and a session's end as one staged block


async def _election_history(mode: str):
    """Three sessions over five elections: the lines dealt in waves, a
    listen again of a waiting candidate, a waiting candidate's resign, a
    leader's resign and its next listen in ONE batch, a ring filled past
    its eight slots (the overflow keeps the chain), and the end of a
    session that leads one election, waits in others, holds the leader AND
    the next in line of one, and stands in the election with the overflow.
    ``mode``: ``"vector"`` (the device machine as it is), ``"chain"`` (the
    same with ``vector_spec`` and ``close_spec`` answering ``None``: the
    generator handlers alone) or ``"cpu"`` (``LeaderElectionState``).
    Returns what the clients saw, every log index (instance ids) as its
    rank and every epoch as its rank among its election's, what the server
    kept, and the device machines' mirrors."""
    from copycat_tpu.coordination import DistributedLeaderElection
    from copycat_tpu.coordination import commands as oc
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState
    from copycat_tpu.server.log import CommandEntry

    saved = {name: vars(DeviceLeaderElectionState).get(name)
             for name in ("vector_spec", "close_spec")}
    if mode == "chain":
        DeviceLeaderElectionState.vector_spec = \
            lambda self, operation, index, session: None
        DeviceLeaderElectionState.close_spec = lambda self, session: None
    registry = LocalServerRegistry()
    server, a = await _spi_cluster(registry,
                                   "cpu" if mode == "cpu" else "tpu")
    clients = {"a": a}
    try:
        for name in "bc":
            clients[name] = AtomixClient(a.client.members,
                                         LocalTransport(registry),
                                         session_timeout=20.0)
            await clients[name].open()
        el = {n: [await c.create(f"e{i}", DistributedLeaderElection)
                  for i in range(4)] for n, c in clients.items()}
        # a second candidacy of ``a`` in e3, right behind its first, and a
        # line of eleven in e4: ``c`` ten times, then ``a``
        a_twice = await a.create("e3", DistributedLeaderElection)
        crowd = [await clients["c"].create("e4", DistributedLeaderElection)
                 for _ in range(10)]
        a_crowd = await a.create("e4", DistributedLeaderElection)
        seen: dict[str, list] = {n: [] for n in clients}
        for name, client in clients.items():
            client.client.session().on_event(
                "elect", lambda m, _n=name: seen[_n].append(
                    (m.resource, m.message)))
        replies: list = []
        noop = lambda epoch: None  # noqa: E731

        async def listen(instance):
            replies.append(("listen", await instance.submit(
                oc.ElectionListen())))

        async def unlisten(instance):
            replies.append(("unlisten", await instance.submit(
                oc.ElectionUnlisten())))

        async def leads(instance, epoch):
            got = await instance.is_leader(epoch)
            replies.append(("is_leader", got))
            return got

        wave = asyncio.gather
        for name in "abc":                       # the lines: a, b, c
            await wave(*(listen(i) for i in el[name]))
        await listen(a_twice)                    # e3: a, b, c, a again
        first = {m[0]: m[1] for m in seen["a"]}  # a leads all four
        assert len(first) == 4
        for inst in el["a"]:
            assert await leads(inst, first[inst.client.instance_id]) is True
            assert await leads(inst, first[inst.client.instance_id] + 1) \
                is False
        await listen(el["b"][0])                 # waiting: listed once
        await unlisten(el["c"][1])               # waiting: out of the line
        # the leader's resign and its next listen in ONE batch: e0 passes
        # to b, a stands behind c
        await wave(unlisten(el["a"][0]), listen(el["a"][0]))
        told_b = [m for m in seen["b"]
                  if m[0] == el["b"][0].client.instance_id]
        assert len(told_b) == 1
        assert await leads(el["b"][0], told_b[0][1]) is True
        assert await leads(el["a"][0], first[
            el["a"][0].client.instance_id]) is False    # a stale epoch
        # e4: a leader and ten behind it, two past the ring's eight slots
        await wave(*(listen(i) for i in crowd))
        await listen(a_crowd)
        await unlisten(crowd[0])                 # the line moves up
        await asyncio.sleep(0.05)
        # the end of session a: it leads e1, e2 and e3 (with its second
        # candidacy next in line there), waits in e0 and in e4's overflow
        await asyncio.wait_for(a.close(), 10)
        await asyncio.sleep(0.1)
        # the elections go on: every leader resigns, its successor is told
        for name in "bc":
            for inst in el[name][:3]:
                await unlisten(inst)
        for inst in crowd[1:4]:
            await unlisten(inst)
        await asyncio.sleep(0.1)  # drain in-flight publishes

        raft = server.server
        log = raft.log
        commands = [e for e in (log.get(i) for i in range(
            1, log.last_index + 1)) if type(e) is CommandEntry]
        ids = sorted({m[0] for ev in seen.values() for m in ev})
        ordinal = {x: k for k, x in enumerate(ids)}
        epochs: dict[int, list] = {}
        for ev in seen.values():
            for resource, epoch in ev:
                epochs.setdefault(resource, []).append(epoch)
        # an election's epochs, over all its candidates, in rising order
        manager = raft.state_machine
        by_instance = {iid: inst.resource.resource_id
                       for iid, inst in manager.instances.items()}
        sessions = sorted(raft.sessions.values(), key=lambda s: s.id)
        engine = getattr(manager, "device_engine", None)
        counters: dict = {}
        mirrors: list = []
        if mode != "cpu":
            c = engine._groups.metrics.counter
            counters = {n: c(n).value for n in (
                "elect_vector_ops", "elect_chain_ops",
                "session_end_vector_instances",
                "session_end_chain_instances")}
            mirrors = [(h.key, m._leader, m._epoch, list(m._listens),
                        list(m._overflow))
                       for h in manager.resources.values()
                       for m in [h.state_machine]]
        return {
            "replies": replies,
            "events": {n: [ordinal[r] for r, _ in ev]
                       for n, ev in seen.items()},
            "rising": all(e == sorted(set(e)) for e in epochs.values()),
            "event_index": [(s.event_index, s.event_ack_index)
                            for s in sessions],
            "client_index": [clients[n].client.session().event_index
                             for n in "bc"],
            "retained": [type(e.operation.operation.operation).__name__
                         for e in commands if not log.is_cleaned(e.index)
                         and hasattr(e.operation, "resource")
                         and hasattr(e.operation.operation, "operation")],
            "instances": len(by_instance),
        }, counters, mirrors, {n: list(ev) for n, ev in seen.items()}
    finally:
        for name, fn in saved.items():
            if fn is None:
                if name in vars(DeviceLeaderElectionState):
                    delattr(DeviceLeaderElectionState, name)
            else:
                setattr(DeviceLeaderElectionState, name, fn)
        for node in (*clients.values(), server):
            try:
                await asyncio.wait_for(node.close(), 5)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass


@async_test(timeout=300)
async def test_election_on_the_vector_lane_is_the_chain_and_the_cpu_state():
    """The same script on the device machine's vector lane, on its
    generator handlers alone and on the CPU ``LeaderElectionState``: every
    reply, each session's events in order, the sessions' ``event_index`` (a
    batch an entry) and which retained commits were cleaned are equal on
    all three; the two device runs agree bit for bit, epochs and mirrors
    included (the same device ops in the same order); the vector run took
    the lane for all but the listen again, the overflow and what stood
    behind it, and ended the session in blocks around the one election it
    could not."""
    vec, counters, mirrors, raw = await _election_history("vector")
    chain, chain_counters, chain_mirrors, chain_raw = \
        await _election_history("chain")
    cpu, _, _, _ = await _election_history("cpu")
    assert vec == chain == cpu
    assert vec["rising"] and len(vec["events"]["b"]) >= 4 \
        and len(vec["events"]["c"]) >= 4
    assert raw == chain_raw and mirrors == chain_mirrors
    assert counters["elect_vector_ops"] >= 30
    assert 1 <= counters["elect_chain_ops"] <= 8
    # a's six candidacies in the five elections: the overflow's by a chain,
    # the rest in the blocks before and after it
    assert counters["session_end_vector_instances"] == 5
    assert counters["session_end_chain_instances"] == 1
    assert chain_counters["elect_vector_ops"] == 0
    assert chain_counters["session_end_vector_instances"] == 0
    assert chain_counters["session_end_chain_instances"] == 6


@async_test(timeout=300)
async def test_election_vector_lane_resumes_from_a_snapshot_mid_line():
    """A leader and two waiting candidates, the machine's host mirror taken
    as a snapshot and restored over it: the look-ahead reads the stand-in
    commits, the line moves on down the vector lane in order, and each
    successor's ``is_leader(epoch)`` is true as soon as it is told."""
    from copycat_tpu.coordination import DistributedLeaderElection
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState

    registry = LocalServerRegistry()
    server, a = await _spi_cluster(registry)
    others = []
    try:
        for _ in range(2):
            c = AtomixClient(a.client.members, LocalTransport(registry),
                             session_timeout=20.0)
            await c.open()
            others.append(c)
        elections = [await c.get("election", DistributedLeaderElection)
                     for c in (a, *others)]
        told: list = []
        checked: list = []

        def on_elect(k):
            def cb(epoch):
                told.append((k, epoch))
                # the token is checked from inside the event's dispatch
                checked.append(asyncio.ensure_future(
                    elections[k].is_leader(epoch)))
            return cb

        for k, election in enumerate(elections):
            await election.on_election(on_elect(k))
        assert [k for k, _ in told] == [0]
        manager = server.server.state_machine
        (machine,) = [h.state_machine for h in manager.resources.values()
                      if isinstance(h.state_machine,
                                    DeviceLeaderElectionState)]
        image = machine.snapshot_state()
        assert image["leader"] is not None and len(image["listens"]) == 3
        sessions = {c.session.id: c.session
                    for c in machine._listens.values()}
        machine._listens.clear()
        machine._leader = machine._epoch = None
        machine.restore_state(image, {})
        for session in sessions.values():
            machine.register(session)
        counter = manager.device_engine._groups.metrics.counter
        chain0 = counter("elect_chain_ops").value
        for k, election in enumerate(elections):
            for _ in range(100):
                if len(told) > k:
                    break
                await asyncio.sleep(0.02)
            assert [j for j, _ in told] == list(range(k + 1))
            await election.resign()
        assert all(await asyncio.gather(*checked)) and len(checked) == 3
        epochs = [epoch for _, epoch in told]
        assert epochs == sorted(set(epochs))
        assert counter("elect_chain_ops").value == chain0
        assert counter("elect_vector_ops").value >= 6
        assert machine._leader is None and not machine._listens
        assert not await elections[0].is_leader(epochs[-1])
    finally:
        for node in (a, *others, server):
            await asyncio.wait_for(node.close(), 5)


def test_an_elect_reaches_its_instance_by_a_dictionary():
    """300 candidacies on one client session hang ONE listener on it for
    ``"elect"``: an event finds its instance by the id it names
    (``manager/instance._EventRouter``), not by a walk of 300 listeners."""
    from copycat_tpu.client.client import ClientSession
    from copycat_tpu.coordination import DistributedLeaderElection
    from copycat_tpu.manager.instance import InstanceClient, _ROUTERS
    from copycat_tpu.manager.operations import InstanceEvent

    class Raft:
        def __init__(self):
            self._session = ClientSession(self)

        def session(self):
            return self._session

    raft = Raft()
    got: list = []
    elections = []
    for iid in range(300):
        election = DistributedLeaderElection(InstanceClient(iid, raft))
        election._listeners.add(lambda epoch, _i=iid: got.append((_i, epoch)))
        elections.append(election)
    listeners = raft.session()._event_listeners["elect"]
    assert len(listeners) == 1
    assert len(_ROUTERS[raft.session()].routes["elect"]) == 300
    raft.session()._dispatch("elect", InstanceEvent(217, 99))
    assert got == [(217, 99)]
