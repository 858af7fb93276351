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
