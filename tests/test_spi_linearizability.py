"""Linearizability of the PUBLIC API under a leader kill.

The committed verdict (`LINEARIZABILITY.md`) checks device-engine
histories; this checks the full SPI stack the way Jepsen would check
the reference: concurrent ``AtomixClient`` sessions drive a shared
resource through ``atomix.get`` (real sessions, RPC, state-machine
multiplexing) while the LEADER server is killed mid-run, and the
client-observed invoke/complete history must satisfy the Wing & Gong
checker. Ops that error or time out are recorded with unknown
completion (the checker tries both "applied" and "never applied" — the
Jepsen-correct treatment of an ambiguous failure). Register histories
run against both executors; lock histories against the CPU stack.
(Reference obligation: `README.md:8` Jepsen claim through
`Atomix.java:205`'s public surface. The CPU-only tests need no jax.)

Soundness bounds baked into the harness: the workload phase is
hard-capped at a fraction of the session timeout, so a session can
never expire mid-history — an expiry performs *implicit* state changes
(e.g. LockState releases a dead holder's lock) that the history cannot
represent and the checker would misread as a violation.
"""

import asyncio
import random
import time

import pytest

# The CPU-stack tests need no jax themselves, but the checker lives in
# copycat_tpu.testing whose package __init__ imports the device-history
# recorder (jax) — so a jax-less environment can't collect this module
# either way; skip it cleanly there.
jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicValue
from copycat_tpu.coordination import DistributedLock
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
from copycat_tpu.server.raft import LEADER
from copycat_tpu.testing.linearize import (
    HOp,
    LockModel,
    RegisterModel,
    check_linearizable,
)

from helpers import async_test
from raft_fixtures import next_ports

from engines import SERVED

OPS_PER_CLIENT = 24
CLIENTS = 3
VALUE_DOMAIN = 4     # small domain so cas sometimes succeeds
SESSION_TIMEOUT = 30.0
WORKLOAD_CAP_S = 10.0  # << SESSION_TIMEOUT: no expiry can land mid-history


async def _register_loop(cid: int, client, history: list, seq: list,
                         deadline: float) -> None:
    reg = await client.get("reg", DistributedAtomicValue)
    rng = random.Random(100 + cid)
    for _ in range(OPS_PER_CLIENT):
        if time.monotonic() > deadline:
            return
        kind = rng.randrange(3)
        if kind == 0:
            v = rng.randrange(1, VALUE_DOMAIN)
            op, coro = ("set", v), reg.set(v)
        elif kind == 1:
            op, coro = ("get",), reg.get()
        else:
            e = rng.randrange(0, VALUE_DOMAIN)
            u = rng.randrange(1, VALUE_DOMAIN)
            op, coro = ("cas", e, u), reg.compare_and_set(e, u)
        seq[0] += 1
        op_id, t0 = seq[0], time.monotonic()
        try:
            raw = await asyncio.wait_for(coro, 15)
        except (Exception, asyncio.TimeoutError):
            # ambiguous: may or may not have applied
            history.append(HOp(op_id=op_id, op=op, result=None, invoke=t0))
            continue
        if op[0] == "set":
            result = 0
        elif op[0] == "get":
            result = 0 if raw is None else int(raw)
        else:
            result = int(bool(raw))
        history.append(HOp(op_id=op_id, op=op, result=result, invoke=t0,
                           complete=time.monotonic()))
        await asyncio.sleep(0.01)  # pace: keep the workload spanning faults


async def _lock_loop(cid: int, client, history: list, seq: list,
                     deadline: float) -> None:
    """try_lock/unlock history for LockModel (who = client id).

    Never re-acquires while holding (the CPU LockState queues a holder's
    re-lock per the reference; the model treats re-acquire as idempotent
    — avoiding the case keeps one model valid for both executors). An
    unlock COMPLETION is recorded with unknown result: after a failover
    re-establishes the session, a leftover local ``holding`` flag can
    drive an unlock of a free lock, which the server accepts silently
    but the model scores 0 — unknown-result lets the checker consider
    both, which is always sound.
    """
    lock = await client.get("lk", DistributedLock)
    rng = random.Random(200 + cid)
    holding = False
    for _ in range(16):
        if time.monotonic() > deadline:
            return
        if holding and rng.random() < 0.7:
            op, coro = ("release", cid), lock.unlock()
        elif holding:
            await asyncio.sleep(0.02)
            continue
        else:
            op, coro = ("acquire", cid), lock.try_lock()
        seq[0] += 1
        op_id, t0 = seq[0], time.monotonic()
        try:
            raw = await asyncio.wait_for(coro, 15)
        except (Exception, asyncio.TimeoutError):
            history.append(HOp(op_id=op_id, op=op, result=None, invoke=t0))
            holding = False  # unknown; stop assuming we hold it
            continue
        if op[0] == "acquire":
            result = int(bool(raw))
            holding = bool(raw)
            history.append(HOp(op_id=op_id, op=op, result=result,
                               invoke=t0, complete=time.monotonic()))
        else:
            holding = False
            history.append(HOp(op_id=op_id, op=op, result=None, invoke=t0))
        await asyncio.sleep(0.01)


async def _run_stack(executor: str, loop_fn, fault: str = "kill"
                     ) -> "tuple[list[HOp], float]":
    """Boot 3 servers + CLIENTS clients, run ``loop_fn`` per client,
    inject the ``fault`` ("kill" = close the leader; "partition" =
    isolate the leader for ~2s of the workload, then heal; "loss" =
    10%/10% request/response loss for the whole run plus a leader
    partition) once a third of the target ops are in flight, return the
    recorded history and the fault time."""
    registry = LocalServerRegistry()
    addrs = next_ports(3)
    kwargs = {}
    if executor == "tpu":
        kwargs = dict(engine_config=SERVED)
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry, local_address=a),
                     election_timeout=0.2, heartbeat_interval=0.04,
                     session_timeout=SESSION_TIMEOUT, executor=executor,
                     **kwargs)
        for a in addrs
    ]
    nem = registry.attach_nemesis()
    if fault == "loss":
        nem.set_loss(request=0.10, response=0.10)
    await asyncio.gather(*(s.open() for s in servers))
    clients = []
    for _ in range(CLIENTS):
        c = AtomixClient(addrs, LocalTransport(registry),
                         session_timeout=SESSION_TIMEOUT)
        await c.open()
        clients.append(c)

    history: list[HOp] = []
    seq = [0]
    deadline = time.monotonic() + WORKLOAD_CAP_S
    tasks = [
        asyncio.ensure_future(loop_fn(i, c, history, seq, deadline))
        for i, c in enumerate(clients)
    ]

    # mid-run nemesis: kill the LEADER server (2/3 keep quorum; sessions
    # pinned to the victim must fail over). Trigger once a third of the
    # ops are in, so the kill provably lands mid-workload.
    while seq[0] < CLIENTS * 12 // 3 and time.monotonic() < deadline:
        await asyncio.sleep(0.02)
    if all(t.done() for t in tasks):
        # On a slow machine WORKLOAD_CAP_S can expire before the kill
        # threshold is reached — the workload simply finished; that is a
        # timing artifact, not a linearizability signal. Teardown with
        # the same guards as the normal path (an unguarded close against
        # already-dead peers can hang or raise, masking the skip).
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
        pytest.skip("workload finished before the nemesis threshold "
                    "(slow machine) — nothing to check")
    leader = next((s for s in servers if s.server.role == LEADER),
                  servers[0])
    if fault == "kill":
        await leader.close()
    else:
        # partition the leader from its peers (clients are anonymous and
        # reach both sides — the Jepsen client model); heal mid-workload
        # so the history records refusals/ambiguity AND recovery
        lead_addr = leader.server.address
        nem.partition([lead_addr], [a for a in addrs if a != lead_addr])
    kill_t = time.monotonic()
    if fault != "kill":
        await asyncio.sleep(2.0)
        nem.partition()  # heal the partition (loss, if any, stays on)

    await asyncio.wait_for(asyncio.gather(*tasks), 240)
    nem.heal()
    for c in clients:
        try:
            await asyncio.wait_for(c.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass
    for s in servers:
        if fault != "kill" or s is not leader:
            try:
                await asyncio.wait_for(s.close(), 10)
            except (Exception, asyncio.TimeoutError):
                pass
    return history, kill_t


def _check(history: list, kill_t: float, model) -> None:
    completed = [h for h in history if h.complete != float("inf")
                 or h.result is not None]
    assert len(completed) >= 12, \
        f"too few completed ops ({len(completed)}) — cluster never healed"
    post_kill = [h for h in history if h.result is not None
                 and h.invoke > kill_t]
    assert post_kill, "no op completed after the fault — failover dead"
    res = check_linearizable(history, model)
    assert res.ok, f"SPI history not linearizable: {res}"


@async_test(timeout=420)
async def test_spi_linearizable_under_leader_kill_cpu():
    _check(*await _run_stack("cpu", _register_loop), model=RegisterModel)


@async_test(timeout=420)
async def test_spi_linearizable_under_leader_kill_tpu():
    _check(*await _run_stack("tpu", _register_loop), model=RegisterModel)


@async_test(timeout=420)
async def test_spi_lock_histories_linearizable_under_leader_kill():
    _check(*await _run_stack("cpu", _lock_loop), model=LockModel)


@async_test(timeout=420)
async def test_spi_linearizable_under_leader_partition_cpu():
    """Round-5 extension (VERDICT r4 #3): the fault is a PARTITION, not
    a clean kill — the isolated leader stays up and dialable, its
    in-flight commands become ambiguous, and the majority side must
    elect and serve while stale-leader reads refuse."""
    _check(*await _run_stack("cpu", _register_loop, fault="partition"),
           model=RegisterModel)


@async_test(timeout=420)
async def test_spi_linearizable_under_partition_and_loss_cpu():
    """Partition + 10%/10% request/response loss for the whole run: lost
    responses make acked-but-unreported commands, the exactly-once
    session dedup's worst case."""
    _check(*await _run_stack("cpu", _register_loop, fault="loss"),
           model=RegisterModel)


@async_test(timeout=420)
async def test_spi_lock_histories_linearizable_under_partition():
    _check(*await _run_stack("cpu", _lock_loop, fault="partition"),
           model=LockModel)


@pytest.mark.parametrize("empty_batch", (False, True),
                         ids=("read", "empty-batch"))
@async_test(timeout=420)
async def test_stale_leader_refuses_reads_with_read_pump(empty_batch):
    """Round-9 stale-read nemesis (read-pump extension): after a
    partition deposes the leader, the OLD leader's lease expires and a
    new leader commits fresh writes on the majority side. A
    linearizable/bounded read sent straight at the deposed leader must
    REFUSE (its leadership confirm cannot reach a quorum) rather than
    serve state that misses the committed write.

    ``empty-batch``: the probe is a ``QueryBatchRequest`` with no
    operations, which stages nothing in a read window. It still pays
    the gate: the leader confirms once and answers its ``last_applied``
    with an empty list; the deposed leader refuses."""
    from copycat_tpu.protocol import messages as msg
    from copycat_tpu.atomic import commands as vc
    from copycat_tpu.manager.operations import InstanceQuery
    from copycat_tpu.resource.operations import ResourceQuery

    registry = LocalServerRegistry()
    addrs = next_ports(3)
    servers = [
        AtomixServer(a, addrs, LocalTransport(registry, local_address=a),
                     election_timeout=0.2, heartbeat_interval=0.04,
                     session_timeout=SESSION_TIMEOUT, executor="cpu")
        for a in addrs
    ]
    nem = registry.attach_nemesis()
    await asyncio.gather(*(s.open() for s in servers))
    client = AtomixClient(addrs, LocalTransport(registry),
                          session_timeout=SESSION_TIMEOUT)
    await client.open()
    probe = None
    try:
        reg = await client.get("reg", DistributedAtomicValue)
        await reg.set(1)
        instance_id = reg.client.instance_id
        old = next(s for s in servers if s.server.role == LEADER)
        old_term = old.server.term
        lead_addr = old.server.address

        def probe_request(consistency: str):
            if empty_batch:
                return msg.QueryBatchRequest(
                    session_id=0, index=0, consistency=consistency,
                    operations=[])
            return msg.QueryRequest(
                session_id=0, index=0, consistency=consistency,
                operation=InstanceQuery(
                    instance_id, ResourceQuery(vc.Get(), consistency)))

        # anonymous connection — it reaches both sides of the partition
        # later (the Jepsen client model)
        probe = LocalTransport(registry).client()
        conn = await probe.connect(lead_addr)
        if empty_batch:
            confirms = [0]
            real_confirm = old.server._confirm_leadership

            async def counting_confirm():
                confirms[0] += 1
                return await real_confirm()

            old.server._confirm_leadership = counting_confirm
            applied = old.server.last_applied
            response = await asyncio.wait_for(
                conn.send(probe_request("linearizable")), 30)
            old.server._confirm_leadership = real_confirm
            assert response.error is None, response.error
            assert response.entries == []
            # (a keep-alive may have applied while the gate confirmed)
            assert 0 < applied <= response.index <= old.server.last_applied
            assert confirms[0] == 1, "the empty batch skipped the gate"
        nem.partition([lead_addr], [a for a in addrs if a != lead_addr])
        # wait until the majority side elected a successor
        successor = None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            successor = next(
                (s for s in servers if s is not old
                 and s.server.role == LEADER
                 and s.server.term > old_term), None)
            if successor is not None:
                break
            await asyncio.sleep(0.05)
        if successor is None:
            pytest.fail("majority never elected a successor")
        # commit a write the deposed leader cannot have seen. Route the
        # client straight at the successor: the old leader is still
        # dialable and ACCEPTS commands it can never commit, so letting
        # the generic retry loop discover the new leader burns a full
        # per-try timeout per wrong dial (generic failover is covered by
        # the leader-kill/partition histories above — this test targets
        # the stale READ refusal).
        client.client._leader_hint = successor.server.address
        client.client._drop_connection()
        await asyncio.wait_for(reg.set(2), 120)
        # direct reads at the DEPOSED leader
        for consistency in ("linearizable", "bounded_linearizable"):
            response = await asyncio.wait_for(
                conn.send(probe_request(consistency)), 30)
            assert response.error in (msg.NOT_LEADER, msg.NO_LEADER), (
                f"deposed leader served a {consistency} read "
                f"({response!r}) that misses the committed write")
        # the healed cluster serves the committed value linearizably
        nem.heal()
        reg._read_cl = "linearizable"
        assert await asyncio.wait_for(reg.get(), 60) == 2
    finally:
        nem.heal()
        if probe is not None:
            try:
                await asyncio.wait_for(probe.close(), 5)
            except (Exception, asyncio.TimeoutError):
                pass
        try:
            await asyncio.wait_for(client.close(), 5)
        except (Exception, asyncio.TimeoutError):
            pass
        for s in servers:
            try:
                await asyncio.wait_for(s.close(), 10)
            except (Exception, asyncio.TimeoutError):
                pass


@async_test(timeout=420)
async def test_spi_linearizable_under_leader_partition_tpu():
    """Partition nemesis against the DEVICE-executor stack: the engines
    replicate deterministically from each server's committed CPU log, so
    a partitioned server's engine simply lags and reconverges by replay
    — the history must stay linearizable through it."""
    _check(*await _run_stack("tpu", _register_loop, fault="partition"),
           model=RegisterModel)
