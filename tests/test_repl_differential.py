"""Replication differential suite: safety of the pipelined plane is
DEMONSTRATED, not asserted (ISSUE 5 acceptance).

- The same seeded workload runs with eight append windows in flight
  per peer (the default) and with ONE (``COPYCAT_REPL_DEPTH=1``, the
  stream's edge: the shape stop-and-wait replication has), and the
  committed logs are compared: bit-for-bit across the members of each
  cluster (replicated entries carry the leader's term/timestamp — any
  pipelining bug that reorders, drops or duplicates an entry breaks byte
  equality), and against the SUBMITTED sequence: exactly those commands,
  once each, in submission order, and the state they produce.
- Nemesis tests (delayed+reordered messages, partitioned peers, leader
  deposition mid-stream) run with ``COPYCAT_INVARIANTS=strict``: every
  commit advance re-verifies quorum support from first principles and
  raises on violation, so a pipelined ack stream that ever outran real
  replication would fail these loudly.
"""

import asyncio
import random

from helpers import async_test
from raft_fixtures import REPL_DEPTHS, Get, Put, create_cluster

from copycat_tpu.io.serializer import Serializer
from copycat_tpu.server.log import CommandEntry
from copycat_tpu.server.raft import LEADER

SEED = 20260803
PHASES = 8
OPS_PER_PHASE = 40


async def _wait_converged(cluster, timeout=20.0):
    leader = cluster.leader
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        target = leader.commit_index
        if all(s.last_applied >= target for s in cluster.servers):
            return leader
        await asyncio.sleep(0.05)
    raise TimeoutError("cluster did not converge")


def _member_log_bytes(server, up_to):
    ser = Serializer()
    return {i: ser.write(e)
            for i in range(1, up_to + 1)
            if (e := server.log.get(i)) is not None}


def _command_stream(server, up_to):
    """The committed command content in log order — the view that
    compares with what was submitted (indices/terms/timestamps are the
    leader's own)."""
    out = []
    for i in range(1, up_to + 1):
        e = server.log.get(i)
        if isinstance(e, CommandEntry) and isinstance(e.operation, Put):
            out.append((e.seq, e.operation.key, e.operation.value))
    return out


async def _drive_workload():
    """One seeded workload: bursts of micro-batched writes through the
    public client API (the shape that exercises multi-window streams)."""
    cluster = await create_cluster(3, session_timeout=30.0)
    try:
        await cluster.await_leader()
        client = await cluster.client(session_timeout=30.0)
        rng = random.Random(SEED)
        submitted = []
        for _ in range(PHASES):
            burst = [(f"k{rng.randrange(8)}", rng.randrange(100))
                     for _ in range(OPS_PER_PHASE)]
            submitted += burst
            await asyncio.gather(*(
                client.submit_command_nowait(Put(key=k, value=v))
                for k, v in burst))
        leader = await _wait_converged(cluster)
        up_to = leader.commit_index
        member_logs = [_member_log_bytes(s, up_to) for s in cluster.servers]
        return {
            "submitted": submitted,
            "depth": leader._repl_depth,
            "commands": _command_stream(leader, up_to),
            "member_logs": member_logs,
            "state": dict(leader.state_machine.data),
            "states": [dict(s.state_machine.data) for s in cluster.servers],
        }
    finally:
        await cluster.close()


def _assert_no_invariant_violations(cluster):
    """The strict commit check raises inside an ack task (logged by the
    task reaper, not fatal), so the crisp test-visible signal is the
    counter it bumps before raising — it must never move."""
    for s in cluster.servers:
        assert s.metrics.counter("repl.invariant_violations").value == 0, \
            f"{s.address}: strict commit invariant violated"


def _assert_members_bit_identical(member_logs):
    base = member_logs[0]
    compared = 0
    for other in member_logs[1:]:
        for i, data in base.items():
            if i in other:
                assert data == other[i], f"member log divergence at {i}"
                compared += 1
    assert compared >= PHASES * OPS_PER_PHASE, compared


@REPL_DEPTHS
def test_members_commit_the_submitted_sequence(depth, monkeypatch):
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)
    results = []

    @async_test(timeout=120)
    async def run():
        results.append(await _drive_workload())

    run()
    (r,) = results
    assert r["depth"] == int(depth)
    # every member holds bit-identical committed bytes
    _assert_members_bit_identical(r["member_logs"])
    # and identical applied state
    for st in r["states"]:
        assert st == r["state"], "member state diverged"
    # against what was submitted: exactly that command sequence, once
    # each (session seqs strictly rising), in submission order,
    # producing the state a plain dict holds after it
    seqs = [seq for seq, _, _ in r["commands"]]
    assert seqs == sorted(set(seqs)), "a command committed twice"
    assert [(k, v) for _, k, v in r["commands"]] == r["submitted"]
    assert len(r["submitted"]) == PHASES * OPS_PER_PHASE
    assert r["state"] == dict(r["submitted"])


# ---------------------------------------------------------------------------
# nemesis under COPYCAT_INVARIANTS=strict
# ---------------------------------------------------------------------------


@REPL_DEPTHS
def test_delayed_reordered_peers_strict(depth, monkeypatch):
    """Per-message random delays reorder in-flight append windows on the
    local transport (plus response loss for at-most-once ambiguity); the
    stream must stay exactly-once and commit must never outrun a real
    quorum (strict check raises inside _advance_commit if it does)."""
    monkeypatch.setenv("COPYCAT_REPL_DEPTH", depth)
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=240)
    async def run():
        cluster = await create_cluster(3, session_timeout=60.0)
        try:
            leader = await cluster.await_leader()
            assert leader._strict_invariants
            client = await cluster.client(session_timeout=60.0)
            nem = cluster.registry.attach_nemesis()
            nem.set_delay(0.0, 0.004)
            nem.set_loss(response=0.05)
            for phase in range(4):
                futs = [client.submit_command_nowait(
                    Put(key="n", value=phase * 25 + i)) for i in range(25)]
                await asyncio.gather(*futs)
            nem.heal()
            await _wait_converged(cluster)
            for s in cluster.servers:
                assert s.state_machine.data.get("n") == 99
                assert s.state_machine.applied_ops == 100, \
                    (f"{s.address} applied {s.state_machine.applied_ops}: "
                     "double- or missed apply under reordering")
            _assert_no_invariant_violations(cluster)
        finally:
            await cluster.close()

    run()


def test_partitioned_peer_mid_stream_strict(monkeypatch):
    """A peer partitioned away mid-stream must not stall commit (quorum
    via the healthy follower), must not pin unbounded in-flight state,
    and must catch up on heal — all under the strict commit check."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=240)
    async def run():
        cluster = await create_cluster(3, session_timeout=60.0)
        try:
            leader = await cluster.await_leader()
            client = await cluster.client(session_timeout=60.0)
            victim = next(s for s in cluster.servers if s is not leader)
            rest = [s.address for s in cluster.servers if s is not victim]
            nem = cluster.registry.attach_nemesis()
            futs = [client.submit_command_nowait(Put(key="p", value=i))
                    for i in range(50)]
            nem.partition([victim.address], rest)  # cut mid-stream
            await asyncio.gather(*futs)            # commits via quorum
            futs = [client.submit_command_nowait(Put(key="p", value=50 + i))
                    for i in range(50)]
            await asyncio.gather(*futs)
            assert leader.role == LEADER
            nem.heal()
            deadline = asyncio.get_running_loop().time() + 30
            while asyncio.get_running_loop().time() < deadline:
                if victim.state_machine.data.get("p") == 99:
                    break
                await asyncio.sleep(0.05)
            assert victim.state_machine.data.get("p") == 99
            assert victim.state_machine.applied_ops == 100
            # drained: nothing in flight once the stream is caught up
            # (poll — an in-flight heartbeat window legitimately shows)
            deadline = asyncio.get_running_loop().time() + 5
            while asyncio.get_running_loop().time() < deadline:
                if leader.metrics.gauge("repl.windows_inflight").value == 0:
                    break
                await asyncio.sleep(0.02)
            assert leader.metrics.gauge("repl.windows_inflight").value == 0
            _assert_no_invariant_violations(cluster)
        finally:
            await cluster.close()

    run()


def test_leader_deposition_mid_stream_strict(monkeypatch):
    """Close the leader while a multi-window stream is in flight: the
    client re-routes, every ACKED write is applied exactly once on the
    survivors, and the survivors' logs are identical."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=240)
    async def run():
        cluster = await create_cluster(3, session_timeout=60.0)
        try:
            leader = await cluster.await_leader()
            client = await cluster.client(session_timeout=60.0)
            futs = [client.submit_command_nowait(Put(key=f"d{i}", value=i))
                    for i in range(120)]
            await asyncio.sleep(0)  # let the batch hit the wire
            await leader.close()    # deposition mid-stream
            results = await asyncio.gather(*futs, return_exceptions=True)
            survivors = [s for s in cluster.servers if s is not leader]
            deadline = asyncio.get_running_loop().time() + 30
            while asyncio.get_running_loop().time() < deadline:
                if any(s.role == LEADER for s in survivors):
                    target = max(s.commit_index for s in survivors)
                    if all(s.last_applied >= target for s in survivors):
                        break
                await asyncio.sleep(0.05)
            # every ACKED write is present on the survivors exactly once
            acked = [i for i, r in enumerate(results)
                     if not isinstance(r, BaseException)]
            for s in survivors:
                for i in acked:
                    assert s.state_machine.data.get(f"d{i}") == i, \
                        f"acked write d{i} missing on {s.address}"
            ser = Serializer()
            a, b = survivors
            up_to = min(a.commit_index, b.commit_index)
            for i in range(1, up_to + 1):
                ea, eb = a.log.get(i), b.log.get(i)
                if ea is not None and eb is not None:
                    assert ser.write(ea) == ser.write(eb), i
            # a fresh write through the new leader still works
            assert await asyncio.wait_for(
                client.submit(Put(key="after", value=1)), 30) is None
            for s in survivors:
                assert s.metrics.counter(
                    "repl.invariant_violations").value == 0
        finally:
            await cluster.close()

    run()
