"""Differential proof for the dependency-classified parallel apply +
cross-group engine fusion (docs/SHARDING.md "Apply ordering").

The vector classifier works on dependency-classified windows:
device-eligible entries on disjoint resource keys join a staged run
ACROSS interleaved ineligible entries, per-key/per-session FIFO is
preserved by the conflict gate (a colliding entry forces the staged
dispatch before it applies), and staged runs from every Raft group fuse
into ONE engine round per server turn (``RaftServer.flush_fused``). Its
contract is BIT-IDENTICAL observable behavior to the host state
machines (``executor="cpu"``), which apply entry by entry in log order.

These tests prove it by running one seeded interleaved-eligibility
script through a device server and a CPU server and comparing
everything the client can see, then racing the device plane against
partition + leader-deposition nemeses under
``COPYCAT_INVARIANTS=strict``, once spread over groups and keys and once
at the classifier's edge where every entry conflicts. The mid-run
engine-failure test covers the explicit failed-pump branch of
``_finalize_vector_run`` (ISSUE 11 satellite: no ``raws[k]`` walk behind
a short-circuit guard).
"""

import asyncio
import random

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import (  # noqa: E402
    DistributedAtomicLong, DistributedAtomicValue)
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.io.serializer import Serializer  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.server.log import CommandEntry  # noqa: E402
from copycat_tpu.server.raft import LEADER  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import SERVED_WIDE  # noqa: E402



async def _cluster(registry, *, executor: str = "tpu",
                   members: int = 1, groups: int = 4,
                   election_timeout: float = 0.5, clients: int = 1):
    addrs = next_ports(members)
    servers = [AtomixServer(a, addrs, LocalTransport(registry),
                            election_timeout=election_timeout,
                            heartbeat_interval=election_timeout / 5,
                            session_timeout=30.0, executor=executor,
                            engine_config=SERVED_WIDE, groups=groups)
               for a in addrs]
    await asyncio.gather(*(s.open() for s in servers))
    cs = [AtomixClient(addrs, LocalTransport(registry),
                       session_timeout=30.0) for _ in range(clients)]
    await asyncio.gather(*(c.open() for c in cs))
    return servers, (cs[0] if clients == 1 else cs)


def _script(seed: int, n_waves: int, wave: int):
    """Seeded interleaved-eligibility script over 6 plain values (the
    vector-eligible steady state, driven by 3 writer sessions) + 2
    LISTENED values driven by a 4th session (listeners force the
    generator path, so every wave interleaves eligible and ineligible
    entries from DIFFERENT sessions — the contiguity-collapsing shape
    the dependency classifier spans; same-session interleaving always
    conflicts, by the session-FIFO gate). Values hash-route across all
    4 groups, so the fused flush mixes groups in one round."""
    rng = random.Random(seed)
    waves = []
    for _ in range(n_waves):
        ops = []
        for _ in range(wave):
            if rng.random() < 0.25:           # ineligible, session 3
                target = 6 + rng.randrange(2)
            else:                             # eligible, sessions 0-2
                target = rng.randrange(6)
            kind = rng.randrange(4)
            ops.append((target, kind, rng.randrange(5), rng.randrange(5)))
        waves.append(ops)
    return waves


async def _run_script(clients, waves):
    """Execute the script; returns (results, events, finals) — the full
    client-observable history. Ops on value ``t`` ride session
    ``t % 3`` (plain values) or session 3 (listened values). Wave 2
    creates a late value mid-script (a catalog entry: ``apply_key``
    None, the whole-window barrier)."""
    values = [await clients[3 if i >= 6 else i % 3].get(
        f"pv{i}", DistributedAtomicValue) for i in range(8)]
    events: list[tuple[int, int]] = []
    listeners = [await values[t].on_change(
        lambda v, t=t: events.append((t, v))) for t in (6, 7)]
    for i, v in enumerate(values):
        await v.set(i)  # deterministic non-None base; lands on device
    results = []
    for w, ops in enumerate(waves):
        if w == 2:
            late = await clients[0].get("pv-late", DistributedAtomicValue)
            await late.set(99)
            values.append(late)

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
        results.append(await asyncio.gather(*(one(*op) for op in ops)))
    finals = [await v.get() for v in values]
    for listener in listeners:
        listener.close()
    await asyncio.sleep(0.05)  # drain in-flight publishes
    return results, events, finals


def _lane_counters(server) -> dict[str, int]:
    flat: dict[str, int] = {}
    for grp in server.groups:
        for name in ("apply.parallel_spans", "apply.conflict_flushes",
                     "vector_runs", "vector_ops"):
            flat[name] = flat.get(name, 0) + grp.metrics.counter(name).value
    flat["apply.fused_dispatches"] = server._metrics.counter(
        "apply.fused_dispatches").value
    return flat


@async_test(timeout=600)
async def test_parallel_apply_bit_identical_to_host_machines():
    """Same seeded interleaved script, the device server against the
    host state machines: results, per-session event order and final
    state must all be identical, with work committed in every group."""
    waves = _script(seed=11, n_waves=5, wave=32)
    histories = []
    metrics = []
    for executor in ("tpu", "cpu"):
        registry = LocalServerRegistry()
        servers, clients = await _cluster(registry, executor=executor,
                                          clients=4)
        try:
            histories.append(await _run_script(clients, waves))
            server = servers[0].server
            # every group took work (the fused flush had cross-group
            # rows to merge)
            for grp in server.groups:
                assert any(isinstance(grp.log.get(i), CommandEntry)
                           for i in range(1, grp.commit_index + 1)), \
                    f"{executor}: group {grp.group_id} committed no command"
            metrics.append(_lane_counters(server))
            assert "apply.fused_dispatches" in str(server.stats_snapshot()), \
                "apply.* family missing from the stats surface"
        finally:
            for c in clients:
                await asyncio.wait_for(c.close(), 5)
            for s in servers:
                await asyncio.wait_for(s.close(), 5)
    device, host = histories
    assert device[0] == host[0], "results diverged from the host machines"
    assert device[1] == host[1], "event order diverged from the host machines"
    assert device[2] == host[2], "final state diverged from the host machines"
    # the script genuinely exercised what it compares: the device server
    # spanned ineligible entries, fused its dispatches and ran vector
    # rows; the reference never entered the vector lane
    on, ref = metrics
    assert on["apply.parallel_spans"] > 0, \
        "the classifier never spanned an ineligible entry"
    assert on["apply.fused_dispatches"] > 0, "fusion never dispatched"
    assert on["vector_ops"] > 0
    assert ref["vector_ops"] == 0 and ref["apply.fused_dispatches"] == 0


@async_test(timeout=600)
async def test_fused_dispatch_merges_groups_per_turn():
    """A concurrent burst across all 4 groups:
    staged runs from different groups land in shared engine rounds —
    the fused-dispatch count stays BELOW the per-group run count, and
    at least one dispatch carried rows from 2+ groups."""
    registry = LocalServerRegistry()
    servers, client = await _cluster(registry)
    try:
        counters = await asyncio.gather(
            *(client.get(f"fc{i}", DistributedAtomicLong)
              for i in range(16)))
        for _ in range(6):
            await asyncio.gather(*(c.add_and_get(1) for c in counters
                                   for _ in range(4)))
        server = servers[0].server
        fused = server._metrics.counter("apply.fused_dispatches").value
        runs = sum(g.metrics.counter("vector_runs").value
                   for g in server.groups)
        rows = server._metrics.histogram("apply.fused_rows")
        groups_hist = server._metrics.histogram("apply.fused_groups")
        assert fused > 0 and runs > 0
        assert fused <= runs, (fused, runs)
        for g in server.groups:
            assert g.metrics.histogram("apply.window_entries").count > 0
        assert groups_hist.max_value >= 2, (
            "no fused dispatch ever mixed rows from 2+ groups "
            f"(max {groups_hist.max_value})")
        assert rows.sum == sum(
            g.metrics.counter("vector_ops").value for g in server.groups)
        # exactly-once across the fused flush
        got = await asyncio.gather(*(c.get() for c in counters))
        assert got == [24] * 16, got
    finally:
        await asyncio.wait_for(client.close(), 5)
        for s in servers:
            await asyncio.wait_for(s.close(), 5)


@async_test(timeout=600)
async def test_mid_run_engine_failure_fails_rows_explicitly():
    """A mid-run engine failure (run_vector raises) must resolve every
    staged entry's future with the pump error — no hung futures, no
    ``raws`` indexing — and the engine must serve the NEXT burst
    normally with exactly-once bookkeeping intact."""
    registry = LocalServerRegistry()
    servers, client = await _cluster(registry)
    try:
        counter = await client.get("mc", DistributedAtomicLong)
        assert await counter.add_and_get(1) == 1  # settle on the device
        engine = servers[0].server.groups[0].state_machine.device_engine
        real = engine.run_vector

        def boom(*a, **k):
            raise RuntimeError("injected mid-run engine failure")

        engine.run_vector = boom
        try:
            results = await asyncio.gather(
                *(asyncio.wait_for(counter.add_and_get(1), 30)
                  for _ in range(8)),
                return_exceptions=True)
        finally:
            engine.run_vector = real
        failed = [r for r in results if isinstance(r, BaseException)]
        assert failed, "injected engine failure never surfaced"
        for r in failed:
            assert not isinstance(r, asyncio.TimeoutError), \
                "a failed pump hung its command future"
        acked = [r for r in results if not isinstance(r, BaseException)]
        # the failed rows never applied; the healthy burst lands on the
        # exact value the acked set implies
        value = await counter.add_and_get(1)
        assert value == 1 + len(acked) + 1, (value, len(acked))
    finally:
        await asyncio.wait_for(client.close(), 5)
        for s in servers:
            await asyncio.wait_for(s.close(), 5)


# ---------------------------------------------------------------------------
# nemesis under COPYCAT_INVARIANTS=strict (ISSUE 11 acceptance)
# ---------------------------------------------------------------------------


def _assert_members_bit_identical(servers) -> None:
    """Every member of every group holds bit-identical committed log
    bytes up to the shared commit boundary."""
    ser = Serializer()
    compared = 0
    for g in range(len(servers[0].server.groups)):
        grps = [s.server.groups[g] for s in servers]
        up_to = min(grp.commit_index for grp in grps)
        base = {i: ser.write(e) for i in range(1, up_to + 1)
                if (e := grps[0].log.get(i)) is not None}
        for other in grps[1:]:
            for i, data in base.items():
                e = other.log.get(i)
                if e is not None:
                    assert ser.write(e) == data, \
                        f"group {g} log divergence at {i}"
                    compared += 1
    assert compared > 0, "nothing compared — the workload never committed"


def _assert_no_invariant_violations(servers) -> None:
    for s in servers:
        for grp in s.server.groups:
            assert grp.metrics.counter(
                "repl.invariant_violations").value == 0, \
                f"{s.address} group {grp.group_id}: strict check fired"


@pytest.mark.parametrize("all_conflict", (False, True),
                         ids=("spread", "all-conflict"))
def test_nemesis_partition_and_deposition_strict(all_conflict, monkeypatch):
    """Partition a follower mid-storm, heal, then depose a leader-
    hosting member mid-storm, under the strict commit invariant: every
    acked op applies exactly once, survivors' per-group logs are
    bit-identical, and the strict check never fires. ``spread``: two
    groups, six counters and a listened value. ``all-conflict``: the
    classifier's edge — ONE group and every operation on ONE key (the
    increments, and a listener that comes and goes on the same counter,
    so its ops move between the vector lane and the per-entry path), so
    every bounding entry forces the flush and none is spanned."""
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    @async_test(timeout=600)
    async def run():
        registry = LocalServerRegistry()
        servers, client = await _cluster(
            registry, members=3, groups=1 if all_conflict else 2,
            election_timeout=0.25)
        live = [s for s in servers]
        try:
            for s in servers:
                assert s.server.groups[0]._strict_invariants
            counters = await asyncio.gather(
                *(client.get(f"nc{i}", DistributedAtomicLong)
                  for i in range(1 if all_conflict else 6)))
            seen: list = []
            if not all_conflict:
                listened = await client.get("nv", DistributedAtomicValue)
                await listened.set(0)
                listener = await listened.on_change(seen.append)
            acked = [0] * len(counters)
            unknown = [0] * len(counters)

            async def one(i: int) -> None:
                try:
                    await asyncio.wait_for(
                        counters[i].increment_and_get(), 30)
                    acked[i] += 1
                except Exception:
                    unknown[i] += 1

            async def ineligible(r: int) -> None:
                """The round's entry for the per-entry path: a write to
                the listened value, or (all-conflict) a listener that
                registers on THE counter and leaves again."""
                if not all_conflict:
                    await listened.set(r)
                    return
                watch = await asyncio.wait_for(
                    counters[0].on_change(seen.append), 30)
                watch.close()

            async def storm(rounds: int) -> None:
                for r in range(rounds):
                    ops = [one(i % len(counters)) for i in range(6)]
                    ops.append(ineligible(r))
                    await asyncio.gather(*ops, return_exceptions=True)

            await storm(3)  # steady state
            # phase 1: partition a follower mid-storm
            nem = registry.attach_nemesis()
            task = asyncio.ensure_future(storm(5))
            await asyncio.sleep(0.05)
            leader0 = next(s for s in servers
                           if s.server.groups[0].role == LEADER)
            victim = next(s for s in servers if s is not leader0)
            rest = [s.address for s in servers if s is not victim]
            nem.partition([victim.address], rest)
            await asyncio.sleep(0.4)
            nem.heal()
            await asyncio.wait_for(task, 120)
            # phase 2: depose a leader-hosting member mid-storm
            task = asyncio.ensure_future(storm(5))
            await asyncio.sleep(0.05)
            depose = next(s for s in live if any(
                g.role == LEADER for g in s.server.groups))
            live.remove(depose)
            await asyncio.wait_for(depose.close(), 10)
            await asyncio.wait_for(task, 120)
            await storm(2)  # settle on the surviving quorum
            # exactly-once window through the public read API
            got = await asyncio.gather(*(c.get() for c in counters))
            for i, value in enumerate(got):
                assert acked[i] <= value <= acked[i] + unknown[i], (
                    f"counter {i}: {value} outside "
                    f"[{acked[i]}, {acked[i] + unknown[i]}]")
            assert sum(acked) >= 6 * 8, "the storms never committed work"
            # survivors converge, then byte-compare their group logs
            deadline = asyncio.get_running_loop().time() + 30
            while asyncio.get_running_loop().time() < deadline:
                if all(grp.last_applied >= min(
                        s.server.groups[grp.group_id].commit_index
                        for s in live)
                       for s in live for grp in s.server.groups):
                    break
                await asyncio.sleep(0.05)
            _assert_members_bit_identical(live)
            _assert_no_invariant_violations(live)
            if all_conflict:
                lanes = [_lane_counters(s.server) for s in live]
                assert sum(c["vector_ops"] for c in lanes) > 0
                assert sum(c["apply.conflict_flushes"] for c in lanes) > 0
                assert not any(c["apply.parallel_spans"] for c in lanes), \
                    "an entry was spanned where every entry conflicts"
            else:
                listener.close()
        finally:
            nem = registry.attach_nemesis()
            nem.heal()
            try:
                await asyncio.wait_for(client.close(), 5)
            except Exception:
                pass
            for s in live:
                try:
                    await asyncio.wait_for(s.close(), 5)
                except Exception:
                    pass

    run()
