"""The health plane (docs/OBSERVABILITY.md "Health & diagnosis"):
detector units on synthetic evidence, nemesis-driven ground truth on
live clusters (partition -> churn + commit stall, slow disk -> fsync
spike, slow follower -> replication-window collapse, expiry storms,
snapshot failures), the durable black-box spill surviving a
SIGKILL-shaped crash, and the ``COPYCAT_HEALTH=0`` off-plane."""

import asyncio
import json
import os
from collections import deque

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.server.log import Storage, StorageLevel  # noqa: E402
from copycat_tpu.utils import knobs  # noqa: E402
from copycat_tpu.server.log import NoOpEntry  # noqa: E402
from copycat_tpu.server.raft import RaftServer  # noqa: E402
from copycat_tpu.server.stats import StatsListener, fetch_stats  # noqa: E402
from copycat_tpu.io.local import LocalTransport, NetworkNemesis  # noqa: E402
from copycat_tpu.testing.nemesis import SlowDiskNemesis, crash_server  # noqa: E402
from copycat_tpu.utils.health import (  # noqa: E402
    CRITICAL,
    OK,
    WARN,
    BlackBox,
    CommitStallDetector,
    FsyncSpikeDetector,
    IngressBacklogDetector,
    LeaderChurnDetector,
    SessionExpiryDetector,
    SnapshotFailureDetector,
    WindowCollapseDetector,
    worst,
)

from helpers import async_test  # noqa: E402
from raft_fixtures import KVStateMachine, Put, create_cluster  # noqa: E402


def _hist(samples, dt=0.2):
    return deque((i * dt, s) for i, s in enumerate(samples))


# ---------------------------------------------------------------------------
# detector units: synthetic evidence windows
# ---------------------------------------------------------------------------


def test_worst_severity_ordering():
    assert worst([]) == OK
    assert worst([OK, WARN, OK]) == WARN
    assert worst([WARN, CRITICAL]) == CRITICAL


def test_leader_churn_grades(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_CHURN_WARN", "3")
    det = LeaderChurnDetector()
    quiet = _hist([{"elections": 5, "transitions": 2}] * 4)
    assert det.evaluate(quiet, 0).severity == OK
    churny = _hist([{"elections": 5, "transitions": 2},
                    {"elections": 7, "transitions": 3}])
    assert det.evaluate(churny, 0).severity == WARN
    storm = _hist([{"elections": 5, "transitions": 2},
                   {"elections": 11, "transitions": 4}])
    f = det.evaluate(storm, 0)
    assert f.severity == CRITICAL
    assert f.evidence["elections"] == [5, 11]


def test_commit_stall_frozen_vs_growing(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_STALL_S", "0.5")
    det = CommitStallDetector()
    healthy = _hist([{"commit_index": i, "log_last_index": i}
                     for i in range(5)])
    assert det.evaluate(healthy, 0).severity == OK
    frozen = _hist([{"commit_index": 10, "log_last_index": 12}] * 5)
    f = det.evaluate(frozen, 0)
    assert f.severity == WARN and "frozen" in f.reason
    growing = _hist([{"commit_index": 10, "log_last_index": 12 + i}
                     for i in range(5)])
    f = det.evaluate(growing, 0)
    assert f.severity == CRITICAL and "growing" in f.reason
    # a short freeze (below the stall bound) is not a stall
    brief = _hist([{"commit_index": 10, "log_last_index": 12}] * 2,
                  dt=0.1)
    assert det.evaluate(brief, 0).severity == OK


def test_fsync_spike_vs_pre_window_baseline(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_FSYNC_FACTOR", "4")
    det = FsyncSpikeDetector()
    flat = _hist([{"fsyncs": i, "fsync_max_ms": 2.0,
                   "fsync_ewma_ms": 2.0} for i in range(4)])
    assert det.evaluate(flat, 0).severity == OK
    # the spike is judged against the baseline at the window START so a
    # sustained slow disk cannot drag the EWMA up to meet itself
    spike = _hist([{"fsyncs": 0, "fsync_max_ms": 2.0,
                    "fsync_ewma_ms": 2.0},
                   {"fsyncs": 5, "fsync_max_ms": 10.0,
                    "fsync_ewma_ms": 3.0}])
    assert det.evaluate(spike, 0).severity == WARN
    cliff = _hist([{"fsyncs": 0, "fsync_max_ms": 2.0,
                    "fsync_ewma_ms": 2.0},
                   {"fsyncs": 5, "fsync_max_ms": 80.0,
                    "fsync_ewma_ms": 10.0}])
    assert det.evaluate(cliff, 0).severity == CRITICAL
    # sub-ms baselines clamp to the 1 ms noise floor: scheduler jitter
    # on a page-cache fsync is not a disk incident
    jitter = _hist([{"fsyncs": 0, "fsync_max_ms": 0.08,
                     "fsync_ewma_ms": 0.08},
                    {"fsyncs": 5, "fsync_max_ms": 0.9,
                     "fsync_ewma_ms": 0.2}])
    assert det.evaluate(jitter, 0).severity == OK
    # no baseline yet (first fsyncs ever): never judged
    cold = _hist([{"fsyncs": 0, "fsync_max_ms": 0.0,
                   "fsync_ewma_ms": 0.0},
                  {"fsyncs": 3, "fsync_max_ms": 50.0,
                   "fsync_ewma_ms": 50.0}])
    assert det.evaluate(cold, 0).severity == OK


def test_window_collapse_floor_hits_and_rewinds():
    det = WindowCollapseDetector()
    # (window, floor, cumulative floor hits) per peer
    healthy = _hist([{"repl_windows": {"p1": (64, 8, 0)}, "rewinds": 0}]
                    * 3)
    assert det.evaluate(healthy, 0).severity == OK
    # a floor hit inside the window fires even though AIMD already
    # regrew the sampled window value — the counter is the witness
    collapsed = _hist([{"repl_windows": {"p1": (64, 8, 0)}, "rewinds": 0},
                       {"repl_windows": {"p1": (32, 8, 2)}, "rewinds": 0}])
    f = det.evaluate(collapsed, 0)
    assert f.severity == WARN and "p1" in f.evidence["peers"]
    storm = _hist([{"repl_windows": {"p1": (64, 8, 0)}, "rewinds": 0},
                   {"repl_windows": {"p1": (8, 8, 1)}, "rewinds": 4}])
    assert det.evaluate(storm, 0).severity == CRITICAL
    # hits before this window don't re-fire; pinned alone (no new hits,
    # no rewinds) stays quiet too
    old_news = _hist([{"repl_windows": {"p1": (8, 8, 3)}, "rewinds": 0}]
                     * 3)
    assert det.evaluate(old_news, 0).severity == OK


def test_expiry_storm_and_snapshot_failures(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_EXPIRY_WARN", "3")
    det = SessionExpiryDetector()
    assert det.evaluate(
        _hist([{"sessions_expired": 2}, {"sessions_expired": 3}]),
        0).severity == OK
    assert det.evaluate(
        _hist([{"sessions_expired": 2}, {"sessions_expired": 6}]),
        0).severity == WARN
    assert det.evaluate(
        _hist([{"sessions_expired": 2}, {"sessions_expired": 20}]),
        0).severity == CRITICAL
    snap = SnapshotFailureDetector()
    assert snap.evaluate(
        _hist([{"snap_failures": 0}, {"snap_failures": 0}]),
        0).severity == OK
    assert snap.evaluate(
        _hist([{"snap_failures": 0}, {"snap_failures": 1}]),
        0).severity == WARN
    assert snap.evaluate(
        _hist([{"snap_failures": 0}, {"snap_failures": 5}]),
        0).severity == CRITICAL


def test_ingress_backlog_growth(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_QUEUE_WARN", "10")
    det = IngressBacklogDetector()
    flat = _hist([{"proxy_inflight": 12, "event_backlog": 0}] * 3)
    assert det.evaluate(flat, None).severity == OK  # high but not growing
    growing = _hist([{"proxy_inflight": 2, "event_backlog": 0},
                     {"proxy_inflight": 14, "event_backlog": 0}])
    f = det.evaluate(growing, None)
    assert f.severity == WARN and f.group is None
    flood = _hist([{"proxy_inflight": 2, "event_backlog": 0},
                   {"proxy_inflight": 30, "event_backlog": 30}])
    assert det.evaluate(flood, None).severity == CRITICAL


# ---------------------------------------------------------------------------
# the durable black-box
# ---------------------------------------------------------------------------


def test_blackbox_roundtrip_and_recovered_tag(tmp_path):
    path = str(tmp_path / "node.blackbox")
    bb = BlackBox(path)
    bb.record("fault", fault="partition")
    bb.record("violation", check="commit_monotone")
    assert [e["kind"] for e in bb.events()] == ["fault", "violation"]
    assert not any(e.get("recovered") for e in bb.events())
    bb.close()
    # the next life reloads the previous one's events, recovered-tagged
    reborn = BlackBox(path)
    kinds = [(e["kind"], e.get("recovered")) for e in reborn.events()]
    assert kinds == [("fault", True), ("violation", True)]
    assert reborn.summary()["recovered_events"] == 2
    reborn.close()


def test_blackbox_distrusts_everything_past_a_torn_frame(tmp_path):
    path = str(tmp_path / "node.blackbox")
    bb = BlackBox(path)
    for i in range(5):
        bb.record("fault", n=i)
    bb.close()
    # tear the file mid-way through: a crash mid-append
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 13)
    reborn = BlackBox(path)
    ns = [e["n"] for e in reborn.recovered]
    assert ns == [0, 1, 2, 3]  # the torn 5th record is dropped
    assert reborn.torn == 1
    reborn.close()


def test_blackbox_truncates_torn_tail_before_appending(tmp_path):
    """A crash mid-append leaves a torn tail; the NEXT life must
    truncate it before appending or ALL of its own events land after
    garbage and the life after that (whose scan stops at the first bad
    frame) silently discards them."""
    path = str(tmp_path / "node.blackbox")
    life1 = BlackBox(path)
    for i in range(3):
        life1.record("fault", life=1, n=i)
    life1.close()
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 5)  # crash mid-append
    life2 = BlackBox(path)
    assert life2.torn == 1
    assert [e["n"] for e in life2.recovered] == [0, 1]
    life2.record("fault", life=2)
    life2.close()
    life3 = BlackBox(path)
    lives = [e.get("life") for e in life3.recovered]
    assert lives == [1, 1, 2]  # life 2's forensics survived
    life3.close()


def test_blackbox_rotation_bounds_disk(tmp_path):
    path = str(tmp_path / "node.blackbox")
    bb = BlackBox(path, max_bytes=4096)
    for i in range(400):
        bb.record("fault", n=i, pad="x" * 40)
    bb.close()
    assert os.path.getsize(path) <= 4096 + 200
    assert os.path.getsize(path + ".1") <= 4096 + 200
    # the ring still serves the most recent events after reload
    reborn = BlackBox(path, max_bytes=4096)
    assert reborn.recovered[-1]["n"] == 399
    reborn.close()


# ---------------------------------------------------------------------------
# live clusters: the monitor, the routes, the A/B knob
# ---------------------------------------------------------------------------


@async_test(timeout=120)
async def test_monitor_ok_on_healthy_cluster_and_routes():
    cluster = await create_cluster(3)
    try:
        client = await cluster.client()
        for i in range(5):
            await client.submit(Put(key=f"k{i}", value=i))
        leader = cluster.leader
        verdict = leader.health.tick()
        assert verdict["status"] == OK and verdict["reasons"] == []
        expected = {
            "leader_churn", "commit_stall", "window_collapse",
            "fsync_spike", "session_expiry", "snapshot_failure",
            "ingress_backlog", "slo_burn"}
        if knobs.get_bool("COPYCAT_PROFILE"):  # loop_stall rides the plane
            expected.add("loop_stall")
        assert set(verdict["detectors"]) == expected
        snap = leader.stats_snapshot()["raft"]
        assert snap["health.checks"] >= 1
        assert snap["health.status"] == 0
        listener = await StatsListener(leader, port=0).open()
        try:
            health = json.loads(await fetch_stats(
                f"127.0.0.1:{listener.port}", "/health"))
            assert health["status"] == OK
            assert health["node"] == str(leader.address)
            healthz = json.loads(await fetch_stats(
                f"127.0.0.1:{listener.port}", "/healthz"))
            # uptime_s/git_sha (utils/buildinfo.py) ride every role's
            # liveness payload: restart + half-rolled detection
            assert healthz.pop("uptime_s") >= 0
            assert "git_sha" in healthz  # None outside a checkout
            healthz.pop("git_sha")
            assert healthz == {"ok": True, "node": str(leader.address),
                               "role": "leader", "term": leader.term}
            unknown = json.loads(await fetch_stats(
                f"127.0.0.1:{listener.port}", "/nope"))
            assert "/health" in unknown["routes"]
            assert "/healthz" in unknown["routes"]
        finally:
            await listener.close()
    finally:
        await cluster.close()


def test_health_off_knob_removes_the_plane(monkeypatch, tmp_path):
    monkeypatch.setenv("COPYCAT_HEALTH", "0")

    async def run():
        cluster = await create_cluster(
            1, storage_factory=lambda i: Storage(
                StorageLevel.DISK, str(tmp_path),
                max_entries_per_segment=16))
        try:
            server = cluster.servers[0]
            assert server.health is None
            assert server.blackbox is None
            assert not any(f.endswith(".blackbox")
                           for f in os.listdir(tmp_path))
            snap = server.stats_snapshot()["raft"]
            assert not any(k.startswith("health.") for k in snap)
            listener = await StatsListener(server, port=0).open()
            try:
                health = json.loads(await fetch_stats(
                    f"127.0.0.1:{listener.port}", "/health"))
                assert health["status"] == "disabled"
            finally:
                await listener.close()
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


# ---------------------------------------------------------------------------
# nemesis-driven ground truth (strict invariants: the faults must not
# trip a safety monitor while the health plane grades them)
# ---------------------------------------------------------------------------


def test_partition_yields_churn_and_commit_stall(monkeypatch):
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    monkeypatch.setenv("COPYCAT_HEALTH_STALL_S", "0.5")
    monkeypatch.setenv("COPYCAT_HEALTH_CHURN_WARN", "2")

    async def run():
        cluster = await create_cluster(3, election_timeout=0.15,
                                       heartbeat_interval=0.03)
        try:
            client = await cluster.client()
            for i in range(5):
                await client.submit(Put(key=f"k{i}", value=i))
            leader = cluster.leader
            for s in cluster.servers:
                s.health.tick()
            # full partition: every member alone — no quorum anywhere
            nemesis = cluster.registry.attach_nemesis(NetworkNemesis())
            nemesis.partition(*[[s.address] for s in cluster.servers])
            # appends land on the old leader but can never commit: the
            # commit-stall signature, with lag growing
            for _ in range(4):
                leader._append(NoOpEntry())
            deadline = asyncio.get_running_loop().time() + 3.0
            stall = churn = OK
            while asyncio.get_running_loop().time() < deadline:
                await asyncio.sleep(0.15)
                leader._append(NoOpEntry())
                for s in cluster.servers:
                    v = s.health.tick()
                    det = v["detectors"]
                    stall = worst([stall,
                                   det["commit_stall"]["status"]])
                    churn = worst([churn,
                                   det["leader_churn"]["status"]])
                if stall == CRITICAL and churn != OK:
                    break
            assert stall == CRITICAL, "commit stall (growing) not graded"
            assert churn != OK, "leader churn not graded"
            # the verdict carries the machinery an operator needs
            v = leader.health.tick()
            assert any("commit stalled" in r for r in v["reasons"])
            assert leader.stats_snapshot()["raft"]["health.status"] >= 1
            nemesis.heal()
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_slow_disk_grades_fsync_spike(monkeypatch, tmp_path):
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    async def run():
        cluster = await create_cluster(
            3, storage_factory=lambda i: Storage(
                StorageLevel.DISK, str(tmp_path / str(i)),
                max_entries_per_segment=32))
        try:
            client = await cluster.client()
            leader = cluster.leader
            # establish the EWMA baseline with healthy-disk commits
            for i in range(10):
                await client.submit(Put(key=f"w{i}", value=i))
            baseline_ms = leader.groups[0]._fsync_ewma_ms
            assert baseline_ms > 0.0
            leader.health.tick()
            # scale the injected delay to the MEASURED baseline: on a
            # loaded CI host healthy fsyncs can already be slow, and a
            # fixed 50ms would not read as a spike against them
            delay_s = max(0.05, baseline_ms * 10.0 / 1e3)
            slow = SlowDiskNemesis(leader, delay_s=delay_s)
            slow.install()
            try:
                for i in range(3):
                    await client.submit(Put(key=f"s{i}", value=i))
            finally:
                slow.remove()
            v = leader.health.tick()
            f = v["detectors"]["fsync_spike"]["groups"]["0"]
            assert f["status"] in (WARN, CRITICAL)
            assert "baseline" in f["reason"]
            assert max(f["evidence"]["fsync_max_ms"]) >= delay_s * 1e3
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_slow_follower_collapses_replication_window(monkeypatch, tmp_path):
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    monkeypatch.setenv("COPYCAT_REPL_WINDOW", "8")

    async def run():
        cluster = await create_cluster(
            3, session_timeout=30.0,
            storage_factory=lambda i: Storage(
                StorageLevel.DISK, str(tmp_path / str(i)),
                max_entries_per_segment=64))
        try:
            # the blocking fsync stalls the shared loop: a short session
            # timeout would expire the client mid-burst
            client = await cluster.client(session_timeout=30.0)
            leader = cluster.leader
            followers = [s for s in cluster.servers if s is not leader]
            # healthy acks first: the AIMD EWMA must learn a fast
            # baseline for the slow follower to read as congestion
            for i in range(20):
                await client.submit(Put(key=f"w{i}", value=i))
            leader.health.tick()
            # scale the injected ack delay to the learned ack baseline:
            # AIMD shrinks on latency RATIOS, and a loaded host's
            # healthy acks may already be tens of ms
            ack_ewma = max((ps.ack_ewma_ms for ps in
                            leader.groups[0]._peer_streams.values()),
                           default=1.0)
            slow = SlowDiskNemesis(followers[0],
                                   delay_s=max(0.06, ack_ewma * 8 / 1e3))
            slow.install()
            fired = OK
            evidence_peers: list = []
            try:
                # the floor-hit counter makes the transient collapse
                # observable after the fact: the burst's consecutive
                # slow acks halve the window to its floor even though
                # AIMD regrows it once the EWMA re-baselines
                for burst in range(3):
                    await asyncio.gather(*(
                        client.submit(Put(key=f"b{burst}.{i}", value=i))
                        for i in range(60)))
                    await asyncio.sleep(0.3)
                    v = leader.health.tick()
                    g = v["detectors"]["window_collapse"]["groups"]
                    got = g["0"]["status"]
                    if got != OK:
                        fired = worst([fired, got])
                        evidence_peers = g["0"]["evidence"]["peers"]
                        break
            finally:
                slow.remove()
            assert fired != OK, \
                "window collapse never graded under a slow follower"
            assert str(followers[0].address) in evidence_peers
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_session_expiry_storm(monkeypatch):
    monkeypatch.setenv("COPYCAT_HEALTH_EXPIRY_WARN", "2")

    async def run():
        cluster = await create_cluster(3)
        try:
            leader = cluster.leader
            clients = [await cluster.client(session_timeout=0.4)
                       for _ in range(3)]
            leader.health.tick()
            # the clients die without closing: keep-alives stop, the
            # leader's wall-clock detector expires the sessions
            for c in clients:
                c._keepalive.cancel()
                c._keepalive = None
            deadline = asyncio.get_running_loop().time() + 5.0
            got = OK
            while asyncio.get_running_loop().time() < deadline:
                await asyncio.sleep(0.2)
                v = leader.health.tick()
                got = v["detectors"]["session_expiry"]["groups"]["0"][
                    "status"]
                if got != OK:
                    break
            assert got in (WARN, CRITICAL)
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_snapshot_failures_graded(monkeypatch, tmp_path):
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "5")

    async def run():
        cluster = await create_cluster(
            1, storage_factory=lambda i: Storage(
                StorageLevel.DISK, str(tmp_path),
                max_entries_per_segment=16))
        try:
            server = cluster.servers[0]
            client = await cluster.client()
            server.health.tick()

            def broken_save(index, payload):
                raise OSError("disk full")

            server.groups[0]._snapshots.save = broken_save
            for i in range(12):
                await client.submit(Put(key=f"k{i}", value=i))
            await server.snapshots_settled()
            v = server.health.tick()
            f = v["detectors"]["snapshot_failure"]["groups"]["0"]
            assert f["status"] in (WARN, CRITICAL)
            assert server.metrics.counter("snap.capture_failures").value > 0
            # the failure also landed in the durable black-box
            kinds = [e["kind"] for e in server.blackbox.events()]
            assert "snapshot_failed" in kinds
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


# ---------------------------------------------------------------------------
# the black-box survives a SIGKILL-shaped crash
# ---------------------------------------------------------------------------


def test_blackbox_survives_crash_and_flight_serves_it(monkeypatch,
                                                      tmp_path):
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")

    async def run():
        storage = lambda i: Storage(StorageLevel.DISK, str(tmp_path),  # noqa: E731
                                    max_entries_per_segment=16)
        cluster = await create_cluster(1, storage_factory=storage)
        try:
            server = cluster.servers[0]
            client = await cluster.client()
            for i in range(5):
                await client.submit(Put(key=f"k{i}", value=i))
            server.health_note("pre_crash_fault", fault="injected")
            assert any(e["kind"] == "pre_crash_fault"
                       for e in server.blackbox.events())
            await crash_server(server)
            # the next life: same storage directory, same address
            reborn = RaftServer(
                server.address, [server.address],
                LocalTransport(cluster.registry,
                               local_address=server.address),
                KVStateMachine(), storage=storage(0),
                election_timeout=0.2, heartbeat_interval=0.04)
            cluster.servers[0] = reborn
            await reborn.open()
            recovered = reborn.blackbox.recovered
            assert any(e["kind"] == "pre_crash_fault"
                       and e.get("recovered") for e in recovered)
            listener = await StatsListener(reborn, port=0).open()
            try:
                flight = json.loads(await fetch_stats(
                    f"127.0.0.1:{listener.port}", "/flight"))
                bb = flight["blackbox"]
                assert bb["recovered_events"] >= 1
                assert any(e["kind"] == "pre_crash_fault"
                           for e in bb["recovered"])
            finally:
                await listener.close()
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


# ---------------------------------------------------------------------------
# SLO burn detection (docs/OBSERVABILITY.md "Retrospective telemetry"):
# objectives judged over the RETAINED series window, not the monitor's
# short evidence deque
# ---------------------------------------------------------------------------


def _slo_rows(server, stuck, ok=0, t0=1000.0):
    """Ingest synthetic retained samples: `stuck` intervals where a
    group's commit sat frozen behind its log tail, then `ok` healthy
    ones (lag closed, commit advancing)."""
    commit = 100
    gauges = ["raft_commit_lag", "raft_commit_index"]
    for i in range(stuck):
        server.series.ingest({"raft_commit_lag": 7,
                              "raft_commit_index": commit,
                              "_gauge_keys": gauges}, t=t0 + i)
    for i in range(ok):
        commit += 3
        server.series.ingest({"raft_commit_lag": 0,
                              "raft_commit_index": commit,
                              "_gauge_keys": gauges}, t=t0 + stuck + i)


def test_slo_burn_availability_grades_and_gauges(monkeypatch):
    monkeypatch.setenv("COPYCAT_SLO_AVAIL", "0.99")

    async def run():
        cluster = await create_cluster(1)
        try:
            server = cluster.servers[0]
            assert "slo_burn" in server.health.tick()["detectors"]
            snap = server.stats_snapshot()["raft"]
            assert snap["slo.avail_objective"] == 0.99
            # ~1 stuck interval in ~21: burn ~5x the 1% budget -> WARN
            _slo_rows(server, stuck=2, ok=20)
            v = server.health.tick()
            slo = v["detectors"]["slo_burn"]["groups"]["server"]
            assert slo["status"] == WARN
            assert "availability burn" in slo["reason"]
            # a window that is mostly stuck: fast burn -> CRITICAL
            _slo_rows(server, stuck=60, t0=2000.0)
            v = server.health.tick()
            slo = v["detectors"]["slo_burn"]["groups"]["server"]
            assert slo["status"] == CRITICAL
            assert slo["evidence"]["unavailable_intervals"]
            snap = server.stats_snapshot()["raft"]
            assert snap["slo.avail_burn"] >= 10
            assert snap["slo.avail_observed"] < 1.0
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_slo_burn_latency_objective(monkeypatch):
    monkeypatch.setenv("COPYCAT_SLO_P99_MS", "10")

    async def run():
        cluster = await create_cluster(1)
        try:
            server = cluster.servers[0]
            snap = server.stats_snapshot()["raft"]
            assert snap["slo.p99_objective_ms"] == 10.0
            # active intervals (commit-latency count advancing) whose
            # sampled p99 breaches the objective in every interval
            count = 0
            for i in range(6):
                count += 5
                server.series.ingest(
                    {"latency.commit_ms": {"count": count, "mean": 20.0,
                                           "p50": 18.0, "p99": 25.0,
                                           "max": 30.0}}, t=1000.0 + i)
            v = server.health.tick()
            slo = v["detectors"]["slo_burn"]["groups"]["server"]
            assert slo["status"] == CRITICAL
            assert "breached the 10ms objective" in slo["reason"]
            snap = server.stats_snapshot()["raft"]
            assert snap["slo.p99_observed_ms"] == 25.0
            assert snap["slo.p99_burn"] == 1.0
            # availability gauges were never registered: no objective
            assert "slo.avail_objective" not in snap
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)


def test_slo_burn_without_objectives_stays_ok():
    async def run():
        cluster = await create_cluster(1)
        try:
            server = cluster.servers[0]
            _slo_rows(server, stuck=30)
            v = server.health.tick()
            slo = v["detectors"]["slo_burn"]["groups"]["server"]
            assert slo["status"] == OK  # nothing configured, no grading
            snap = server.stats_snapshot()["raft"]
            assert not any(k.startswith("slo.") for k in snap)
        finally:
            await cluster.close()

    from helpers import arun
    arun(run(), timeout=120)
