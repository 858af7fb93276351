"""Device-plane flight recorder: telemetry ground truth, invariant
monitors, fault correlation (docs/OBSERVABILITY.md § device plane).

Ground-truth obligations (ISSUE 3): a steady-state run shows ZERO
elections/leader-changes after warmup; a nemesis partition run shows
elections > 0 and leaderless rounds > 0 that disappear after heal; the
invariant monitor flags a deliberately corrupted snapshot and stays
silent on a healthy one; and the telemetry-off step is bit-identical to
the telemetry-on step's state evolution (the block is pure output).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models.telemetry import (  # noqa: E402
    DeviceTelemetryHub,
    InvariantViolation,
    POOL_NAMES,
)
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import (  # noqa: E402
    Config,
    DeviceTelemetry,
)
from copycat_tpu.testing.nemesis import Nemesis  # noqa: E402
from copycat_tpu.utils.metrics import merge_snapshots  # noqa: E402

from engines import MONOTONE, device_plane  # noqa: E402

TEL_CFG = Config(telemetry=True)


def make():
    return device_plane(TEL_CFG)


def counter_value(rg, name, **labels):
    return rg.telemetry.registry.counter(name, **labels).value


# ---------------------------------------------------------------------------
# the knob: off is bit-identical, on is pure output
# ---------------------------------------------------------------------------


def test_telemetry_off_state_bit_identical():
    """Same seeds, same submits: the telemetry-on and telemetry-off
    programs must produce bit-identical STATE every round (the block
    derives from existing intermediates — no extra RNG, no writes)."""
    on, off = device_plane(TEL_CFG, seed=7), device_plane(seed=7)
    assert off.telemetry is None
    assert on.telemetry is not None
    for _ in range(15):
        for rg in (on, off):
            for g in range(rg.num_groups):
                rg.submit(g, ap.OP_LONG_ADD, 1)
            rg.step_round()
    s_on, s_off = on.state, off.state
    for name, a, b in zip(s_on._fields, s_on, s_off):
        if name == "resources":
            for rn, ra, rb in zip(a._fields, a, b):
                assert (np.asarray(ra) == np.asarray(rb)).all(), rn
        else:
            assert (np.asarray(a) == np.asarray(b)).all(), name
    assert on.rounds == off.rounds == 15 and int(on.value(0)) > 0


# ---------------------------------------------------------------------------
# ground truth: steady state vs nemesis
# ---------------------------------------------------------------------------


def test_steady_state_zero_elections_after_warmup():
    rg = make()
    rg.wait_for_leaders()
    rg.run(5)  # settle any residual churn
    e0 = counter_value(rg, "device.elections_started")
    c0 = counter_value(rg, "device.leader_changes")
    l0 = counter_value(rg, "device.leaderless_rounds")
    commit0 = counter_value(rg, "device.commit_advance")
    tags = [rg.submit(g, ap.OP_LONG_ADD, 1) for g in range(8)]
    rg.run_until(tags)
    rg.run(10)
    assert counter_value(rg, "device.elections_started") == e0
    assert counter_value(rg, "device.leader_changes") == c0
    assert counter_value(rg, "device.leaderless_rounds") == l0
    # real work flowed and was attributed to the right pool
    assert counter_value(rg, "device.commit_advance") > commit0
    assert counter_value(rg, "device.applies", pool="value") >= 8
    assert rg.telemetry.monitor.violations == 0


def test_nemesis_partition_shows_elections_then_heals():
    rg = make()
    rg.wait_for_leaders()
    rg.run(5)
    nem = Nemesis(rg, seed=3, period=10, faults=("partition",))
    e0 = counter_value(rg, "device.elections_started")
    l0 = counter_value(rg, "device.leaderless_rounds")
    for _ in range(30):
        nem.tick()
        rg.step_round()
    e_fault = counter_value(rg, "device.elections_started")
    l_fault = counter_value(rg, "device.leaderless_rounds")
    assert e_fault > e0, "partitions must force elections"
    assert l_fault > l0, "partitions must produce leaderless rounds"
    # heal → settle → a quiet window records NO new churn
    nem.heal()
    rg.run(40)
    e1 = counter_value(rg, "device.elections_started")
    l1 = counter_value(rg, "device.leaderless_rounds")
    rg.run(20)
    assert counter_value(rg, "device.elections_started") == e1
    assert counter_value(rg, "device.leaderless_rounds") == l1
    # the whole storm ran under the online monitor without a violation
    assert rg.telemetry.monitor.violations == 0

    # fault correlation: the flight ring holds the injected partition
    # events AND telemetry events recording the churn they caused
    kinds = [ev["kind"] for ev in rg.telemetry.flight.events()]
    assert "fault" in kinds and "telemetry" in kinds
    faults = [ev for ev in rg.telemetry.flight.events()
              if ev["kind"] == "fault"]
    assert any(ev["fault"] == "partition" for ev in faults)
    assert faults[-1]["fault"] == "heal"
    text = rg.telemetry.flight.render_text()
    assert "partition" in text


def test_events_drained_counted():
    """A queued-lock grant pushes a session event through the outbox;
    the drain shows up in device.events_drained."""
    rg = make()
    rg.wait_for_leaders()
    t1 = rg.submit(0, ap.OP_LOCK_ACQUIRE, 1, -1)
    t2 = rg.submit(0, ap.OP_LOCK_ACQUIRE, 2, -1)
    rg.run_until([t1, t2])
    t3 = rg.submit(0, ap.OP_LOCK_RELEASE, 1)
    rg.run_until([t3])
    rg.run(5)
    assert counter_value(rg, "device.events_drained") >= 1
    assert counter_value(rg, "device.applies", pool="lock") >= 3


# ---------------------------------------------------------------------------
# fused + deep planes: telemetry rides the amortized fetches
# ---------------------------------------------------------------------------


def test_step_rounds_fused_ingests_every_round():
    rg = make()
    rg.wait_for_leaders()
    r0 = counter_value(rg, "device.rounds")
    rg.step_rounds(5)
    assert counter_value(rg, "device.rounds") == r0 + 5
    assert rg.telemetry._rounds == rg.rounds


def test_deep_drive_telemetry_one_fetch():
    from copycat_tpu.models.bulk import BulkDriver

    rg = device_plane(MONOTONE._replace(telemetry=True))
    rg.wait_for_leaders()
    r0 = counter_value(rg, "device.rounds")
    drv = BulkDriver(rg)
    res = drv.drive(np.repeat(np.arange(4), 6), ap.OP_LONG_ADD, 1)
    assert (res.results == np.tile(np.arange(1, 7), 4)).all()
    assert counter_value(rg, "device.rounds") == r0 + res.rounds
    assert counter_value(rg, "device.applies", pool="value") >= 24
    # scan mode (whole blind phase as one program): stacked telemetry
    scan = BulkDriver(rg, deep_scan=True)
    r1 = counter_value(rg, "device.rounds")
    res2 = scan.drive(np.repeat(np.arange(4), 5), ap.OP_LONG_ADD, 1)
    assert counter_value(rg, "device.rounds") == r1 + res2.rounds
    assert rg.telemetry.monitor.violations == 0


# ---------------------------------------------------------------------------
# invariant monitor: silent on healthy, loud on corruption
# ---------------------------------------------------------------------------


def _tel(G=4, commit=0, term=1, lane=0, leaderless=0, changes=0):
    z = np.zeros(G, np.int32)
    return DeviceTelemetry(
        elections_started=z,
        leader_changes=np.full(G, changes, np.int32), term_bumps=z,
        leaderless=np.full(G, leaderless, np.int32),
        commit_advance=z, commit_max=np.full(G, commit, np.int32),
        term_max=np.full(G, term, np.int32),
        leader_lane=np.full(G, lane, np.int32),
        leader_term=np.full(G, term, np.int32),
        applies=np.zeros((G, len(POOL_NAMES)), np.int32),
        ring_occ_max=z, submit_rejections=z, vote_splits=z,
        events_drained=z, events_dropped=z)


def test_monitor_silent_on_healthy_sequence():
    hub = DeviceTelemetryHub(4, mode="observe")
    for r, commit in enumerate((1, 2, 2, 5)):
        hub.ingest(_tel(commit=commit, term=1 + r // 2), r)
    assert hub.monitor.violations == 0


def test_monitor_flags_corrupted_snapshot():
    hub = DeviceTelemetryHub(4, mode="observe")
    hub.ingest(_tel(commit=5), 0)
    hub.ingest(_tel(commit=3), 1)       # commit regressed: corruption
    assert hub.monitor.violations >= 1
    assert hub.registry.counter("device.invariant_violations",
                                kind="commit_monotone").value >= 1
    kinds = [ev.get("check") for ev in hub.flight.events()
             if ev["kind"] == "violation"]
    assert "commit_monotone" in kinds


def test_monitor_flags_term_regression_and_split_brain():
    hub = DeviceTelemetryHub(4, mode="observe")
    hub.ingest(_tel(commit=1, term=5, lane=1, changes=1), 0)
    # a zombie VIEW regression without an election is legitimate
    # (higher-term leader stepped down, stale leader still visible)
    hub.ingest(_tel(commit=1, term=3, lane=1), 1)
    assert hub.registry.counter("device.invariant_violations",
                                kind="term_monotone").value == 0
    # but a fresh ELECTION at a non-increasing term is a safety breach
    hub.ingest(_tel(commit=1, term=4, lane=2, changes=1), 2)
    assert hub.registry.counter("device.invariant_violations",
                                kind="term_monotone").value >= 1
    v0 = hub.monitor.violations
    hub.ingest(_tel(commit=1, term=5, lane=2), 3)   # 2nd leader, term 5
    assert hub.registry.counter("device.invariant_violations",
                                kind="leader_per_term").value >= 1
    assert hub.monitor.violations > v0


def test_monitor_strict_raises():
    hub = DeviceTelemetryHub(4, mode="strict")
    hub.ingest(_tel(commit=5), 0)
    with pytest.raises(InvariantViolation, match="commit"):
        hub.ingest(_tel(commit=3), 1)


def test_monitor_leaderless_bound():
    hub = DeviceTelemetryHub(4, mode="observe")
    hub.monitor.leaderless_max = 0.5
    hub.ingest(_tel(leaderless=1), 0)   # 4/4 leaderless > 0.5
    assert hub.registry.counter("device.invariant_violations",
                                kind="leaderless_bound").value == 1


def test_strict_mode_raises_through_the_engine_path():
    rg = make()
    rg.wait_for_leaders()
    rg.telemetry.monitor.mode = "strict"
    # fabricate a corruption baseline: pretend we saw commits far ahead
    rg.telemetry.monitor._last_commit[:] = 10_000
    rg.telemetry.monitor._commit_total = 40_000
    with pytest.raises(InvariantViolation):
        rg.step_round()


def test_env_opt_in_enables_telemetry(monkeypatch):
    monkeypatch.setenv("COPYCAT_INVARIANTS", "strict")
    rg = device_plane()
    assert rg.config.telemetry
    assert rg.telemetry is not None
    assert rg.telemetry.monitor.mode == "strict"
    monkeypatch.setenv("COPYCAT_INVARIANTS", "off")
    rg2 = device_plane()
    assert not rg2.config.telemetry and rg2.telemetry is None


# ---------------------------------------------------------------------------
# exposition: snapshots, shard merge, stats routes, CLI watch rendering
# ---------------------------------------------------------------------------


def test_device_snapshot_and_shard_merge():
    rg = make()
    rg.wait_for_leaders()
    rg.run(5)
    snap = rg.device_snapshot()
    assert snap["device.rounds"] == rg.rounds
    assert "device.elections_started" in snap
    assert "device.leaderless_groups" in snap.get("_gauge_keys", [])
    # single-host merged view is the local view (taken a moment later:
    # everything but the registry's age)
    merged = rg.merged_device_snapshot()
    assert {**merged, "uptime_s": None} == {**snap, "uptime_s": None}
    # per-shard attribution folds back to the totals via merge_snapshots
    shards = rg.telemetry.shard_snapshots(4)
    assert len(shards) == 4 and sum(s["groups"] for s in shards) == 8
    merged = merge_snapshots(
        [{k: v for k, v in s.items() if k.startswith("device.")}
         for s in shards])
    per_group = rg.telemetry.per_group_totals()
    assert merged["device.elections_started"] == int(
        per_group["elections_started"].sum())
    assert merged["device.commit_advance"] == int(
        per_group["commit_advance"].sum())


def test_stats_listener_flight_route():
    from types import SimpleNamespace

    from copycat_tpu.server.stats import StatsListener

    hub = DeviceTelemetryHub(2, mode="observe")
    hub.flight.record("fault", 3, fault="partition")
    raft = SimpleNamespace(state_machine=SimpleNamespace(
        _engine=SimpleNamespace(_groups=SimpleNamespace(telemetry=hub))))
    listener = StatsListener(raft)
    body, ctype = listener._route("/flight")
    assert ctype == "application/json"
    import json
    events = json.loads(body)["events"]
    assert events and events[0]["kind"] == "fault"
    body, _ = listener._route("/flight.txt")
    assert b"partition" in body
    # no engine → a clear "disabled" note, not a 500
    bare = StatsListener(SimpleNamespace(state_machine=object()))
    body, _ = bare._route("/flight")
    assert b"disabled" in body
    # /flight is advertised on unknown-path responses
    body, _ = listener._route("/nope")
    assert b"/flight" in body


def test_cli_watch_rendering():
    from copycat_tpu.cli import _flatten_numeric, _render_watch

    snap = {"node": "127.0.0.1:5001", "role": "leader",
            "raft": {"ops": 10, "lat": {"count": 4, "mean": 1.5,
                                        "p50": 1.0, "p99": 3.0, "max": 3.0},
                     "_gauge_keys": ["raft_term"], "raft_term": 7},
            "manager": {"device": {"device.rounds": 5}}}
    flat = _flatten_numeric(snap)
    assert flat["raft.ops"] == 10
    assert flat["raft.lat.p99"] == 3.0
    assert flat["manager.device.device.rounds"] == 5
    assert "raft._gauge_keys" not in flat
    prev = dict(flat, **{"raft.ops": 0})
    frame = _render_watch(snap, prev, 2.0)
    assert "node: 127.0.0.1:5001" in frame
    assert "+5.0/s" in frame  # (10 - 0) / 2s
