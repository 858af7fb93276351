"""The bench-baseline perf-regression gate (``testing/bench_gate.py``):
window math, unit/missing-baseline handling, the update path, and the
committed golden's shape."""

import json
import os

from copycat_tpu.testing import bench_gate


def _artifact(scenario="spi", value=10000.0, unit="ops/sec", **meta):
    return {"scenario": scenario, "value": value, "unit": unit,
            "meta": meta or {"git_sha": "abc", "host": {"cpus": 2}}}


def _golden(value=10000.0, tolerance=0.25, scenario="spi",
            unit="ops/sec"):
    return {"tolerance": tolerance,
            "scenarios": {scenario: {"value": value, "unit": unit,
                                     "recorded": {}}}}


def test_gate_passes_inside_the_window():
    ok, line = bench_gate.gate_artifact(_artifact(value=8000), _golden())
    assert ok and "ok 8,000.0" in line
    ok, _ = bench_gate.gate_artifact(_artifact(value=7500.0), _golden())
    assert ok  # exactly on the floor passes


def test_gate_fails_below_the_floor():
    ok, line = bench_gate.gate_artifact(_artifact(value=7000), _golden())
    assert not ok
    assert "REGRESSION" in line and "floor 7,500.0" in line


def test_gate_flags_stale_baseline_above_the_window():
    ok, line = bench_gate.gate_artifact(_artifact(value=20000), _golden())
    assert ok  # a win never fails the gate...
    assert "stale" in line  # ...but the window should be refreshed


def test_gate_missing_baseline_and_unit_change():
    ok, line = bench_gate.gate_artifact(
        _artifact(scenario="novel"), _golden())
    assert not ok and "--update-golden" in line
    ok, line = bench_gate.gate_artifact(
        _artifact(unit="reads/sec"), _golden())
    assert not ok and "unit changed" in line


def test_gate_rejects_empty_headline():
    ok, line = bench_gate.gate_artifact(
        {"scenario": "spi", "value": 0, "unit": "ops/sec"}, _golden())
    assert not ok and "no positive headline" in line


def test_update_golden_records_value_and_meta(tmp_path):
    golden_path = str(tmp_path / "baseline.json")
    artifact_path = str(tmp_path / "a.json")
    with open(artifact_path, "w") as f:
        json.dump(_artifact(value=12345.0), f)
    rc = bench_gate.main([artifact_path, "--golden", golden_path,
                          "--update-golden"])
    assert rc == 0
    golden = json.load(open(golden_path))
    assert golden["scenarios"]["spi"]["value"] == 12345.0
    assert golden["scenarios"]["spi"]["recorded"]["git_sha"] == "abc"
    # the freshly recorded window gates its own artifact green
    assert bench_gate.main([artifact_path, "--golden", golden_path]) == 0
    # and a regressed rerun red, printing the update command
    with open(artifact_path, "w") as f:
        json.dump(_artifact(value=3000.0), f)
    assert bench_gate.main([artifact_path, "--golden", golden_path]) == 1


def test_committed_golden_covers_the_ci_smokes():
    golden = bench_gate.load_golden(bench_gate.DEFAULT_GOLDEN)
    assert os.path.exists(bench_gate.DEFAULT_GOLDEN)
    for scenario in ("spi", "sharded", "apply"):
        entry = golden["scenarios"][scenario]
        assert entry["value"] > 0
        assert entry["unit"] == "ops/sec"
        # the recorded attribution explains a miss on a different host
        assert "host" in entry["recorded"]
        assert "knobs" in entry["recorded"]
    assert 0 < golden["tolerance"] < 1


def test_gate_tolerates_series_and_metrics_payloads(tmp_path):
    """Artifacts now carry the run's retained /series windows next to
    the metrics snapshots (bench.py SERIES_WINDOWS); the gate grades
    the headline value identically and never commits either bulky
    payload into the golden."""
    artifact = _artifact(value=8000)
    artifact["metrics"] = {"server": {"raft_term": 1}}
    artifact["series"] = {"server": {"node": "n", "role": "member",
                                     "samples": [{"t": 1.0,
                                                  "values": {"x": 1}}]}}
    ok, line = bench_gate.gate_artifact(artifact, _golden())
    assert ok and "ok 8,000.0" in line
    golden_path = tmp_path / "golden.json"
    golden = bench_gate.load_golden(str(golden_path))
    bench_gate.update_golden([artifact], golden)
    entry = golden["scenarios"]["spi"]
    assert "series" not in entry and "metrics" not in entry
    assert entry["value"] == 8000
