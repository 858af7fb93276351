"""Metrics subsystem tests (utils/metrics.py + driver wiring)."""

import json

import pytest

from copycat_tpu.utils.metrics import (
    Histogram,
    MetricsRegistry,
    merge_snapshots,
)


def test_histogram_percentiles_interpolate():
    h = Histogram()
    for v in range(1, 101):
        h.record(float(v))
    assert h.count == 100 and h.mean == pytest.approx(50.5)
    # linear interpolation at rank p/100*(n-1) — numpy's default method
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(99) == pytest.approx(99.01)
    assert h.percentile(0) == pytest.approx(1.0)
    assert h.percentile(100) == pytest.approx(100.0)
    assert Histogram().percentile(99) == 0.0


def test_histogram_small_sample_not_biased():
    # two samples: any mid percentile interpolates between them instead
    # of snapping to an endpoint
    h = Histogram()
    h.record(10.0)
    h.record(20.0)
    assert h.percentile(50) == pytest.approx(15.0)
    assert 10.0 < h.percentile(99) < 20.0
    one = Histogram()
    one.record(7.0)
    assert one.percentile(99) == 7.0


def test_histogram_reservoir_bounded():
    h = Histogram(reservoir=100)
    for v in range(10_000):
        h.record(float(v))
    assert h.count == 10_000
    assert len(h._values) == 100
    assert 0 < h.percentile(50) < 10_000


def test_histogram_merge():
    a = Histogram()
    b = Histogram()
    for v in range(100):
        a.record(float(v))
        b.record(float(v + 1000))
    a.merge_from(b)
    assert a.count == 200
    assert a.sum == pytest.approx(sum(range(100)) + sum(range(1000, 1100)))
    assert a.percentile(99) > 1000


def test_registry_snapshot():
    reg = MetricsRegistry()
    reg.counter("ops").inc(5)
    reg.histogram("lat").record(2.0)
    with reg.timer("step"):
        pass
    snap = reg.snapshot()
    assert snap["ops"] == 5
    assert snap["lat"]["count"] == 1 and snap["lat"]["p99"] == 2.0
    assert snap["step"]["count"] == 1
    assert reg.rate("ops") > 0


def test_rate_of_missing_counter_is_zero():
    reg = MetricsRegistry()
    assert reg.rate("never_incremented") == 0.0
    assert reg.rate("never", node="5001") == 0.0


def test_gauge():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13
    assert reg.snapshot()["depth"] == 13
    # same name+labels -> same gauge object
    assert reg.gauge("depth") is g


def test_labels_key_metrics_independently():
    reg = MetricsRegistry()
    reg.counter("frames", direction="in").inc(3)
    reg.counter("frames", direction="out").inc(7)
    reg.counter("frames").inc(1)
    snap = reg.snapshot()
    assert snap["frames{direction=in}"] == 3
    assert snap["frames{direction=out}"] == 7
    assert snap["frames"] == 1
    # label order does not matter
    assert reg.counter("x", a=1, b=2) is reg.counter("x", b=2, a=1)


def test_registry_merge_with_labels():
    total = MetricsRegistry()
    for port in (5001, 5002):
        node = MetricsRegistry()
        node.counter("ops").inc(10)
        node.gauge("term").set(port)
        node.histogram("lat").record(float(port))
        total.merge(node, node=str(port))
    snap = total.snapshot()
    assert snap["ops{node=5001}"] == 10
    assert snap["ops{node=5002}"] == 10
    assert snap["term{node=5002}"] == 5002
    assert snap["lat{node=5001}"]["count"] == 1
    # merging the same node again accumulates counters
    again = MetricsRegistry()
    again.counter("ops").inc(1)
    total.merge(again, node="5001")
    assert total.snapshot()["ops{node=5001}"] == 11


def test_render_prometheus():
    reg = MetricsRegistry()
    reg.counter("ops_total").inc(5)
    reg.gauge("commit_lag", node="5001").set(2)
    reg.histogram("latency_ms").record(1.5)
    text = reg.render_prometheus()
    assert "# TYPE copycat_ops_total counter" in text
    assert "copycat_ops_total 5" in text
    assert 'copycat_commit_lag{node="5001"} 2' in text
    assert "# TYPE copycat_latency_ms summary" in text
    assert 'copycat_latency_ms{quantile="0.99"} 1.5' in text
    assert "copycat_latency_ms_count 1" in text
    # namespace override (the stats listener uses per-layer namespaces)
    assert "custom_ops_total 5" in reg.render_prometheus(namespace="custom")


def test_render_json_roundtrips():
    reg = MetricsRegistry()
    reg.counter("ops").inc(2)
    parsed = json.loads(reg.render_json())
    assert parsed["ops"] == 2


def test_merge_snapshots():
    a = MetricsRegistry()
    a.counter("ops").inc(5)
    a.histogram("lat").record(1.0)
    b = MetricsRegistry()
    b.counter("ops").inc(7)
    b.histogram("lat").record(3.0)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["ops"] == 12
    assert merged["lat"]["count"] == 2
    assert merged["lat"]["mean"] == pytest.approx(2.0)
    assert merged["lat"]["p99"] == 3.0


def test_merge_snapshots_keeps_gauges_point_in_time():
    # summing per-node gauges would fabricate values (term 5+5=10); the
    # _gauge_keys hint keeps them max'd instead
    a = MetricsRegistry()
    a.gauge("raft_term").set(5)
    a.gauge("raft_is_leader").set(1)
    a.counter("ops").inc(2)
    b = MetricsRegistry()
    b.gauge("raft_term").set(5)
    b.gauge("raft_is_leader").set(0)
    b.counter("ops").inc(3)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    assert merged["raft_term"] == 5
    assert merged["raft_is_leader"] == 1
    assert merged["ops"] == 5
    assert "raft_term" in merged["_gauge_keys"]


def test_driver_records_commit_latency():
    jax = pytest.importorskip("jax")  # noqa: F841
    from copycat_tpu.ops import apply as ap

    from engines import device_plane

    rg = device_plane()
    rg.wait_for_leaders()
    tags = [rg.submit(0, ap.OP_LONG_ADD, 1) for _ in range(8)]
    rg.run_until(tags)
    snap = rg.metrics.snapshot()
    assert snap["ops_submitted"] == 8
    assert snap["ops_committed"] == 8
    lat = snap["commit_latency_rounds"]
    assert lat["count"] == 8 and lat["p50"] >= 1
    assert snap["step_wall_ms"]["count"] == rg.rounds
