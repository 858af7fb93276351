"""Pipelined bulk driver (models/bulk.py — VERDICT r3 #4).

Correctness of the vectorized schedule + double-buffered rounds: results
must match the queue-managed path exactly (per-group FIFO order), spills
from backpressure must retry, and tags must not collide with the
queue-managed path's.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.models import BulkDriver  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402

from engines import MONOTONE, device_plane  # noqa: E402


@pytest.fixture(scope="module")
def rg():
    groups = device_plane(seed=11)
    groups.wait_for_leaders()
    return groups


def test_bulk_counter_results_match_sequential_semantics(rg):
    driver = BulkDriver(rg)
    # 5 adds per group with distinct amounts: per-group FIFO means the
    # k-th op's result is the prefix sum
    amounts = np.tile(np.arange(1, 6), 8)
    groups = np.repeat(np.arange(8), 5)
    res = driver.drive(groups, ap.OP_LONG_ADD, amounts)
    want = np.tile(np.cumsum(np.arange(1, 6)), 8)
    assert (res.results == want).all(), res.results
    assert (res.latency_rounds() >= 1).all()


def test_bulk_deep_per_group_chains_spill_and_complete(rg):
    """More ops per group than submit slots x scheduled rounds can carry
    at once — the respill path must keep FIFO and complete everything."""
    driver = BulkDriver(rg)
    per_group = 40  # 10 scheduled rounds at S=4, plus backpressure spills
    groups = np.repeat(np.arange(8), per_group)
    base = rg.value(0, peer=0)
    res = driver.drive(groups, ap.OP_LONG_ADD, 1)
    finals = res.results.reshape(8, per_group)[:, -1]
    assert (np.diff(res.results.reshape(8, per_group), axis=1) == 1).all()
    assert (finals == res.results.reshape(8, per_group)[:, 0]
            + per_group - 1).all()
    assert base >= 0  # engine still healthy


def test_bulk_and_queued_paths_interleave_without_tag_collisions(rg):
    driver = BulkDriver(rg)
    t = rg.submit(0, ap.OP_LONG_ADD, a=1000)
    res = driver.drive(np.arange(8), ap.OP_LONG_ADD, 1)
    rg.run_until([t])
    assert res.results.size == 8
    assert rg.results[t] >= 1000  # queue op resolved with its own value


def test_queue_op_applying_during_bulk_drive_still_resolves(rg):
    """A queue-managed op already IN the log when a bulk drive starts is
    reported by the device exactly once — during a bulk round. The bulk
    harvest must route it into rg.results, not drop it behind the tag
    filter."""
    driver = BulkDriver(rg)
    t = rg.submit(1, ap.OP_LONG_ADD, a=500)
    rg.step_round()           # accepted into the log, not yet resolved
    res = driver.drive(np.arange(8), ap.OP_LONG_ADD, 1)
    assert res.results.size == 8
    # resolved by the bulk rounds themselves (or the drain) — run_until
    # must find it already present without timing out
    rg.run_until([t], max_rounds=10)
    assert rg.results[t] >= 500


def test_bulk_latency_percentiles_shape(rg):
    driver = BulkDriver(rg)
    res = driver.drive(np.arange(8), ap.OP_LONG_ADD, 1)
    pct = res.latency_percentiles_ms()
    assert set(pct) == {"p50", "p99"} and pct["p99"] >= pct["p50"] > 0


def test_bulk_query_drive_on_classic_engine(rg):
    """drive_queries works on NON-monotone engines too — queries never
    append, so the tag gate is irrelevant (docstring contract)."""
    driver = BulkDriver(rg)
    driver.drive(np.arange(8), ap.OP_LONG_ADD, 5)
    got = driver.drive_queries(np.repeat(np.arange(8), 3), ap.OP_VALUE_GET,
                               consistency="sequential")
    # every group's counter is at least 5 (other tests in this module
    # share the engine); reads must be served and consistent per group
    assert (got.reshape(8, 3) == got.reshape(8, 3)[:, :1]).all()
    assert (got >= 5).all()


def test_deep_scan_mode_matches_dispatch_mode():
    """``BulkDriver(deep_scan=True)`` — the whole blind phase as ONE
    lax.scan program — produces identical results, stream cursors, and
    session events to the per-window dispatch mode (same seeds)."""
    def build():
        rg = device_plane(MONOTONE, seed=9)
        rg.wait_for_leaders()
        return rg

    rg1, rg2 = build(), build()
    d1 = BulkDriver(rg1)
    d2 = BulkDriver(rg2, deep_scan=True)
    gs = np.repeat(np.arange(8), 10)
    r1 = d1.drive(gs, ap.OP_LONG_ADD, 1)
    r2 = d2.drive(gs, ap.OP_LONG_ADD, 1)
    assert list(r1.results) == list(r2.results)
    assert (rg1._stream_count == rg2._stream_count).all()

    # second drive reuses the compiled scan (same shapes) and mixed
    # per-op payloads take the non-const scatter path
    ops = np.where(np.arange(80) % 2 == 0, ap.OP_LONG_ADD,
                   ap.OP_VALUE_GET)
    r1 = d1.drive(gs, ops, 2)
    r2 = d2.drive(gs, ops, 2)
    assert list(r1.results) == list(r2.results)

    # session events (lock grant) surface identically through the
    # stacked [W, ...] event path
    # (acquire(7) is granted, acquire(8) queues, release(7) hands over:
    # one drive, the shape of test_monotone_deep's, so one program)
    for rg, d in ((rg1, d1), (rg2, d2)):
        d.drive([0, 0, 0],
                [ap.OP_LOCK_ACQUIRE, ap.OP_LOCK_ACQUIRE, ap.OP_LOCK_RELEASE],
                [7, 8, 7], [0, -1, 0])
    assert rg1.events.get(0) == rg2.events.get(0)
    assert any(code == ap.EV_LOCK_GRANT and target == 8
               for _, code, target, _a in rg2.events.get(0, []))
