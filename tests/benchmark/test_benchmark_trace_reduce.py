"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps, held to a small synthetic trace with a known answer:
overlapping device operations, an operation nested in a loop, and a gap covered
by a harness annotation.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import trace_reduce as tr  # noqa: E402

MS = 1e6  # ns

# device operations, (name, start, duration) in ns, on a window of 0..100 ms:
#   a while loop 0..40 holding fusion.1 0..10, fusion.2 10..30 (and 10 ms of
#   its own); an async copy 25..45 overlapping the loop's end; fusion.1 again
#   70..80; nothing 45..70 and 80..100
OPS = [
    ("%while.7 = (s32[]) while(...)", 0 * MS, 40 * MS),
    ("%fusion.1 = s32[8] fusion(...)", 0 * MS, 10 * MS),
    ("%fusion.2 = s32[8] fusion(...)", 10 * MS, 20 * MS),
    ("%copy-start.3 = (s32[8]) copy-start(...)", 25 * MS, 20 * MS),
    ("%fusion.1 = s32[8] fusion(...)", 70 * MS, 10 * MS),
]
MODULES = [("jit_raw_plane_scan(123)", 0 * MS, 45 * MS),
           ("jit_raw_plane_scan(123)", 70 * MS, 10 * MS)]
NOTES = [
    ("bench.window", 0 * MS, 100 * MS),
    ("bench.dispatch", 0 * MS, 44 * MS),
    ("bench.fetch", 46 * MS, 22 * MS),        # covers most of the gap 45..70
    ("bench.dispatch", 69 * MS, 12 * MS),
]
DEVICES = {"/device:TPU:0": {tr.OPS_LINE: OPS, tr.MODULES_LINE: MODULES}}


def test_union_counts_overlaps_once():
    assert tr.union_ns([(0, 40), (25, 45), (70, 80)]) == 55
    assert tr.union_ns([(5, 6), (0, 10), (10, 12)]) == 12
    assert tr.union_ns([]) == 0


def test_busy_idle_and_window_of_the_synthetic_trace():
    out = tr.reduce_trace(DEVICES, NOTES)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.055)
    assert out["idle_share_pct"] == pytest.approx(45.0)
    assert out["n_devices"] == 1
    assert [m[0] for m in out["modules"]] == ["jit_raw_plane_scan(123)"] * 2


def test_top_operations_are_charged_their_self_time():
    out = tr.reduce_trace(DEVICES, NOTES)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["fusion.2"] == pytest.approx(0.020)
    assert ops["copy-start.3"] == pytest.approx(0.020)
    # 40 ms less its children's 30 ms; the copy is not its child
    assert ops["while.7"] == pytest.approx(0.010)
    assert [n for n, _ in out["device_ops"]][-1] == "while.7"


def test_idle_gaps_are_named_by_the_annotation_that_covers_them():
    out = tr.reduce_trace(DEVICES, NOTES)
    gaps = dict(out["idle_gaps"])
    assert gaps == {"bench.fetch": pytest.approx(0.025),
                    "host: unattributed": pytest.approx(0.020)}
    assert out["idle_gaps"][0][0] == "bench.fetch"
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_the_window_is_cut_to_the_annotation_and_averaged_over_devices():
    notes = [("bench.window", 20 * MS, 60 * MS)]        # 20..80
    two = {**DEVICES, "/device:TPU:1": {tr.OPS_LINE: [
        ("%fusion.9 = s32[8] fusion(...)", 20 * MS, 60 * MS)]}}
    out = tr.reduce_trace(two, notes)
    assert out["window_s"] == pytest.approx(0.060)
    # chip 0: 20..45 and 70..80 = 35 ms; chip 1: 60 ms
    assert out["busy_s"] == pytest.approx((0.035 + 0.060) / 2)
    assert out["n_devices"] == 2


def test_a_trace_with_no_device_operation_reduces_to_nothing():
    assert tr.reduce_trace({}, NOTES) == {}
    assert tr.reduce_trace({"/device:TPU:0": {tr.OPS_LINE: []}}, NOTES) == {}
    assert tr.load_xplane(os.path.join(REPO, "tests", "benchmark", "data",
                                       "no-such-trace")) == ({}, [])


def test_short_name_keeps_what_stands_before_the_equals_sign():
    assert tr.short_name("%while.7 = (s32[]{:T(128)}) while(...)") == "while.7"
    assert tr.short_name("bench.window") == "bench.window"
