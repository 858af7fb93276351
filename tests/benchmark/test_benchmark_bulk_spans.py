"""The ten per-layer metrics that read the bulk drive's own record in
``mixed-400kx5-4chip.bulk``: eight over the stages' spans (``bulk.admit`` to
``bulk.return``, reducer ``span_mean_ms``), ``bulk.unspanned_ms`` over the
root span's self time and ``bulk.h2d_bytes_per_op`` over the counter
``engine.staged_bytes`` (both ``program_report``). Data only: a file each
under ``benchmarks/layer_metrics/`` and an entry each at the end of
``per_layer``. This file pins the ten by name, holds their files to their
entries, and has a traced run of each tiny bulk cell print them beside the
nine the cell had (``data_bulk_spans/BENCHMARK.json``: ``data_bulk/``'s two
tiny cells under the nine and this file's entries; configuration and traffic
are ``data_bulk/``'s). What else the cell reports is other files' to hold. No
number from here is a device number.
"""

import functools
import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
HERE = os.path.join(REPO, "tests", "benchmark")
DATA = os.path.join(HERE, "data_bulk")
SPANS = os.path.join(HERE, "data_bulk_spans", "BENCHMARK.json")
CELL = "mixed-400kx5-4chip.bulk"
ONE, FOUR = "mixed-tiny-bulk.bulk-tiny", "mixed-tiny-bulk.bulk-tiny4"
STAGES = ("admit", "plan", "stage", "dispatch", "wait", "fetch", "harvest",
          "return")
ENTRY = {"unit": "ms", "better": "lower", "source": "program_span",
         "layer": "host runtime around the batch",
         "moves": "bulk_ops_per_s", "workloads": [CELL]}
#: this file's metrics: entry, and the keys of the file that a reducer reads
HELD = {
    **{f"bulk.{stage}_ms": (ENTRY, {
        "reducer": "span_mean_ms", "kind": "span", "key": f"bulk.{stage}"})
       for stage in STAGES},
    "bulk.unspanned_ms": (ENTRY, {
        "reducer": "program_report", "kind": "report",
        "key": ["spans", "bulk.drive", "self_ms"],
        "over": ["spans", "bulk.drive", "n"]}),
    "bulk.h2d_bytes_per_op": (
        {**ENTRY, "unit": "bytes/op", "source": "program_counter"},
        {"reducer": "program_report", "kind": "report", "per": "op",
         "key": ["counters", "engine.staged_bytes"]}),
}
#: what the cell had (PR 26), which a traced run prints these beside;
#: ``test_benchmark_bulk_plane.py`` holds them
NINE = ["bulk.drive_ms", "bulk.drive_max_ms", "bulk.fetches_per_drive",
        "bulk.d2h_bytes_per_op", "bulk.rounds_per_drive",
        "step.deep_scan_roofline", "device.idle_share.bulk",
        "placement.collectives", "placement.peak_skew"]
#: what a CPU run cannot read, and what one device has none of
CHIP_ONLY = {"placement.peak_skew", "step.deep_scan_roofline"}
MESH_ONLY = {"placement.collectives"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_bulk_spans")


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def metric_file(name, root):
    return json.load(open(os.path.join(
        root, "benchmarks", "layer_metrics", name + ".json")))


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_a_bulk_span_metric(bench, root, name):
    """The entry by its name, wherever in the list a later PR left it, and
    its file equal to it; nothing of what stands before or after."""
    entry, reads = HELD[name]
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    # the list as a prefix: a later cell joins by its name at its end
    assert {**m, "workloads": m["workloads"][:1]} == {"name": name, **entry}
    spec = metric_file(name, root)
    assert all(spec[k] == m[k] for k in (
        "name", "unit", "better", "layer", "source", "moves"))
    assert all(spec[k] == v for k, v in reads.items())
    assert set(spec) == {"name", "unit", "better", "layer", "source",
                         "moves", "what", *reads}
    assert spec["what"]
    assert os.path.exists(os.path.join(
        root, "benchmarks", "reducers", spec["reducer"] + ".py"))
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert CELL in moved["workloads"]


def holds_all_ten_bulk_span_metrics(bench, root):
    assert len(HELD) == 10
    for name in HELD:
        holds_a_bulk_span_metric(bench, root, name)


def holds_the_twins_to_the_cell(bench, root):
    """``data_bulk_spans``'s entries for this file's names are the root
    file's, read in both tiny cells; the nine beside them are
    ``data_bulk``'s own."""
    tiny = json.load(open(os.path.join(root, os.path.relpath(SPANS, REPO))))
    was = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_bulk", "BENCHMARK.json")))
    keys = ("name", "unit", "better", "source", "layer", "moves")
    for name in HELD:
        (real,) = [m for m in bench["per_layer"] if m["name"] == name]
        (twin,) = [m for m in tiny["per_layer"] if m["name"] == name]
        assert all(twin[k] == real[k] for k in keys)
        assert twin["workloads"] == [ONE, FOUR]
    assert [m for m in tiny["per_layer"] if m["name"] not in HELD] \
        == was["per_layer"]
    assert {k: v for k, v in tiny.items() if k != "per_layer"} \
        == {k: v for k, v in was.items() if k != "per_layer"}


def holds_the_ten_after_the_nine(bench, root):
    """What a traced run of the cell is asked for, in the root file's
    order; a later PR's metric on the cell follows and is not this file's."""
    names = [m["name"] for m in run_py().metrics_of(bench, "per_layer", CELL)]
    assert names[:len(NINE)] == NINE
    assert names[len(NINE):len(NINE) + len(HELD)] == list(HELD)


ROOT_FILE_RULES = [holds_all_ten_bulk_span_metrics,
                   holds_the_twins_to_the_cell, holds_the_ten_after_the_nine]


@pytest.mark.parametrize("name", sorted(HELD))
def test_a_bulk_span_metric_is_its_entry_and_its_file(bench, name):
    holds_a_bulk_span_metric(bench, REPO, name)


def test_the_twins_are_the_cells_metrics(bench):
    holds_the_twins_to_the_cell(bench, REPO)


def test_the_cell_reads_nineteen_metrics_the_ten_after_the_nine(bench):
    holds_the_ten_after_the_nine(bench, REPO)


def test_the_reducers_read_a_recorded_drive_and_nothing_on_a_program_without():
    """The reducers that were there, on a recorded report and ring: the
    stages' means, the root's self time a drive, the staged bytes an
    operation; ``None`` (the metric is left out of the line) where the
    program records no such span or counter, as the parent of the PR that
    added them does not."""
    report_of = load(os.path.join(BENCH, "reducers", "program_report.py"),
                     "program_report_bulk_spans")
    mean_of = load(os.path.join(BENCH, "reducers", "span_mean_ms.py"),
                   "span_mean_ms_bulk_spans")
    sources = {"clock": {"acked_ops": 6 * 2048},
               "spans": {"bulk.stage": [30.0, 34.0, 32.0],
                         "bulk.drive": [100.0, 104.0, 99.0]}}
    report = {"spans": {"bulk.drive": {"n": 6, "total_ms": 606.0,
                                       "mean_ms": 101.0, "max_ms": 104.0,
                                       "self_ms": 0.75}},
              "counters": {"engine.staged_bytes": 6 * 2048 * 52}}
    assert mean_of.reduce(sources, metric_file("bulk.stage_ms", REPO)) == 32.0
    assert mean_of.reduce(sources, metric_file("bulk.wait_ms", REPO)) is None
    unspanned, h2d = (metric_file(n, REPO) for n in (
        "bulk.unspanned_ms", "bulk.h2d_bytes_per_op"))
    assert report_of.reduce_report(report, sources, unspanned) == 0.125
    assert report_of.reduce_report(report, sources, h2d) == 52.0
    assert report_of.reduce_report({"spans": {}, "counters": {}}, sources,
                                   unspanned) is None
    assert report_of.reduce_report({"spans": {}, "counters": {}}, sources,
                                   h2d) is None


@pytest.fixture(scope="module", params=[ONE, FOUR],
                ids=["one-device", "mesh-of-4"])
def traced(request):
    """One traced run of a tiny bulk cell and what it left in the tracer."""
    from copycat_tpu.utils.tracing import TRACER

    rc, line = run_py().run_cell(
        request.param, 2**31 + 38, 0.3, True, None, bench_file=SPANS,
        data_root=DATA, require_tpu=False)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    json.dumps(line)
    return request.param, line, TRACER.report()


def test_a_traced_run_prints_all_ten_beside_the_nine(traced):
    cell, line, report = traced
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    beside = set(NINE) - CHIP_ONLY - (MESH_ONLY if cell == ONE else set())
    assert set(metrics) == set(HELD) | beside
    for name, (entry, _) in HELD.items():
        assert isinstance(metrics[name], float) and metrics[name] >= 0
        assert line["metrics"][name]["unit"] == entry["unit"]
    # 64 groups of 32 operations in two windows of 16 and three settle
    # rounds: the accumulators (int32, bool, int32 an operation; a flag and
    # a base a group) and the stacked payload (four int32 planes and a
    # valid plane a slot, a tag a group, five rounds)
    accumulators = 64 * (32 * (4 + 1 + 4) + 1 + 4)
    payload = 5 * 64 * (16 * (4 * 4 + 1) + 4)
    assert metrics["bulk.h2d_bytes_per_op"] == \
        (accumulators + payload) / (64 * 32)
    assert metrics["bulk.fetches_per_drive"] == 1.0
    assert metrics["bulk.rounds_per_drive"] == 5.0
    # the stages and what none of them covers make up the root, drive for
    # drive, and the root is the wall the harness clocks around drive()
    spans = report["spans"]
    drives = spans["bulk.drive"]["n"]
    assert drives >= 3 and all(
        spans[f"bulk.{stage}"]["n"] == drives for stage in STAGES)
    assert sum(spans[f"bulk.{stage}"]["total_ms"] for stage in STAGES) \
        + spans["bulk.drive"]["self_ms"] == pytest.approx(
            spans["bulk.drive"]["total_ms"])
    assert metrics["bulk.unspanned_ms"] == pytest.approx(
        spans["bulk.drive"]["self_ms"] / drives)
    assert metrics["bulk.unspanned_ms"] <= 0.03 * spans["bulk.drive"]["mean_ms"]
    assert sum(metrics[f"bulk.{stage}_ms"] for stage in STAGES) \
        + metrics["bulk.unspanned_ms"] == pytest.approx(
            spans["bulk.drive"]["mean_ms"])
    assert spans["bulk.drive"]["mean_ms"] <= metrics["bulk.drive_max_ms"]
    assert report["counters"]["engine.staged_bytes"] \
        == drives * (accumulators + payload)


def test_an_untraced_run_prints_none_of_them_and_records_nothing():
    from copycat_tpu.utils.tracing import TRACER

    TRACER.clear()
    rc, line = run_py().run_cell(
        ONE, 2**31 + 39, 0.3, False, None, bench_file=SPANS,
        data_root=DATA, require_tpu=False)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"bulk_ops_per_s", "setup_s"}
    assert TRACER.traces() == {} and TRACER.report()["spans"] == {}
