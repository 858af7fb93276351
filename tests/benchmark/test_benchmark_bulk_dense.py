"""The per-layer metric that says the bulk drive planned from its
submission's segments: ``bulk.dense_drives_share`` over the counter
``engine.bulk_dense_drives`` and the drives the root span ``bulk.drive``
counted, in ``mixed-400kx5-4chip.bulk``. Data only: one file under
``benchmarks/layer_metrics/`` on the reducer ``program_report`` and one entry
at the end of ``per_layer``. This file pins the entry by name, holds its file
to it, reads it from a recorded report, and has a traced run of the tiny bulk
cell print 1.0 (``data_bulk_dense/BENCHMARK.json``: ``data_bulk_spans/``'s
file under this entry; configuration and traffic are ``data_bulk/``'s). What
else the cell reports is other files' to hold. No number from here is a
device number.
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
DENSE = os.path.join(HERE, "data_bulk_dense", "BENCHMARK.json")
CELL = "mixed-400kx5-4chip.bulk"
ONE, FOUR = "mixed-tiny-bulk.bulk-tiny", "mixed-tiny-bulk.bulk-tiny4"
NAME = "bulk.dense_drives_share"
ENTRY = {"name": NAME, "unit": "drives/drive", "better": "higher",
         "source": "program_counter",
         "layer": "host runtime around the batch",
         "moves": "bulk_ops_per_s", "workloads": [CELL]}
#: the keys of the metric's file that the reducer reads
READS = {"reducer": "program_report", "kind": "report",
         "key": ["counters", "engine.bulk_dense_drives"],
         "over": ["spans", "bulk.drive", "n"]}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_bulk_dense")


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def metric_file(root):
    return json.load(open(os.path.join(
        root, "benchmarks", "layer_metrics", NAME + ".json")))


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_the_dense_share(bench, root):
    """The entry by its name, wherever in the list a later PR left it, and
    its file equal to it; nothing of what stands before or after."""
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    # the list as a prefix: a later cell joins by its name at its end
    assert {**m, "workloads": m["workloads"][:1]} == ENTRY
    spec = metric_file(root)
    assert all(spec[k] == m[k] for k in (
        "name", "unit", "better", "layer", "source", "moves"))
    assert all(spec[k] == v for k, v in READS.items())
    assert set(spec) == {"name", "unit", "better", "layer", "source",
                         "moves", "what", *READS}
    assert spec["what"]
    assert os.path.exists(os.path.join(
        root, "benchmarks", "reducers", spec["reducer"] + ".py"))
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    assert CELL in moved["workloads"]
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert NAME in [x["name"] for x in run_py().metrics_of(
        bench, "per_layer", CELL)]


def holds_the_twin_to_the_cell(bench, root):
    """``data_bulk_dense``'s entry is the root file's, read in both tiny
    cells; everything else there is ``data_bulk_spans``'s."""
    tiny = json.load(open(os.path.join(root, os.path.relpath(DENSE, REPO))))
    was = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_bulk_spans", "BENCHMARK.json")))
    (real,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert tiny["per_layer"][-1] == {**real, "workloads": [ONE, FOUR]}
    assert {**tiny, "per_layer": tiny["per_layer"][:-1]} == was


ROOT_FILE_RULES = [holds_the_dense_share, holds_the_twin_to_the_cell]


def test_the_dense_share_is_its_entry_and_its_file(bench):
    holds_the_dense_share(bench, REPO)


def test_the_twin_is_the_cells_metric(bench):
    holds_the_twin_to_the_cell(bench, REPO)


@pytest.mark.parametrize("counters,want", [
    ({"engine.bulk_dense_drives": 6, "engine.bulk_grouped_drives": 6}, 1.0),
    ({"engine.bulk_dense_drives": 3, "engine.bulk_grouped_drives": 5}, 0.5),
    ({"engine.bulk_dense_drives": 0, "engine.bulk_grouped_drives": 0}, 0.0),
    ({"engine.staged_bytes": 6 * 2048 * 52}, None),
], ids=["every-drive", "half", "none-of-them", "a-program-without"])
def test_it_reads_a_recorded_report(counters, want):
    """The reducer that was there: the counter's delta over the drives the
    root span counted; a zero is a reading, and ``None`` (the metric is left
    out of the line) where the program's report has no such counter, as the
    parent of the PR that added it has not, or no drive."""
    reducer = load(os.path.join(BENCH, "reducers", "program_report.py"),
                   "program_report_bulk_dense")
    sources = {"clock": {"acked_ops": 6 * 2048}}
    report = {"spans": {"bulk.drive": {"n": 6, "total_ms": 606.0,
                                       "mean_ms": 101.0, "self_ms": 0.75}},
              "counters": counters}
    assert reducer.reduce_report(report, sources, metric_file(REPO)) == want
    assert reducer.reduce_report({"spans": {}, "counters": counters},
                                 sources, metric_file(REPO)) is None


def test_a_traced_run_of_the_tiny_cell_prints_one():
    """The plane's submission is ``np.repeat(np.arange(G), B)``: every drive
    of the window, and every warm-up drive before the tracer was on, took
    the dense plan, and the metrics beside it read what they read before."""
    from copycat_tpu.utils.tracing import TRACER

    rc, line = run_py().run_cell(
        ONE, 2**31 + 39, 0.3, True, None, bench_file=DENSE,
        data_root=DATA, require_tpu=False)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    json.dumps(line)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics[NAME] == 1.0
    assert line["metrics"][NAME]["unit"] == ENTRY["unit"]
    counters, spans = (TRACER.report()[k] for k in ("counters", "spans"))
    assert counters["engine.bulk_dense_drives"] \
        == counters["engine.bulk_grouped_drives"] \
        == spans["bulk.drive"]["n"] >= 3
    assert metrics["bulk.h2d_bytes_per_op"] == (
        64 * (32 * (4 + 1 + 4) + 1 + 4)
        + 5 * 64 * (16 * (4 * 4 + 1) + 4)) / (64 * 32)
    assert metrics["bulk.fetches_per_drive"] == 1.0
    assert metrics["bulk.rounds_per_drive"] == 5.0
    assert {s.meta["plan"] for trace in TRACER.traces().values()
            for s in trace if s.name == "bulk.plan"} == {"dense"}
