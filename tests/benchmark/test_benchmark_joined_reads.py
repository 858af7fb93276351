"""The two per-layer metrics that say how often a read window's reads rode
the round of the vector run the window found parked, and what that leaves of
the map cell's fetches: ``engine.joined_drives_per_kop`` over the counter
``engine.query_joined_drives`` in ``served-1k.read90`` and
``map-1kx10k.putget50``, and ``runtime.fetches_per_kop`` over
``engine.fetches`` in the map cell, which joined that list by its name (PR 53
folded its copy, ``map.fetches_per_kop``, into the layer's reading). Data
only: a file under ``benchmarks/layer_metrics/`` and an entry of
``per_layer`` each, on the reducer ``program_report``. This file pins the two by name, holds
their files to their entries, and has a traced run of each tiny cell print
them (``data_joined/BENCHMARK.json``: the tiny served read cell and the tiny
map cell under this file's entries and the counts they are read beside;
configurations and traffic are ``data/``'s and ``data_map/``'s). What else
the cells report is other files' to hold. No number from here is a device
number.
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
JOINED = os.path.join(HERE, "data_joined", "BENCHMARK.json")
W, R, M = "served-1k.write", "served-1k.read90", "map-1kx10k.putget50"
#: this file's metrics: entry (its list as far as this file holds it: a
#: later cell joins at its end), and the keys of the file that a reducer reads
HELD = {
    "engine.joined_drives_per_kop": (
        {"unit": "drives/kop", "better": "higher",
         "source": "program_counter",
         "layer": "resource manager + device executor",
         "moves": "served_ops_per_s", "workloads": [R, M]},
        {"reducer": "program_report", "kind": "report", "per": "kop",
         "key": ["counters", "engine.query_joined_drives"]}),
    "runtime.fetches_per_kop": (
        {"unit": "fetches/kop", "better": "lower",
         "source": "program_counter",
         "layer": "host runtime around the batch",
         "moves": "served_ops_per_s", "workloads": [W, R, M]},
        {"reducer": "program_report", "kind": "report", "per": "kop",
         "key": ["counters", "engine.fetches"]}),
}
#: tiny cell -> (the cell it stands for, where its traffic and
#: configuration live, the count of all its windows' evaluations)
CELLS = {
    "served-tiny.read90-tiny": (R, "data", "engine.query_drives_per_kop"),
    "map-tiny.putget50-tiny": (M, "data_map", "engine.query_drives_per_kop"),
}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_joined")


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def metric_file(name, root):
    return json.load(open(os.path.join(
        root, "benchmarks", "layer_metrics", name + ".json")))


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_a_joined_reads_metric(bench, root, name):
    """The entry by its name, wherever in the list a later PR left it, and
    its file equal to it; nothing of what stands before or after."""
    entry, reads = HELD[name]
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    held = len(entry["workloads"])
    assert {**m, "workloads": m["workloads"][:held]} == {"name": name,
                                                         **entry}
    spec = metric_file(name, root)
    assert all(spec[k] == m[k] for k in (
        "name", "unit", "better", "layer", "source", "moves"))
    assert all(spec[k] == v for k, v in reads.items())
    assert spec["what"] and "workloads" not in spec
    assert os.path.exists(os.path.join(
        root, "benchmarks", "reducers", spec["reducer"] + ".py"))
    cells = {w["name"]: w for w in bench["workloads"]}
    moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in cells and cell in moved.get("workloads", cells)


def holds_both_joined_reads_metrics(bench, root):
    for name in HELD:
        holds_a_joined_reads_metric(bench, root, name)


def holds_the_twins_to_the_cells(bench, root):
    """``data_joined``'s entries for this file's names are the root file's,
    cell for tiny cell."""
    tiny = json.load(open(os.path.join(root, os.path.relpath(JOINED, REPO))))
    keys = ("name", "unit", "better", "source", "layer", "moves")
    for name in HELD:
        (real,) = [m for m in bench["per_layer"] if m["name"] == name]
        (twin,) = [m for m in tiny["per_layer"] if m["name"] == name]
        assert all(twin[k] == real[k] for k in keys)
        # the tiny cells stand for cells of the root list, in its order
        stood_for = [CELLS[c][0] for c in twin["workloads"]]
        assert stood_for == [c for c in real["workloads"] if c in stood_for]


ROOT_FILE_RULES = [holds_both_joined_reads_metrics,
                   holds_the_twins_to_the_cells]


@pytest.mark.parametrize("name", sorted(HELD))
def test_a_joined_reads_metric_is_its_entry_and_its_file(bench, name):
    holds_a_joined_reads_metric(bench, REPO, name)


def test_the_twins_are_the_cells_metrics(bench):
    holds_the_twins_to_the_cells(bench, REPO)


def test_both_read_the_programs_report_and_nothing_on_a_program_without():
    """The reducer that was there: the counter's delta over the window per
    1,000 acknowledged operations, a zero a reading, and ``None`` (the
    metric is left out of the line) where the program's report has no such
    counter, as the parent of the PR that added it has not."""
    reducer = load(os.path.join(BENCH, "reducers", "program_report.py"),
                   "program_report_joined")
    sources = {"clock": {"acked_ops": 400_000}}
    report = {"counters": {"engine.query_joined_drives": 2_200,
                           "engine.query_vector_drives": 2_360,
                           "engine.fetches": 2_520}}
    joined, fetches = (metric_file(n, REPO) for n in sorted(HELD))
    assert reducer.reduce_report(report, sources, joined) == 5.5
    assert reducer.reduce_report(report, sources, fetches) == 6.3
    report["counters"]["engine.query_joined_drives"] = 0
    assert reducer.reduce_report(report, sources, joined) == 0.0
    del report["counters"]["engine.query_joined_drives"]
    assert reducer.reduce_report(report, sources, joined) is None
    assert reducer.reduce_report({}, sources, fetches) is None


@pytest.fixture(scope="module", params=sorted(CELLS), ids=["map", "read90"])
def traced(request):
    """One traced run of a tiny cell and what it left in the tracer."""
    from copycat_tpu.utils.tracing import TRACER

    tiny = request.param
    rc, line = run_py().run_cell(
        tiny, 2**31 + 36, 0.6, True, None, bench_file=JOINED,
        data_root=os.path.join(HERE, CELLS[tiny][1]), require_tpu=False)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    json.dumps(line)
    return tiny, line, TRACER.report()


def test_a_traced_run_prints_this_files_metrics(traced):
    """Every window's evaluation is counted once, joined or alone, and a
    joined one is one of them; a window that joined fetched once for its
    round and its reads, so the fetches are the rounds and the evaluations
    that ran alone."""
    tiny, line, report = traced
    _real, _data, drives = CELLS[tiny]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    mine = [n for n, (entry, _) in HELD.items()
            if CELLS[tiny][0] in entry["workloads"]]
    assert set(mine) <= set(metrics)
    for name in mine:
        assert isinstance(metrics[name], float)
        assert line["metrics"][name]["unit"] == HELD[name][0]["unit"]
    joined = metrics["engine.joined_drives_per_kop"]
    assert 0 <= joined <= metrics[drives] and metrics[drives] > 0
    counters = report["counters"]
    assert counters["engine.fetches"] == (
        counters["engine.rounds"] + counters["engine.query_vector_drives"]
        - counters["engine.query_joined_drives"]
        + counters["engine.query_settle_rounds"])
    if tiny.startswith("map-tiny"):
        assert metrics["runtime.fetches_per_kop"] == pytest.approx(
            metrics["engine.rounds_per_kop"] + metrics[drives] - joined)
