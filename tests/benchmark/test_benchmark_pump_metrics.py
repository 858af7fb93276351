"""The per-layer metrics that read the pumps' batch-scope spans and the
tracer's whole-window report: their files, their reducer, and a traced run of
both tiny served cells that has to print every one of them. The bench file is
this test's own (``data_pump/BENCHMARK.json``: the two tiny served cells with
the real file's metric entries); traffic and configurations are
``tests/benchmark/data``'s, metric files and reducers ``benchmarks/``'s. No
number from here is a device number.
"""

import functools
import importlib.util
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(REPO, "tests", "benchmark", "data")
PUMP = os.path.join(REPO, "tests", "benchmark", "data_pump", "BENCHMARK.json")
W, R, RAW = "served-1k.write", "served-1k.read90", "mixed-100kx5.raw-nemesis"
#: the metrics the benchmark had before the pumps were put on the record
#: (PR 23), in the order they have in ``BENCHMARK.json`` and with the cells
#: each list begins with there: a list is held as a prefix, since a later
#: cell joins a metric by its name at the end of the list (PR 53)
FIRST = [
    ("client.ack_p50_ms", [W, R]), ("client.ack_p99_ms", [R]),
    ("server.append_ms", [W]), ("server.append_ms.read", [R]),
    ("engine.apply_ms", [W]), ("engine.rounds_per_kop", [W, R]),
    ("step.ms_per_round", [RAW]), ("step.commit_p99_rounds", [RAW]),
    ("step.scan_roofline", [RAW]), ("device.idle_share.served", [W, R]),
    ("device.idle_share.raw", [RAW]),
]
#: the pumps' own metrics (PR 24), which follow them; what follows these is
#: a later PR's, and this file says nothing of it
PUMPS = [
    ("client.stage_ms", [W, R]), ("client.resolve_ms", [W, R]),
    ("engine.classify_ms", [W]), ("engine.park_ms", [W]),
    ("engine.marshal_ms", [W]), ("engine.finalize_ms", [W]),
    ("runtime.stage_ms", [W, R]), ("runtime.wait_ms", [W, R]),
    ("runtime.fetch_ms", [W, R]), ("runtime.harvest_ms", [W, R]),
    ("runtime.fetches_per_kop", [W, R]), ("runtime.d2h_bytes_per_op", [W, R]),
    ("server.read_queue_ms", [R]), ("server.read_gate_ms", [R]),
    ("server.reads_per_window", [R]), ("engine.read_drain_ms", [R]),
    ("engine.read_eval_ms", [R]), ("engine.query_drives_per_kop", [R]),
    ("engine.settle_rounds_per_kop", [R]), ("host.unspanned_share", [W, R]),
]
PINNED = {name for name, _ in FIRST + PUMPS}
#: the round's call signature on the record (PR 25's counter, PR 34's
#: metric): the one served metric after the pinned ones that this file holds
LEAVES = "runtime.leaves_per_kop"

CELLS = {W: "served-tiny.write-tiny", R: "served-tiny.read90-tiny"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_pump")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def program_report():
    return load(os.path.join(BENCH, "reducers", "program_report.py"),
                "program_report")


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


ROOT_METRICS = [m["name"] for m in json.load(open(os.path.join(
    REPO, "BENCHMARK.json")))["per_layer"]]


def metric_file(name, root):
    return json.load(open(os.path.join(
        root, "benchmarks", "layer_metrics", name + ".json")))


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_the_accepted_metrics_first_and_unchanged(bench, root):
    pinned = bench["per_layer"][:len(FIRST) + len(PUMPS)]
    assert [(m["name"], m["workloads"][:len(cells)])
            for m, (_, cells) in zip(pinned, FIRST + PUMPS)] == FIRST + PUMPS
    assert (metric_file("engine.apply_ms", root)["key"],
            metric_file("server.append_ms", root)["key"],
            metric_file("server.append_ms.read", root)["key"],
            metric_file("engine.rounds_per_kop", root)["key"]) == (
        "apply", "group.append", "group.append", "rounds")


def layers_of(root):
    """The first column of PERF.md section 3's table."""
    perf = open(os.path.join(root, "PERF.md")).read()
    section = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    return set(re.findall(r"^\| ([^|`]+?) \| ", section, re.M))


def vocabulary_of(root):
    return open(os.path.join(root, "docs", "OBSERVABILITY.md")).read()


def holds_a_metric_file(bench, root, name, layers, vocabulary):
    """What holds for every per-layer metric, whoever added it."""
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = metric_file(name, root)
    assert set(spec) >= {"name", "unit", "better", "layer", "source",
                         "moves", "kind", "key", "reducer", "what"}
    assert all(spec[k] == m[k] for k in (
        "name", "unit", "better", "layer", "source", "moves"))
    assert os.path.exists(os.path.join(
        root, "benchmarks", "reducers", spec["reducer"] + ".py"))
    assert spec["layer"] in layers, (spec["layer"], layers)
    assert len(spec["unit"]) <= 16
    moved = next(e for e in bench["end_to_end"] if e["name"] == spec["moves"])
    cells = {w["name"] for w in bench["workloads"]}
    assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    if spec["reducer"] == "span_mean_ms" and spec["source"] == "program_span":
        assert f"| `{spec['key']}` |" in vocabulary, spec["key"]


def holds_every_metric_file(bench, root):
    layers, vocabulary = layers_of(root), vocabulary_of(root)
    for m in bench["per_layer"]:
        holds_a_metric_file(bench, root, m["name"], layers, vocabulary)


def holds_a_pump_metric(bench, root, name):
    """What is PR 24's own, held over its twenty names only."""
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = metric_file(name, root)
    # never the raw cell, whose end-to-end metrics it does not move
    assert RAW not in m["workloads"]
    cells = dict(PUMPS)[name]
    assert m["workloads"][:len(cells)] == cells and set(cells) <= set(CELLS)
    if spec["reducer"] == "span_mean_ms":
        assert spec["source"] == "program_span"
    else:
        assert spec["reducer"] == "program_report"
        assert isinstance(spec["key"], list)
        assert spec["source"] == ("program_span" if spec["key"][0]
                                  == "timeline" else "program_counter")


def holds_every_pump_metric(bench, root):
    for name, _ in PUMPS:
        holds_a_pump_metric(bench, root, name)


def held_in(bench, real, names):
    """The entries of ``names`` that ``real`` reads, by name; nothing of a
    later metric on a served cell, which brings a test of its own."""
    return {m["name"]: m for m in bench["per_layer"]
            if real in m["workloads"] and m["name"] in names}


def holds_the_twins_metrics_to_the_served_cells(bench, root):
    """``data_pump``'s entries for the names this file holds are the root
    file's, cell for tiny cell."""
    keys = ("name", "unit", "better", "source", "layer", "moves")
    pump = json.load(open(os.path.join(root, os.path.relpath(PUMP, REPO))))
    for real, tiny in CELLS.items():
        wanted = held_in(bench, real, PINNED | {LEAVES})
        twins = held_in(pump, tiny, PINNED | {LEAVES})
        assert set(twins) == set(wanted)
        assert all(twins[n][k] == wanted[n][k] for n in wanted for k in keys)
    leaves = next(m for m in bench["per_layer"] if m["name"] == LEAVES)
    assert leaves["workloads"][:2] == [W, R]
    assert metric_file(LEAVES, root)["key"] == ["counters",
                                                "engine.dispatch_leaves"]


ROOT_FILE_RULES = [holds_the_accepted_metrics_first_and_unchanged,
                   holds_every_metric_file, holds_every_pump_metric,
                   holds_the_twins_metrics_to_the_served_cells]


def test_the_accepted_metrics_stand_first_and_unchanged(bench):
    holds_the_accepted_metrics_first_and_unchanged(bench, REPO)


@pytest.fixture(scope="module")
def layers():
    return layers_of(REPO)


@pytest.fixture(scope="module")
def vocabulary():
    return vocabulary_of(REPO)


@pytest.mark.parametrize("name", ROOT_METRICS)
def test_a_metric_file_loads_and_resolves(bench, layers, vocabulary, name):
    holds_a_metric_file(bench, REPO, name, layers, vocabulary)


@pytest.mark.parametrize("name", [name for name, _ in PUMPS])
def test_a_pump_metric_reads_the_served_cells_through_its_reducers(
        bench, name):
    holds_a_pump_metric(bench, REPO, name)


def test_the_twins_metrics_are_the_served_cells_metrics(bench):
    holds_the_twins_metrics_to_the_served_cells(bench, REPO)


RECORDED = {
    "window_s": 20.0,
    "spans": {"engine.wait": {"n": 4, "total_ms": 2.0, "mean_ms": 0.5,
                              "max_ms": 0.75, "self_ms": 2.0}},
    "timeline": {"engine.wait": 0.5, "client.submit": 87.0,
                 "unspanned": 12.5},
    "counters": {"engine.fetches": 1500, "engine.fetch_bytes": 3_000_000,
                 "group.query_ops": 180_000, "group.query_windows": 1200,
                 "engine.query_settle_rounds": 0, "group.idle": 0},
    "cut": False,
}


@pytest.mark.parametrize("spec,expected", [
    ({"key": ["counters", "engine.fetches"], "per": "kop"}, 1500 / 750.0),
    ({"key": ["counters", "engine.fetch_bytes"], "per": "op"}, 4.0),
    ({"key": ["counters", "group.query_ops"],
      "over": ["counters", "group.query_windows"]}, 150.0),
    ({"key": ["counters", "engine.query_settle_rounds"], "per": "kop"}, 0.0),
    ({"key": ["timeline", "unspanned"]}, 12.5),
    ({"key": ["spans", "engine.wait", "mean_ms"]}, 0.5),
    ({"key": ["counters", "engine.absent"], "per": "kop"}, None),
    ({"key": ["nothing", "here"]}, None),
    ({"key": ["counters", "engine.fetches", "deeper"]}, None),
    ({"key": ["counters", "group.query_ops"],
      "over": ["counters", "group.idle"]}, None),
    ({"key": ["counters", "group.query_ops"],
      "over": ["counters", "group.absent"]}, None),
], ids=["per-kop", "per-op", "over", "a-zero-is-a-reading", "plain",
        "nested", "absent-key", "absent-block", "past-a-leaf",
        "zero-divisor", "absent-divisor"])
def test_program_report_reduces_a_recorded_report(program_report, spec,
                                                  expected):
    sources = {"clock": {"acked_ops": 750_000}}
    got = program_report.reduce_report(RECORDED, sources, spec)
    assert got == expected and (got is None or isinstance(got, float))


def test_program_report_without_acknowledged_operations_or_a_report(
        program_report, monkeypatch):
    spec = {"key": ["counters", "engine.fetches"], "per": "kop"}
    assert program_report.reduce_report(
        RECORDED, {"clock": {"acked_ops": 0}}, spec) is None
    assert program_report.reduce_report(RECORDED, {"clock": {}}, spec) is None
    # a program that has no report (the parent of the PR that added it)
    from copycat_tpu.utils import tracing

    monkeypatch.delattr(tracing.Tracer, "report")
    assert program_report.reduce({"clock": {"acked_ops": 5}}, spec) is None


@pytest.fixture(scope="module", params=sorted(CELLS), ids=["read90", "write"])
def traced(request, harness):
    """One traced run of a tiny served cell, and what it left in the tracer:
    every test of the printed line reads this one run."""
    from copycat_tpu.utils.tracing import TRACER

    real = request.param
    rc, line = harness.run_cell(CELLS[real], 2**31 + 99, 0.6, True, None,
                                bench_file=PUMP, data_root=DATA,
                                require_tpu=False)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    json.dumps(line)
    # what a run leaves in the tracer is the window's, frozen
    report = TRACER.report()
    assert not TRACER.enabled and report is TRACER.report()
    return real, line, report


def test_a_traced_run_prints_every_metric_of_its_cell(bench, traced):
    real, line, report = traced
    # the pinned metrics only: a later metric on a served cell brings a
    # test of its own
    wanted = held_in(bench, real, PINNED)
    missing = set(wanted) - set(line["metrics"])
    assert not missing, missing
    for name, got in line["metrics"].items():
        assert isinstance(got["value"], float) and got["value"] >= 0, name
        if name in wanted:
            assert got["unit"] == wanted[name]["unit"]
    assert 0 <= line["metrics"]["host.unspanned_share"]["value"] < 100
    assert line["metrics"]["runtime.fetches_per_kop"]["value"] > 0
    assert line["metrics"]["runtime.wait_ms"]["value"] > 0
    if real.endswith("read90"):
        assert line["metrics"]["server.reads_per_window"]["value"] >= 1
        assert line["metrics"]["engine.query_drives_per_kop"]["value"] > 0
    assert sum(report["timeline"].values()) == pytest.approx(100, abs=0.01)
    assert report["window_s"] >= 0.6     # the profiler's holds lengthen it


def test_a_traced_run_prints_the_dispatch_leaves(bench, traced):
    """The served metric after the pinned ones, on the line the same run
    printed: the counter's delta over the operations the window
    acknowledged, a few leaves a round and never none."""
    real, line, report = traced
    wanted = held_in(bench, real, {LEAVES})[LEAVES]
    got = line["metrics"][LEAVES]
    assert got["unit"] == wanted["unit"] == "leaves/kop"
    leaves = report["counters"]["engine.dispatch_leaves"]
    rounds = line["metrics"]["engine.rounds_per_kop"]["value"]
    assert leaves > 0 and got["value"] > 0
    # both are per 1,000 acknowledged operations of the same window
    assert got["value"] / rounds == pytest.approx(
        leaves / report["counters"]["engine.rounds"])
