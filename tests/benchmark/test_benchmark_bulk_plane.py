"""The bulk client plane rehearsed on the CPU at a tiny size, on one device and
over a mesh of four virtual ones: the contract's line with ``correct: true``,
every per-layer metric a CPU run can read, no collective in the sharded scan; a
fault in the harness's inputs, or the drive broken underneath it, gives
``correct: false``; what the root ``BENCHMARK.json`` names for the plane
resolves. Sizes come from ``tests/benchmark/data_bulk``, never from the cell's
own files. No number from here is a device number.
"""

import copy
import functools
import importlib.util
import json
import os
import shutil
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(REPO, "tests", "benchmark", "data_bulk")
ONE, FOUR = "mixed-tiny-bulk.bulk-tiny", "mixed-tiny-bulk.bulk-tiny4"
CELL = "mixed-400kx5-4chip.bulk"
#: the same traffic on one chip (PR 53): it joined the four-chip cell's lists
ONE_CHIP = "mixed-100kx5.bulk"
#: what the plane checks and reads over a mesh only
MESH_ONLY = {"placement.collectives", "placement.peak_skew"}
#: the cell's metrics that this file holds (PR 26), in the root file's order;
#: a metric on the cell that is not named here is a later PR's and brings a
#: test and a tiny data directory of its own
NINE = ["bulk.drive_ms", "bulk.drive_max_ms", "bulk.fetches_per_drive",
        "bulk.d2h_bytes_per_op", "bulk.rounds_per_drive",
        "step.deep_scan_roofline", "device.idle_share.bulk",
        "placement.collectives", "placement.peak_skew"]
#: the lists the one-chip cell joined, by name: the four-chip cell's as they
#: stood at PR 53 but the placement's two and the two shares that
#: ``tests/test_bulk_kept_buffers.py``, which is not the benchmark's to edit,
#: holds to the four-chip cell alone (``OWED``: a later PR that may edit that
#: file appends the cell). This file holds these eighteen and nothing of what
#: the cell joins or is given later: that is the later PR's to hold
JOINED = [name for name in NINE if name not in MESH_ONLY] + [
    "bulk.admit_ms", "bulk.plan_ms", "bulk.stage_ms", "bulk.dispatch_ms",
    "bulk.wait_ms", "bulk.fetch_ms", "bulk.harvest_ms", "bulk.return_ms",
    "bulk.unspanned_ms", "bulk.h2d_bytes_per_op", "bulk.dense_drives_share"]
#: the two lists the one-chip cell is owed (PERF.md section 7), and a later
#: entry that names it: what ``after_a_later_prs_joins`` rehearses
OWED = ("bulk.kept_bytes_share", "bulk.early_bytes_share")
LATER, LIKE = "rehearsed.bulk_drives", "bulk.rounds_per_drive"
#: what a CPU run cannot read: its devices report no memory, and
#: ``peaks.json`` holds no peak for them
CHIP_ONLY = {"placement.peak_skew", "step.deep_scan_roofline"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_bulk")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def tiny():
    return json.load(open(os.path.join(DATA, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def drive(harness, cell, trace=False, fault=None, seed=2**31 + 26):
    rc, line = harness.run_cell(
        cell, seed, 0.3, trace, fault,
        bench_file=os.path.join(DATA, "BENCHMARK.json"), data_root=DATA,
        require_tpu=False)
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    return line


@pytest.mark.parametrize("cell,trace", [
    (ONE, False), (ONE, True), (FOUR, False), (FOUR, True)],
    ids=["one-device", "one-device-traced", "mesh-of-4", "mesh-of-4-traced"])
def test_cell_prints_the_contracts_line_and_is_correct(harness, tiny, cell,
                                                       trace, capsys):
    line = drive(harness, cell, trace)
    # the plane states no form of the scan's accumulators: it asks the
    # program's own builder for each and reads the text of the one the
    # drives called. Today a single-device engine drives the scatter form
    # and a mesh the one-hot form; a ``perf_opt`` that gives one chip the
    # one-hot form edits no benchmark file
    assert ("bulk plane: the drives built the scan with onehot="
            f"{cell == FOUR}") in capsys.readouterr().out
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    # three drives at least, of 32 operations for each of 64 groups
    assert line["attempted"] >= 3 * 32 * 64 and line["attempted"] % 2048 == 0
    assert line["device"]["count"] == (4 if cell == FOUR else 1)
    group = "per_layer" if trace else "end_to_end"
    wanted = {m["name"]: m for m in harness.metrics_of(tiny, group, cell)}
    assert all(v["unit"] == wanted[k]["unit"]
               for k, v in line["metrics"].items())
    assert all(isinstance(v["value"], float) and v["value"] >= 0
               for v in line["metrics"].values())
    if not trace:
        assert set(line["metrics"]) == {"bulk_ops_per_s", "setup_s"}
        assert line["metrics"]["bulk_ops_per_s"]["value"] > 0
        return
    assert set(line["metrics"]) == set(wanted) - CHIP_ONLY
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert len(line["breakdown"]["device_ops"]) <= 10
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["bulk.fetches_per_drive"] == 1.0
    assert got["bulk.rounds_per_drive"] == 5.0     # two windows, three settle
    # res int32 + valid bool + round int32 an operation, one flag a group
    assert got["bulk.d2h_bytes_per_op"] == 9 + 1 / 32
    assert got["bulk.drive_max_ms"] >= got["bulk.drive_ms"] > 0
    if cell == FOUR:
        assert got["placement.collectives"] == 0.0
    else:
        assert "placement.collectives" not in wanted


def after_a_later_prs_joins(bench, data_root):
    """A copy of the root file after the PRs that PERF.md section 7 names:
    ``mixed-100kx5.bulk`` appended to the two lists it is owed, and one new
    entry that lists it, whose file lies under ``data_root`` (a copy of the
    tiny data directory) as a later PR's would lie under ``benchmarks/``."""
    bench = copy.deepcopy(bench)
    for m in bench["per_layer"]:
        if m["name"] in OWED:
            m["workloads"].append(ONE_CHIP)
    spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                       LIKE + ".json")))
    os.makedirs(os.path.join(data_root, "layer_metrics"))
    with open(os.path.join(data_root, "layer_metrics", LATER + ".json"),
              "w") as f:
        json.dump({**spec, "name": LATER, "key": "drives"}, f)
    bench["per_layer"].append({
        **{k: spec[k] for k in ("unit", "better", "source", "layer",
                                "moves")},
        "name": LATER, "workloads": [ONE_CHIP]})
    return bench


@pytest.mark.parametrize("later", [False, True],
                         ids=["as-it-stands", "after-a-later-prs-joins"])
def test_the_one_chip_cells_lists_print_on_one_device(harness, bench, tiny,
                                                      tmp_path, later):
    """``mixed-100kx5.bulk`` joined eighteen lists of the root file. The tiny
    one-device cell under the very entries that list it prints every one of
    the eighteen that a CPU run can read, nothing that was not asked for, and
    nothing of the placement, which one chip checks and reads nothing of.
    The run is held to what the root file asks, not to a list frozen here:
    after a later PR has appended the cell to more lists and brought an entry
    for it, the same assertions hold and the run prints those too."""
    data_root = DATA
    if later:
        data_root = str(tmp_path / "data_bulk")
        shutil.copytree(DATA, data_root)
        bench = after_a_later_prs_joins(bench, data_root)
        for rule in ROOT_FILE_RULES:
            rule(bench, REPO)
    asked = [{**m, "workloads": [ONE]}
             for m in harness.metrics_of(bench, "per_layer", ONE_CHIP)]
    names = [m["name"] for m in asked]
    assert [name for name in names if name in JOINED] == JOINED
    assert not MESH_ONLY & set(names)
    rehearsal = tmp_path / "BENCHMARK.json"
    rehearsal.write_text(json.dumps({**tiny, "per_layer": asked}))
    rc, line = harness.run_cell(ONE, 2**31 + 53, 0.3, True, None,
                                bench_file=str(rehearsal),
                                data_root=data_root, require_tpu=False)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(JOINED) - CHIP_ONLY <= set(line["metrics"]) <= set(names)
    assert not [name for name in line["metrics"] if "placement" in name]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["bulk.fetches_per_drive"] == 1.0
    assert got["bulk.dense_drives_share"] == 1.0
    if later:
        assert set(names) == set(JOINED) | set(OWED) | {LATER}
        assert set(line["metrics"]) == set(names) - CHIP_ONLY
        assert got[LATER] >= 3 and got["bulk.kept_bytes_share"] > 0


class FakeScan:
    def __init__(self, entries):
        self.entries = entries

    def _cache_size(self):
        return self.entries


@pytest.mark.parametrize("called,found", [
    ({False: 2, True: 0}, False), ({False: 0, True: 1}, True),
    ({False: 0, True: 0}, None), ({False: 1, True: 1}, None)],
    ids=["scatter", "onehot", "neither", "both"])
def test_the_scan_is_the_one_form_that_was_called(harness, called, found):
    """``driven_scan`` over a builder that hands back, for each form, a
    jitted function with as many compiled entries as ``called`` says: the
    form that was called, and an error where none or both were."""
    plane = harness.load_module("planes", "bulk", DATA)
    asked = []

    def program(config, onehot, donate):
        asked.append((config, onehot, donate))
        return FakeScan(called[onehot])

    if found is None:
        with pytest.raises(RuntimeError, match="exactly one"):
            plane.driven_scan(program, "config", True)
    else:
        scan, onehot = plane.driven_scan(program, "config", True)
        assert onehot is found and scan.entries == called[found]
    assert asked == [("config", False, True), ("config", True, True)]


def test_a_jax_without_the_count_of_compiled_entries_is_said_so(harness):
    """``_cache_size`` is not JAX's public surface: a jitted function
    without it makes the plane say what it lacks, not guess a form."""
    plane = harness.load_module("planes", "bulk", DATA)
    with pytest.raises(RuntimeError, match="no _cache_size"):
        plane.driven_scan(lambda config, onehot, donate: object(),
                          "config", True)


@pytest.mark.parametrize("fault", ["drop-ack", "flip-result"])
def test_a_fault_in_the_harness_gives_correct_false(harness, fault):
    line = drive(harness, ONE, fault=fault)
    assert line["correct"] is False and line["failed"] == 1


def test_a_result_altered_where_it_is_produced_gives_correct_false(
        harness, monkeypatch):
    """The timed path broken underneath the harness: the window's second
    drive hands back one group's first result one too high."""
    from copycat_tpu.models import bulk

    real, state = bulk.BulkDriver.drive, {"calls": 0}

    def broken(self, groups, *args, **kwargs):
        res = real(self, groups, *args, **kwargs)
        state["calls"] += 1
        if state["calls"] == 4:          # two warm-up drives come first
            res.results[:] += 1
        return res

    monkeypatch.setattr(bulk.BulkDriver, "drive", broken)
    line = drive(harness, ONE)
    assert state["calls"] >= 5
    assert line["correct"] is False and line["failed"] > 0


def test_the_programs_own_mark_for_no_result_counts_as_unresolved(
        harness, monkeypatch):
    """The plane takes a result as resolved where it names a round of its
    drive. The mark the program leaves where none came back is read here
    from the program's source, put into one operation of a window's drive
    underneath the harness, and has to be counted."""
    import inspect
    import re

    from copycat_tpu.models import bulk

    mark = re.search(r"rndbuf = rg\._stage_acc\(\s*np\.full\(\(G, Bpad\), "
                     r"([^,]+), np\.int32\)\)", inspect.getsource(bulk))
    assert mark, "models/bulk.py stages its round accumulator otherwise now"
    unresolved = eval(mark.group(1), {})
    real, state = bulk.BulkDriver.drive, {"calls": 0}

    def lossy(self, groups, *args, **kwargs):
        res = real(self, groups, *args, **kwargs)
        state["calls"] += 1
        assert 0 <= res.resolve_round.min()
        assert res.resolve_round.max() < res.rounds < unresolved
        if state["calls"] == 4:          # two warm-up drives come first
            res.resolve_round[7] = unresolved
        return res

    monkeypatch.setattr(bulk.BulkDriver, "drive", lossy)
    line = drive(harness, ONE)
    assert state["calls"] >= 5
    assert line["correct"] is False and line["failed"] == 1


def test_a_drive_that_commits_nothing_gives_correct_false(harness,
                                                          monkeypatch):
    """A drive that returns its state unchanged: the window's drives hand
    back the last warm-up drive's results and leave the engine alone."""
    from copycat_tpu.models import bulk

    real, state = bulk.BulkDriver.drive, {"calls": 0, "last": None}

    def idle(self, groups, *args, **kwargs):
        state["calls"] += 1
        if state["calls"] <= 2:
            state["last"] = real(self, groups, *args, **kwargs)
        time.sleep(0.05)                 # a drive's time, so the window is few
        return state["last"]

    monkeypatch.setattr(bulk.BulkDriver, "drive", idle)
    line = drive(harness, ONE)
    assert line["correct"] is False and line["failed"] > 0


def test_the_plain_replay_catches_a_flipped_result_and_a_lost_add(harness):
    import numpy as np

    from benchmarks import generators as gen
    from benchmarks import reference

    plane = harness.load_module("planes", "bulk", DATA)
    S, B, G = 16, 32, 3
    ops = tuple(np.tile(np.tile(x, B // S), (G, 1))
                for x in gen.mixed_pattern(S))
    groups = np.asarray([0, 2])
    models = [reference.PlainGroup() for _ in groups]
    drives = [np.asarray([[model.apply(*(int(x[g, j]) for x in ops), None)
                           or 0 for j in range(B)]
                          for g, model in zip(groups, models)])
              for _ in range(3)]
    compared, wrong, first, counters = plane.replay_drives(drives, groups, ops)
    # the two election listens of a round return a log index: not compared
    assert (compared, wrong, first) == (2 * 3 * (B - 2), 0, "")
    assert counters == [3 * B // S * 2] * 2     # two adds of 1 a round
    drives[1][1, 5] ^= 1
    compared, wrong, first, _ = plane.replay_drives(drives, groups, ops)
    assert wrong == 1 and "group 2 drive 1 op 5" in first


@pytest.mark.parametrize("counters,clock,spec,expected", [
    ({"fetches": 7}, {"drives": 7}, {"key": "fetches", "over": "drives"}, 1.0),
    ({"fetch_bytes": 900}, {"acked_ops": 100},
     {"key": "fetch_bytes", "over": "acked_ops"}, 9.0),
    ({"fetches": 0}, {"drives": 7}, {"key": "fetches", "over": "drives"}, 0.0),
    ({}, {"drives": 7}, {"key": "fetches", "over": "drives"}, None),
    ({"fetches": 7}, {"drives": 0}, {"key": "fetches", "over": "drives"},
     None),
    ({"fetches": 7}, {}, {"key": "fetches", "over": "drives"}, None),
], ids=["per-drive", "per-op", "a-zero-is-a-reading", "absent-counter",
        "zero-divisor", "absent-divisor"])
def test_counter_over_clock(counters, clock, spec, expected):
    reducer = load(os.path.join(BENCH, "reducers", "counter_over_clock.py"),
                   "counter_over_clock")
    assert reducer.reduce({"counters": counters, "clock": clock},
                          spec) == expected


# -- what the root BENCHMARK.json names for the plane -----------------------

def holds_the_cell_its_configuration_and_its_traffic(bench, root):
    here = os.path.join(root, "benchmarks")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixed-400kx5-4chip", "bulk", 4)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    # the source's own words, and every deviation from it declared
    north_star = json.load(open(os.path.join(REPO, "BASELINE.json")))[
        "north_star"]
    quoted = entry["source"].split('"')[1]
    assert quoted in north_star and "100k Raft groups" in quoted
    assert entry["reduced"] == ["chips", "groups"]
    config = json.load(open(os.path.join(root, entry["file"])))
    traffic = json.load(open(os.path.join(
        here, "traffic", cell["traffic"] + ".json")))
    one_chip = json.load(open(os.path.join(
        here, "configs", "mixed-100kx5.json")))
    # the one-chip configuration's every value, at four times the groups
    sizes = ("peers", "log_slots", "submit_slots", "use_pallas",
             "append_window", "applies_per_round", "pool_budgets",
             "timer_min", "timer_max", "resource")
    assert all(config[k] == one_chip[k] for k in sizes)
    assert config["groups"] == 4 * one_chip["groups"] == 400_000
    assert config["chips"] == 4
    assert set(config["reduced_from"]) == set(entry["reduced"])
    assert config["source"] == entry["source"]
    assert config["monotone_tag_accept"] is True
    assert {"source", "guarantees", "assumed", "memory"} <= set(config)
    assert traffic["plane"] == "bulk" and traffic["deep_scan"] is True
    assert traffic["ops_per_group"] == 2 * config["submit_slots"]
    assert os.path.exists(os.path.join(here, "planes", "bulk.py"))
    return cell, config, traffic


def holds_the_cells_metrics_to_the_tiny_cells(bench, root):
    """The entries named in ``NINE`` and nothing of the rest: a metric on
    the cell that ``NINE`` does not name is a later PR's."""
    tiny = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_bulk", "BENCHMARK.json")))
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "bulk_ops_per_s")
    # a cell under this rate shares its bound; the list is held as a prefix
    assert rate["workloads"][:2] == [CELL, ONE_CHIP]
    assert 0.01 <= rate["bound"] <= 0.25
    assert (rate["unit"], rate["better"], rate["source"]) == (
        "ops/s", "higher", "host_clock")
    assert {m["name"] for m in run_py().metrics_of(
        bench, "end_to_end", CELL)} == {"bulk_ops_per_s", "setup_s"}
    keys = ("name", "unit", "better", "source", "layer", "moves")
    real = [m for m in bench["per_layer"] if m["name"] in NINE]
    rehearsed = {m["name"]: m for m in run_py().metrics_of(
        tiny, "per_layer", FOUR)}
    assert [m["name"] for m in real] == NINE
    for m in real:
        assert CELL in m["workloads"], m["name"]
        assert all(m[k] == rehearsed[m["name"]][k] for k in keys), m["name"]
        assert m["moves"] == "bulk_ops_per_s"


def holds_the_one_chip_cell_to_the_four_chip_cells_lists(bench, root):
    """``mixed-100kx5.bulk`` is data only: the raw cell's configuration, the
    four-chip cell's traffic, one chip, and its name after the four-chip
    cell's in every list of ``JOINED``."""
    cell = next(w for w in bench["workloads"] if w["name"] == ONE_CHIP)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixed-100kx5", "bulk", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == []                       # the source's scale
    assert {m["name"] for m in run_py().metrics_of(
        bench, "end_to_end", ONE_CHIP)} == {"bulk_ops_per_s", "setup_s"}
    joined = [m for m in bench["per_layer"] if m["name"] in JOINED]
    assert [m["name"] for m in joined] == JOINED and len(JOINED) == 18
    for m in joined:
        assert m["workloads"][:2] == [CELL, ONE_CHIP], m["name"]
        assert m["moves"] == "bulk_ops_per_s"
    for m in bench["per_layer"]:
        if m["name"] in MESH_ONLY:
            assert ONE_CHIP not in m["workloads"], m["name"]


ROOT_FILE_RULES = [holds_the_cell_its_configuration_and_its_traffic,
                   holds_the_cells_metrics_to_the_tiny_cells,
                   holds_the_one_chip_cell_to_the_four_chip_cells_lists]


def test_the_cell_its_configuration_and_its_traffic_resolve(bench, harness):
    held = holds_the_cell_its_configuration_and_its_traffic(bench, REPO)
    # and they are what a run of the cell loads
    assert harness.load_cell(bench, CELL, BENCH) == held


def test_the_cells_metrics_are_the_tiny_cells_metrics(bench):
    holds_the_cells_metrics_to_the_tiny_cells(bench, REPO)


def test_the_one_chip_cell_is_in_the_four_chip_cells_lists(bench, harness):
    holds_the_one_chip_cell_to_the_four_chip_cells_lists(bench, REPO)
    # and a run of it loads the raw cell's deployment and the bulk mix
    cell, config, traffic = harness.load_cell(bench, ONE_CHIP, BENCH)
    assert (config["groups"], config["peers"], cell["chips"]) == (
        100_000, 5, 1)
    assert traffic == harness.load_cell(bench, CELL, BENCH)[2]
