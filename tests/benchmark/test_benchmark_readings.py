"""The per-layer list counts readings, not copies (PR 53).

A reading is a metric in a cell: a ``(metric, cell)`` pair of the root
``BENCHMARK.json``. One entry, with one file under
``benchmarks/layer_metrics/``, gives a layer's reading for every cell of its
``workloads``; a cell joins it by its name at the end of that list, and a new
entry is for a reading that no entry gives. This file holds every reading of
the root file to its cell, to the end-to-end metric it moves and to its file,
holds the 142 readings that stood at PR 52 (``data_readings/
per_layer_pr52.json``: the 128 entries under the names they had, with their
files' definitions) to the same definition under the name that gives them
now, and refuses two entries with one definition, so that copies cannot come
back. No run, and no number.
"""

import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(REPO, "tests", "benchmark")
#: what a reading is defined by: every key of a metric's file but its name
#: and its ``what``
DEFINITION = ("reducer", "kind", "key", "per", "over", "unit", "better",
              "source", "layer", "moves")
#: the 36 names PR 53 took out of the list, and the entry that gives each
#: one's readings now (31 folded into an entry that stood beside them, 5
#: renamed for the layer's reading where the lock's and the election's
#: copies were all there was); PERF.md section 3 has the same table
NAME_NOW = {
    **{f"{cell}.ack_p50_ms": "client.ack_p50_ms"
       for cell in ("cluster", "map", "lock", "election")},
    "map.ack_p99_ms": "client.ack_p99_ms",
    "crash.ack_p99_ms": "client.ack_p99_ms",
    "map.stage_ms": "client.stage_ms",
    "crash.fsyncs_per_kop": "cluster.fsyncs_per_kop",
    **{f"{cell}.rounds_per_kop": "engine.rounds_per_kop"
       for cell in ("cluster", "map", "lock", "election", "crash")},
    **{f"device.idle_share.{cell}": "device.idle_share.served"
       for cell in ("cluster", "map", "lock", "election", "crash")},
    **{f"{cell}.fetches_per_kop": "runtime.fetches_per_kop"
       for cell in ("map", "lock", "election")},
    **{f"{cell}.d2h_bytes_per_op": "runtime.d2h_bytes_per_op"
       for cell in ("map", "lock", "election")},
    "map.read_eval_ms": "engine.read_eval_ms",
    "map.query_drives_per_kop": "engine.query_drives_per_kop",
    **{f"{cell}.{old}": new for cell in ("lock", "election")
       for old, new in (("handoff_p50_ms", "event.handoff_p50_ms"),
                        ("apply_ms", "engine.apply_ms.served"),
                        ("event_seal_ms", "event.seal_ms"),
                        ("event_push_ms", "event.push_ms"),
                        ("round_roofline", "step.round_roofline"))},
}


def load_root(root=REPO):
    return json.load(open(os.path.join(root, "BENCHMARK.json")))


def metric_file(name, root=REPO):
    return json.load(open(os.path.join(
        root, "benchmarks", "layer_metrics", name + ".json")))


def definition_of(spec):
    return {k: spec[k] for k in DEFINITION if k in spec}


#: (name at PR 52, cell) -> the definition its file had
STOOD = {(row["name"], cell): definition_of(row)
         for row in json.load(open(os.path.join(
             HERE, "data_readings", "per_layer_pr52.json")))["per_layer"]
         for cell in row["workloads"]}
#: (name now, cell) -> the name at PR 52
STOOD_AS = {(NAME_NOW.get(name, name), cell): name for name, cell in STOOD}
READINGS = [(m["name"], cell) for m in load_root()["per_layer"]
            for cell in m["workloads"]]


@pytest.fixture(scope="module")
def bench():
    return load_root()


def test_the_table_of_pr_52_is_whole():
    assert len({name for name, _ in STOOD}) == 128 and len(STOOD) == 142
    assert len(NAME_NOW) == 36 and len(set(NAME_NOW.values())) == 15
    assert set(NAME_NOW) <= {name for name, _ in STOOD}
    # no two readings of PR 52 fell onto one pair
    assert len(STOOD_AS) == 142


@pytest.mark.parametrize("name,cell", READINGS,
                         ids=[f"{n}-{c}" for n, c in READINGS])
def test_a_reading_is_its_cells_and_its_files(bench, name, cell):
    """The cell is there and reports what the entry moves, the entry's file
    and reducer resolve, and a reading that stood at PR 52 has the
    definition it had then, under whatever name."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert cell in {w["name"] for w in bench["workloads"]}
    (moved,) = [e for e in bench["end_to_end"] if e["name"] == entry["moves"]]
    assert cell in moved.get("workloads", [cell])
    spec = metric_file(name)
    assert all(spec[k] == entry[k] for k in (
        "name", "unit", "better", "source", "layer", "moves"))
    assert set(spec) - {"name", "what"} <= set(DEFINITION) and spec["what"]
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "reducers", spec["reducer"] + ".py"))
    if (name, cell) in STOOD_AS:
        assert definition_of(spec) == STOOD[STOOD_AS[name, cell], cell]


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_every_reading_that_stood(bench, root):
    """Each of the 142 is given by an entry whose list names its cell and
    whose file has the definition the old name's file had; no old name is
    an entry any more."""
    given = {(m["name"], cell) for m in bench["per_layer"]
             for cell in m["workloads"]}
    assert set(STOOD_AS) <= given, set(STOOD_AS) - given
    assert not set(NAME_NOW) & {m["name"] for m in bench["per_layer"]}
    for (name, cell), old in STOOD_AS.items():
        assert definition_of(metric_file(name, root)) == STOOD[old, cell]


def holds_no_two_entries_to_one_definition(bench, root):
    """A cell that wants a reading an entry gives joins that entry's list:
    an entry whose file defines what another's defines is a copy."""
    seen = {}
    for m in bench["per_layer"]:
        key = json.dumps(definition_of(metric_file(m["name"], root)),
                         sort_keys=True)
        assert key not in seen, (
            f"{m['name']} defines what {seen[key]} defines: append the "
            f"cell to {seen[key]}'s workloads instead")
        seen[key] = m["name"]


def holds_a_list_to_cells_named_once(bench, root):
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        assert len(set(cells)) == len(cells), m["name"]


ROOT_FILE_RULES = [holds_every_reading_that_stood,
                   holds_no_two_entries_to_one_definition,
                   holds_a_list_to_cells_named_once]


def test_every_reading_that_stood_is_given(bench):
    holds_every_reading_that_stood(bench, REPO)
    assert len(bench["per_layer"]) >= 97 and len(READINGS) >= 166


def test_no_two_entries_have_one_definition(bench):
    holds_no_two_entries_to_one_definition(bench, REPO)
    holds_a_list_to_cells_named_once(bench, REPO)


def test_a_copy_of_an_entry_under_another_name_is_refused(tmp_path):
    """What PR 35, 40, 45 and 49 each did for want of a way in: the same
    file under the cell's name. The rule names the entry to join."""
    os.makedirs(tmp_path / "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks", "layer_metrics"),
                    tmp_path / "benchmarks" / "layer_metrics")
    bench = load_root()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "engine.rounds_per_kop"]
    copy = {**entry, "name": "later.rounds_per_kop",
            "workloads": ["served-1k.write"]}
    with open(tmp_path / "benchmarks" / "layer_metrics"
              / "later.rounds_per_kop.json", "w") as f:
        json.dump({**metric_file(entry["name"]), "name": copy["name"],
                   "what": "the same reading, said again"}, f)
    bench["per_layer"].append(copy)
    with pytest.raises(AssertionError, match="engine.rounds_per_kop's "
                                             "workloads instead"):
        holds_no_two_entries_to_one_definition(bench, str(tmp_path))
    # a reading of its own passes: another key
    with open(tmp_path / "benchmarks" / "layer_metrics"
              / "later.rounds_per_kop.json", "w") as f:
        json.dump({**metric_file(entry["name"]), "name": copy["name"],
                   "key": "settle_rounds", "what": "another counter"}, f)
    holds_no_two_entries_to_one_definition(bench, str(tmp_path))
    # and a cell named twice in one list is refused
    entry["workloads"].append(entry["workloads"][0])
    with pytest.raises(AssertionError, match="engine.rounds_per_kop"):
        holds_a_list_to_cells_named_once(bench, str(tmp_path))
