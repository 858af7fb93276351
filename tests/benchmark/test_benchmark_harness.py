"""The benchmark's harness rehearsed on the CPU at tiny sizes: each plane
prints the contract's line with ``correct: true``; a fault in the harness's
inputs, or the timed path broken underneath it, gives ``correct: false``;
``run.py`` refuses to run without a TPU; ``BENCHMARK.json`` is well formed and
everything it names resolves. Tiny sizes come from ``tests/benchmark/data``,
never from the cells' own files. No number from here is a device number.
"""

import functools
import glob
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(REPO, "tests", "benchmark", "data")
SERVED_W, SERVED_R, RAW = ("served-tiny.write-tiny",
                           "served-tiny.read90-tiny", "mixed-tiny.raw-tiny")

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run")


@pytest.fixture(scope="module")
def harness():
    return run_py()


def drive(harness, cell, trace=False, fault=None, seed=2**31 + 77):
    rc, line = harness.run_cell(
        cell, seed, 0.5, trace, fault,
        bench_file=os.path.join(DATA, "BENCHMARK.json"), data_root=DATA,
        require_tpu=False)
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    return line


def compared(line):
    """The line's last key: each number compared beside its limit."""
    assert list(line)[-1] == "checks" and line["checks"]
    for what, value, limit in line["checks"]:
        assert isinstance(what, str) and 0 < len(what) <= 160
        assert type(value) in (int, float) and type(limit) in (int, float)
    return line["checks"]


@pytest.mark.parametrize("cell,trace", [
    (SERVED_W, False), (SERVED_R, True), (RAW, False), (RAW, True),
    (RAW + "4", False)],
    ids=["served-write", "served-read90-traced", "raw", "raw-traced",
         "raw-over-a-mesh-of-4"])
def test_cell_prints_the_contracts_line_and_is_correct(harness, cell, trace,
                                                       capfd):
    line = drive(harness, cell, trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    # every number compared beside its limit: the line's last key, and the
    # last lines of standard error (PERF.md section 7 item 12, PR 53)
    assert all(value <= limit for _, value, limit in compared(line))
    plane = "served" if cell.startswith("served") else "raw"
    said = [text for text in capfd.readouterr().err.splitlines()
            if text.startswith(plane + " plane: check: ")]
    assert len(said) == len(line["checks"])
    assert all(text.endswith(f": {value} (limit {limit})")
               for text, (_, value, limit) in zip(said, line["checks"]))
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == (4 if cell.endswith("4") else 1)
    bench = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in harness.metrics_of(bench, group, cell)}
    assert set(line["metrics"]) <= wanted and line["metrics"]
    if not trace:
        assert set(line["metrics"]) == wanted
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    if cell == SERVED_R:
        # the tail stands per layer where it is too unsteady end to end
        assert "ack_p99_ms" not in wanted
        assert {"client.ack_p99_ms", "client.ack_p50_ms"} <= set(
            line["metrics"])
    units = {m["name"]: m["unit"] for m in bench[group]}
    assert all(v["unit"] == units[k] for k, v in line["metrics"].items())


@pytest.mark.parametrize("cell", [SERVED_W, RAW], ids=["served", "raw"])
@pytest.mark.parametrize("fault", ["drop-ack", "flip-result"])
def test_a_fault_in_the_harness_gives_correct_false(harness, cell, fault):
    line = drive(harness, cell, fault=fault)
    assert line["correct"] is False
    # and the line says which number passed its limit
    assert any(value > limit for _, value, limit in compared(line))


def test_a_reply_altered_where_it_is_produced_gives_correct_false(
        harness, monkeypatch):
    """The served path broken underneath the harness: the client's
    resource hands back one wrong counter value."""
    from copycat_tpu.atomic import DistributedAtomicLong

    real, state = DistributedAtomicLong.add_and_get, {"left": 1}

    async def broken(self, delta):
        value = await real(self, delta)
        if state["left"]:
            state["left"] -= 1
            return value + 1
        return value

    monkeypatch.setattr(DistributedAtomicLong, "add_and_get", broken)
    line = drive(harness, SERVED_W)
    assert line["correct"] is False and state["left"] == 0


def test_a_step_that_reports_wrong_results_gives_correct_false(
        harness, monkeypatch):
    """The raw plane broken underneath the harness: the compiled step
    reports every result one too high."""
    from copycat_tpu.ops import consensus

    real = consensus.step

    def broken(state, submits, deliver, key, config):
        state, out = real(state, submits, deliver, key, config=config)
        return state, out._replace(out_result=out.out_result + 1)

    raw = harness.load_module("planes", "raw", DATA)
    raw.scan_program.cache_clear()
    monkeypatch.setattr(consensus, "step", broken)
    try:
        line = drive(harness, RAW)
    finally:
        raw.scan_program.cache_clear()
    assert line["correct"] is False and line["failed"] > 0


def test_a_step_that_returns_its_state_unchanged_gives_no_result(
        harness, monkeypatch):
    from copycat_tpu.ops import consensus

    real = consensus.step

    def idle(state, submits, deliver, key, config):
        _, out = real(state, submits, deliver, key, config=config)
        return state, out

    raw = harness.load_module("planes", "raw", DATA)
    raw.scan_program.cache_clear()
    monkeypatch.setattr(consensus, "step", idle)
    try:
        with pytest.raises(RuntimeError, match="have a leader"):
            drive(harness, RAW)
    finally:
        raw.scan_program.cache_clear()


def run_cli(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        capture_output=True, text=True, timeout=180)


def test_run_py_exits_2_and_prints_no_line_without_a_tpu():
    out = run_cli(REPO, "--workload", "served-1k.write", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode == 2
    assert '"correct"' not in out.stdout
    assert "needs 1 TPU chip" in out.stderr


def copy_the_benchmark(to, *more):
    """``BENCHMARK.json`` and the directories under its ``paths``, as a
    checkout that holds the benchmark alone has them."""
    for path in ("BENCHMARK.json", *more):
        os.makedirs(os.path.dirname(to / path), exist_ok=True)
        shutil.copy(os.path.join(REPO, path), to / path)
    for path in ("benchmarks", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(REPO, path), to / path,
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_run_py_fails_in_a_directory_with_the_benchmark_alone(tmp_path):
    copy_the_benchmark(tmp_path)
    out = run_cli(tmp_path, "--workload", "served-1k.write", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and '"correct"' not in out.stdout


# -- BENCHMARK.json ----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


# Every assertion of ``tests/benchmark/`` over the root file's lists is a
# plain function of (bench, root) in its file's ROOT_FILE_RULES: the tests
# call it with the repository, the rehearsal of an addition below with a
# copy that a later PR's entries were appended to.

def holds_the_contracts_keys_and_limits(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 << 10
    fours = sum(w["chips"] == 4 for w in bench["workloads"])
    assert fours <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def holds_every_name_and_unit_to_the_allowed_characters(bench, root):
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in bench[group]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in bench[group]}) == len(bench[group])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for x in bench["configs"] + bench["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def holds_that_everything_named_resolves(bench, root):
    here = os.path.join(root, "benchmarks")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        assert c["file"].startswith("benchmarks/configs/")
        held = json.load(open(os.path.join(root, c["file"])))
        assert all(key in held for key in c["reduced"])
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        mix = json.load(open(os.path.join(
            here, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(
            here, "planes", mix["plane"] + ".py"))
        reported = {m["name"] for g in ("end_to_end", "per_layer")
                    for m in run_py().metrics_of(bench, g, w["name"])}
        assert "setup_s" in reported
        assert len(reported & set(end_to_end)) >= 2
        assert reported - set(end_to_end)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(
            here, "layer_metrics", m["name"] + ".json")))
        assert all(spec[k] == m[k] for k in ("name", "unit", "better",
                                             "layer", "moves", "source"))
        assert os.path.exists(os.path.join(
            here, "reducers", spec["reducer"] + ".py"))
        moved = end_to_end[m["moves"]]
        # every cell that reads the metric reports the metric it moves
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    peaks = json.load(open(os.path.join(here, "peaks.json")))
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


ROOT_FILE_RULES = [holds_the_contracts_keys_and_limits,
                   holds_every_name_and_unit_to_the_allowed_characters,
                   holds_that_everything_named_resolves]


def test_benchmark_json_has_the_contracts_keys_and_limits(bench):
    holds_the_contracts_keys_and_limits(bench, REPO)


def test_every_name_and_unit_is_made_of_the_allowed_characters(bench):
    holds_every_name_and_unit_to_the_allowed_characters(bench, REPO)


def test_everything_benchmark_json_names_resolves(bench):
    holds_that_everything_named_resolves(bench, REPO)


# -- an addition rehearsed ----------------------------------------------------

def every_files_rules():
    """ROOT_FILE_RULES of every test file here, found as a later PR's file
    will be: by its name. A file that opens the root ``BENCHMARK.json`` has
    to offer some."""
    rules = []
    for path in sorted(glob.glob(os.path.join(
            REPO, "tests", "benchmark", "test_*.py"))):
        name = os.path.basename(path)[:-3]
        found = getattr(load(path, "rules_of_" + name), "ROOT_FILE_RULES", [])
        if re.search(r'REPO,\s*"BENCHMARK\.json"', open(path).read()):
            assert found, f"{name} reads the root file and offers no rules"
        rules += [(name, rule) for rule in found]
    return rules


CLUSTER, BULK = "cluster-3x1k.write", "mixed-400kx5-4chip.bulk"
#: the one-chip bulk cell, and the two lists PERF.md section 7 says it is owed
BULK_ONE = "mixed-100kx5.bulk"
OWED = ("bulk.kept_bytes_share", "bulk.early_bytes_share")


def append_a_served_cell(root, bench, config, traffic, why):
    """A later deployment and cell on the served plane: copies of
    ``served-1k`` and the write mix under new names, an entry each, and the
    cell's name at the end of ``served_ops_per_s``'s list."""
    here, cell = root / "benchmarks", f"{config}.{traffic}"
    shutil.copy(here / "configs" / "served-1k.json",
                here / "configs" / (config + ".json"))
    shutil.copy(here / "traffic" / "write.json",
                here / "traffic" / (traffic + ".json"))
    first = bench["configs"][0]
    bench["configs"].append({
        "name": config, "source": first["source"],
        "file": f"benchmarks/configs/{config}.json",
        "reduced": first["reduced"], "why": "a later deployment"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1, "why": why})
    next(m for m in bench["end_to_end"]
         if m["name"] == "served_ops_per_s")["workloads"].append(cell)
    return cell


def append_a_metric(root, bench, name, like, key, cells):
    """A later entry for a reading no entry gives: ``like``'s file under
    ``name`` with a ``key`` of its own (the same file under another name is
    a copy, which test_benchmark_readings.py refuses)."""
    metrics = root / "benchmarks" / "layer_metrics"
    spec = json.load(open(metrics / (like + ".json")))
    with open(metrics / (name + ".json"), "w") as f:
        json.dump({**spec, "name": name, "key": key}, f)
    bench["per_layer"].append({
        **{k: spec[k] for k in ("unit", "better", "source", "layer",
                                "moves")}, "name": name, "workloads": cells})


def append_what_later_prs_add(root):
    """What a ``model_config`` PR adds: a configuration, a mix on a plane
    that is there, a one-chip cell under ``served_ops_per_s`` and two
    per-layer metrics at the end of the list, one of them read in the cluster
    cell too. And what a ``tracing`` PR adds: a metric on the two bulk cells.
    Files added, and no file that was there edited but ``BENCHMARK.json``."""
    bench = json.load(open(root / "BENCHMARK.json"))
    cell = append_a_served_cell(root, bench, "rehearsed-1k", "rehearsed",
                                "a later cell on the served plane")
    for name, like, key, cells in (
            ("rehearsed.alone_per_kop", "runtime.fetches_per_kop",
             ["counters", "rehearsed.alone"], [cell]),
            ("rehearsed.shared_per_kop", "runtime.fetches_per_kop",
             ["counters", "rehearsed.shared"], [cell, CLUSTER]),
            ("rehearsed.bulk_per_drive", "bulk.fetches_per_drive",
             "rehearsed_fetches", [BULK, BULK_ONE])):
        append_a_metric(root, bench, name, like, key, cells)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    return bench


def test_a_later_prs_entries_pass_every_rule_of_every_test_file(tmp_path):
    """The door stays open: what later PRs append passes every assertion
    that ``tests/benchmark/`` makes over the root file's lists. A test that
    pins the length, the tail or the remainder of a list fails here in the
    PR that writes it."""
    rules = every_files_rules()
    assert {name for name, _ in rules} >= {
        "test_benchmark_harness", "test_benchmark_pump_metrics",
        "test_benchmark_bulk_plane", "test_benchmark_cluster_plane",
        "test_benchmark_readings"}
    # the layers' names and the spans' vocabulary, which a metric is held to
    copy_the_benchmark(tmp_path, "PERF.md",
                       os.path.join("docs", "OBSERVABILITY.md"))
    was = json.load(open(tmp_path / "BENCHMARK.json"))
    bench = append_what_later_prs_add(tmp_path)
    for group in ("configs", "workloads", "per_layer"):
        assert bench[group][:len(was[group])] == was[group]
        assert len(bench[group]) > len(was[group])
    for there, last in ((CLUSTER, "rehearsed.shared_per_kop"),
                        (BULK, "rehearsed.bulk_per_drive"),
                        (BULK_ONE, "rehearsed.bulk_per_drive")):
        assert run_py().metrics_of(bench, "per_layer", there)[-1][
            "name"] == last
    for _, rule in rules:
        rule(bench, str(tmp_path))
    # and they were held to the copy: its new cell without its traffic
    # file no longer resolves
    os.remove(tmp_path / "benchmarks" / "traffic" / "rehearsed.json")
    with pytest.raises(FileNotFoundError, match="rehearsed.json"):
        holds_that_everything_named_resolves(bench, str(tmp_path))


#: the layers' readings a later cell on the served plane reads as they are
JOINS = ("client.ack_p50_ms", "engine.rounds_per_kop",
         "device.idle_share.served")
#: its one entry of its own, and the entry whose file it is written after
OWN, LIKE = "joining.own_per_kop", "engine.settle_rounds_per_kop"


def append_a_cell_that_joins(root):
    """What a ``model_config`` PR adds since PR 53, where an entry already
    gives the reading its cell wants: the cell, its name at the end of
    ``served_ops_per_s``'s list and of three per-layer lists, and one entry
    of its own for the reading no entry gives. And a cell that is there
    joins two more lists: the one-chip bulk cell the two it is owed. Entries
    and names appended; no file that was there edited but
    ``BENCHMARK.json``."""
    bench = json.load(open(root / "BENCHMARK.json"))
    cell = append_a_served_cell(root, bench, "joining-1k", "joins",
                                "a later cell that reads what the layers give")
    for m in bench["per_layer"]:
        if m["name"] in JOINS:
            m["workloads"].append(cell)
        if m["name"] in OWED and BULK_ONE not in m["workloads"]:
            m["workloads"].append(BULK_ONE)
    append_a_metric(root, bench, OWN, LIKE, ["counters", "joining.own"],
                    [cell])
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    return bench, cell


def test_a_later_cell_that_joins_three_lists_passes_every_rule(tmp_path):
    """A cell joins a metric by its list: appended to the lists of three
    entries that give its readings, it passes every assertion of every test
    file, and a traced run of it would be asked for those three and its
    own. A test that holds a list whole, and not as a prefix, fails here."""
    rules = every_files_rules()
    assert "test_benchmark_readings" in {name for name, _ in rules}
    copy_the_benchmark(tmp_path, "PERF.md",
                       os.path.join("docs", "OBSERVABILITY.md"))
    was = json.load(open(tmp_path / "BENCHMARK.json"))
    bench, cell = append_a_cell_that_joins(tmp_path)
    for group in ("end_to_end", "per_layer"):
        for before, after in zip(was[group], bench[group]):
            held = len(before.get("workloads", []))
            assert {**after, "workloads": after.get("workloads", [])[:held]} \
                == {"workloads": [], **before}
    assert len(bench["per_layer"]) == len(was["per_layer"]) + 1
    assert [m["name"] for m in run_py().metrics_of(
        bench, "per_layer", cell)] == [
            m["name"] for m in was["per_layer"] if m["name"] in JOINS
        ] + [OWN]
    assert set(OWED) <= {m["name"] for m in run_py().metrics_of(
        bench, "per_layer", BULK_ONE)}
    for _, rule in rules:
        rule(bench, str(tmp_path))
    # the same reading under the cell's own name is what the rules refuse
    metrics = tmp_path / "benchmarks" / "layer_metrics"
    with open(metrics / (OWN + ".json"), "w") as f:
        json.dump({**json.load(open(metrics / (LIKE + ".json"))),
                   "name": OWN}, f)
    with pytest.raises(AssertionError):
        for _, rule in rules:
            rule(bench, str(tmp_path))


# -- the yardstick's own arithmetic ------------------------------------------

def test_percentile_of_an_exact_histogram():
    import numpy as np

    from benchmarks import generators as gen

    hist = np.zeros(10, np.int64)
    hist[1], hist[5] = 90, 10            # 90 samples of 1 round, 10 of 5
    assert gen.percentile_rounds(hist, 0.50) == (1, 1 + 50 / 90)
    bucket, exact = gen.percentile_rounds(hist, 0.99)
    assert bucket == 5 and exact == pytest.approx(5.9)
    assert gen.percentile_rounds(np.zeros(4, np.int64), 0.99) == (0, 0.0)


def test_isolation_masks_cycle_and_cut_one_peer():
    import numpy as np

    from benchmarks import generators as gen

    victims = gen.isolation_masks(48, 7, 5, period=20, seed=3)
    assert victims.shape == (48, 7)
    isolated = (victims >= 0).all(axis=1)
    assert isolated.tolist() == [r % 20 < 10 for r in range(48)]
    deliver = np.asarray(gen.victim_deliver(victims[0], 7, 5))
    for g in range(7):
        v = victims[0, g]
        assert not deliver[g, v].any() and not deliver[g, :, v].any()
        assert deliver[g].sum() == 16
    assert np.asarray(gen.victim_deliver(victims[10], 7, 5)).all()


def test_the_plain_model_catches_a_flipped_result():
    import numpy as np

    from benchmarks import generators as gen
    from benchmarks import reference

    S = 16
    pattern = gen.mixed_pattern(S)
    model = reference.PlainGroup()
    valid = np.ones((1, 1, S), bool)
    tag = np.arange(1, S + 1).reshape(1, 1, S)
    index = tag.copy()
    result = np.asarray([model.apply(int(pattern[0][j]), int(pattern[1][j]),
                                     int(pattern[2][j]), j + 1)
                         for j in range(S)]).reshape(1, 1, S)
    assert reference.replay_reports(
        (valid, tag, result, index), pattern, S, [0])[:2] == (S, 0)
    result[0, 0, 3] ^= 1
    compared, wrong, first = reference.replay_reports(
        (valid, tag, result, index), pattern, S, [0])
    assert (compared, wrong) == (S, 1) and "plain model" in first


def test_roofline_bytes_come_from_shapes():
    harness_dir = os.path.join(BENCH, "reducers", "hbm_roofline.py")
    spec = importlib.util.spec_from_file_location("hbm_roofline", harness_dir)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.least_seconds_per_round(819_000_000, 819e9) == 0.002
    sources = {"clock": {"program": "raw_plane_scan", "state_bytes": 819e6,
                         "rounds_per_dispatch": 10},
               "trace": {"modules": [["jit_raw_plane_scan(1)", 0.0, 1e9],
                                     ["jit_other(2)", 0.0, 5e9]]},
               "peaks": {"hbm_bytes_per_s": 819e9}}
    assert module.reduce(sources, {}) == pytest.approx(2.0)
    sources["trace"]["modules"] = []
    assert module.reduce(sources, {}) is None
