"""The cluster plane rehearsed on the CPU at a tiny size (three members of
capacity 16 over disk logs, eight counters): the contract's line with
``correct: true``, every per-layer metric a CPU run can read, each check seen
when what it guards is broken underneath, both faults ``correct: false``; what
the root ``BENCHMARK.json`` names for the plane resolves. Sizes come from
``tests/benchmark/data_cluster``, never from the cell's own files. No number
from here is a device number.
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
DATA = os.path.join(REPO, "tests", "benchmark", "data_cluster")
TINY, CELL = "cluster-tiny.write-tiny", "cluster-3x1k.write"
#: the cell's metrics that this file holds, in the root file's order: PR 27's
#: twelve, and the four that read what PR 28, 30 and 32 record (PR 34). The
#: first three are the layers' readings the cell joined by its name in their
#: lists (PR 53 folded ``cluster.ack_p50_ms``, ``cluster.rounds_per_kop`` and
#: ``device.idle_share.cluster`` into them). A metric on the cell that is not
#: named here is a later PR's and brings a test and a tiny data directory of
#: its own
JOINED = ["client.ack_p50_ms", "engine.rounds_per_kop",
          "device.idle_share.served"]
NEW = JOINED + [
    "cluster.quorum_wait_ms", "cluster.fsync_ms",
    "cluster.follower_append_ms", "cluster.snapshot_ms",
    "cluster.fsyncs_per_kop", "cluster.log_bytes_per_op",
    "cluster.repl_windows_per_kop", "cluster.snapshots_per_kop",
    "cluster.apply_ms", "cluster.snapshot_finish_ms",
    "cluster.captures_deferred_per_kop",
    "cluster.codec_python_bodies_per_kop", "cluster.log_writes_per_kop"]
#: spans of the block lane only: a turn whose commands were staged one by
#: one records the coarse ``group.commit`` instead, and eight clients fall
#: into either lane from run to run
BLOCK_LANE = {"cluster.quorum_wait_ms", "cluster.fsync_ms",
              "cluster.follower_append_ms", "cluster.apply_ms"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_cluster")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def plane(harness):
    return harness.load_module("planes", "cluster", DATA)


@pytest.fixture(scope="module")
def tiny():
    return json.load(open(os.path.join(DATA, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.fixture(autouse=True)
def often(monkeypatch):
    """A capture every 64 applied entries, so that a window of a few hundred
    operations holds several on every member."""
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "64")


def drive(harness, capsys, trace=False, fault=None, seed=2**31 + 27):
    rc, line = harness.run_cell(
        TINY, seed, 0.4, trace, fault,
        bench_file=os.path.join(DATA, "BENCHMARK.json"), data_root=DATA,
        require_tpu=False)
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    out, err = capsys.readouterr()
    # each number compared, beside its limit, as the last lines of stderr
    checks = {text[text.index("("):text.index(")") + 1]:
              int(text.rsplit(": ", 1)[1].split(" ")[0])
              for text in err.splitlines() if "cluster plane: check:" in text}
    assert err.rstrip().splitlines()[-1].startswith(
        "cluster plane: check: (j)")
    assert list(checks) == [f"({c})" for c in "abcdefghij"]
    return line, checks, out


def test_cell_prints_the_contracts_line_and_is_correct(harness, capsys):
    line, checks, out = drive(harness, capsys)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0, out
    assert set(checks.values()) == {0}
    assert set(line["metrics"]) == {"served_ops_per_s", "ack_p99_ms",
                                    "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # what the deployment is, said at its start
    assert "storage DISK fsync=commit" in out and "a 4 KiB append and " in out
    assert "0.5 ms one way on every message (measured" in out
    assert "recovery lane by member: snapshot, snapshot, snapshot" in out
    assert "snapshots restored [1, 1, 1]" in out     # and none replayed all


def test_a_traced_run_prints_the_cells_metrics(harness, tiny, capsys):
    line, checks, out = drive(harness, capsys, trace=True)
    assert line["correct"] is True and set(checks.values()) == {0}, out
    wanted = {m["name"]: m for m in harness.metrics_of(tiny, "per_layer",
                                                       TINY)
              if m["name"] in NEW}
    assert list(wanted) == NEW
    missing = set(wanted) - set(line["metrics"])
    assert missing <= BLOCK_LANE, missing
    for name, got in line["metrics"].items():
        assert got["unit"] == wanted[name]["unit"]
        assert isinstance(got["value"], float) and got["value"] >= 0, name
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["cluster.snapshots_per_kop"] > 0 < got["cluster.snapshot_ms"]
    assert got["cluster.fsyncs_per_kop"] > 0 < got["engine.rounds_per_kop"]
    # an entry of this mix takes 60 to 70 bytes in each of three logs
    assert 150 < got["cluster.log_bytes_per_op"] < 300
    # what PR 28, 30 and 32 record: a finish on the worker for every cut,
    # none due in flight or some (0 is a reading), entries through the C
    # walk and blocks in one write
    assert got["cluster.snapshot_finish_ms"] > 0
    assert "cluster.captures_deferred_per_kop" in got
    assert got["cluster.codec_python_bodies_per_kop"] > 0
    assert 0 < got["cluster.log_writes_per_kop"] < 3000
    assert "snapshot.fetch x" in out and "snapshot.write x" in out


@pytest.mark.parametrize("fault,seen", [
    ("drop-ack", {"(b)", "(c)", "(i)"}), ("flip-result", {"(a)"})])
def test_a_fault_in_the_harness_gives_correct_false(harness, capsys, fault,
                                                    seen):
    line, checks, _ = drive(harness, capsys, fault=fault)
    assert line["correct"] is False
    # the model that lost an acknowledged add also differs from that
    # counter's replies in the traffic before the crash
    assert seen <= {c for c, v in checks.items() if v} <= seen | {"(a)"}
    assert checks["(c)"] in (0, 3)          # one counter, on every member


def test_a_reply_a_followers_value_and_a_skipped_sync_are_seen(
        harness, plane, capsys, monkeypatch):
    """Three things broken underneath the harness in one run, each seen by
    its own check and by no other: the session hands back one reply one too
    high (a); one follower's device engine holds another value than the log
    gave it (c); one member's log never syncs (g)."""
    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.ops import apply as ops
    from copycat_tpu.server.log import Log

    state = {"replies": 0}
    real_add = DistributedAtomicLong.add_and_get

    async def add_and_get(self, delta):
        got = await real_add(self, delta)
        state["replies"] += 1
        return got + (state["replies"] == 40)

    monkeypatch.setattr(DistributedAtomicLong, "add_and_get", add_and_get)

    real_caught_up = plane.Members.caught_up

    async def caught_up(self, *args):
        took = await real_caught_up(self, *args)
        if "altered" not in state:
            state["altered"] = follower = next(
                g for g in self.groups if g.role != "leader")
            holder = next(h for h in follower.state_machine.resources.values()
                          if h.key == "ctr3")
            follower.state_machine.device_engine.run_vector(
                [holder.state_machine._group], [ops.OP_LONG_ADD], [1], [0],
                [0])
            # its later captures would carry the altered value into the
            # reopened cluster, where another check reads it
            follower.server._snap_every = 1 << 30
        return took

    monkeypatch.setattr(plane.Members, "caught_up", caught_up)

    real_sync = Log.sync

    def sync(self):
        if self._name.endswith(str(15960)):      # the first member's log
            return
        real_sync(self)

    monkeypatch.setattr(Log, "sync", sync)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False, out
    assert {c: v for c, v in checks.items() if v} == {
        "(a)": 1, "(c)": 1, "(g)": 1}
    assert "ctr3: read" in out and "member 127.0.0.1:1596" in out


def test_a_segment_cut_below_its_last_sync_is_seen(harness, plane, capsys,
                                                   monkeypatch):
    """Durability broken underneath: every log claims it was synced twice
    as far as it was, so the cut takes acknowledged entries from all three
    members. No capture runs, so no snapshot holds what the logs lose."""
    from copycat_tpu.server.log import Log

    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "1000000")
    real = Log.synced_tail

    def short(self):
        tail = real.fget(self)
        return tail and (tail[0], tail[1] // 2)

    monkeypatch.setattr(Log, "synced_tail", property(short))
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False
    assert checks["(i)"] > 0 and checks["(j)"] == 3, out
    assert {c for c, v in checks.items() if v} == {"(i)", "(j)"}
    assert "replay-only" in out


def test_the_plain_reference_applies_acknowledged_deltas_in_order():
    from benchmarks import reference_cluster as ref

    model = ref.PlainCounters()
    assert [model.add("a", d) for d in (5, 7)] == [5, 12]
    assert model.get("a") == 12 and model.get("never") == 0
    names = ["a", "b"]
    assert ref.differences(model, names, [12, 0]) == (0, "")
    wrong, first = ref.differences(model, names, [12, 1])
    assert wrong == 1 and first.startswith("b: read 1")
    # an add cut off unanswered may or may not have been committed
    assert ref.differences(model, names, [15, 0], {"a": 3}) == (0, "")
    assert ref.differences(model, names, [14, 0], {"a": 3})[0] == 1


def test_the_log_directory_is_on_the_first_base_that_is_no_tmpfs(plane,
                                                                 monkeypatch):
    kinds = {"/t": "tmpfs", "/w": "tmpfs", "/h": "ext4"}
    monkeypatch.setattr(plane.tempfile, "gettempdir", lambda: "/t")
    monkeypatch.setattr(plane.os, "getcwd", lambda: "/w")
    monkeypatch.setattr(plane.os.path, "expanduser", lambda p: "/h")
    monkeypatch.setattr(plane.os, "access", lambda p, mode: True)
    monkeypatch.setattr(plane, "fs_type", kinds.get)
    assert plane.pick_log_base() == ("/h", "ext4")
    kinds["/h"] = "tmpfs"                # all three: it runs, and says so
    assert plane.pick_log_base() == ("/t", "tmpfs")
    monkeypatch.undo()
    assert plane.fs_type("/proc") == "proc"


def test_a_program_without_the_synced_length_fails_at_once(plane,
                                                           monkeypatch):
    """What the parent commit does on the new cell: no result, exit 1."""
    from copycat_tpu.server.log import Log

    monkeypatch.delattr(Log, "synced_tail")
    with pytest.raises(SystemExit) as failed:
        plane.run(type("Ctx", (), {"cell": {"name": CELL}})())
    assert "Log.synced_tail" in str(failed.value)


# -- what the root BENCHMARK.json names for the plane -----------------------

def holds_the_cell_its_configuration_and_its_traffic(bench, root):
    here = os.path.join(root, "benchmarks")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cluster-3x1k", "cluster-write", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    baseline = json.load(open(os.path.join(REPO, "BASELINE.json")))
    # the source's own words
    assert baseline["configs"][0] in entry["source"]
    assert entry["reduced"] == ["hosts"]
    config = json.load(open(os.path.join(root, entry["file"])))
    traffic = json.load(open(os.path.join(
        here, "traffic", cell["traffic"] + ".json")))
    served = json.load(open(os.path.join(here, "configs", "served-1k.json")))
    write = json.load(open(os.path.join(here, "traffic", "write.json")))
    # served-1k's resources, engine and timeouts; three members, a disk, a wire
    same = ("resources", "capacity", "peers", "counters", "maps", "locks",
            "elections", "election_timeout_s", "heartbeat_interval_s",
            "session_timeout_s", "consistency", "codec", "transport")
    assert all(config[k] == served[k] for k in same)
    assert (config["members"], config["storage"], config["fsync"],
            config["wire_delay_ms"], config["hosts"]) == (
        3, "DISK", "commit", 0.5, 1)
    assert set(config["reduced_from"]) == {"hosts"}
    assert "wire_delay_ms" in config["assumed"]
    assert config["source"] == entry["source"]
    assert len(config["guarantees"]) == 6
    assert any("quorum (2 of 3)" in g for g in config["guarantees"])
    assert any("last sync" in g for g in config["guarantees"])
    # served-1k.write's mix unchanged
    mix = ("clients", "read_share", "delta_min", "delta_max",
           "warmup_quiet_s", "generator")
    assert all(traffic[k] == write[k] for k in mix)
    assert traffic["plane"] == "cluster"
    return cell, config, traffic


def holds_the_cells_metrics_to_the_tiny_cells(bench, root):
    """The entries named in ``NEW`` and nothing of the rest: what follows
    them in the root file, and a metric on the cell that ``NEW`` does not
    name, is a later PR's."""
    tiny = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_cluster", "BENCHMARK.json")))
    assert {m["name"] for m in run_py().metrics_of(
        bench, "end_to_end", CELL)} == {"served_ops_per_s", "ack_p99_ms",
                                        "setup_s"}
    keys = ("name", "unit", "better", "source", "layer", "moves")
    real = [m for m in bench["per_layer"] if m["name"] in NEW]
    rehearsed = {m["name"]: m for m in run_py().metrics_of(
        tiny, "per_layer", TINY)}
    assert [m["name"] for m in real] == NEW
    for m in real:
        # a list the cell began begins with it; one it joined holds it after
        # the served cells. Either way a later cell follows by its name
        if m["name"] in JOINED:
            assert m["workloads"][:3] == [
                "served-1k.write", "served-1k.read90", CELL], m["name"]
        else:
            assert m["workloads"][:1] == [CELL], m["name"]
        assert all(m[k] == rehearsed[m["name"]][k] for k in keys), m["name"]


ROOT_FILE_RULES = [holds_the_cell_its_configuration_and_its_traffic,
                   holds_the_cells_metrics_to_the_tiny_cells]


def test_the_cell_its_configuration_and_its_traffic_resolve(bench, harness):
    held = holds_the_cell_its_configuration_and_its_traffic(bench, REPO)
    # and they are what a run of the cell loads
    assert harness.load_cell(bench, CELL, BENCH) == held


def test_the_cells_metrics_are_the_tiny_cells_metrics(bench):
    holds_the_cells_metrics_to_the_tiny_cells(bench, REPO)
