"""The election plane rehearsed on the CPU at a tiny size (20 elections raced
by 3 of 5 sessions each, a session killed every 2 s, in the served tests'
engine shape): the contract's line with ``correct: true``, every per-layer
metric a CPU run can read, a kill schedule that spans the warm-up, each check
(a) to (h) seen when what it guards is broken underneath, both faults
``correct: false``; what the root ``BENCHMARK.json`` names for the plane
resolves. Sizes come from ``tests/benchmark/data_election``, never from the
cell's own files. No number from here is a device number.
"""

import contextlib
import functools
import gc
import importlib.util
import io
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
DATA = os.path.join(REPO, "tests", "benchmark", "data_election")
TINY, CELL, CONFIG = "election-tiny.failover-tiny", \
    "election-1kx3.failover", "election-1kx3"
#: the cell's metrics that this file holds, in the root file's order. A metric
#: on the cell that is not named here is a later PR's and brings a test and a
#: tiny data directory of its own
#: (PR 53 folded the cell's copies of a layer's reading into the entry that
#: gives it: the cell joined those lists by its name, and the names here are
#: the kept ones)
NEW = ["client.ack_p50_ms", "engine.rounds_per_kop",
       "device.idle_share.served", "runtime.fetches_per_kop",
       "runtime.d2h_bytes_per_op", "event.handoff_p50_ms",
       "engine.apply_ms.served", "event.seal_ms", "event.push_ms",
       "step.round_roofline", "election.failover_p50_ms",
       "election.failover_max_ms", "election.expire_lag_ms",
       "election.session_end_ms", "election.end_rounds_per_session",
       "election.chain_ops_per_kop"]
#: what the source states, and the deployment may not cut
STATED = {"elections": 1000, "capacity": 1024, "peers": 3, "sessions": 10,
          "candidates_per_election": 3, "hold_ms": 0, "listener_slots": 8,
          "event_slots": 32, "other_pool_slots": 0,
          "session_timeout_s": 4.0, "consistency": "LINEARIZABLE"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_election")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def tiny():
    return json.load(open(os.path.join(DATA, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def drive(harness, capsys, trace=False, fault=None, seed=2**31 + 45):
    """One run of the tiny cell. A program that compiles for seconds while
    the sessions are open (the first run of a lane in this process, from a
    cold cache) starves them of their keep-alives and the plane exits in
    its set-up: such a run gets a second go, with the program compiled."""
    for attempt in (0, 1):
        # what earlier runs in this process froze out of collection is back
        # in it: collect now, not in the middle of a 2 s session timeout
        gc.collect()
        try:
            rc, line = harness.run_cell(
                TINY, seed, 2.6, trace, fault,
                bench_file=os.path.join(DATA, "BENCHMARK.json"),
                data_root=DATA, require_tpu=False)
        except SystemExit:
            if attempt:
                raise
            capsys.readouterr()
            continue
        break
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    out, err = capsys.readouterr()
    # each number compared, beside its limit, as the last lines of stderr
    checks = {text[text.index("("):text.index(")") + 1]:
              int(text.rsplit(": ", 1)[1].split(" ")[0])
              for text in err.splitlines()
              if "election plane: check:" in text}
    assert err.rstrip().splitlines()[-1].startswith(
        "election plane: check: (h)")
    assert list(checks) == [f"({c})" for c in "abcdefgh"]
    return line, checks, out


def seen(checks):
    return {c for c, v in checks.items() if v}


@pytest.fixture(scope="module")
def plain(harness):
    """One untraced run that several tests read."""
    class Capture:
        def __init__(self):
            self.out, self.err = io.StringIO(), io.StringIO()

        def readouterr(self):
            got = self.out.getvalue(), self.err.getvalue()
            self.out.seek(0), self.out.truncate()
            self.err.seek(0), self.err.truncate()
            return got

    cap = Capture()
    with contextlib.redirect_stdout(cap.out), \
            contextlib.redirect_stderr(cap.err):
        return drive(harness, cap)


def test_cell_prints_the_contracts_line_and_is_correct(plain):
    line, checks, out = plain
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0, out
    assert not seen(checks)
    assert set(line["metrics"]) == {"served_ops_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # what the deployment is, said at its start
    assert "20 elections, 5 sessions, 60 instances created" in out
    assert "(12 to 12 a session)" in out
    assert "0 through a generator" in out and "0 as chains" in out


def test_the_kill_schedule_spans_the_warm_up(plain):
    """The kill clock runs on one clock from the warm-up through the window:
    a session killed before the window opens expires inside it, one is
    killed inside it, and whoever was killed expired and was replaced."""
    _, _, out = plain
    kills = [float(m) for m in re.findall(r"killed at ([+-][0-9.]+)s", out)]
    ends = [float(m) for m in re.findall(r"its end applied at ([+-][0-9.]+)s",
                                         out)]
    assert len(kills) >= 3 and len(ends) == len(kills)
    assert kills == sorted(kills) and kills[0] < -2.0   # in the warm-up
    # one clock: kills 2 s apart, the window 1.5 s after one of them
    assert all(abs((b - a) - 2.0) < 0.3 for a, b in zip(kills, kills[1:])), \
        kills
    before = max(k for k in kills if k < 0)
    assert abs(before + 1.5) < 0.3
    assert any(0 < k < 2.6 for k in kills)              # and in the window
    # each expired a session timeout (2 s) after its last contact, and at the
    # window's first instant one killed session awaited its expiry
    assert all(1.8 <= e - k < 2.6 for k, e in zip(kills, ends))
    assert any(k < 0 < e for k, e in zip(kills, ends))
    assert re.search(r"sessions expired (\d+), killed \1\b", out)
    assert re.search(r"warm-up .* 1 expired and replaced", out)
    # every end one vector turn of one engine round
    assert set(re.findall(r"over (\d+) engine round", out)) == {"1"}
    assert "and 0 after" in out and " and 1 after" not in out


def test_the_fifths_and_the_hand_overs_are_printed(plain):
    _, _, out = plain
    (fifths,) = re.findall(r"operations/s by fifths of the window: ([^;]+);",
                           out)
    assert len(fifths.split(", ")) == 5
    assert re.search(r"voluntary hand-overs [\d,]+ \(\d+\.\d+ an election\), "
                     r"failovers \d+", out)
    assert "the loop's longest stall inside the window" in out


def test_a_traced_run_prints_the_cells_metrics(harness, tiny, capsys):
    line, checks, out = drive(harness, capsys, trace=True)
    assert line["correct"] is True and not seen(checks), out
    wanted = {m["name"]: m for m in harness.metrics_of(tiny, "per_layer",
                                                       TINY)}
    assert list(wanted) == NEW
    # the roofline needs a device's peak; the CPU has none in peaks.json
    assert set(wanted) - set(line["metrics"]) == {"step.round_roofline"}
    for name, got in line["metrics"].items():
        assert got["unit"] == wanted[name]["unit"]
        assert isinstance(got["value"], float) and got["value"] >= 0, name
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["election.chain_ops_per_kop"] == 0
    assert got["election.end_rounds_per_session"] == 1.0
    assert got["engine.rounds_per_kop"] > 0 < got["runtime.fetches_per_kop"]
    assert got["engine.apply_ms.served"] > 0 < got["event.push_ms"]
    assert got["event.seal_ms"] > 0 < got["election.session_end_ms"]
    assert got["election.failover_max_ms"] >= got["election.failover_p50_ms"] \
        >= 2000.0
    assert got["election.expire_lag_ms"] == pytest.approx(
        got["election.failover_p50_ms"] - 2000.0)
    assert got["event.handoff_p50_ms"] > 0 < got["client.ack_p50_ms"]
    assert "its stop took" in out and "on a thread beside it" in out
    # the new span and counters are in the tracer's report
    from copycat_tpu.utils.tracing import TRACER
    report = TRACER.report()
    assert {"session.end", "event.seal", "event.push", "client.event",
            "apply"} <= set(report["spans"])
    counters = report["counters"]
    assert counters["engine.elect_vector_ops"] > 0 == counters[
        "engine.elect_chain_ops"]
    assert counters["engine.session_end_vector_instances"] > 0 == counters[
        "engine.session_end_chain_instances"]
    assert counters["group.sessions_expired_total"] == report["spans"][
        "session.end"]["n"] >= 1
    elects = counters["group.events.published"]
    assert elects >= counters["client.events_received"] > 0.8 * elects
    # a batch an entry and session: a session's end seals its elects of one
    # session together
    assert 0.9 * elects < counters["group.events.sealed"] < elects


@pytest.mark.parametrize("fault,where", [("flip-result", "(b)"),
                                         ("drop-ack", "(a)")])
def test_a_fault_in_the_harness_gives_correct_false(harness, capsys, fault,
                                                    where):
    line, checks, _ = drive(harness, capsys, fault=fault)
    assert line["correct"] is False
    # a flipped is_leader is a just-elected candidate whose token was
    # refused; a resign dropped from the committed history is a leader the
    # plain election never let go, so its clients saw others it did not
    assert where in seen(checks)


def test_a_leader_nobody_made_and_a_stale_epoch_are_seen(harness, capsys,
                                                        monkeypatch):
    """A candidate that is told it leads while it waits in line: its
    clients saw a leader the plain election did not make (a). And an
    ``is_leader`` that answers for the leader and not for the epoch: the
    probes after the quiesce hand it an older epoch of each election (b)."""
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState

    real = DeviceLeaderElectionState.vector_finalize
    state = {"n": 0}

    def vector_finalize(self, kind, operation, raw, commit):
        result = real(self, kind, operation, raw, commit)
        state["n"] += 1
        if kind == 11 and raw == 0 and state["n"] > 400 \
                and not state.get("done"):
            state["done"] = True
            commit.session.publish("elect", 1 << 20)
        return result

    def is_leader(self, commit):
        try:
            return self._epoch is not None
        finally:
            commit.close()

    monkeypatch.setattr(DeviceLeaderElectionState, "vector_finalize",
                        vector_finalize)
    monkeypatch.setattr(DeviceLeaderElectionState, "is_leader", is_leader)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False, out
    assert {"(a)", "(b)"} <= seen(checks) and checks["(b)"] >= 10


def test_commands_and_session_ends_that_leave_the_vector_lane_are_seen(
        harness, capsys, monkeypatch):
    """Every listen and unlisten through its generator handler, and a
    session's end that closes its instances one chain after another: still
    right, counted, and an end takes more than one engine round."""
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState

    monkeypatch.setattr(DeviceLeaderElectionState, "vector_spec",
                        lambda self, operation, index, session: None)
    monkeypatch.setattr(DeviceLeaderElectionState, "close_spec",
                        lambda self, session: None)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False
    assert seen(checks) == {"(e)"} and checks["(e)"] > 50, out
    assert "0 instances in a vector turn" in out
    assert not re.search(r" 0 as chains", out)
    rounds = [int(n) for n in re.findall(r"over (\d+) engine round", out)]
    assert max(rounds) >= 3


def test_a_compilation_in_the_window_and_a_cpu_machine_are_seen(
        harness, capsys, monkeypatch):
    """A program the warm-up never ran, compiled by the first resign that
    comes 3 s after the collection that ends warm-up (the window opens 0.5
    to 2.5 s after it, at its place on the kill clock, and lasts 2.6 s): (g).
    And an engine that is full one election early, so that the last falls
    to the CPU machine: not on the device (d), nor in its arrays (c)."""
    import time

    import jax
    import jax.numpy as jnp

    from copycat_tpu.coordination import DistributedLeaderElection
    from copycat_tpu.manager.device_executor import DeviceEngine

    allocate = DeviceEngine.allocate
    monkeypatch.setattr(
        DeviceEngine, "allocate",
        lambda self: None if self._next_group >= 19 else allocate(self))
    state = {"due": None}
    real_tune = harness.Context.gc_tune

    def gc_tune():
        real_tune()
        state["due"] = time.perf_counter() + 3.0

    monkeypatch.setattr(harness.Context, "gc_tune", staticmethod(gc_tune))
    real = DistributedLeaderElection.resign

    async def resign(self):
        if state["due"] and time.perf_counter() > state["due"]:
            state["due"] = None
            jax.jit(lambda x: x * 7 + 3)(jnp.arange(911)).block_until_ready()
        return await real(self)

    monkeypatch.setattr(DistributedLeaderElection, "resign", resign)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False
    assert seen(checks) == {"(c)", "(d)", "(g)"} and checks["(d)"] == 1


def test_a_session_the_harness_left_alive_and_the_server_ended_is_seen(
        harness, capsys, monkeypatch):
    """The server expires a session that the harness had not killed (its
    detector appends an ``UnregisterEntry`` for the session heard from
    last, 3 s after the collection that ends warm-up: inside the window):
    an end that was not a kill (h)."""
    import time

    from copycat_tpu.server.log import UnregisterEntry
    from copycat_tpu.server.raft_group import RaftGroup
    from copycat_tpu.server.session import SessionState

    state = {"due": None, "lose": None}
    real_tune = harness.Context.gc_tune

    def gc_tune():
        real_tune()
        state["due"] = state["lose"] = time.perf_counter() + 3.0

    monkeypatch.setattr(harness.Context, "gc_tune", staticmethod(gc_tune))
    real = RaftGroup._leader_maintenance

    def _leader_maintenance(self):
        if state["due"] and time.perf_counter() > state["due"]:
            state["due"] = None
            live = [s for s in self.sessions.values()
                    if s.state is SessionState.OPEN
                    and s.id not in self._expiring_sessions
                    # not one the harness has just killed
                    and s.connection is not None and not s.connection.closed]
            victim = max(live, key=lambda s: s.last_contact)
            self._expiring_sessions.add(victim.id)
            self._append(UnregisterEntry(session_id=victim.id, expired=True))
        return real(self)

    monkeypatch.setattr(RaftGroup, "_leader_maintenance", _leader_maintenance)
    # and one resign of another session's is lost: a call that raised (f),
    # whatever the ended session's candidacies were doing
    from copycat_tpu.coordination import DistributedLeaderElection

    resign = DistributedLeaderElection.resign

    async def lossy(self):
        if state["lose"] and time.perf_counter() > state["lose"]:
            state["lose"] = None
            raise RuntimeError("this resign is lost")
        return await resign(self)

    monkeypatch.setattr(DistributedLeaderElection, "resign", lossy)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False and line["failed"] >= 1, out
    assert {"(f)", "(h)"} <= seen(checks)


def test_the_deal_gives_every_session_as_many_and_no_election_one_twice():
    import numpy as np

    plane = run_py().load_module("planes", "election", BENCH)
    for seed in (0, 45, 2**31 + 45):
        dealt = plane.deal(np.random.default_rng(seed), 1000, 10, 3)
        assert dealt.shape == (1000, 3)
        assert all(len(set(row)) == 3 for row in dealt.tolist())
        assert np.bincount(dealt.ravel(), minlength=10).tolist() == [300] * 10
    other = plane.deal(np.random.default_rng(1), 1000, 10, 3)
    assert (other != dealt).any()


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_the_election_cells_entries(bench, root):
    """This PR's entries, by name: the configuration at the width its source
    states, the one-chip cell under ``served_ops_per_s`` and no tail, and
    the sixteen metrics, each on that cell alone and in this order among
    themselves."""
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmarks/configs/election-1kx3.json"
    assert "BASELINE.json configs[1]" in config["source"]
    assert "DistributedLeaderElection" in config["source"]
    assert "testNextElection" in config["source"]
    held = json.load(open(os.path.join(root, config["file"])))
    assert {k: held[k] for k in STATED} == STATED
    assert set(config["reduced"]) == {"members", "wire_delay_ms"} \
        == set(held["reduced_from"])
    assert set(held["assumed"]) >= {"sessions", "candidates_per_election",
                                    "hold_ms", "session_timeout_s",
                                    "storage"}
    for words in ("at most one leader", "its epochs rise",
                  "in the order the listens committed", "exactly once",
                  "only for the current leader's epoch",
                  "never sooner than the session timeout",
                  "none in the host overflow", "as a generator chain"):
        assert any(words in g for g in held["guarantees"]), words
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "failover", 1)
    mix = json.load(open(os.path.join(
        root, "benchmarks", "traffic", "failover.json")))
    assert (mix["plane"], mix["candidacies"], mix["hold_ms"]) == (
        "election", held["elections"] * held["candidates_per_election"], 0)
    # a session dies every 4 s on one clock from the warm-up on, and the
    # window opens between two kills: 5 kills and 5 expiries in 20 s
    assert mix["kill_every_s"] == held["session_timeout_s"] == 4.0
    assert 0 < mix["window_opens_after_kill_s"] < mix["kill_every_s"]
    assert mix["warmup_expiries"] >= 1
    assert bench["run_seconds"] / mix["kill_every_s"] == 5
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "served_ops_per_s")
    assert CELL in rate["workloads"]
    tail = next(m for m in bench["end_to_end"] if m["name"] == "ack_p99_ms")
    assert CELL not in tail["workloads"]
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW
    # a list is held as a prefix: the cell's own begin with it, and it
    # joined a layer's reading after the cells that stood there
    assert all(CELL in m["workloads"] for m in mine)
    assert all(m["workloads"][:1] == [CELL] for m in mine
               if m["name"].startswith("election."))
    assert all(m["moves"] == "served_ops_per_s" for m in mine)


def holds_the_twins_entries_to_the_cells(bench, root):
    """``data_election``'s entries are the root file's for the names
    above."""
    twin = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_election", "BENCHMARK.json")))
    keys = ("name", "unit", "better", "source", "layer", "moves")
    real = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert [{k: m[k] for k in keys} for m in twin["per_layer"]] == [
        {k: real[n][k] for k in keys} for n in NEW]
    for e in twin["end_to_end"]:
        root_e = next(r for r in bench["end_to_end"] if r["name"] == e["name"])
        assert all(e[k] == root_e[k] for k in e)


ROOT_FILE_RULES = [holds_the_election_cells_entries,
                   holds_the_twins_entries_to_the_cells]


def test_the_election_cells_entries_are_in_the_root_file(bench):
    holds_the_election_cells_entries(bench, REPO)
    assert run_py().metrics_of(bench, "end_to_end", CELL) == [
        m for m in bench["end_to_end"]
        if m["name"] in ("served_ops_per_s", "setup_s")]


def test_the_twin_reads_what_the_cell_reads(bench):
    holds_the_twins_entries_to_the_cells(bench, REPO)


def test_every_metrics_file_is_there_and_says_what_it_reads(bench):
    for name in NEW:
        spec = json.load(open(os.path.join(
            BENCH, "layer_metrics", name + ".json")))
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert all(spec[k] == entry[k] for k in
                   ("name", "unit", "better", "layer", "source", "moves"))
        assert os.path.exists(os.path.join(
            BENCH, "reducers", spec["reducer"] + ".py"))
        assert len(spec["what"]) > 40


def test_a_program_whose_election_is_a_chain_fails_at_once(harness,
                                                           monkeypatch):
    """The parent of the PR that added the cell cannot run it: the plane
    leaves with a message before it builds anything, and does not drive
    3,000 generator chains a cycle."""
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState

    monkeypatch.delattr(DeviceLeaderElectionState, "vector_spec")
    with pytest.raises(SystemExit, match="no vector_spec of its own"):
        harness.run_cell(TINY, 1, 0.2, False,
                         bench_file=os.path.join(DATA, "BENCHMARK.json"),
                         data_root=DATA, require_tpu=False)
