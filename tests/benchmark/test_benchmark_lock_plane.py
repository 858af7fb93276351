"""The lock plane rehearsed on the CPU at a tiny size (16 locks raced by 2
sessions in the served tests' engine shape): the contract's line with
``correct: true``, every per-layer metric a CPU run can read, each check (a)
to (g) seen when what it guards is broken underneath, both faults ``correct:
false``; the reference on a hand-written history; what the root
``BENCHMARK.json`` names for the plane resolves. Sizes come from
``tests/benchmark/data_lock``, never from the cell's own files. No number from
here is a device number.
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
DATA = os.path.join(REPO, "tests", "benchmark", "data_lock")
TINY, CELL, CONFIG = "lock-tiny.contend2-tiny", "lock-10kx3.contend4", \
    "lock-10kx3"
#: the cell's metrics that this file holds, in the root file's order. A metric
#: on the cell that is not named here is a later PR's and brings a test and a
#: tiny data directory of its own
#: (PR 53 folded the cell's copies of a layer's reading into the entry that
#: gives it: the cell joined those lists by its name, and the names here are
#: the kept ones)
NEW = ["client.ack_p50_ms", "engine.rounds_per_kop",
       "device.idle_share.served", "runtime.fetches_per_kop",
       "runtime.d2h_bytes_per_op", "lock.grant_p50_ms", "lock.grant_p99_ms",
       "event.handoff_p50_ms", "engine.apply_ms.served", "event.seal_ms",
       "event.push_ms", "lock.chain_ops_per_kop", "lock.publishes_per_kop",
       "lock.events_per_publish", "step.round_roofline"]
#: what the source states, and the deployment may not cut
STATED = {"locks": 10000, "capacity": 10240, "peers": 3, "sessions": 4,
          "contenders_per_lock": 4, "hold_ms": 0, "wait_slots": 8,
          "event_slots": 32, "other_pool_slots": 0,
          "consistency": "LINEARIZABLE"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_lock")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def tiny():
    return json.load(open(os.path.join(DATA, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def drive(harness, capsys, trace=False, fault=None, seed=2**31 + 40):
    rc, line = harness.run_cell(
        TINY, seed, 0.8, trace, fault,
        bench_file=os.path.join(DATA, "BENCHMARK.json"), data_root=DATA,
        require_tpu=False)
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    out, err = capsys.readouterr()
    # each number compared, beside its limit, as the last lines of stderr
    checks = {text[text.index("("):text.index(")") + 1]:
              int(text.rsplit(": ", 1)[1].split(" ")[0])
              for text in err.splitlines() if "lock plane: check:" in text}
    assert err.rstrip().splitlines()[-1].startswith("lock plane: check: (g)")
    assert list(checks) == [f"({c})" for c in "abcdefg"]
    return line, checks, out


def seen(checks):
    return {c for c, v in checks.items() if v}


def test_cell_prints_the_contracts_line_and_is_correct(harness, capsys):
    line, checks, out = drive(harness, capsys)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0, out
    assert not seen(checks)
    assert set(line["metrics"]) == {"served_ops_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # what the deployment is, said at its start, and the window's cycles
    assert "16 locks, 2 sessions, 32 instances created" in out
    assert "every lock was handed over at least" in out
    assert "0 through a generator" in out


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
    assert got["lock.chain_ops_per_kop"] == 0
    assert got["engine.rounds_per_kop"] > 0 < got["runtime.fetches_per_kop"]
    assert got["engine.apply_ms.served"] > 0 < got["event.push_ms"]
    assert got["event.seal_ms"] > 0
    assert got["lock.grant_p99_ms"] >= got["lock.grant_p50_ms"] > 0
    assert got["event.handoff_p50_ms"] > 0 < got["client.ack_p50_ms"]
    # every grant an event, several to a request
    assert got["lock.events_per_publish"] >= 1
    assert 0 < got["lock.publishes_per_kop"] <= 500
    # the new spans and counters are in the tracer's report
    from copycat_tpu.utils.tracing import TRACER
    report = TRACER.report()
    assert {"event.seal", "event.push", "client.event", "apply"} <= set(
        report["spans"])
    counters = report["counters"]
    assert counters["engine.lock_vector_ops"] > 0 == counters[
        "engine.lock_chain_ops"]
    grants = counters["group.events.published"]
    # a grant is an event of the device's ring where a waiter stood in it,
    # else the Lock command's own (two contenders a lock drift apart)
    assert grants >= counters["engine.events_ingested"] >= 0
    assert grants >= counters["client.events_received"] > 0.9 * grants
    assert counters["group.events.sealed"] == grants  # a batch an entry
    assert 0 < counters["group.events.publish_requests"] <= grants


@pytest.mark.parametrize("fault", ["flip-result", "drop-ack"])
def test_a_fault_in_the_harness_gives_correct_false(harness, capsys, fault):
    line, checks, _ = drive(harness, capsys, fault=fault)
    assert line["correct"] is False
    # a flipped reply is an id the plain lock grants and no client saw, a
    # dropped unlock a release the plain lock never got: either way the
    # plain lock is still held after the quiesce, so it is not free either
    assert seen(checks) == {"(a)", "(c)"}


def test_two_holders_at_once_are_seen(harness, capsys, monkeypatch):
    """A lock that grants every acquire at once (the device's holder never
    set): two contenders hold it together (b), and the grants are not the
    plain lock's (a)."""
    from copycat_tpu.manager.device_executor import DeviceLockState

    real = DeviceLockState.vector_finalize
    state = {"n": 0}

    def vector_finalize(self, kind, operation, raw, commit):
        result = real(self, kind, operation, raw, commit)
        state["n"] += 1
        if kind == 8 and raw == 2 and state["n"] > 200 \
                and not state.get("done"):
            # a queued waiter is told it holds the lock
            state["done"] = True
            commit.session.publish(
                "lock", {"id": commit.index, "acquired": True})
        return result

    monkeypatch.setattr(DeviceLockState, "vector_finalize", vector_finalize)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False, out
    assert "(b)" in seen(checks) or "(a)" in seen(checks)


def test_a_lock_left_held_is_seen(harness, capsys, monkeypatch):
    """The quiesce finds one lock that its last holder never let go: the
    device's holder is set and a fresh instance's try_lock() is refused."""
    from copycat_tpu.coordination import DistributedLock

    state = {"n": 0}
    real = DistributedLock.unlock

    async def unlock(self):
        state["n"] += 1
        if state["n"] == 500:
            return None                   # acknowledged, never sent
        return await real(self)

    monkeypatch.setattr(DistributedLock, "unlock", unlock)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False, out
    assert "(c)" in seen(checks) and checks["(c)"] >= 2


def test_a_lock_that_fell_to_the_cpu_machines_is_seen(harness, capsys,
                                                     monkeypatch):
    from copycat_tpu.manager.device_executor import DeviceEngine

    real = DeviceEngine.allocate
    monkeypatch.setattr(
        DeviceEngine, "allocate",
        lambda self: None if self._next_group >= 15 else real(self))
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False
    assert seen(checks) == {"(d)"} and checks["(d)"] == 1


def test_commands_that_leave_the_vector_lane_are_seen(harness, capsys,
                                                     monkeypatch):
    """Every Lock and Unlock through its generator handler: still right, and
    counted."""
    from copycat_tpu.manager.device_executor import DeviceLockState

    monkeypatch.setattr(DeviceLockState, "vector_spec",
                        lambda self, operation, index, session: None)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False
    assert seen(checks) == {"(e)"} and checks["(e)"] > 50, out


def test_a_call_that_raises_is_seen(harness, capsys, monkeypatch):
    from copycat_tpu.coordination import DistributedLock

    state = {"n": 0}
    real = DistributedLock.unlock

    async def unlock(self):
        state["n"] += 1
        if state["n"] == 300:
            raise RuntimeError("the 300th unlock is lost")
        return await real(self)

    monkeypatch.setattr(DistributedLock, "unlock", unlock)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False and line["failed"] >= 1
    assert "(f)" in seen(checks)


def test_a_compilation_inside_the_window_is_seen(harness, capsys,
                                                 monkeypatch):
    """A program the warm-up never ran, compiled by the first unlock that
    comes 0.7 s after the collection that ends warm-up (the window opens
    0.5 s after it)."""
    import time

    import jax
    import jax.numpy as jnp

    from copycat_tpu.coordination import DistributedLock

    state = {"due": None}
    real_tune = harness.Context.gc_tune

    def gc_tune():
        real_tune()
        state["due"] = time.perf_counter() + 0.7

    monkeypatch.setattr(harness.Context, "gc_tune", staticmethod(gc_tune))
    real = DistributedLock.unlock

    async def unlock(self):
        if state["due"] and time.perf_counter() > state["due"]:
            state["due"] = None
            jax.jit(lambda x: x * 5 + 2)(jnp.arange(907)).block_until_ready()
        return await real(self)

    monkeypatch.setattr(DistributedLock, "unlock", unlock)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False and seen(checks) == {"(g)"}


# -- the reference --------------------------------------------------------------

def test_the_plain_locks_on_a_hand_written_history():
    from benchmarks import reference_lock as ref

    locks = ref.PlainLocks(2)
    assert locks.acquire(0, 11) is True           # free: granted at once
    assert locks.acquire(0, 12) is None           # held: queued
    assert locks.acquire(0, 13, wait=False) is False   # a try-lock: refused
    assert locks.acquire(0, 14) is None
    assert (locks.holder(0), locks.waiting(0)) == (11, [12, 14])
    with pytest.raises(ValueError, match="12 released, 11 holds"):
        locks.release(0, 12)
    assert locks.release(0, 11) == 12 and locks.release(0, 12) == 14
    assert locks.release(0, 14) is None and locks.holder(0) is None
    assert locks.acquire(1, 5, wait=False) is True and locks.free() == 1
    # a lock's history replayed: ascending, none twice, none skipped,
    # whatever order the replies were noted in; a release by another than
    # the holder is counted and skipped
    model = ref.PlainLocks(1)
    assert ref.grant_order(model, 0, [7, 3, 9], [3, 7]) == ([3, 7, 9], 0)
    assert model.holder(0) == 9 and model.free() == 0
    model = ref.PlainLocks(1)
    assert ref.grant_order(model, 0, [3, 7, 9], [3, 9, 7]) == ([3, 7, 9], 1)


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_the_lock_cells_entries(bench, root):
    """This PR's entries, by name: the configuration at the width its source
    states, the one-chip cell under ``served_ops_per_s`` and no tail, and
    the fifteen metrics, each on that cell alone and in this order among
    themselves."""
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmarks/configs/lock-10kx3.json"
    assert "BASELINE.json configs[3]" in config["source"]
    assert "DistributedLock" in config["source"]
    held = json.load(open(os.path.join(root, config["file"])))
    assert {k: held[k] for k in STATED} == STATED
    assert set(config["reduced"]) <= {"members", "wire_delay_ms",
                                      "contenders_per_lock"}
    assert set(held["assumed"]) >= {"sessions", "contenders_per_lock",
                                    "hold_ms"}
    assert any("none in the host overflow" in g for g in held["guarantees"])
    assert any("at most one holder" in g for g in held["guarantees"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "contend4", 1)
    mix = json.load(open(os.path.join(
        root, "benchmarks", "traffic", "contend4.json")))
    assert (mix["plane"], mix["contenders"], mix["hold_ms"]) == (
        "lock", held["locks"] * held["contenders_per_lock"], 0)
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
               if m["name"].startswith("lock."))
    assert all(m["moves"] == "served_ops_per_s" for m in mine)


def holds_the_twins_entries_to_the_cells(bench, root):
    """``data_lock``'s entries are the root file's for the names above."""
    twin = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_lock", "BENCHMARK.json")))
    keys = ("name", "unit", "better", "source", "layer", "moves")
    real = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert [{k: m[k] for k in keys} for m in twin["per_layer"]] == [
        {k: real[n][k] for k in keys} for n in NEW]
    for e in twin["end_to_end"]:
        root_e = next(r for r in bench["end_to_end"] if r["name"] == e["name"])
        assert all(e[k] == root_e[k] for k in e)


ROOT_FILE_RULES = [holds_the_lock_cells_entries,
                   holds_the_twins_entries_to_the_cells]


def test_the_lock_cells_entries_are_in_the_root_file(bench):
    holds_the_lock_cells_entries(bench, REPO)
    assert run_py().metrics_of(bench, "end_to_end", CELL) == [
        m for m in bench["end_to_end"]
        if m["name"] in ("served_ops_per_s", "setup_s")]


def test_the_twin_reads_what_the_cell_reads(bench):
    holds_the_twins_entries_to_the_cells(bench, REPO)


def test_a_program_whose_lock_is_a_chain_fails_at_once(harness, monkeypatch):
    """The parent of the PR that added the cell cannot run it: the plane
    leaves with a message before it builds anything, and does not drive
    40,000 generator chains a cycle."""
    from copycat_tpu.manager.device_executor import DeviceLockState

    monkeypatch.delattr(DeviceLockState, "vector_spec")
    with pytest.raises(SystemExit, match="no vector_spec of its own"):
        harness.run_cell(TINY, 1, 0.2, False,
                         bench_file=os.path.join(DATA, "BENCHMARK.json"),
                         data_root=DATA, require_tpu=False)
