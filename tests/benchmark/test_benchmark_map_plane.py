"""The map plane rehearsed on the CPU at a tiny size (8 maps of 64 keys in an
engine of capacity 8 whose map table has two buckets): the contract's line with
``correct: true``, every per-layer metric a CPU run can read, each check (a) to
(h) seen when what it guards is broken underneath, both faults ``correct:
false``; the warm-up that ends on calls as well as on quiet, with the rule it
replaced as its control; the reference on a hand-written history; the
roofline's reducer on a made-up trace; what the root ``BENCHMARK.json`` names
for the plane resolves.
Sizes come from ``tests/benchmark/data_map``, never from the cell's own files.
No number from here is a device number.
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
DATA = os.path.join(REPO, "tests", "benchmark", "data_map")
TINY, CELL, CONFIG = "map-tiny.putget50-tiny", "map-1kx10k.putget50", \
    "map-1kx10k"
#: the cell's metrics that this file holds, in the root file's order. A metric
#: on the cell that is not named here is a later PR's and brings a test and a
#: tiny data directory of its own
#: (PR 53 folded the cell's copies of a layer's reading into the entry that
#: gives it: the cell joined those lists by its name, and the names here are
#: the kept ones)
NEW = ["client.ack_p50_ms", "client.ack_p99_ms", "engine.rounds_per_kop",
       "device.idle_share.served", "client.stage_ms",
       "runtime.d2h_bytes_per_op", "engine.read_eval_ms",
       "engine.query_drives_per_kop", "map.commit_ms",
       "map.chain_ops_per_kop", "map.lookup_roofline"]
#: what the source states, and the deployment may not cut
STATED = {"maps": 1000, "keys_per_map": 10000, "capacity": 1024, "peers": 3,
          "map_slots": 16384, "other_pool_slots": 0, "consistency": "ATOMIC"}

pytest.importorskip("jax")
sys.path.insert(0, REPO)


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.cache
def run_py():
    return load(os.path.join(BENCH, "run.py"), "benchmark_run_map")


@pytest.fixture(scope="module")
def harness():
    return run_py()


@pytest.fixture(scope="module")
def tiny():
    return json.load(open(os.path.join(DATA, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def bench():
    return json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def drive(harness, capsys, trace=False, fault=None, seed=2**31 + 35,
          data_root=DATA):
    line, checks, out, _ = drive_err(harness, capsys, trace, fault, seed,
                                     data_root)
    return line, checks, out


def drive_err(harness, capsys, trace=False, fault=None, seed=2**31 + 35,
              data_root=DATA):
    rc, line = harness.run_cell(
        TINY, seed, 1.0, trace, fault,
        bench_file=os.path.join(DATA, "BENCHMARK.json"), data_root=data_root,
        require_tpu=False)
    assert rc == 0
    json.dumps(line)                       # the line is plain JSON
    out, err = capsys.readouterr()
    # each number compared, beside its limit, as the last lines of stderr
    checks = {text[text.index("("):text.index(")") + 1]:
              int(text.rsplit(": ", 1)[1].split(" ")[0])
              for text in err.splitlines() if "map plane: check:" in text}
    assert err.rstrip().splitlines()[-1].startswith("map plane: check: (h)")
    assert list(checks) == [f"({c})" for c in "abcdefgh"]
    return line, checks, out, err


def seen(checks):
    return {c for c, v in checks.items() if v}


def test_cell_prints_the_contracts_line_and_is_correct(harness, capsys):
    line, checks, out = drive(harness, capsys)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0, out
    assert not seen(checks)
    assert set(line["metrics"]) == {"served_ops_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # what the deployment is, said at its start, and what the load cost
    assert "map table 512 slots = 2 buckets a replica" in out
    assert "loaded 512 keys through DistributedMap.put" in out
    assert "0 through a generator" in out


def test_a_traced_run_prints_the_cells_metrics(harness, tiny, capsys):
    line, checks, out = drive(harness, capsys, trace=True)
    assert line["correct"] is True and not seen(checks), out
    wanted = {m["name"]: m for m in harness.metrics_of(tiny, "per_layer",
                                                       TINY)}
    assert list(wanted) == NEW
    # the roofline needs a device's peak; the CPU has none in peaks.json
    assert set(wanted) - set(line["metrics"]) == {"map.lookup_roofline"}
    for name, got in line["metrics"].items():
        assert got["unit"] == wanted[name]["unit"]
        assert isinstance(got["value"], float) and got["value"] >= 0, name
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["map.chain_ops_per_kop"] == 0
    assert got["engine.rounds_per_kop"] > 0
    assert got["engine.query_drives_per_kop"] > 0
    assert got["map.commit_ms"] > 0 < got["engine.read_eval_ms"]
    assert got["client.ack_p99_ms"] >= got["client.ack_p50_ms"] > 0


@pytest.mark.parametrize("fault,check", [("flip-result", "(a)"),
                                         ("drop-ack", "(b)")])
def test_a_fault_in_the_harness_gives_correct_false(harness, capsys, fault,
                                                    check):
    line, checks, _ = drive(harness, capsys, fault=fault)
    assert line["correct"] is False
    assert seen(checks) == {check} and checks[check] == 1


def test_a_reply_and_a_stored_value_that_differ_are_seen(harness, capsys,
                                                         monkeypatch):
    """Broken underneath the harness: the session hands back one put's
    previous value one too high (a); the device holds another value under
    one key than the put that was acknowledged (b)."""
    from copycat_tpu.collections import DistributedMap
    from copycat_tpu.manager.device_executor import DeviceMapState

    state = {"puts": 0, "stores": 0}
    real_put = DistributedMap.put

    async def put(self, key, value, ttl=None):
        got = await real_put(self, key, value, ttl)
        state["puts"] += 1
        return got + 1 if state["puts"] == 700 else got

    monkeypatch.setattr(DistributedMap, "put", put)
    real_spec = DeviceMapState.vector_spec

    def vector_spec(self, operation):
        spec = real_spec(self, operation)
        if spec is not None and spec[4] == 4:          # a put
            state["stores"] += 1
            if state["stores"] == 800:
                return (spec[0], spec[1], spec[2] ^ 1, spec[3], spec[4])
        return spec

    monkeypatch.setattr(DeviceMapState, "vector_spec", vector_spec)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False, out
    assert seen(checks) <= {"(a)", "(b)"} and checks["(a)"] >= 1
    # the altered value is read back, unless a later put covered it: then
    # that put's reply, the previous value, gave it away under (a)
    assert checks["(b)"] == 1 or checks["(a)"] == 2


@pytest.mark.parametrize("keys, wanted", [((-1, 0), "(c)"), ((0, 1), "(d)")],
                         ids=["device-count", "host-shadow"])
def test_a_key_missing_on_the_device_or_held_on_the_host_is_seen(
        harness, capsys, monkeypatch, keys, wanted):
    from copycat_tpu.manager.device_executor import DeviceEngine

    real = DeviceEngine.map_keys

    def map_keys(self):
        on_device, shadowed = real(self)
        return on_device + keys[0], shadowed + keys[1]

    monkeypatch.setattr(DeviceEngine, "map_keys", map_keys)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False and seen(checks) == {wanted}


def test_puts_that_leave_the_vector_lane_are_seen(harness, capsys,
                                                  monkeypatch):
    """Every put through its generator handler: still right, and counted."""
    from copycat_tpu.manager.device_executor import DeviceMapState

    monkeypatch.setattr(DeviceMapState, "vector_spec",
                        lambda self, operation: None)
    line, checks, out = drive(harness, capsys)
    assert line["correct"] is False
    assert seen(checks) == {"(e)"} and checks["(e)"] > 100, out


def test_a_map_that_fell_to_the_cpu_machines_is_seen(harness, capsys,
                                                     monkeypatch):
    from copycat_tpu.manager.device_executor import DeviceEngine

    real = DeviceEngine.allocate
    monkeypatch.setattr(
        DeviceEngine, "allocate",
        lambda self: None if self._next_group >= 7 else real(self))
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False
    # its 64 keys are then not on the device either
    assert seen(checks) == {"(c)", "(f)"}
    assert (checks["(c)"], checks["(f)"]) == (64, 1)


def test_a_call_that_raises_is_seen(harness, capsys, monkeypatch):
    from copycat_tpu.collections import DistributedMap

    state = {"gets": 0}
    real_get = DistributedMap.get

    async def get(self, key):
        state["gets"] += 1
        if state["gets"] == 300:
            raise RuntimeError("the 300th get is lost")
        return await real_get(self, key)

    monkeypatch.setattr(DistributedMap, "get", get)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False and line["failed"] == 1
    assert seen(checks) == {"(g)"}


def test_a_compilation_inside_the_window_is_seen(harness, capsys,
                                                 monkeypatch):
    """A program the warm-up never ran, compiled by the first put that comes
    0.7 s after the collection that ends warm-up (the window opens 0.5 s
    after it)."""
    import time

    import jax
    import jax.numpy as jnp

    from copycat_tpu.collections import DistributedMap

    state = {"due": None}
    real_tune = harness.Context.gc_tune

    def gc_tune():
        real_tune()
        state["due"] = time.perf_counter() + 0.7

    monkeypatch.setattr(harness.Context, "gc_tune", staticmethod(gc_tune))
    real_put = DistributedMap.put

    async def put(self, key, value, ttl=None):
        if state["due"] and time.perf_counter() > state["due"]:
            state["due"] = None
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(911)).block_until_ready()
        return await real_put(self, key, value, ttl)

    monkeypatch.setattr(DistributedMap, "put", put)
    line, checks, _ = drive(harness, capsys)
    assert line["correct"] is False and seen(checks) == {"(h)"}


# -- the warm-up: quiet in calls as well as in seconds ---------------------------

def plane(harness):
    return harness.load_module("planes", "map", DATA)


def mix_with(tmp_path, **keys):
    """A data root whose only file is the twin's traffic mix with ``keys``
    set; the harness finds everything else where it did."""
    mix = json.load(open(os.path.join(DATA, "traffic", "putget50-tiny.json")))
    mix.update(keys)
    os.makedirs(tmp_path / "traffic")
    with open(tmp_path / "traffic" / "putget50-tiny.json", "w") as f:
        json.dump(mix, f)
    return str(tmp_path)


#: the case's floor a client; with 0 the rule is the one it replaced, quiet in
#: seconds alone, which is the control
HELD = {"seconds-alone": 0, "calls-too": 600}


@pytest.mark.parametrize("floor", HELD.values(), ids=HELD.keys())
def test_a_held_loop_does_not_pass_for_a_quiet_one(harness, capsys, tmp_path,
                                                   monkeypatch, floor):
    """The cell's fault at the twin's size. A tenth of a second after the
    first client task started, one call holds the event loop for longer than
    ``warmup_quiet_s`` (on the chip: the collector over the load's heap); the
    first call that comes 0.8 s after the hold, the k-th, runs a program that
    nothing has compiled yet (on the chip: the read window's second program;
    by the clock and not by a count, so that it falls into the window the
    control opens, whatever the machine's pace). Quiet in seconds alone
    ends the warm-up inside the hold, opens the window and prints (h); with
    ``k`` under the floor in calls the warm-up goes on past that program and
    every check holds."""
    import time

    import jax
    import jax.numpy as jnp

    from copycat_tpu.collections import DistributedMap

    spies = []

    class Spy(harness.Compiles):
        def __init__(self):
            super().__init__()
            spies.append(self)

    monkeypatch.setattr(harness, "Compiles", Spy)
    config = json.load(open(os.path.join(DATA, "configs", "map-tiny.json")))
    load = config["maps"] * config["preloaded_keys_per_map"]
    state = {"puts": 0, "began": None, "held": None, "calls": 0, "k": None}

    def before_a_call():
        if state["puts"] <= load:           # the load's puts
            return
        now = time.perf_counter()
        state["began"] = state["began"] or now
        if state["held"] is None:
            # a tenth of a second into the traffic, whatever this process
            # still had to compile for it done: the quiet is not yet over
            if now - state["began"] >= 0.1 and spies[0].quiet_for() >= 0.1:
                time.sleep(0.5)
                state["held"] = time.perf_counter()
            return
        state["calls"] += 1
        if state["k"] is None and now - state["held"] >= 0.8:
            state["k"] = state["calls"]
            jax.jit(lambda x: x * 5 + 2)(jnp.arange(733)).block_until_ready()

    real_put, real_get = DistributedMap.put, DistributedMap.get

    async def put(self, key, value, ttl=None):
        state["puts"] += 1
        before_a_call()
        return await real_put(self, key, value, ttl)

    async def get(self, key):
        before_a_call()
        return await real_get(self, key)

    monkeypatch.setattr(DistributedMap, "put", put)
    monkeypatch.setattr(DistributedMap, "get", get)
    root = mix_with(tmp_path, warmup_quiet_calls_per_client=floor)
    line, checks, out, err = drive_err(harness, capsys, data_root=root)
    clients = config["maps"]
    assert state["k"] is not None, out
    warm = next(t for t in out.splitlines() if "map plane: warm-up" in t)
    since = int(warm.split(" calls, ")[1].split(" ")[0].replace(",", ""))
    if not floor:
        assert line["correct"] is False and seen(checks) == {"(h)"}, out
        # the record of such a run: when, which program, then the checks
        events = [t for t in err.splitlines() if "compile events inside" in t]
        assert len(events) == 1 and " +" in events[0], err
        assert "jit__lambda" in events[0] or "<lambda>" in events[0], err
        assert err.index(events[0]) < err.index("map plane: check: (a)")
    else:
        assert state["k"] < floor * clients, (state, out)
        assert line["correct"] is True and not seen(checks), out
        assert since >= floor * clients, warm
        assert "compile events inside" not in err


def test_a_warm_up_that_never_goes_quiet_ends_in_the_deadlines_error(
        harness, monkeypatch):
    """A program compiled every tenth of a second: no quiet, and after the
    deadline an error that says how far the calls got."""
    import time

    import jax
    import jax.numpy as jnp

    from copycat_tpu.collections import DistributedMap

    monkeypatch.setattr(plane(harness), "DEADLINE_S", 1.5)
    state = {"next": 0.0, "n": 0}
    real_get = DistributedMap.get

    async def get(self, key):
        if time.perf_counter() >= state["next"]:
            state["n"] += 1
            n = state["n"]
            jax.jit(lambda x: x + n)(jnp.arange(7)).block_until_ready()
            state["next"] = time.perf_counter() + 0.1
        return await real_get(self, key)

    monkeypatch.setattr(DistributedMap, "get", get)
    with pytest.raises(RuntimeError, match=(
            r"warm-up not over after 2 s: [\d,]+ calls, [\d,]+ of the 80 it "
            r"takes since the last program; so far \d+ programs")):
        harness.run_cell(TINY, 3, 0.2, False,
                         bench_file=os.path.join(DATA, "BENCHMARK.json"),
                         data_root=DATA, require_tpu=False)


@pytest.mark.parametrize("path, clients, per_client", [
    (os.path.join(BENCH, "traffic", "putget50.json"), 1000, 20),
    (os.path.join(DATA, "traffic", "putget50-tiny.json"), 8, 10),
    (None, 1000, 20)], ids=["the-cell", "the-twin", "no-key"])
def test_the_floor_in_calls_is_read_from_the_traffic_file(harness, path,
                                                          clients, per_client):
    """``warmup_quiet_calls_per_client`` times the clients; a mix without
    the key takes the default, which is the cell's."""
    mod = plane(harness)
    if path is None:
        mix = json.load(open(os.path.join(BENCH, "traffic", "putget50.json")))
        del mix["warmup_quiet_calls_per_client"]
        assert mod.QUIET_CALLS_PER_CLIENT == per_client
    else:
        mix = json.load(open(path))
        assert mix["warmup_quiet_calls_per_client"] == per_client
    assert mix["clients"] == clients
    assert mod.quiet_calls(mix) == clients * per_client


# -- the reference, the keys and the reducer ------------------------------------

def test_the_plain_maps_on_a_hand_written_history():
    from benchmarks import reference_map as ref

    maps = ref.PlainMaps(2)
    assert maps.put(0, 7, 0) is None            # a stored 0 is not absent
    assert maps.get(0, 7) == 0 and maps.get(1, 7) is None
    assert maps.put(0, 7, 5) == 0 and maps.put(0, 7, 6) == 5
    assert maps.put_if_absent(0, 7, 9) == 6 and maps.get(0, 7) == 6
    assert maps.put_if_absent(0, 8, 9) is None and maps.get(0, 8) == 9
    assert maps.replace(0, 9, 1) is None and not maps.contains_key(0, 9)
    assert maps.replace(0, 8, 1) == 9
    assert maps.get_or_default(0, 9, -1) == -1
    assert (maps.size(0), maps.size(1), maps.total()) == (2, 0, 2)
    assert maps.remove(0, 7) == 6 and maps.remove(0, 7) is None
    assert maps.is_empty(1) and not maps.is_empty(0)


def test_the_keys_are_distinct_sparse_and_fit_their_buckets():
    """FNV-1a by hand for two ordinals; 10,000 distinct keys a map; and, by
    the program's own bucket function, no bucket of the first maps' key sets
    holds more than its 256 slots (the run's check (d) holds all 1,000)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_map as ref
    from copycat_tpu.ops import apply as ap

    def fnv(n):
        h = 0x811C9DC5
        for byte in n.to_bytes(8, "little"):
            h = ((h ^ byte) * 0x01000193) & 0xFFFFFFFF
        return h & 0x7FFFFFFF

    assert ref.fnv1a31(np.asarray([0, 123456789])).tolist() == [
        fnv(0), fnv(123456789)]
    for m in (0, 1, 999):
        keys = ref.keys_of(m, 10000, 10000)
        assert len(set(keys.tolist())) == 10000 and keys.max() > 2**30
        buckets = np.asarray(ap.map_bucket(jnp.asarray(keys, jnp.int32), 64))
        assert np.bincount(buckets, minlength=64).max() <= ap.MAP_BUCKET
    ranks = ref.zipf_ranks(np.random.default_rng(1), 10000, 0.99, 50000)
    share = np.bincount(ranks, minlength=10000) / 50000
    assert 0.08 < share[0] < 0.12 and share[0] > 1.8 * share[1] > 0.03


def test_the_roofline_counts_buckets_and_never_the_table():
    red = load(os.path.join(BENCH, "reducers", "map_lookup_roofline.py"),
               "map_lookup_roofline")
    spec = json.load(open(os.path.join(
        BENCH, "layer_metrics", "map.lookup_roofline.json")))
    clock = {"traced_commands": 1000, "traced_queries": 3000, "replicas": 3,
             "bucket_bytes": 4096, "other_state_bytes": 1_000_000,
             "programs": ["jit_round", "jit_query"],
             "round_program": "jit_round"}
    least = (2 * 3 * 1000 + 3000) * 4096
    assert red.least_bytes(1000, 3000, 3, 4096) == least
    # the names as the trace gives them (PR 53; the plane had "jit_round_",
    # which matched no round that ran alone): a round, a query that ran
    # alone, and a round a window's query rode (PR 36), which is a round too
    trace = {"modules": [["jit_round(1)", 0.0, 1e6], ["jit_query(2)", 2e6, 5e5],
                         ["jit_round_query(4)", 4e6, 1e6],
                         ["jit_other(3)", 6e6, 9e9]],
             "device_ops": [["fusion.3", 0.002]]}
    sources = {"clock": clock, "trace": trace,
               "peaks": {"hbm_bytes_per_s": 819e9}}
    # two rounds and a query, 2.5 ms; each round also moves the other state
    want = 100.0 * (least + 2 * 1_000_000 * 2) / 819e9 / 2.5e-3
    assert red.reduce(sources, spec) == pytest.approx(want)
    assert 0 < want < 100
    # a kernel of the lookup's name is timed alone, and the state not added
    trace["device_ops"].append(["map_lookup.1", 1e-4])
    assert red.reduce(sources, spec) == pytest.approx(
        100.0 * least / 819e9 / 1e-4)
    # and the plane hands the reducer the names the trace has
    plane = open(os.path.join(BENCH, "planes", "map.py")).read()
    assert '"round_program": "jit_round",' in plane
    assert '"programs": ["jit_round", "jit_query", "jit_fused"]' in plane
    # nothing to read: no trace, no peak (a CPU run), no traced operation
    assert red.reduce({**sources, "trace": {}}, spec) is None
    assert red.reduce({**sources, "peaks": {}}, spec) is None
    assert red.reduce({**sources, "clock": {
        **clock, "traced_commands": 0, "traced_queries": 0}}, spec) is None
    assert red.reduce({**sources, "clock": {}}, spec) is None


# -- the root file's lists: plain functions of (bench, root), see
# -- ROOT_FILE_RULES in test_benchmark_harness.py

def holds_the_map_cells_entries(bench, root):
    """This PR's entries, by name: the configuration at the widths its source
    states, the one-chip cell under ``served_ops_per_s``, and the eleven
    metrics, each on that cell alone and in this order among themselves."""
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmarks/configs/map-1kx10k.json"
    assert "BASELINE.json configs[2]" in config["source"]
    assert "YCSB" in config["source"]
    held = json.load(open(os.path.join(root, config["file"])))
    assert {k: held[k] for k in STATED} == STATED
    assert set(config["reduced"]) <= {"members", "wire_delay_ms",
                                      "preloaded_keys_per_map"}
    cut = "preloaded_keys_per_map" in config["reduced"]
    assert (held["preloaded_keys_per_map"] < held["keys_per_map"]) == cut
    assert held["preloaded_keys_per_map"] % 1000 == 0
    assert any("none in the host shadow" in g for g in held["guarantees"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "putget50", 1)
    mix = json.load(open(os.path.join(
        root, "benchmarks", "traffic", "putget50.json")))
    assert (mix["plane"], mix["clients"], mix["read_share"],
            mix["zipfian_constant"]) == ("map", 1000, 0.5, 0.99)
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
               if m["name"].startswith("map."))
    assert all(m["moves"] == "served_ops_per_s" for m in mine)
    # no accepted roofline is pointed at the cell: 2 x the whole state over
    # a round that moves buckets would read several hundred percent
    assert all(CELL not in m.get("workloads", []) for m in bench["per_layer"]
               if m["name"].endswith("roofline")
               and m["name"] != "map.lookup_roofline")


def holds_the_twins_entries_to_the_cells(bench, root):
    """``data_map``'s entries are the root file's for the names above."""
    twin = json.load(open(os.path.join(
        root, "tests", "benchmark", "data_map", "BENCHMARK.json")))
    keys = ("name", "unit", "better", "source", "layer", "moves")
    real = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert [{k: m[k] for k in keys} for m in twin["per_layer"]] == [
        {k: real[n][k] for k in keys} for n in NEW]
    for e in twin["end_to_end"]:
        root_e = next(r for r in bench["end_to_end"] if r["name"] == e["name"])
        assert all(e[k] == root_e[k] for k in e)


ROOT_FILE_RULES = [holds_the_map_cells_entries,
                   holds_the_twins_entries_to_the_cells]


def test_the_map_cells_entries_are_in_the_root_file(bench):
    holds_the_map_cells_entries(bench, REPO)
    assert run_py().metrics_of(bench, "end_to_end", CELL) == [
        m for m in bench["end_to_end"]
        if m["name"] in ("served_ops_per_s", "setup_s")]


def test_the_twin_reads_what_the_cell_reads(bench):
    holds_the_twins_entries_to_the_cells(bench, REPO)


def test_a_program_without_the_bucketed_table_fails_at_once(harness,
                                                            monkeypatch):
    """The parent of the PR that added the cell cannot hold it: the plane
    leaves with a message before it builds anything, and does not load ten
    million keys at a put a round trip."""
    from copycat_tpu.ops import apply as ap

    monkeypatch.delattr(ap, "map_buckets")
    with pytest.raises(SystemExit, match="no bucketed map table"):
        harness.run_cell(TINY, 1, 0.2, False,
                         bench_file=os.path.join(DATA, "BENCHMARK.json"),
                         data_root=DATA, require_tpu=False)
