"""A read window's query rides the round it waits for.

A read window that finds a vector run parked for its engine routes its reads
ahead of the run, and the run's round evaluates them on the state it writes:
one program call and one fetch where the round and the query program cost two
of each (``models/raft_groups.py``: ``_jitted_programs``' fourth program,
``step_round(query=)``, ``drive_vector(query=)``, ``stage_query_vector`` and
``finish_query_vector``; ``server/raft_group.py``: ``_evaluate_reads``,
``_route_ahead``). Nothing a client can see may differ from the two-step
order (the round, then the query program alone), which stays for every window
the joint call cannot serve. On the CPU, at ``tests/engines.py``'s shapes;
no number from here is a device number.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import (  # noqa: E402
    DistributedAtomicLong, DistributedAtomicValue)
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.io.local import (  # noqa: E402
    LocalServerRegistry, LocalTransport)
from copycat_tpu.manager import device_executor as dx  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.models.raft_groups import RaftGroups  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.ops.consensus import Config  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402
from copycat_tpu.server.raft import RaftServer  # noqa: E402

from helpers import arun, the_tick_after_the_window  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from test_round_signature import assert_same  # noqa: E402

from engines import (  # noqa: E402
    G, SERVED, SERVED_MAP, device_plane, wide_map)

COUNTED = ("rounds", "fetches", "query_vector_drives", "query_joined_drives",
           "query_settle_rounds", "dispatch_leaves")


def counted(rg: RaftGroups) -> dict:
    values = rg.metrics.counter_values()
    return {name: values.get(name, 0) for name in COUNTED}


def delta(rg: RaftGroups, before: dict) -> dict:
    return {name: value - before[name]
            for name, value in counted(rg).items()}


def settled(rg: RaftGroups) -> RaftGroups:
    """Leaders elected and their first entries applied, so that a run's
    first round accepts every row."""
    rg.wait_for_leaders()
    rg.run(3)
    return rg


def z(x) -> np.ndarray:
    return np.asarray(x, np.int64)


# -- the engine: the joint call against the two calls ----------------------

def counter_windows(rng):
    """Adds to a seeded half of the groups, a read of every group: a read
    beside a write of its own group answers with that write."""
    for _ in range(6):
        wg = np.flatnonzero(rng.random(G) < 0.5)
        wg = wg if wg.size else np.array([0])
        yield ((wg, np.full(wg.size, ap.OP_LONG_ADD),
                rng.integers(1, 9, wg.size), np.zeros(wg.size),
                np.zeros(wg.size)),
               (np.arange(G), ap.OP_VALUE_GET, 0, 0, 0))


def map_windows(rng):
    """Puts of keys of both buckets into a seeded half of the maps, a get
    of one of those keys from every map (there or not), and every map's
    size from a second slot: reads two wide, the width a window of two
    reads a map compiles."""
    keys = rng.integers(1, 2**31 - 1, 12)
    for _ in range(6):
        wg = np.flatnonzero(rng.random(G) < 0.5)
        wg = wg if wg.size else np.array([0])
        yield ((wg, np.full(wg.size, ap.OP_MAP_PUT),
                rng.choice(keys, wg.size), rng.integers(0, 99, wg.size),
                np.full(wg.size, ap.MAP_TELL)),
               (np.tile(np.arange(G), 2),
                np.repeat([ap.OP_MAP_GET, ap.OP_MAP_SIZE], G),
                np.concatenate([rng.choice(keys, G), np.zeros(G, int)]),
                0, np.repeat([ap.MAP_TELL, 0], G)))


@pytest.mark.parametrize("build, windows", [
    (device_plane, counter_windows), (wide_map, map_windows)],
    ids=["counters", "bucketed-map"])
def test_joined_windows_equal_the_two_step_order_leaf_for_leaf(build, windows):
    rg, ref = settled(build(seed=36)), settled(build(seed=36))
    before, ref_before = counted(rg), counted(ref)
    n = 0
    for writes, reads in windows(np.random.default_rng(36)):
        query = rg.stage_query_vector(*reads)
        rows = rg.drive_vector(*map(z, writes), query=query)
        got = rg.finish_query_vector(query)
        want_rows = ref.drive_vector(*map(z, writes))
        want = ref.drive_query_vector(*reads)
        np.testing.assert_array_equal(rows, want_rows)
        np.testing.assert_array_equal(got, want)
        n += 1
    assert_same(rg.state, ref.state, "state")
    assert_same(rg._key, ref._key, "carried key")
    assert delta(rg, before) == {
        "rounds": n, "fetches": n, "query_vector_drives": n,
        "query_joined_drives": n, "query_settle_rounds": 0,
        "dispatch_leaves": 7 * n}
    assert delta(ref, ref_before) == {
        "rounds": n, "fetches": 2 * n, "query_vector_drives": n,
        "query_joined_drives": 0, "query_settle_rounds": 0,
        "dispatch_leaves": 8 * n}


def test_a_pure_read_window_takes_the_query_program_alone():
    rg = settled(device_plane(seed=1))
    before = counted(rg)
    assert rg.drive_query_vector(np.arange(G), ap.OP_VALUE_GET).tolist() \
        == [0] * G
    assert delta(rg, before) == {
        "rounds": 0, "fetches": 1, "query_vector_drives": 1,
        "query_joined_drives": 0, "query_settle_rounds": 0,
        "dispatch_leaves": 3}


def test_a_round_that_left_a_row_unresolved_gives_its_reads_back():
    """An engine that applies one entry a round: four adds commit in the
    run's first round and one is reported, so what the read rode in on saw
    one add of four. It is thrown away, the run is finished, and the read
    evaluated alone answers with all four."""
    rg = settled(device_plane(Config(applies_per_round=1), seed=2))
    before = counted(rg)
    query = rg.stage_query_vector([0], ap.OP_VALUE_GET)
    rows = rg.drive_vector(z([0] * 4), z([ap.OP_LONG_ADD] * 4), z([5] * 4),
                           z([0] * 4), z([0] * 4), query=query)
    assert rows.tolist() == [5, 10, 15, 20]
    assert query.evaluations == 0 and not query.done.any()
    assert rg.finish_query_vector(query).tolist() == [20]
    got = delta(rg, before)
    assert got["rounds"] >= 4 and got["query_joined_drives"] == 0
    assert got["query_vector_drives"] == 1
    assert got["fetches"] == got["rounds"] + 1


def test_a_read_the_round_could_not_serve_is_evaluated_again():
    """The round resolved every row but one read's group had not applied
    what it committed (``served`` false in the slab): the served reads are
    kept, the other settles as ``drive_query_vector``'s always did."""
    rg = settled(device_plane(seed=3))
    query = rg.stage_query_vector([0, 1], ap.OP_VALUE_GET)
    real = rg._round_query

    def unserved_group_one(*args):
        state, key, out, slab = real(*args)
        return state, key, out, slab.at[1, 1].set(0)

    rg._round_query = unserved_group_one
    before = counted(rg)
    rg.drive_vector(z([0, 1]), z([ap.OP_LONG_ADD] * 2), z([7, 9]), z([0, 0]),
                    z([0, 0]), query=query)
    assert query.done.tolist() == [True, False] and query.evaluations == 1
    assert rg.finish_query_vector(query).tolist() == [7, 9]
    assert delta(rg, before) == {
        "rounds": 2, "fetches": 3, "query_vector_drives": 1,
        "query_joined_drives": 0, "query_settle_rounds": 1,
        "dispatch_leaves": 7 + 5 + 3}


def test_a_driver_with_round_programs_of_its_own_evaluates_reads_alone():
    """``parallel/multihost.py`` builds its engine with ``_build_state=
    False`` and brings its own round and query programs: it has no joint
    program, a round hands the rows back untouched, and the query program
    answers them."""
    assert device_plane(_build_state=False)._round_query is None
    rg = settled(device_plane(seed=4))
    rg._round_query = None
    before = counted(rg)
    query = rg.stage_query_vector(np.arange(G), ap.OP_VALUE_GET)
    rg.drive_vector(z([2]), z([ap.OP_LONG_ADD]), z([6]), z([0]), z([0]),
                    query=query)
    assert query.evaluations == 0 and query.rode is None
    assert rg.finish_query_vector(query).tolist() == [0, 0, 6] + [0] * (G - 3)
    assert delta(rg, before) == {
        "rounds": 1, "fetches": 2, "query_vector_drives": 1,
        "query_joined_drives": 0, "query_settle_rounds": 0,
        "dispatch_leaves": 8}


def test_a_refused_staging_leaves_the_reads_to_the_query_program():
    """Five rows for one group overflow its four submit slots: the direct
    lane refuses the run (``drive_vector`` returns ``None`` for the tracked
    lane to take) before it has looked at the reads."""
    rg = settled(device_plane(seed=5))
    query = rg.stage_query_vector([0], ap.OP_VALUE_GET)
    assert rg.drive_vector(z([0] * 5), z([ap.OP_LONG_ADD] * 5), z([1] * 5),
                           z([0] * 5), z([0] * 5), query=query) is None
    assert query.evaluations == 0
    assert rg.finish_query_vector(query).tolist() == [0]


def test_the_joint_program_compiles_for_no_more_widths_than_the_query():
    """Windows of one to four reads a group, joined and alone. A width is
    padded to a power of two, so three and four reads a group share a
    program; every width the joint program was compiled for the query
    program was compiled for too, and a second pass compiles nothing.
    (The programs are the process's, shared by every engine of this
    shape: other tests may have compiled some of them already.)"""
    rg = settled(device_plane(seed=6))
    joint, alone = rg._round_query, rg._query

    def one_pass() -> set:
        widths = set()
        for reads_a_group in (1, 2, 3, 4, 2, 1):
            reads = np.repeat(np.arange(G), reads_a_group)
            query = rg.stage_query_vector(reads, ap.OP_VALUE_GET)
            widths.add(query.slots)
            rg.drive_vector(z([0]), z([ap.OP_LONG_ADD]), z([1]), z([0]),
                            z([0]), query=query)
            rg.finish_query_vector(query)
            assert query.evaluations == 1
            rg.drive_query_vector(reads, ap.OP_VALUE_GET)
        return widths

    before = joint._cache_size()
    assert one_pass() == {1, 2, 4}
    warm = (joint._cache_size(), alone._cache_size())
    assert warm[0] - before <= 3 and warm[0] <= warm[1]
    one_pass()
    assert (joint._cache_size(), alone._cache_size()) == warm


# -- the served path ---------------------------------------------------------

async def deployment(engine_config, joint: bool = True):
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)
    server = AtomixServer(
        addr, [addr], LocalTransport(registry), election_timeout=0.5,
        heartbeat_interval=0.1, session_timeout=60.0, executor="tpu",
        engine_config=engine_config)
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=60.0)
    await client.open()
    engine = server.server.state_machine.device_engine._ensure()
    if not joint:
        engine._round_query = None      # the two-step order
    return server, client, engine


async def shut(server, client) -> None:
    await client.close()
    await server.close()


async def in_order(calls: dict) -> tuple[dict, list]:
    """Run the named calls at once: ``(replies by name, the names in the
    order their replies arrived)``."""
    order: list = []

    async def one(name, call):
        reply = await call
        order.append(name)
        return name, reply

    replies = await asyncio.gather(*(one(n, c) for n, c in calls.items()))
    return dict(replies), order


async def counter_script(client) -> list:
    ctrs = [await client.get(f"c{i}", DistributedAtomicLong)
            for i in range(6)]
    for c in ctrs:
        c.with_consistency(Consistency.ATOMIC)
    await asyncio.gather(*(c.add_and_get(1) for c in ctrs))   # on the device
    out = []
    for k in range(4):
        out.append(await in_order({
            **{("add", i): ctrs[i].add_and_get(k + 2)
               for i in range(k % 2, 6, 2)},
            **{("get", i): ctrs[i].get() for i in range(6)}}))
    return out


async def map_script(client) -> list:
    maps = [await client.get(f"m{i}", DistributedMap) for i in range(4)]
    for m in maps:
        m.with_consistency(Consistency.ATOMIC)
    await asyncio.gather(*(m.put(1, 10 + i) for i, m in enumerate(maps)))
    out = []
    for k in range(4):
        writers = range(k % 2, 4, 2)
        readers = [i for i in range(4) if i not in writers]
        out.append(await in_order({
            **{("put", i): maps[i].put(k % 3, 100 * k + i) for i in writers},
            **{("get", i): maps[i].get(1) for i in readers},
            **{("size", i): maps[i].size() for i in readers}}))
    return out


@pytest.mark.parametrize("engine_config, script", [
    (SERVED, counter_script), (SERVED_MAP, map_script)],
    ids=["counters", "bucketed-map"])
def test_a_served_window_joined_answers_as_the_two_step_order(
        engine_config, script):
    """Windows of reads and writes through the public path, on a server
    whose rounds take the reads along and on one whose rounds cannot: the
    same replies in the same order, the same state on the device, one
    fetch a window where the other pays two."""
    async def drive(joint: bool):
        server, client, engine = await deployment(engine_config, joint)
        try:
            before = counted(engine)
            with the_tick_after_the_window():
                out = await script(client)
            return out, delta(engine, before), engine.state, engine._key
        finally:
            await shut(server, client)

    got, counts, state, key = arun(drive(True), timeout=240)
    want, ref_counts, ref_state, ref_key = arun(drive(False), timeout=240)
    assert got == want
    assert_same(state, ref_state, "state")
    assert_same(key, ref_key, "carried key")
    # the warm-up burst of writes is a run of its own; each of the four
    # windows after it is one round
    assert counts["query_vector_drives"] == 4 == counts["query_joined_drives"]
    assert ref_counts["query_vector_drives"] == 4
    assert ref_counts["query_joined_drives"] == 0
    assert counts["rounds"] == ref_counts["rounds"]
    assert ref_counts["fetches"] - counts["fetches"] == 4
    assert counts["query_settle_rounds"] == 0


def crowd(bucket: int, n: int) -> list[int]:
    """``n`` keys of one bucket of ``engines.WIDE_MAP``'s two."""
    keys = np.arange(1, 4 * n, dtype=np.uint32)
    mixed = keys * np.uint32(2654435761)
    return keys[(mixed >> 16) % 2 == bucket][:n].tolist()


async def value_case(client):
    v = await client.get("v", DistributedAtomicValue)
    v.with_consistency(Consistency.ATOMIC)
    await v.set(1)
    return {"set": v.set(5), "get": v.get()}, {"set": None, "get": 5}, True


async def long_case(client):
    c = await client.get("c", DistributedAtomicLong)
    c.with_consistency(Consistency.ATOMIC)
    await c.add_and_get(1)
    return ({"add": c.add_and_get(4), "get": c.get()},
            {"add": 5, "get": 5}, True)


async def map_case(client):
    m = await client.get("m", DistributedMap)
    m.with_consistency(Consistency.ATOMIC)
    await m.put(1, 10)
    return ({"put": m.put(1, 11), "get": m.get(1), "size": m.size()},
            {"put": 10, "get": 11, "size": 1}, False)


async def first_put_case(client):
    """The put that makes the map's table hold anything flips the record
    ``query_spec`` reads first (``_device``)."""
    m = await client.get("m", DistributedMap)
    m.with_consistency(Consistency.ATOMIC)
    return ({"put": m.put(1, 11), "get": m.get(1), "size": m.size()},
            {"put": None, "get": 11, "size": 1}, False)


async def full_bucket_case(client):
    """A put that finds its bucket full is shadowed on the host when the
    run is finalized: routed before that, its key's get would have gone
    to the device, which holds no such key, and ``size`` would have
    counted the table alone."""
    m = await client.get("m", DistributedMap)
    m.with_consistency(Consistency.ATOMIC)
    keys = crowd(0, ap.MAP_BUCKET + 1)
    for at in range(0, ap.MAP_BUCKET, 32):
        await asyncio.gather(*(m.put(k, k + 1)
                               for k in keys[at:at + 32][:ap.MAP_BUCKET - at]))
    last = keys[-1]
    return ({"put": m.put(last, 77), "get": m.get(last), "size": m.size(),
             "other": m.get(keys[0])},
            {"put": None, "get": 77, "size": ap.MAP_BUCKET + 1,
             "other": keys[0] + 1}, False)


@pytest.mark.parametrize("case", [
    value_case, long_case, map_case, first_put_case, full_bucket_case],
    ids=lambda c: c.__name__)
def test_a_read_routed_before_a_parked_write_answers_with_that_write(case):
    """Per state class with a ``query_spec``: a window holds a write of a
    resource and reads of the same one, and the run is still parked when
    the window is evaluated. ``DeviceAtomicValueState`` promises that its
    route outlives the finalize, and its read rides the round;
    ``DeviceMapState`` does not, and a read of a map with a parked row is
    routed after the run has landed: either way the reply holds the
    write."""
    async def drive():
        server, client, engine = await deployment(SERVED_MAP)
        try:
            calls, want, rides = await case(client)
            before = counted(engine)
            with the_tick_after_the_window():
                got, order = await in_order(calls)
            assert got == want
            # the write's reply first: its run is finalized before any read
            assert order[0] == next(iter(calls))
            counts = delta(engine, before)
            assert counts["query_joined_drives"] == int(rides)
            if rides:
                assert counts["fetches"] == counts["rounds"] == 1
        finally:
            await shut(server, client)

    arun(drive(), timeout=240)


def test_only_the_value_promises_that_its_route_outlives_the_finalize():
    """The classes with a ``query_spec`` of their own, by name: a new one
    decides for itself, and the base says no."""
    with_spec = {cls.__name__: cls.ROUTE_OUTLIVES_FINALIZE
                 for cls in vars(dx).values()
                 if isinstance(cls, type)
                 and issubclass(cls, dx.DeviceBackedStateMachine)
                 and "query_spec" in vars(cls)}
    assert with_spec == {"DeviceBackedStateMachine": False,
                         "DeviceAtomicValueState": True,
                         "DeviceMapState": False}


def test_a_parked_run_on_another_engine_takes_no_reads_along():
    """``flush_fused`` hands the window's rows to the window's engine
    alone; a run parked for another engine is dispatched as ever."""
    class Engine:
        window = None

        def __init__(self):
            self.queries = []

        def run_vector(self, groups, opc, a, b, c, query=None):
            self.queries.append(query)
            return [0] * len(groups)

    class Machine:
        _group = 0

    class Group:
        group_id = 0

        def __init__(self, engine):
            self.state_machine = type("SM", (), {"device_engine": engine})()
            self.finalized = []

        def _finalize_vector_run(self, run, raws, error):
            self.finalized.append((len(run), raws, error))

    mine, other = Engine(), Engine()
    server = RaftServer.__new__(RaftServer)
    row = (0, None, None, Machine(), None, None, (1, 0, 0, 0, 0))
    groups = [Group(other), Group(mine), Group(other)]
    server._fused_runs = [(g, [row]) for g in groups]
    server._fuse_scheduled = False
    server._pump_batch = server._park_span = None
    from copycat_tpu.utils.metrics import MetricsRegistry
    registry = MetricsRegistry()
    server._m_apply_fused = registry.counter("fused")
    server._m_apply_fused_rows = registry.histogram("rows")
    server._m_apply_fused_groups = registry.histogram("groups")
    assert len(server.parked_rows(mine)) == 1
    assert len(server.parked_rows(other)) == 2
    assert server.parked_rows(Engine()) == []
    query = object()
    server.flush_fused("read", mine, query)
    assert mine.queries == [query] and other.queries == [None]
    assert [g.finalized for g in groups] == [[(1, [0], None)]] * 3
    assert server._fused_runs == []
