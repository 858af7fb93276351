"""The map table of buckets: a keyed op fetches its key's bucket and never the
table, and the served path answers from what the device holds.

At ``engines.wide_map``'s shape (two buckets of 256): seeded streams of every
map opcode against a plain dict, with a bucket that fills, ``clear``, ``size``,
``contains_value`` and TTL expiry inside a bucket; replicas' tables equal at
equal ``applied_index``; one bucket traces to the sweep, with no gather.
``DistributedMap`` through ``AtomixServer(executor="tpu")`` against
``executor="cpu"`` and ``benchmarks/reference_map.PlainMaps`` on one stream, op
by op, ``None`` told from 0, a full bucket shadowed on the host and read back;
snapshot and restore of a member holding a two-bucket map.
"""

import asyncio
import functools
import os
import random
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import reference_map  # noqa: E402
from copycat_tpu.collections import DistributedMap  # noqa: E402
from copycat_tpu.io.local import LocalServerRegistry, LocalTransport  # noqa: E402
from copycat_tpu.manager.atomix import AtomixClient, AtomixServer  # noqa: E402
from copycat_tpu.manager.device_executor import DeviceMapState  # noqa: E402
from copycat_tpu.ops import apply as ap  # noqa: E402
from copycat_tpu.resource.consistency import Consistency  # noqa: E402
from copycat_tpu.server.log import Storage, StorageLevel  # noqa: E402
from copycat_tpu.testing.nemesis import crash_server  # noqa: E402

from helpers import async_test  # noqa: E402
from raft_fixtures import next_ports  # noqa: E402

from engines import G, SERVED_MAP, WIDE_MAP, wide_map  # noqa: E402

P = 3
BUCKETS = ap.map_buckets(WIDE_MAP.resource.map_slots)


@functools.cache
def bucket_of(key: int) -> int:
    return int(ap.map_bucket(jnp.asarray(key, jnp.int32), BUCKETS))


def keys_in_bucket(bucket: int, n: int, start: int = 1) -> list[int]:
    out, key = [], start
    while len(out) < n:
        if bucket_of(key) == bucket:
            out.append(key)
        key += 1
    return out


# -- the kernel against a plain dict ------------------------------------------

MAP_OPS = (ap.OP_MAP_PUT, ap.OP_MAP_GET, ap.OP_MAP_REMOVE,
           ap.OP_MAP_PUT_IF_ABSENT, ap.OP_MAP_GET_OR_DEFAULT,
           ap.OP_MAP_REMOVE_IF, ap.OP_MAP_REPLACE, ap.OP_MAP_REPLACE_IF,
           ap.OP_MAP_CONTAINS_KEY, ap.OP_MAP_CONTAINS_VALUE, ap.OP_MAP_SIZE,
           ap.OP_MAP_IS_EMPTY, ap.OP_MAP_CLEAR)
WEIGHTS = (30, 15, 8, 8, 3, 3, 5, 3, 5, 3, 5, 2, 1)
#: no remove and no clear: the table only fills
FILLING = (40, 15, 0, 15, 3, 0, 5, 0, 5, 3, 5, 2, 0)


class PlainMap:
    """One group's map as a dict of key -> (value, deadline), with the
    kernel's replies; ``room(key)`` says whether the key's bucket has a
    free slot (the table's one bound)."""

    def __init__(self, slots: int) -> None:
        self.m: dict[int, tuple[int, int]] = {}
        self.buckets = ap.map_buckets(slots)
        self.per_bucket = slots // self.buckets

    def room(self, key: int, alive: dict) -> bool:
        if self.buckets == 1:
            return len(alive) < self.per_bucket
        mine = bucket_of(key)
        return sum(1 for k in alive if bucket_of(k) == mine) < self.per_bucket

    def apply(self, o: int, k: int, v: int, c: int, now: int) -> int:
        m = self.m
        tell = c == ap.MAP_TELL and o != ap.OP_MAP_REPLACE_IF
        alive = {kk: vv for kk, vv in m.items() if vv[1] == 0 or vv[1] > now}
        absent = ap.ABSENT if tell else 0
        full = ap.FULL if tell else ap.FAIL
        dl = now + c if c > 0 else 0
        if o == ap.OP_MAP_PUT:
            if k in alive:
                r = alive[k][0]
                m[k] = (v, dl)
            elif self.room(k, alive):
                r = absent
                m[k] = (v, dl)
            else:
                r = full
        elif o == ap.OP_MAP_GET:
            r = alive[k][0] if k in alive else absent
        elif o == ap.OP_MAP_REMOVE:
            r = alive[k][0] if k in alive else absent
            m.pop(k, None)
        elif o == ap.OP_MAP_PUT_IF_ABSENT:
            if k in alive:
                r = alive[k][0] if tell else 0
            elif self.room(k, alive):
                r = ap.ABSENT if tell else 1
                m[k] = (v, dl)
            else:
                r = full
        elif o == ap.OP_MAP_GET_OR_DEFAULT:
            r = alive[k][0] if k in alive else v
        elif o == ap.OP_MAP_REMOVE_IF:
            r = int(k in alive and alive[k][0] == v)
            if r:
                m.pop(k)
        elif o == ap.OP_MAP_REPLACE:
            if k in alive:
                r = alive[k][0]
                m[k] = (v, 0)
            else:
                r = ap.ABSENT if tell else ap.FAIL
        elif o == ap.OP_MAP_REPLACE_IF:
            r = int(k in alive and alive[k][0] == v)
            if r:
                m[k] = (c, 0)
        elif o == ap.OP_MAP_CONTAINS_KEY:
            r = int(k in alive)
        elif o == ap.OP_MAP_CONTAINS_VALUE:
            r = int(any(vv[0] == k for vv in alive.values()))
        elif o == ap.OP_MAP_SIZE:
            r = len(alive)
        elif o == ap.OP_MAP_IS_EMPTY:
            r = int(not alive)
        else:
            r = 0
            m.clear()
        for kk in [kk for kk, vv in m.items() if vv[1] != 0 and vv[1] <= now]:
            del m[kk]          # an expired slot is free to the next put
        return r


@pytest.fixture(scope="module")
def entry():
    return jax.jit(ap.apply_entry)


@pytest.mark.parametrize("seed, keyspace, ttl", [
    (1, 150, False), (2, 150, True), (3, 400, False)],
    ids=["sparse", "deadlines", "buckets-fill"])
def test_every_map_opcode_against_a_plain_dict(entry, seed, keyspace, ttl):
    """600 entries a group (1,000 where the bucket is to fill) of every map opcode, telling and not, with and
    without deadlines; with 400 keys of which 360 share a bucket that
    bucket fills, and a put into it answers FULL (FAIL untold) while the
    other bucket goes on."""
    res = ap.init_resources(G, P, WIDE_MAP.resource)
    assert res.map_table.shape == (G, P, BUCKETS, *ap.MAP_TILE)
    assert res.map_key.shape == (G, P, 0)
    rng = random.Random(seed)
    models = [PlainMap(WIDE_MAP.resource.map_slots) for _ in range(G)]
    now, fulls = 1, 0
    crowd = keys_in_bucket(0, 360) + keys_in_bucket(1, 40)
    bc = lambda x: jnp.broadcast_to(
        jnp.asarray(x, jnp.int32)[:, None], (G, P))
    for _ in range(1000 if keyspace > 300 else 600):
        now += rng.randint(0, 2)
        rows = []
        for g in range(G):
            o = rng.choices(MAP_OPS, FILLING if keyspace > 300
                            else WEIGHTS)[0]
            k, v = rng.randint(0, keyspace), rng.randint(0, 5)
            if keyspace > 300:
                k = rng.choice(crowd)
            c = ap.MAP_TELL if rng.random() < 0.5 else 0
            if ttl and c == 0 and o in (ap.OP_MAP_PUT,
                                        ap.OP_MAP_PUT_IF_ABSENT):
                c = rng.choice((0, 3, 10))
            if o == ap.OP_MAP_REPLACE_IF:
                c = rng.randint(0, 5)
            a = v if o == ap.OP_MAP_CONTAINS_VALUE else k
            rows.append((o, a, v, c, models[g].apply(o, a, v, c, now)))
        o_, a_, b_, c_, want = zip(*rows)
        res, out = entry(res, bc(o_), bc(a_), bc(b_), bc(c_), bc([0] * G),
                         bc([now] * G), jnp.ones((G, P), bool))
        out = np.asarray(out)
        assert (out == np.asarray(want)[:, None]).all(), (rows, out)
        fulls += sum(w in (ap.FULL, ap.FAIL) and o == ap.OP_MAP_PUT
                     for o, _, _, _, w in rows)
    assert (fulls > 0) == (keyspace > 300)
    # every replica applied the same entries: equal tables, equal counts
    for x in (res.map_table, res.map_count):
        x = np.asarray(x)
        assert (x == x[:, :1]).all()
    if not ttl:      # no deadline anywhere: the live count is the size
        assert np.asarray(res.map_count)[:, 0, 0].tolist() == [
            len(m.m) for m in models]


def test_one_bucket_is_the_sweep_and_holds_no_gather():
    """``map_slots = 16`` (and any table that is not whole buckets of 256)
    is one bucket: the four planes, no ``map_table``, and a traced apply with
    no gather, scatter, loop or conditional in it."""
    assert ap.map_buckets(16) == ap.map_buckets(256) == ap.map_buckets(300) == 1
    assert ap.map_buckets(512) == 2 and ap.map_buckets(16384) == 64
    res = ap.init_resources(G, P, ap.ResourceConfig())
    assert res.map_key.shape == (G, P, 16)
    assert res.map_table.shape == res.map_count.shape == (G, P, 0)
    z = jnp.zeros((G, P), jnp.int32)
    text = str(jax.make_jaxpr(ap.apply_entry)(
        res, z, z, z, z, z, z, z == 0))
    for word in ("gather", "scatter", "while", "cond["):
        assert word not in text, word
    wide = ap.init_resources(G, P, WIDE_MAP.resource)
    text = str(jax.make_jaxpr(ap.apply_entry)(
        wide, z, z, z, z, z, z, z == 0))
    assert "gather" in text and "scatter" in text


def test_replicas_tables_are_equal_at_equal_applied_index():
    """Through the consensus step: puts, removes and a clear for every
    group, and every lane that has applied as much as its group's leader
    holds the leader's table and count; the query lane reads it."""
    rg = wide_map(seed=5)
    rg.wait_for_leaders()
    rng = np.random.default_rng(5)
    model = [dict() for _ in range(G)]
    for wave in range(12):
        n = G * 4
        groups = np.repeat(np.arange(G), 4)
        keys = rng.integers(1, 600, n)
        vals = rng.integers(0, 9, n)
        opc = np.where(rng.random(n) < 0.8, ap.OP_MAP_PUT, ap.OP_MAP_REMOVE)
        if wave == 7:
            opc[::9] = ap.OP_MAP_CLEAR
        got = rg.drive_vector(groups, opc, keys, vals,
                              np.full(n, ap.MAP_TELL))
        for g, o, k, v, r in zip(groups.tolist(), opc.tolist(),
                                 keys.tolist(), vals.tolist(), got.tolist()):
            m = model[g]
            if o == ap.OP_MAP_PUT:
                want = m.get(k, ap.ABSENT)
                if r == ap.FULL:
                    assert k not in m
                    continue
                m[k] = v
            elif o == ap.OP_MAP_REMOVE:
                want = m.pop(k, ap.ABSENT)
            else:
                want = 0
                m.clear()
            assert r == want, (wave, g, o, k, r, want)
    rg.run(3)     # the followers learn the last commit a round later
    applied = np.asarray(rg.state.applied_index)
    table = np.asarray(rg.state.resources.map_table)
    count = np.asarray(rg.state.resources.map_count)
    for g in range(G):
        lead = rg.leader(g)
        same = applied[g] == applied[g, lead]
        assert same.all()
        assert (table[g, same] == table[g, lead]).all()
        assert (count[g, same] == count[g, lead]).all()
        assert count[g, lead, 0] == len(model[g])
    sizes = rg.drive_query_vector(np.arange(G), ap.OP_MAP_SIZE)
    assert sizes.tolist() == [len(m) for m in model]
    some = [next(iter(m), 0) for m in model]
    vals = rg.drive_query_vector(np.arange(G), ap.OP_MAP_GET, some, 0,
                                 ap.MAP_TELL)
    assert vals.tolist() == [m.get(k, ap.ABSENT)
                             for m, k in zip(model, some)]
    held = rg.drive_query_vector(np.arange(G), ap.OP_MAP_CONTAINS_VALUE, 3)
    assert held.tolist() == [int(3 in m.values()) for m in model]


# -- the served path ------------------------------------------------------------

async def _stack(registry, executor, storage=None, addr=None):
    addr = addr or next_ports(1)[0]
    server = AtomixServer(
        addr, [addr], LocalTransport(registry, local_address=addr),
        election_timeout=0.5, heartbeat_interval=0.1, session_timeout=20.0,
        executor=executor, engine_config=SERVED_MAP,
        **({"storage": storage} if storage is not None else {}))
    await server.open()
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=20.0)
    await client.open()
    return server, client


def _script(seed: int, waves: int, wave: int, crowd: list[int]):
    """Seeded waves of map calls over two maps: small keys, so that 0 is a
    stored value and ``None`` an answer, and ``crowd``, keys of one bucket,
    more of them than it has slots."""
    rng = random.Random(seed)
    kinds = ("put", "put", "put", "get", "get", "remove", "put_if_absent",
             "replace", "get_or_default", "contains_key", "size", "is_empty")
    # first the crowd whole into map 0: its bucket fills, the rest is
    # shadowed, and the seeded waves then meet both kinds of key
    out = [[(0, "put", key, rng.randrange(3)) for key in crowd[at:at + 28]]
           for at in range(0, len(crowd), 28)]
    for _ in range(waves):
        ops = []
        for _ in range(wave):
            key = rng.choice(crowd) if rng.random() < 0.5 \
                else rng.randrange(12)
            ops.append((rng.randrange(2), rng.choice(kinds), key,
                        rng.randrange(3)))
        out.append(ops)
    return out


async def _run_script(client, waves, plain=None):
    maps = [await client.get(f"m{i}", DistributedMap) for i in range(2)]
    for m in maps:
        m.with_consistency(Consistency.ATOMIC)
    results = []
    for ops in waves:
        calls = []
        for which, kind, key, value in ops:
            m = maps[which]
            calls.append({
                "put": lambda: m.put(key, value),
                "get": lambda: m.get(key),
                "remove": lambda: m.remove(key),
                "put_if_absent": lambda: m.put_if_absent(key, value),
                "replace": lambda: m.replace(key, value),
                "get_or_default": lambda: m.get_or_default(key, -7),
                "contains_key": lambda: m.contains_key(key),
                "size": lambda: m.size(),
                "is_empty": lambda: m.is_empty(),
            }[kind]())
        # a wave's commands are one pump turn: vector runs with several
        # rows a map; its reads follow them
        results.append(await asyncio.gather(*calls))
    finals = [[await m.get(k) for k in range(12)] + [await m.size()]
              for m in maps]
    return results, finals


def _plain_results(waves):
    """The same script on ``reference_map.PlainMaps``: commands of a wave in
    order, then (the client flushes commands first) its reads."""
    plain = reference_map.PlainMaps(2)
    results = []
    for ops in waves:
        row = [None] * len(ops)
        reads = ("get", "get_or_default", "contains_key", "size", "is_empty")
        for only_reads in (False, True):
            for i, (which, kind, key, value) in enumerate(ops):
                if (kind in reads) != only_reads:
                    continue
                row[i] = {
                    "put": lambda: plain.put(which, key, value),
                    "get": lambda: plain.get(which, key),
                    "remove": lambda: plain.remove(which, key),
                    "put_if_absent":
                        lambda: plain.put_if_absent(which, key, value),
                    "replace": lambda: plain.replace(which, key, value),
                    "get_or_default":
                        lambda: plain.get_or_default(which, key, -7),
                    "contains_key": lambda: plain.contains_key(which, key),
                    "size": lambda: plain.size(which),
                    "is_empty": lambda: plain.is_empty(which),
                }[kind]()
        results.append(row)
    return results, plain


@async_test(timeout=240)
async def test_distributed_map_tpu_against_cpu_and_the_plain_maps():
    """One seeded stream through ``executor="tpu"`` and ``executor="cpu"``:
    every reply equal, op by op, ``None`` told from 0. Commands ran on the
    vector lane (one device op each, none through a generator), the reads
    on the query lane; a full bucket's put is shadowed on the host, reads
    back and counts in ``size``. Where a wave holds no read of a key it
    also writes, the plain maps agree too."""
    crowd = keys_in_bucket(1, 270, start=1000)
    waves = _script(11, 40, 24, crowd)
    registry = LocalServerRegistry()
    out = {}
    for executor in ("tpu", "cpu"):
        server, client = await _stack(registry, executor)
        try:
            out[executor] = await _run_script(client, waves)
            if executor == "tpu":
                manager = server.server.state_machine
                engine = manager.device_engine
                metrics = engine._groups.metrics
                machines = [h.state_machine
                            for h in manager.resources.values()]
                assert [type(m) for m in machines] == [DeviceMapState] * 2
                assert metrics.counter("map_vector_ops").value > 400
                # the crowd's shadowed keys take their handlers, no other
                assert metrics.counter("map_chain_ops").value < 60
                assert metrics.counter("query_vector_drives").value > 0
                on_device, shadowed = engine.map_keys()
                assert shadowed == sum(m._shadows() for m in machines) > 0
                assert on_device + shadowed == sum(
                    f[-1] for f in out["tpu"][1])
                # the host answers from the device: no record a device key
                assert all(not h.on_device for m in machines
                           for h in m._held.values())
        finally:
            await client.close()
            await server.close()
    assert out["tpu"] == out["cpu"]
    replies = [r for wave in out["tpu"][0] for r in wave]
    assert None in replies and 0 in replies
    plain_results, plain = _plain_results(waves)
    assert [plain.size(i) for i in range(2)] == [
        f[-1] for f in out["tpu"][1]]
    assert [[plain.get(i, k) for k in range(12)] for i in range(2)] == [
        f[:-1] for f in out["tpu"][1]]
    reads = ("get", "get_or_default", "contains_key", "size", "is_empty")
    for ops, got, want in zip(waves, out["tpu"][0], plain_results):
        for (_, kind, _, _), g, w in zip(ops, got, want):
            if kind not in reads:       # a wave's reads race its writes
                assert g == w, (kind, g, w)


@async_test(timeout=240)
async def test_a_member_restores_a_two_bucket_map_from_its_snapshot(
        tmp_path, monkeypatch):
    """A member with a map in both buckets, one key shadowed on the host (a
    string) and one for a full bucket, crashes after a capture: the table
    rides the engine's checkpoint, the image holds a record for the two
    shadowed keys alone, and every key reads back from the reborn member."""
    monkeypatch.setenv("COPYCAT_SNAPSHOTS", "1")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_ENTRIES", "8")
    monkeypatch.setenv("COPYCAT_SNAPSHOT_RETAIN", "0")
    registry = LocalServerRegistry()
    (addr,) = next_ports(1)
    storage = lambda: Storage(StorageLevel.DISK, str(tmp_path / "m0"))
    server, client = await _stack(registry, "tpu", storage(), addr)
    reborn = None
    try:
        m = await client.get("m", DistributedMap)
        crowd = keys_in_bucket(0, 257, start=5000)
        spread = keys_in_bucket(1, 20, start=9000)
        for k in crowd + spread:
            assert await m.put(k, k % 7) is None
        assert await m.put("name", "text") is None
        assert await m.put(spread[0], 0) == spread[0] % 7
        for _ in range(10):
            await m.put(spread[1], 1)
        raft = server.server
        await raft.snapshots_settled()
        assert raft._snap_index > 0 and raft.groups[0]._snap_supported
        machine = next(iter(raft.state_machine.resources.values())
                       ).state_machine
        image = machine.snapshot_state()
        assert sorted(map(str, (k for k, _, _ in image["held"]))) == sorted(
            [str(crowd[-1]), "name"])
        assert image["device"] is True
        await crash_server(raft)
        reborn = AtomixServer(
            addr, [addr], LocalTransport(registry, local_address=addr),
            storage=storage(), election_timeout=0.5, heartbeat_interval=0.1,
            session_timeout=20.0, executor="tpu", engine_config=SERVED_MAP)
        assert reborn.server.last_applied >= raft._snap_index
        assert reborn.server.groups[0].metrics.counter(
            "snap.restores").value == 1
        await reborn.open()
        assert await asyncio.wait_for(m.size(), 30) == 257 + 20 + 1
        assert await m.get(spread[0]) == 0
        assert await m.get(spread[1]) == 1
        assert await m.get(crowd[-1]) == crowd[-1] % 7
        assert await m.get(crowd[0]) == crowd[0] % 7
        assert await m.get("name") == "text"
        assert await m.get(4999) is None
        on_device, shadowed = reborn.server.state_machine \
            .device_engine.map_keys()
        assert (on_device, shadowed) == (256 + 20, 2)
    finally:
        for node in (client, reborn):
            if node is not None:
                try:
                    await asyncio.wait_for(node.close(), 10)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass
