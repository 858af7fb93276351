"""Differential oracle harness (SURVEY §7.3 #6).

One seeded, randomized, multi-resource-type op sequence is driven through
BOTH execution paths and every single result is diffed:

- the CPU oracle: a real 3-server Raft cluster (AtomixServers over
  LocalTransport) with the resource library on top — the reference test
  topology ("real consensus, fake network"), and
- the device engine: ``RaftGroups`` stepping the batched ``[G,P]``
  consensus + apply kernels, driven through the typed facades.

Results are normalized to a canonical form (the CPU path's ``None`` absent
sentinel ↔ the device path's 0/FAIL encodings) by per-op adapters; any
divergence fails with the op index and full history prefix for replay.
"""

import asyncio
import random

import pytest

jax = pytest.importorskip("jax")

from copycat_tpu.atomic import DistributedAtomicLong, DistributedAtomicValue
from copycat_tpu.collections import (
    DistributedMap,
    DistributedMultiMap,
    DistributedQueue,
    DistributedSet,
)
from copycat_tpu.coordination import DistributedLock
from copycat_tpu.models import (
    DeviceLock,
    DeviceLong,
    DeviceMap,
    DeviceMultiMap,
    DeviceQueue,
    DeviceSet,
    DeviceValue,
)

from atomix_fixtures import Stack
from helpers import async_test

from engines import device_plane

SEED = 20260729
NUM_OPS = 1000
KEYS = list(range(1, 11))       # map keyspace well under map_slots=16
VALUES = list(range(1, 51))     # nonzero: 0 is the canonical absent value
QUEUE_CAP = 12                  # stay under queue_slots=16 on both paths


def _gen_ops(rng: random.Random, n: int) -> list[tuple]:
    """Generate (resource, op, args) tuples; stateful guards keep the
    sequence within the device pools' fixed capacities and the lock
    protocol (only the tracked holder unlocks)."""
    ops = []
    queue_size = 0
    lock_holder = None  # None | "a" | "b"
    mm_pairs: set = set()      # live (key, value) pairs; device pool is 16
    for _ in range(n):
        kind = rng.choice(("value", "long", "map", "set", "queue", "lock",
                           "mmap"))
        if kind == "value":
            op = rng.choice(("get", "set", "cas", "get_and_set"))
            if op == "get":
                ops.append(("value", "get", ()))
            elif op == "set":
                ops.append(("value", "set", (rng.choice(VALUES),)))
            elif op == "cas":
                ops.append(("value", "cas",
                            (rng.choice(VALUES), rng.choice(VALUES))))
            else:
                ops.append(("value", "get_and_set", (rng.choice(VALUES),)))
        elif kind == "long":
            op = rng.choice(("get", "add", "inc", "dec"))
            if op == "get":
                ops.append(("long", "get", ()))
            elif op == "add":
                ops.append(("long", "add", (rng.randint(-7, 7),)))
            else:
                ops.append(("long", op, ()))
        elif kind == "map":
            k = rng.choice(KEYS)
            v = rng.choice(VALUES)
            op = rng.choice(("put", "get", "get_or_default", "put_if_absent",
                             "remove", "remove_if", "replace", "replace_if",
                             "contains_key", "contains_value", "size",
                             "is_empty"))
            args = {"put": (k, v), "get": (k,), "get_or_default": (k, v),
                    "put_if_absent": (k, v), "remove": (k,),
                    "remove_if": (k, v), "replace": (k, v),
                    "replace_if": (k, rng.choice(VALUES), v),
                    "contains_key": (k,), "contains_value": (v,),
                    "size": (), "is_empty": ()}[op]
            ops.append(("map", op, args))
        elif kind == "set":
            v = rng.choice(KEYS)
            op = rng.choice(("add", "remove", "contains", "size"))
            ops.append(("set", op, (v,) if op != "size" else ()))
        elif kind == "queue":
            op = rng.choice(("offer", "poll", "peek", "size"))
            if op == "offer":
                if queue_size >= QUEUE_CAP:
                    op = "poll"
                else:
                    queue_size += 1
            if op == "poll" and queue_size > 0:
                queue_size -= 1
            ops.append(("queue", op,
                        (rng.choice(VALUES),) if op == "offer" else ()))
        elif kind == "mmap":
            k = rng.choice(KEYS[:5])
            v = rng.choice(VALUES[:6])
            op = rng.choice(("put", "remove_all", "remove_entry",
                             "contains_key", "contains_entry",
                             "contains_value", "count", "size", "is_empty"))
            if op == "put" and len(mm_pairs | {(k, v)}) > 14:
                op = "remove_all"  # stay under the device pair pool
            if op == "put":
                mm_pairs.add((k, v))
            elif op == "remove_all":
                mm_pairs = {p for p in mm_pairs if p[0] != k}
            elif op == "remove_entry":
                mm_pairs.discard((k, v))
            args = {"put": (k, v), "remove_all": (k,),
                    "remove_entry": (k, v), "contains_key": (k,),
                    "contains_entry": (k, v), "contains_value": (v,),
                    "count": (k,), "size": (), "is_empty": ()}[op]
            ops.append(("mmap", op, args))
        else:  # lock
            if lock_holder is None:
                who = rng.choice(("a", "b"))
                lock_holder = who
                ops.append(("lock", "try_lock", (who,)))
            elif rng.random() < 0.6:
                ops.append(("lock", "unlock", (lock_holder,)))
                lock_holder = None
            else:
                # contended try_lock by the other client: must fail on both
                other = "b" if lock_holder == "a" else "a"
                ops.append(("lock", "try_lock_contended", (other,)))
    return ops


class CpuPath:
    """The oracle: resource library over a real 3-server CPU cluster."""

    def __init__(self, stack, client_a, client_b):
        self.stack = stack
        self.client_a = client_a
        self.client_b = client_b

    async def open(self):
        self.value = await self.client_a.get("value", DistributedAtomicValue)
        self.long = await self.client_a.get("long", DistributedAtomicLong)
        self.map = await self.client_a.get("map", DistributedMap)
        self.set = await self.client_a.get("set", DistributedSet)
        self.queue = await self.client_a.get("queue", DistributedQueue)
        self.mmap = await self.client_a.get("mmap", DistributedMultiMap)
        self.lock = {"a": await self.client_a.get("lock", DistributedLock),
                     "b": await self.client_b.get("lock", DistributedLock)}

    async def run(self, kind, op, args):
        if kind == "value":
            if op == "get":
                return (await self.value.get()) or 0
            if op == "set":
                return await self.value.set(*args)
            if op == "cas":
                return bool(await self.value.compare_and_set(*args))
            if op == "get_and_set":
                return (await self.value.get_and_set(*args)) or 0
        if kind == "long":
            if op == "get":
                return await self.long.get()
            if op == "add":
                return await self.long.add_and_get(*args)
            if op == "inc":
                return await self.long.increment_and_get()
            if op == "dec":
                return await self.long.decrement_and_get()
        if kind == "map":
            m = self.map
            if op == "put":
                return (await m.put(*args)) or 0
            if op == "get":
                return (await m.get(*args)) or 0
            if op == "get_or_default":
                return await m.get_or_default(*args)
            if op == "put_if_absent":
                return (await m.put_if_absent(*args)) is None
            if op == "remove":
                return (await m.remove(*args)) or 0
            if op == "remove_if":
                return bool(await m.remove_if_present(*args))
            if op == "replace":
                return await m.replace(*args)          # old value | None
            if op == "replace_if":
                return bool(await m.replace_if_present(*args))
            if op == "contains_key":
                return bool(await m.contains_key(*args))
            if op == "contains_value":
                return bool(await m.contains_value(*args))
            if op == "size":
                return await m.size()
            if op == "is_empty":
                return bool(await m.is_empty())
        if kind == "set":
            s = self.set
            if op == "add":
                return bool(await s.add(*args))
            if op == "remove":
                return bool(await s.remove(*args))
            if op == "contains":
                return bool(await s.contains(*args))
            if op == "size":
                return await s.size()
        if kind == "queue":
            q = self.queue
            if op == "offer":
                return bool(await q.offer(*args))
            if op == "poll":
                return await q.poll()                  # value | None
            if op == "peek":
                return await q.peek()
            if op == "size":
                return await q.size()
        if kind == "mmap":
            mm = self.mmap
            if op == "put":
                return bool(await mm.put(*args))
            if op == "remove_all":
                return len(await mm.remove(*args))   # removed-values list
            if op == "remove_entry":
                return bool(await mm.remove(*args))
            if op == "contains_key":
                return bool(await mm.contains_key(*args))
            if op == "contains_entry":
                return bool(await mm.contains_entry(*args))
            if op == "contains_value":
                return bool(await mm.contains_value(*args))
            if op == "count":
                return await mm.size(*args)          # per-key size
            if op == "size":
                return await mm.size()
            if op == "is_empty":
                return bool(await mm.is_empty())
        if kind == "lock":
            (who,) = args
            if op in ("try_lock", "try_lock_contended"):
                return bool(await self.lock[who].try_lock())
            if op == "unlock":
                return await self.lock[who].unlock()
        raise AssertionError(f"unhandled {kind}.{op}")


class DevicePath:
    """The engine under test: typed facades over the batched device step."""

    def __init__(self):
        # one group per resource type: value/long share an opcode register,
        # so they must live in separate groups
        self.rg = device_plane()
        self.rg.wait_for_leaders()
        self.value = DeviceValue(self.rg, 0)
        self.long = DeviceLong(self.rg, 1)
        self.map = DeviceMap(self.rg, 2)
        self.set = DeviceSet(self.rg, 3)
        self.queue = DeviceQueue(self.rg, 4)
        self.lock = {"a": DeviceLock(self.rg, 5, 1),
                     "b": DeviceLock(self.rg, 5, 2)}
        self.mmap = DeviceMultiMap(self.rg, 6)

    def run(self, kind, op, args):
        if kind == "value":
            v = self.value
            return {"get": v.get, "set": v.set, "cas": v.compare_and_set,
                    "get_and_set": v.get_and_set}[op](*args)
        if kind == "long":
            n = self.long
            return {"get": n.get, "add": n.add_and_get,
                    "inc": n.increment_and_get,
                    "dec": n.decrement_and_get}[op](*args)
        if kind == "map":
            m = self.map
            if op == "put_if_absent":
                return m.put_if_absent(*args)
            return {"put": m.put, "get": m.get,
                    "get_or_default": m.get_or_default, "remove": m.remove,
                    "remove_if": m.remove_if, "replace": m.replace,
                    "replace_if": m.replace_if,
                    "contains_key": m.contains_key,
                    "contains_value": m.contains_value, "size": m.size,
                    "is_empty": m.is_empty}[op](*args)
        if kind == "set":
            s = self.set
            return {"add": s.add, "remove": s.remove, "contains": s.contains,
                    "size": s.size}[op](*args)
        if kind == "queue":
            q = self.queue
            return {"offer": q.offer, "poll": q.poll, "peek": q.peek,
                    "size": q.size}[op](*args)
        if kind == "mmap":
            mm = self.mmap
            return {"put": mm.put, "remove_all": mm.remove,
                    "remove_entry": mm.remove_entry,
                    "contains_key": mm.contains_key,
                    "contains_entry": mm.contains_entry,
                    "contains_value": mm.contains_value,
                    "count": mm.count, "size": mm.size,
                    "is_empty": mm.is_empty}[op](*args)
        if kind == "lock":
            (who,) = args
            if op in ("try_lock", "try_lock_contended"):
                return self.lock[who].try_lock(0)
            if op == "unlock":
                return self.lock[who].unlock()
        raise AssertionError(f"unhandled {kind}.{op}")


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
@async_test(timeout=900)
async def test_differential_cpu_oracle_vs_device_engine(seed):
    rng = random.Random(seed)
    ops = _gen_ops(rng, NUM_OPS)

    # Build the device path FIRST: its jit compile blocks the event loop,
    # and the CPU cluster's session keep-alives must not miss their window
    # while XLA compiles (a long block expires sessions, whose fan-out
    # detaches resource instances — correct behavior, wrong test).
    dev = DevicePath()

    stack = await Stack().start(3, session_timeout=30.0)
    try:
        client_a = await stack.client(session_timeout=30.0)
        client_b = await stack.client(session_timeout=30.0)
        cpu = CpuPath(stack, client_a, client_b)
        await cpu.open()

        mismatches = []
        for i, (kind, op, args) in enumerate(ops):
            got_cpu = await asyncio.wait_for(cpu.run(kind, op, args), 30)
            got_dev = dev.run(kind, op, args)
            if got_cpu != got_dev:
                mismatches.append((i, kind, op, args, got_cpu, got_dev))
                if len(mismatches) >= 5:
                    break
        assert not mismatches, (
            "CPU oracle and device engine diverged "
            f"(seed={seed}):\n" + "\n".join(
                f"  op[{i}] {k}.{o}{a}: cpu={c!r} device={d!r}"
                for i, k, o, a, c, d in mismatches))
    finally:
        await stack.close()
