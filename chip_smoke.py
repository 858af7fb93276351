#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process that holds the chip, through the
entry points a user calls, at deployment sizes, and checks every result
against a plain reference:

- raw tensor plane (BASELINE config #5): ``ops.consensus.step`` under
  ``lax.scan`` as ``bench.run_throughput`` drives it — ``mixed`` at
  100,000 groups x 5 peers with the nemesis masks and the Pallas tally —
  against a dict-and-int host model of the same ops in commit order;
- bulk client plane: ``BulkDriver`` / ``BulkSessionClient`` deep drives
  (``deep_step`` and the one-program ``deep_scan``) at 10,000 groups x 64
  ops, the donation path, against running sums;
- served path: ``AtomixServer(executor="tpu")`` + ``AtomixClient`` with
  1,000+ device-backed resources, every reply equal to the same script on
  ``AtomixServer(executor="cpu")``, every acknowledged write read back.

``--chips 4`` runs only the path across chips: ``RaftGroups`` over a mesh
of the four chips at 400,000 groups x 5 against one chip at the same size.

The LAST line of stdout is ``{"ok": true, "device": {...}}``. Any other
platform than the TPU, any phase that raises and any comparison that
fails exits non-zero with no such line. Data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

FAIL = -(2 ** 31)


def say(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """Seconds XLA spent compiling (or loading from the persistent cache)
    since the last ``take()``, summed from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring

        self._secs = 0.0
        self._hits = self._misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += secs

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._misses += 1

    def take(self) -> str:
        out = (f"compile {self._secs:.1f}s ({self._hits} cache hits, "
               f"{self._misses} new entries)")
        self._secs, self._hits, self._misses = 0.0, 0, 0
        return out


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**20:,.0f} MiB"


# -- the plain host model: a dict and an int per group ---------------------

class PlainGroup:
    """One group's resources as plain Python values — the reference the
    device results are held to (independent of ``ops/apply.py``)."""

    def __init__(self, queue_slots: int = 16, wait_slots: int = 8) -> None:
        self.counter = 0
        self.map: dict[int, int] = {}
        self.set: set[int] = set()
        self.queue: list[int] = []
        self.holder: int | None = None
        self.waiters: list[int] = []
        self.leader: int | None = None
        self.epoch = 0
        self.listeners: list[int] = []
        self._q, self._w = queue_slots, wait_slots

    def apply(self, op: int, a: int, b: int, index: int | None) -> int | None:
        """Result of one committed op; ``None`` = not modelled (an
        election epoch when the entry's log index is unknown)."""
        from copycat_tpu.ops import apply as ap

        if op == ap.OP_LONG_ADD:
            self.counter += a
            return self.counter
        if op == ap.OP_MAP_PUT:
            old = self.map.get(a, 0)
            self.map[a] = b
            return old
        if op == ap.OP_MAP_GET:
            return self.map.get(a, 0)
        if op == ap.OP_SET_ADD:
            new = a not in self.set
            self.set.add(a)
            return int(new)
        if op == ap.OP_SET_REMOVE:
            had = a in self.set
            self.set.discard(a)
            return int(had)
        if op == ap.OP_Q_OFFER:
            if len(self.queue) >= self._q:
                return 0
            self.queue.append(a)
            return 1
        if op == ap.OP_Q_POLL:
            return self.queue.pop(0) if self.queue else FAIL
        if op == ap.OP_LOCK_ACQUIRE:
            if self.holder is None:
                self.holder = a
                return 1
            if self.holder == a:
                return 1
            if a in self.waiters:
                return 2
            if b != 0 and len(self.waiters) < self._w:
                self.waiters.append(a)
                return 2
            return 0
        if op == ap.OP_LOCK_RELEASE:
            if self.holder != a:
                return 0
            self.holder = self.waiters.pop(0) if self.waiters else None
            return 1
        if op == ap.OP_ELECT_LISTEN:
            if self.leader is None:
                self.leader = a
                self.epoch = index if index is not None else -1
                return index
            if self.leader == a:
                return self.epoch if self.epoch >= 0 else None
            if a not in self.listeners and len(self.listeners) < self._w:
                self.listeners.append(a)
            return 0
        if op == ap.OP_ELECT_RESIGN:
            if self.leader != a:
                if a in self.listeners:
                    self.listeners.remove(a)
                return 0
            if self.listeners:
                self.leader = self.listeners.pop(0)
                self.epoch = index if index is not None else -1
            else:
                self.leader = None
            return 1
        raise ValueError(f"op {op} is not in the smoke's mix")


def mixed_pattern(S: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``bench.mixed_submits``' per-slot (opcode, a, b) rows."""
    from copycat_tpu import bench

    if bench.SUBMIT_SLOTS != S:
        raise SystemExit(f"bench.SUBMIT_SLOTS={bench.SUBMIT_SLOTS}, the "
                         f"smoke drives S={S} (unset COPYCAT_BENCH_*)")
    sub = bench.mixed_submits(1)
    return (np.asarray(sub.opcode[0]), np.asarray(sub.a[0]),
            np.asarray(sub.b[0]))


def mixed_config(S: int, pallas_interpret: bool, **extra):
    from copycat_tpu import bench
    from copycat_tpu.ops.consensus import Config

    return Config(use_pallas=True, pallas_interpret=pallas_interpret,
                  append_window=S, applies_per_round=S,
                  pool_budgets=tuple(
                      int(x) for x in bench.MIXED_POOL_BUDGETS.split(",")),
                  timer_min=bench.MIXED_TIMERS[0],
                  timer_max=bench.MIXED_TIMERS[1],
                  resource=bench.RESOURCE_CONFIGS["mixed"], **extra)


# -- phase 1: the raw tensor plane -----------------------------------------

def raw_plane_program(config, G: int, P: int, S: int):
    """``bench.run_throughput``'s scan, with every op tagged by its
    (round, slot) so the host can replay the commit order: returns the
    state, the committed count, each group's largest counter result and
    the sampled groups' per-round apply reports."""
    import jax
    import jax.numpy as jnp

    from copycat_tpu import bench
    from copycat_tpu.ops import apply as ap
    from copycat_tpu.ops.consensus import install_snapshots, step

    opc = mixed_pattern(S)[0]
    add_slots = jnp.asarray(opc == ap.OP_LONG_ADD)
    slot = jnp.arange(S, dtype=jnp.int32)[None, :]

    def run(state, key, pattern, victims, sample):
        def body(carry, xs):
            state, key, applied_prev, add_max = carry
            victim, r = xs
            key, k = jax.random.split(key)
            sub = pattern._replace(
                tag=jnp.broadcast_to(r * S + slot + 1, (G, S)))
            state, out = step(state, sub, bench.victim_deliver(victim, G, P),
                              k, config=config)
            state = install_snapshots(state, out.stale, out.leader,
                                      config=config)
            applied_now = jnp.max(state.applied_index, axis=1)
            n = jnp.sum(applied_now - applied_prev, dtype=jnp.int32)
            is_add = out.out_valid & (out.out_tag > 0) \
                & add_slots[(out.out_tag - 1) % S]
            add_max = jnp.maximum(add_max, jnp.max(
                jnp.where(is_add, out.out_result, 0), axis=1))
            report = tuple(x[sample] for x in (
                out.out_valid, out.out_tag, out.out_result, out.out_index))
            return (state, key, applied_now, add_max), (n, report)

        applied0 = jnp.max(state.applied_index, axis=1)
        rounds = jnp.arange(victims.shape[0], dtype=jnp.int32)
        (state, key, _, add_max), (counts, reports) = jax.lax.scan(
            body, (state, key, applied0, jnp.zeros((G,), jnp.int32)),
            (victims, rounds))
        return state, counts.sum(), add_max, reports

    return jax.jit(run)


def replay_reports(reports, pattern, S: int, groups) -> int:
    """Replay each sampled group's apply reports in log-index order on a
    :class:`PlainGroup`; every reported result must equal the model's.
    Returns the number of results compared."""
    opc, a_, b_ = pattern
    valid, tag, result, index = (np.asarray(x) for x in reports)
    compared = 0
    for k, g in enumerate(groups):
        seen: dict[int, tuple[int, int]] = {}
        rr, aa = np.nonzero(valid[:, k] & (tag[:, k] > 0))
        for r, a in zip(rr.tolist(), aa.tolist()):
            entry = (int(tag[r, k, a]), int(result[r, k, a]))
            idx = int(index[r, k, a])
            # at-least-once: a lane catching up re-reports the same entry
            if seen.setdefault(idx, entry) != entry:
                raise AssertionError(
                    f"group {g}: index {idx} reported twice with "
                    f"different contents: {seen[idx]} vs {entry}")
        tags = [t for t, _ in seen.values()]
        if len(set(tags)) != len(tags):
            raise AssertionError(f"group {g}: an op applied twice")
        model = PlainGroup()
        for idx in sorted(seen):
            t, got = seen[idx]
            j = (t - 1) % S
            want = model.apply(int(opc[j]), int(a_[j]), int(b_[j]), idx)
            if want != got:
                raise AssertionError(
                    f"group {g} index {idx} tag {t} (op {int(opc[j])}): "
                    f"device returned {got}, the plain model {want}")
            compared += 1
    return compared


def raw_plane(compiles: Compiles, seed: int, G: int = 100_000, P: int = 5,
              L: int = 32, S: int = 16, rounds: int = 48,
              sample: int = 2048, pallas_interpret: bool = False) -> None:
    import jax
    from functools import partial

    from copycat_tpu import bench
    from copycat_tpu.ops.consensus import (
        full_delivery, init_state, make_submits, step)

    t0 = time.perf_counter()
    config = mixed_config(S, pallas_interpret)
    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    state = init_state(G, P, L, init_key, config)
    jit_step = jax.jit(partial(step, config=config))
    state, key = bench.elect_all(state, jit_step, make_submits(G, S),
                                 full_delivery(G, P), key, G)
    rng = np.random.default_rng(seed)
    groups = np.sort(rng.choice(G, min(sample, G), replace=False))
    victims = bench.isolation_masks(rounds, G, P, period=20, seed=seed + 1)
    run = raw_plane_program(config, G, P, S)
    state, n, add_max, reports = run(
        state, key, bench.mixed_submits(G), victims, jax.numpy.asarray(groups))
    n = int(jax.block_until_ready(n))
    if n <= 0:
        raise AssertionError("raw plane: nothing committed")
    compared = replay_reports(reports, mixed_pattern(S), S, groups)
    # every group: the counter on the most-applied lane is the largest
    # LONG_ADD result ever reported for it (no lost or doubled add)
    value, applied = (np.asarray(x) for x in jax.device_get(
        (state.resources.value, state.applied_index)))
    best = value[np.arange(G), applied.argmax(axis=1)]
    add_max = np.asarray(add_max)
    # replicas that applied the same prefix hold the same counter
    split = ((applied[:, :, None] == applied[:, None, :])
             & (value[:, :, None] != value[:, None, :])).any(axis=(1, 2))
    if split.any():
        bad = np.flatnonzero(split)
        raise AssertionError(
            f"raw plane: replicas of {bad.size} groups diverged, first "
            f"{bad[0]}: applied {applied[bad[0]]} counters {value[bad[0]]}")
    if not (best == add_max).all():
        bad = np.flatnonzero(best != add_max)
        raise AssertionError(
            f"raw plane: {bad.size} groups' counters differ from their "
            f"reported results, first {bad[:5]}: state {best[bad[:5]]} "
            f"vs reports {add_max[bad[:5]]}")
    if compared <= 0 or add_max.max() <= 0:
        raise AssertionError("raw plane: no result was compared")
    say(f"raw plane: mixed G={G} P={P} L={L} S={S} pallas=on nemesis=on "
        f"budgets={bench.MIXED_POOL_BUDGETS}: {rounds} rounds, {n:,} ops "
        f"committed, {compared:,} results of {groups.size} sampled groups "
        f"equal the plain model, all {G:,} counters equal their reports "
        f"and agree across replicas; "
        f"{compiles.take()}, {time.perf_counter() - t0:.1f}s, process "
        f"peak so far {peak_bytes(jax.devices()[0])}")


# -- phase 2: the bulk client plane (the donation path) --------------------

def bulk_plane(compiles: Compiles, seed: int, G: int = 10_000,
               per_group: int = 64, S: int = 16,
               pallas_interpret: bool = False) -> None:
    import jax

    from copycat_tpu.models import BulkDriver, BulkSessionClient, RaftGroups
    from copycat_tpu.ops import apply as ap
    from copycat_tpu.ops.apply import ResourceConfig
    from copycat_tpu.ops.consensus import Config

    t0 = time.perf_counter()
    rg = RaftGroups(G, 3, log_slots=64, submit_slots=S, seed=seed,
                    config=Config(use_pallas=True,
                                  pallas_interpret=pallas_interpret,
                                  append_window=S, applies_per_round=S,
                                  resource=ResourceConfig.counters_only(),
                                  monotone_tag_accept=True))
    rg.wait_for_leaders()
    rng = np.random.default_rng(seed)
    groups = np.repeat(np.arange(G), per_group)
    total = np.zeros(G, np.int64)
    driver = BulkDriver(rg)
    client = session = None
    # two drives per form: the second reuses the buffers the first donated
    for form in ("deep_step", "deep_step", "deep_scan", "deep_scan"):
        deltas = rng.integers(1, 100, groups.size)
        if form == "deep_step":
            got = driver.drive(groups, ap.OP_LONG_ADD, deltas).results
        else:
            if client is None:
                client = BulkSessionClient(rg, deep_scan=True)
                session = client.open_session()
            seqs = session.submit_batch(groups, ap.OP_LONG_ADD, deltas)
            if client.flush() != groups.size:
                raise AssertionError("bulk plane: sessioned drive "
                                     "committed short")
            got = session.results_window(int(seqs[0]), groups.size)
        per = deltas.reshape(G, per_group)
        want = (total[:, None] + np.cumsum(per, axis=1)).ravel()
        if not (np.asarray(got) == want).all():
            bad = np.flatnonzero(np.asarray(got) != want)
            raise AssertionError(
                f"bulk plane ({form}): {bad.size} results differ from the "
                f"running sums, first at row {bad[0]}: {got[bad[0]]} vs "
                f"{want[bad[0]]}")
        total += per.sum(axis=1)
    # read back: every acknowledged add is in the state the drives left
    value, applied = (np.asarray(x) for x in jax.device_get(
        (rg.state.resources.value, rg.state.applied_index)))
    if not (value[np.arange(G), applied.argmax(axis=1)] == total).all():
        raise AssertionError("bulk plane: counters read back differ from "
                             "the acknowledged sums")
    say(f"bulk plane: G={G} P=3 L=64 S={S} B={per_group}, donation="
        f"{'on' if rg.donate else 'off'}: 2 deep_step + 2 deep_scan "
        f"drives, {4 * groups.size:,} results equal the running sums and "
        f"read back; {rg.rounds} rounds; {compiles.take()}, "
        f"{time.perf_counter() - t0:.1f}s, process peak so far "
        f"{peak_bytes(jax.devices()[0])}")


# -- phase 3: the served path ----------------------------------------------

async def served_script(executor: str, seed: int, counters: int, others: int,
                        waves: int, port: int) -> tuple[list, int | None]:
    """One op script through the public API; returns every reply in
    script order plus the engine's on-device instance count."""
    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.collections import DistributedMap
    from copycat_tpu.coordination import (
        DistributedLeaderElection, DistributedLock)
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.io.transport import Address
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.manager.device_executor import DeviceEngineConfig
    from copycat_tpu.resource.consistency import Consistency

    rng = np.random.default_rng(seed)
    registry = LocalServerRegistry()
    addr = Address("127.0.0.1", port)
    server = AtomixServer(
        addr, [addr], LocalTransport(registry), election_timeout=0.5,
        heartbeat_interval=0.1, session_timeout=120.0, executor=executor,
        engine_config=DeviceEngineConfig(capacity=1024, num_peers=3))
    await server.open()                       # prewarm: engine + leaders
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=120.0)
    await client.open()
    replies: list = []
    try:
        ctrs = await asyncio.gather(*(
            client.get(f"ctr{i}", DistributedAtomicLong)
            for i in range(counters)))
        maps = [await client.get(f"map{i}", DistributedMap)
                for i in range(others)]
        locks = [await client.get(f"lock{i}", DistributedLock)
                 for i in range(others)]
        elects = [await client.get(f"elect{i}", DistributedLeaderElection)
                  for i in range(others)]
        # pipelined commands: every counter in flight at once, per wave
        deltas = rng.integers(1, 1000, (waves, counters))
        for w in range(waves):
            replies += await asyncio.gather(*(
                c.add_and_get(int(d)) for c, d in zip(ctrs, deltas[w])))
        # every acknowledged write read back, at ATOMIC read consistency
        reads = await asyncio.gather(*(
            c.with_consistency(Consistency.ATOMIC).get() for c in ctrs))
        if reads != deltas.sum(axis=0).tolist():
            raise AssertionError(f"served path ({executor}): ATOMIC reads "
                                 "differ from the acknowledged adds")
        replies += reads
        for m in maps:
            keys = rng.choice(1000, 12, replace=False).tolist()
            vals = rng.integers(1, 10_000, 12).tolist()
            replies += await asyncio.gather(*(
                m.put(k, v) for k, v in zip(keys, vals)))
            back = await asyncio.gather(*(m.get(k) for k in keys))
            if back != vals:
                raise AssertionError(f"served path ({executor}): map reads "
                                     "differ from the acknowledged puts")
            replies += back
            replies.append(await m.put(keys[0], 7))
            replies.append(await m.remove(keys[1]))
            replies.append(await m.get(keys[1]))
            replies.append(await m.size())
        for lk in locks:
            await lk.lock()
            await lk.unlock()
            replies.append(await lk.try_lock())
            await lk.unlock()
        for el in elects:
            epochs: list[int] = []
            await el.on_election(epochs.append)
            for _ in range(200):
                if epochs:
                    break
                await asyncio.sleep(0.01)
            if not epochs:
                raise AssertionError(f"served path ({executor}): sole "
                                     "listener was not elected")
            # the epoch is an opaque fencing token (a log index of the
            # plane that holds the election): compare what it fences
            replies.append(await el.is_leader(epochs[0]))
            replies.append(await el.is_leader(epochs[0] + 999))
            await el.resign()
            replies.append(await el.is_leader(epochs[0]))
        on_device = None
        if executor == "tpu":
            engine = server.server.state_machine.device_engine
            on_device = engine._next_group - len(engine._free)
        return replies, on_device
    finally:
        for node in (client, server):
            try:
                await asyncio.wait_for(node.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass


def served_path(compiles: Compiles, seed: int, counters: int = 1000,
                others: int = 6, waves: int = 3) -> None:
    import jax

    from copycat_tpu.io import codec

    t0 = time.perf_counter()
    native = codec.codec() is not None
    say(f"served path: codec={'native copycat_codec.so' if native else 'python (' + str(codec.codec_error()) + ')'}"
        f", transport=LocalTransport (in-process)")
    got, on_device = asyncio.run(asyncio.wait_for(served_script(
        "tpu", seed, counters, others, waves, 15997), 900))
    took = time.perf_counter() - t0
    want, _ = asyncio.run(asyncio.wait_for(served_script(
        "cpu", seed, counters, others, waves, 15996), 900))
    if got != want:
        bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        raise AssertionError(
            f"served path: {len(bad)} of {len(want)} replies differ from "
            f"the CPU state machines, first at {bad[0]}: {got[bad[0]]!r} "
            f"vs {want[bad[0]]!r}")
    eligible = counters + 3 * others
    if on_device != eligible:
        raise AssertionError(
            f"served path: {on_device} instances on the device, "
            f"{eligible} device-eligible resources created — the rest "
            "fell to the CPU machines in silence")
    say(f"served path: AtomixServer(executor=tpu) capacity=1024 P=3 all "
        f"pools: {counters} longs + {others} maps + {others} locks + "
        f"{others} elections, {on_device} instances on the device; "
        f"{waves * counters} pipelined commands + {counters} ATOMIC "
        f"reads; {len(want):,} replies equal the CPU state machines', "
        f"every acknowledged write read back; {compiles.take()}, "
        f"{took:.1f}s, process peak so far "
        f"{peak_bytes(jax.devices()[0])}")


# -- --chips 4: the path across chips --------------------------------------

def _drive_mesh(devices, seed: int, G: int, P: int, L: int, S: int,
                per_group: int, pallas_interpret: bool):
    """One engine over ``devices`` (a mesh when more than one): elect,
    one ``deep_step`` drive and one ``deep_scan`` drive of the mixed op
    pattern. Returns the results, the placement facts and the engine's
    final integer state (host copies), then drops the engine."""
    import jax

    from copycat_tpu.models import BulkDriver, RaftGroups
    from copycat_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=devices) if len(devices) > 1 else None
    config = mixed_config(S, pallas_interpret, monotone_tag_accept=True)
    with jax.default_device(devices[0]):
        rg = RaftGroups(G, P, log_slots=L, submit_slots=S, seed=seed,
                        config=config, mesh=mesh)
        rg.wait_for_leaders()
        opc, a_, b_ = mixed_pattern(S)
        groups = np.repeat(np.arange(G), per_group)
        j = np.tile(np.arange(per_group) % S, G)
        results = []
        for scan in (False, True):
            res = BulkDriver(rg, deep_scan=scan).drive(
                groups, opc[j], a_[j], b_[j])
            results.append(np.asarray(res.results).reshape(G, per_group))
        facts = {"rounds": rg.rounds, "shards": [], "bytes": []}
        for leaf in jax.tree.leaves(rg.state):
            if leaf.size:  # a compiled-out pool ([G,P,0]) holds nothing
                facts["shards"].append(sorted(
                    (s.device.id, s.data.shape[0])
                    for s in leaf.addressable_shards))
        for d in devices:
            stats = d.memory_stats() or {}
            facts["bytes"].append((stats.get("bytes_in_use"),
                                   stats.get("peak_bytes_in_use")))
        census = None
        if mesh is not None:
            from copycat_tpu.parallel.scaling import census_text
            sub, dl = rg._stage_round(rg._empty_submits()), rg.deliver
            census = census_text(rg._step.lower(
                rg.state, sub, dl, rg._key).compile().as_text())
        final = jax.device_get((rg.state.term, rg.state.commit_index,
                                rg.state.resources.value))
    return np.concatenate(results, axis=1), facts, census, final


def mesh_plane(compiles: Compiles, seed: int, devices, G: int = 400_000,
               P: int = 5, L: int = 32, S: int = 16, per_group: int = 32,
               sample: int = 1024, pallas_interpret: bool = False) -> None:
    import gc

    t0 = time.perf_counter()
    n = len(devices)
    # every check runs and every fact is printed before the phase fails:
    # a four-chip call is too dear to stop at the first finding
    problems: list[str] = []
    got, facts, census, final = _drive_mesh(
        devices, seed, G, P, L, S, per_group, pallas_interpret)
    in_use = [b for b, _ in facts["bytes"]]
    peaks = [p for _, p in facts["bytes"]]
    uneven = [shards for shards in facts["shards"]
              if [rows for _, rows in shards] != [G // n] * n
              or len({dev for dev, _ in shards}) != n]
    say(f"mesh plane: mixed G={G} P={P} L={L} S={S} over {n} chips ran: "
        f"{facts['rounds']} rounds incl. 1 deep_step + 1 deep_scan drive "
        f"of {per_group} ops/group; {len(facts['shards'])} state leaves, "
        f"{len(uneven)} not split {G // n} groups per chip; bytes in use "
        f"per chip {in_use}, peak {peaks}; collectives in the step: "
        f"{census or 'none'}; {compiles.take()}")
    # a quarter of every [G,P,...] leaf on each chip
    if uneven:
        problems.append(f"state leaves not split evenly: {uneven[:3]}")
    if census:
        problems.append(f"the sharded step holds collectives: {census}")
    if all(p is not None for p in peaks) and max(peaks) > 1.5 * min(peaks):
        problems.append("peak bytes differ across chips (something was "
                        f"staged whole on one): {peaks}")
    gc.collect()
    # the comparison: the same seed on ONE chip at the same total size
    want, facts1, _, final1 = _drive_mesh(
        devices[:1], seed, G, P, L, S, per_group, pallas_interpret)
    if not (got == want).all():
        bad = np.argwhere(got != want)
        problems.append(
            f"{len(bad)} results differ between {n} chips and one, first "
            f"at group/op {bad[0]}: {got[tuple(bad[0])]} vs "
            f"{want[tuple(bad[0])]}")
    if not all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(final, final1)):
        problems.append(f"final integer state differs between {n} chips "
                        "and one")
    # and both against the plain model (deep drives commit in submission
    # order), on sampled groups; election epochs are not modelled here
    opc, a_, b_ = mixed_pattern(S)
    rng = np.random.default_rng(seed)
    compared = wrong = 0
    for g in np.sort(rng.choice(G, min(sample, G), replace=False)):
        model = PlainGroup()
        for k in range(2 * per_group):
            j = (k % per_group) % S
            wanted = model.apply(int(opc[j]), int(a_[j]), int(b_[j]), None)
            if wanted is not None:
                compared += 1
                wrong += wanted != got[g, k]
    if wrong or not compared:
        problems.append(f"{wrong} of {compared} sampled results differ "
                        "from the plain model")
    say(f"mesh plane: one chip at the same size ran {facts1['rounds']} "
        f"rounds, peak {facts1['bytes'][0][1]}; {got.size:,} results and "
        f"the final term/commit/counter state compared with the {n}-chip "
        f"run, {compared:,} results with the plain model; "
        f"{compiles.take()}, {time.perf_counter() - t0:.1f}s")
    if problems:
        raise AssertionError("mesh plane: " + "; ".join(problems))
    say(f"mesh plane: results identical to one chip, {G // n} groups of "
        f"every state leaf on each of {n} chips, zero collectives in the "
        "step")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 = only the path across chips and what it "
                             "is compared with")
    args = parser.parse_args()

    import jax

    from copycat_tpu.utils.platform import (
        device_info, enable_compilation_cache)

    cache_dir = enable_compilation_cache()
    device = device_info()
    say(f"chip_smoke: {json.dumps(device)} seed={args.seed} "
        f"compile cache {cache_dir}")
    if device["platform"] != "tpu" or device["device_count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{device['device_count']} x {device['platform']}",
              file=sys.stderr, flush=True)
        raise SystemExit(2)
    compiles = Compiles()
    try:
        if args.chips == 4:
            mesh_plane(compiles, args.seed, jax.devices()[:4])
        else:
            raw_plane(compiles, args.seed)
            bulk_plane(compiles, args.seed)
            served_path(compiles, args.seed)
    except BaseException:
        say(f"chip_smoke: FAILED; in the failed phase: {compiles.take()}")
        raise
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
