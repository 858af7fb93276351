"""The replicated, durable deployment: ``members`` ``AtomixServer(executor="tpu")``
members in one Raft group, each over its own ``Storage(DISK)`` directory with the
configured fsync policy, a fixed wire delay on every message of the
``LocalTransport`` registry, and one ``AtomixClient`` session that knows every
address, driven by ``served.py``'s closed loop through the public resource API.

The reference is ``benchmarks/reference_cluster.PlainCounters``: a dict of ints
that applies each acknowledged delta in order. Against it the plane holds every
reply, every counter read back at ATOMIC, every member's own device value, and
what a cluster reopened over logs cut back to their last sync returns (checks
(a) to (j) below; all exact, limit 0).

Copied: the loop, warm-up, window, traced run and result keys from
``planes/served.py``; ``crash`` from ``copycat_tpu/testing/nemesis.crash_server``.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile
import time

import numpy as np

#: replies still missing this long after the window count as failed
GRACE_S = 5.0
#: warm-up ends when JAX's compile events have been quiet this long
QUIET_S = 2.0
#: between the collection that ends warm-up and the window's first instant
SETTLE_S = 0.5
#: seconds of the window the profiler covers in a traced run
TRACED_S = 3.0
#: the traffic runs this long again after the checks, and the crash cuts it off
CRASH_BURST_S = 0.5
#: a member has this long to apply what the leader committed
CATCH_UP_S = 60.0


def fs_type(path: str) -> str:
    """File system type of the mount that holds ``path`` (``/proc/mounts``)."""
    path, best = os.path.realpath(path), ("", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, kind = line.split()[:3]
                mount = mount.replace("\\040", " ")
                inside = path == mount or path.startswith(
                    mount.rstrip("/") + "/")
                if inside and len(mount) > len(best[0]):
                    best = (mount, kind)
    except OSError:
        pass
    return best[1]


def pick_log_base() -> tuple[str, str]:
    """The first of the system's temporary directory, the working directory
    and the home directory whose file system is not ``tmpfs`` (a sync there
    reaches no device), and its type; the first of them if all are."""
    bases = [tempfile.gettempdir(), os.getcwd(), os.path.expanduser("~")]
    kinds = [fs_type(b) for b in bases]
    for base, kind in zip(bases, kinds):
        if kind != "tmpfs" and os.access(base, os.W_OK):
            return base, kind
    return bases[0], kinds[0]


def mean_sync_ms(directory: str, n: int = 32) -> float:
    """Mean time of appending 4 KiB and fsyncing it, in ``directory``."""
    path, block = os.path.join(directory, "sync-probe"), b"\0" * 4096
    with open(path, "ab") as f:
        t0 = time.perf_counter()
        for _ in range(n):
            f.write(block)
            f.flush()
            os.fsync(f.fileno())
        took = time.perf_counter() - t0
    os.remove(path)
    return took / n * 1e3


def require(ctx) -> None:
    """A program that cannot run the deployment fails here, at once."""
    from copycat_tpu.server.log import Log

    if not hasattr(Log, "synced_tail"):
        raise SystemExit(
            "cluster plane: this program's log does not say how far it was "
            "last synced (Log.synced_tail), so the durability check of "
            f"cell {ctx.cell['name']} cannot cut a log back to it: the "
            "deployment is not supported here")


async def crash(servers: list) -> None:
    """``testing/nemesis.crash_server`` for every member at one instant:
    stop them as a SIGKILL would, without the graceful close (no
    ``log.close()``, no last sync). Nothing is awaited before the last
    member has stopped, so none works on while another is already down."""
    rafts = [server.server for server in servers]
    for raft in rafts:
        raft._closing = True
        raft._open = False
        raft._cancel_timers()
        raft._stop_replication()
        for group in raft.groups:
            for fut in group._commit_futures.values():
                if not fut.done():
                    fut.cancel()
            group._commit_futures.clear()
    for raft in rafts:
        await raft._server.close()
        await raft._client.close()
        raft._peer_connections.clear()


def cut_to_last_sync(group) -> int:
    """Take from a crashed member's log directory everything written after
    its last sync: the newest segment is cut back to the synced length and
    any later file removed. Returns the bytes that went."""
    tail = group.log.synced_tail
    if tail is None:
        return 0
    path, length = tail
    directory, name = os.path.split(path)
    stem, _, first = name[:-len(".seg")].rpartition("-")
    lost = max(0, os.path.getsize(path) - length)
    os.truncate(path, length)
    for other in os.listdir(directory):
        other_stem, _, start = other[:-len(".seg")].rpartition("-")
        if (other.endswith(".seg") and other_stem == stem
                and int(start) > int(first)):
            lost += os.path.getsize(os.path.join(directory, other))
            os.remove(os.path.join(directory, other))
    return lost


class Members:
    """The cluster's members over their directories: open, find the leader,
    read what each holds."""

    def __init__(self, cfg: dict, root: str) -> None:
        from copycat_tpu.io.local import LocalServerRegistry
        from copycat_tpu.io.transport import Address

        self.cfg, self.root = cfg, root
        self.addrs = [Address("127.0.0.1", cfg["port"] + i)
                      for i in range(cfg["members"])]
        self.registry = LocalServerRegistry()
        self.registry.attach_nemesis().set_delay(cfg["wire_delay_ms"] / 1e3)
        self.servers: list = []

    async def open(self) -> None:
        from copycat_tpu.io.local import LocalTransport
        from copycat_tpu.manager.atomix import AtomixServer
        from copycat_tpu.manager.device_executor import DeviceEngineConfig
        from copycat_tpu.server.log import Storage, StorageLevel

        cfg = self.cfg
        sizes = {"capacity": cfg["capacity"], "num_peers": cfg["peers"]}
        if "log_slots" in cfg:          # tiny test sizes only
            sizes["log_slots"] = cfg["log_slots"]
        self.servers = [AtomixServer(
            addr, self.addrs, LocalTransport(self.registry),
            storage=Storage(StorageLevel[cfg["storage"]],
                            os.path.join(self.root, f"member{i}"),
                            fsync=cfg["fsync"]),
            election_timeout=cfg["election_timeout_s"],
            heartbeat_interval=cfg["heartbeat_interval_s"],
            session_timeout=cfg["session_timeout_s"], executor="tpu",
            engine_config=DeviceEngineConfig(**sizes))
            for i, addr in enumerate(self.addrs)]
        await asyncio.gather(*(s.open() for s in self.servers))

    def client(self):
        from copycat_tpu.io.local import LocalTransport
        from copycat_tpu.manager.atomix import AtomixClient

        return AtomixClient(self.addrs, LocalTransport(self.registry),
                            session_timeout=self.cfg["session_timeout_s"])

    @property
    def groups(self) -> list:
        return [s.server.groups[0] for s in self.servers]

    def engines(self) -> list:
        return [g.state_machine.device_engine for g in self.groups]

    async def leader(self, timeout: float = 30.0):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            leaders = [g for g in self.groups if g.role == "leader"]
            if len(leaders) == 1 and all(
                    g.leader_address == leaders[0].address
                    for g in self.groups):
                return leaders[0]
            await asyncio.sleep(0.02)
        raise RuntimeError("cluster plane: no leader that every member "
                           f"knows within {timeout:.0f}s")

    async def caught_up(self, timeout: float = CATCH_UP_S) -> float:
        """Wait until every member has applied what the leader committed;
        the seconds it took."""
        t0 = time.perf_counter()
        target = (await self.leader()).commit_index
        while any(g.last_applied < target for g in self.groups):
            if time.perf_counter() - t0 > timeout:
                raise RuntimeError(
                    "cluster plane: members applied "
                    f"{[g.last_applied for g in self.groups]} of {target} "
                    f"within {timeout:.0f}s")
            await asyncio.sleep(0.02)
        await asyncio.sleep(0.1)     # a parked fused run rides the next turn
        return time.perf_counter() - t0

    def device_values(self, names: list[str]) -> list[list]:
        """Per member, the value its own device engine holds for each named
        counter (``None`` where the member keeps it off the device)."""
        from copycat_tpu.ops import apply as ops

        out = []
        for group in self.groups:
            manager = group.state_machine
            holders = {h.key: h for h in manager.resources.values()}
            rows = [getattr(holders[n].state_machine, "_group", None)
                    if n in holders else None for n in names]
            on = [r for r in rows if r is not None]
            zeros = [0] * len(on)
            got = iter(manager.device_engine.run_query_vector(
                on, [ops.OP_VALUE_GET] * len(on), zeros, zeros, zeros)
                if on else [])
            out.append([None if r is None else next(got) for r in rows])
        return out

    def on_device(self) -> int:
        return sum(e._next_group - len(e._free) for e in self.engines())

    def counts(self) -> dict[str, list[int]]:
        """The program's own counters, one value per member."""
        def read(name):
            return [g.metrics.counter(name).value for g in self.groups]

        return {
            "log_syncs": read("log.syncs"),
            "log_bytes": read("log.bytes_appended"),
            "repl_windows": read("repl.windows_sent"),
            "snapshots": read("snap.snapshots_taken"),
            "snapshot_bytes": read("snap.snapshot_bytes"),
            "truncated": read("snap.truncated_entries"),
            "elections": read("raft_elections_started"),
            "deferred": read("raft_elections_deferred"),
            "installs": read("snap.installs_sent"),
            "rewinds": read("repl.rewinds"),
            "fast_lane": read("commands_fast_lane"),
            "general_lane": read("commands_general_lane"),
            "rounds": [e._groups.metrics.counter("rounds").value
                       for e in self.engines()],
        }

    def lanes(self) -> list[str]:
        return ["snapshot" if g.metrics.gauge("snap.lane").value
                else "replay-only" for g in self.groups]

    async def close(self) -> None:
        for server in self.servers:
            try:
                await asyncio.wait_for(server.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass


async def _drive(ctx, root: str, kind: str) -> dict:
    from benchmarks import reference_cluster
    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.collections import DistributedMap
    from copycat_tpu.coordination import (
        DistributedLeaderElection, DistributedLock)
    from copycat_tpu.io import codec
    from copycat_tpu.resource.consistency import Consistency
    from copycat_tpu.utils import tracing

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    n_ctr, clients, n_mem = cfg["counters"], mix["clients"], cfg["members"]
    if clients != n_ctr:
        raise SystemExit(f"cluster plane: {clients} clients for {n_ctr} "
                         "counters; the mix drives one client per counter")
    perf = time.perf_counter
    t_setup = perf()
    sync_ms = mean_sync_ms(root)
    say(f"cluster plane: logs under {root} on {kind}"
        + (" -- A TMPFS: A SYNC THERE REACHES NO DEVICE" if kind == "tmpfs"
           else "") + f"; a 4 KiB append and fsync takes {sync_ms:.3f} ms "
        f"(mean of 32); storage {cfg['storage']} fsync={cfg['fsync']}")
    members = Members(cfg, root)
    await members.open()
    t_open = perf() - t_setup
    await members.leader()
    client = members.client()
    await client.open()
    names = [f"ctr{i}" for i in range(n_ctr)]
    reopened = client2 = None
    out: dict = {}
    try:
        ctrs = await asyncio.gather(*(
            client.get(name, DistributedAtomicLong) for name in names))
        for kind_, prefix, n in ((DistributedMap, "map", cfg["maps"]),
                                 (DistributedLock, "lock", cfg["locks"]),
                                 (DistributedLeaderElection, "elect",
                                  cfg["elections"])):
            for i in range(n):
                await client.get(f"{prefix}{i}", kind_)
        for c in ctrs:
            c.with_consistency(Consistency.ATOMIC)
        # the delay as this loop delivers it: one leg, and a read's round trip
        t = perf()
        for _ in range(100):
            await asyncio.sleep(cfg["wire_delay_ms"] / 1e3)
        leg_ms = (perf() - t) * 10
        t = perf()
        for _ in range(20):
            await ctrs[0].get()
        read_ms = (perf() - t) * 50
        say(f"cluster plane: {n_mem} members, codec="
            f"{'native' if codec.codec() is not None else 'python'}, "
            f"LocalTransport with {cfg['wire_delay_ms']} ms one way on every "
            f"message (measured on an idle loop: a leg {leg_ms:.3f} ms, an "
            f"ATOMIC read's round trip {read_ms:.3f} ms); capacity "
            f"{cfg['capacity']} P={cfg['peers']}; {n_ctr} longs + "
            f"{cfg['maps']} maps + {cfg['locks']} locks + {cfg['elections']} "
            f"elections; members open {t_open:.1f}s, with the client and the "
            f"creates {perf() - t_setup:.1f}s; {ctx.compiles.note()}")
        say("cluster plane: recovery lane by member: "
            + ", ".join(members.lanes()))

        # the traffic, from the seed: one shared ring of draws, each client
        # starting at its own offset
        rng = np.random.default_rng(ctx.seed)
        ring = 1 << 16
        deltas = rng.integers(mix["delta_min"], mix["delta_max"] + 1,
                              ring).tolist()
        is_read = (rng.random(ring) < mix["read_share"]).tolist()
        offsets = rng.integers(0, ring, clients).tolist()

        model = reference_cluster.PlainCounters()
        pending: dict[str, int] = {}      # an add sent and not yet answered
        calls: list[float] = []           # every reply: call instant
        acks: list[float] = []            # every reply: reply instant
        state = {"stop": False, "issued": 0, "raised": 0, "wrong": 0,
                 "first_wrong": "", "flip": ctx.fault == "flip-result"}

        async def one(i: int) -> None:
            c, name, mask = ctrs[i], names[i], ring - 1
            while not state["stop"]:
                k = offsets[i] = (offsets[i] + 1) & mask
                read, d = is_read[k], deltas[k]
                state["issued"] += 1
                if not read:
                    pending[name] = d
                t = perf()
                try:
                    got = await (c.get() if read else c.add_and_get(d))
                except Exception as e:  # noqa: BLE001 - counted, not hidden
                    state["raised"] += 1
                    state["first_wrong"] = state["first_wrong"] or repr(e)
                    continue
                calls.append(t)
                acks.append(perf())
                if read:
                    want = model.get(name)
                else:
                    del pending[name]
                    want = model.add(name, d)
                if state["flip"] and not read:
                    got, state["flip"] = got ^ 1, False
                if got != want:
                    state["wrong"] += 1
                    state["first_wrong"] = state["first_wrong"] or (
                        f"{name}: reply {got}, the model holds {want}")

        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]

        # warm-up: the cell's own traffic until nothing has compiled for
        # QUIET_S (the fused-rounds programs compile on demand), as the
        # served plane's. The members are then some 3 s into their first
        # traffic and their engines' 64-slot log rings are still filling:
        # every capture packs a fuller ring than the last until they have
        # wrapped (48 s in): the engine's part of an image climbs through
        # the window (0.2 to 0.6 MB, PR 51). The rate no longer falls with
        # it: what fell was the sessions' reply cache a capture walked,
        # which a session now bounds by count (PERF.md section 6, PR 51).
        t_warm, quiet = perf(), mix.get("warmup_quiet_s", QUIET_S)
        while True:
            await asyncio.sleep(0.25)
            if ctx.compiles.quiet_for() >= quiet and perf() - t_warm >= quiet:
                break
            if perf() - t_warm > 300:
                raise RuntimeError("cluster plane: still compiling after "
                                   "300 s of warm-up")
        ctx.gc_tune()
        # the collection holds the loop: let the calls it delayed be answered
        # before the window opens, or they sit in its tail. Here a turn of
        # the closed loop takes longer than SETTLE_S: wait until every client
        # has had an answer since the collection
        settled = len(acks) + clients
        while len(acks) < settled and perf() - t_warm < 330:
            await asyncio.sleep(0.05)
        await asyncio.sleep(SETTLE_S)
        say(f"cluster plane: warm-up {perf() - t_warm:.1f}s, "
            f"{len(acks):,} calls; recovery lane by member: "
            + ", ".join(members.lanes()) + f"; {ctx.compiles.note()}")

        # -- the window ------------------------------------------------------
        leader0 = (await members.leader()).address
        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
        compiled_before = ctx.compiles.count
        before, issued0, first = members.counts(), state["issued"], len(acks)
        t_start = perf()
        held: list[tuple[float, float]] = []   # the profiler held the loop
        profiler_elections = 0
        if ctx.trace:
            await asyncio.sleep(min(1.0, ctx.seconds / 4))
            t = perf()
            ctx.profile_start()
            held.append((t, perf()))
            await asyncio.sleep(min(TRACED_S, ctx.seconds / 2))
            t, e0 = perf(), sum(members.counts()["elections"])
            ctx.profile_stop()
            held.append((t, perf()))
            # the stop holds the loop for seconds, longer than an election
            # timeout: the timers it overran fire now, and are its doing
            await asyncio.sleep(2 * cfg["election_timeout_s"])
            profiler_elections = sum(members.counts()["elections"]) - e0
        await asyncio.sleep(max(0.0, t_start + ctx.seconds - perf()))
        t_end = perf()
        state["stop"] = True
        after = members.counts()
        inside_window = {k: [a - b for a, b in zip(after[k], before[k])]
                         for k in after}
        issued = state["issued"] - issued0
        compiled_inside = ctx.compiles.count - compiled_before
        spans: dict[str, list[float]] = {}
        if ctx.trace:
            tracing.disable()
            by_member: dict[tuple[str, str], list[float]] = {}
            for trace in tracing.TRACER.traces().values():
                for s in trace:
                    spans.setdefault(s.name, []).append(s.duration_ms)
                    if s.meta and "member" in s.meta:
                        by_member.setdefault(
                            (str(s.meta["member"]), s.name), []).append(
                                s.duration_ms)
            report = tracing.TRACER.report()
            say("cluster plane: spans over the whole window (program's "
                "report): " + ", ".join(
                    f"{name} x{v['n']} mean {v['mean_ms']:.3f} ms"
                    for name, v in sorted(report["spans"].items())))
            say("cluster plane: captures in the ring, oldest first, ms: "
                + "; ".join(
                    f"{name} " + " ".join(f"{d:.0f}" for d in spans[name])
                    for name in ("snapshot.capture", "snapshot.fetch",
                                 "snapshot.write") if name in spans))
            say(f"cluster plane: spans in the ring at window end by member "
                f"(the leader was {leader0}): " + ", ".join(
                    f"{member} {name} x{len(d)} mean {sum(d) / len(d):.3f} ms"
                    for (member, name), d in sorted(by_member.items())))
        _, unanswered = await asyncio.wait(tasks, timeout=GRACE_S)
        for t in unanswered:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

        # -- the checks, outside the window ----------------------------------
        t_check = perf()
        acks_a, calls_a = np.asarray(acks[first:]), np.asarray(calls[first:])
        inside = acks_a <= t_end
        lat_ms = (acks_a[inside] - calls_a[inside]) * 1e3
        acked = int(inside.sum())
        # a traced run's tail: calls in flight while the profiler started or
        # stopped (it holds the event loop for seconds) are left out
        clear = np.ones(acked, bool)
        for h0, h1 in held:
            clear &= (acks_a[inside] < h0) | (calls_a[inside] > h1)
        if ctx.fault == "drop-ack":
            name = next(n for n in names if model.get(n) > 0)
            model.values[name] -= deltas[0]
        # ``pending`` now holds the adds that raised or were cancelled
        # unanswered: each may have been committed all the same
        with ctx.annotate("check"):
            back = await asyncio.wait_for(asyncio.gather(*(
                c.get() for c in ctrs)), 60)
        unread, first_unread = reference_cluster.differences(
            model, names, back, pending)
        await members.caught_up()
        held_by = members.device_values(names)
        off_model, first_off = 0, ""
        for addr, values in zip(members.addrs, held_by):
            n, what = reference_cluster.differences(
                model, names, values, pending)
            off_model += n
            first_off = first_off or (f"member {addr} {what}" if n else "")
        eligible = n_mem * (n_ctr + cfg["maps"] + cfg["locks"]
                            + cfg["elections"])
        failed = state["raised"] + len(unanswered)
        first_wrong = state["first_wrong"]
        elections = sum(inside_window["elections"]) - profiler_elections
        unsynced = sum(1 for n in inside_window["log_syncs"] if n == 0)
        no_snapshot = sum(1 for n in inside_window["snapshots"] if n == 0)
        on_device = members.on_device()
        lanes = members.lanes()
        say(f"cluster plane: inside the window by member: log syncs "
            f"{inside_window['log_syncs']}, log bytes "
            f"{inside_window['log_bytes']}, replication windows sent "
            f"{inside_window['repl_windows']}, snapshots "
            f"{inside_window['snapshots']} of "
            f"{inside_window['snapshot_bytes']} bytes (entries released "
            f"{inside_window['truncated']}), engine rounds "
            f"{inside_window['rounds']}, elections started "
            f"{inside_window['elections']} (deferred once after a stall of "
            f"the loop {inside_window['deferred']}; since boot "
            f"{after['elections']}), snapshot installs sent "
            f"{inside_window['installs']}, replication rewinds "
            f"{inside_window['rewinds']}, commands staged as one block "
            f"{inside_window['fast_lane']} and one by one "
            f"{inside_window['general_lane']}; recovery lane by member: "
            + ", ".join(lanes))

        # -- durability: the traffic again, cut off by a crash of every
        # member; logs cut back to their last sync; a fresh cluster over them
        state["stop"], acked_before = False, len(acks)
        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]
        await asyncio.sleep(mix.get("crash_burst_s", CRASH_BURST_S))
        t_crash = perf()
        state["stop"] = True             # a loop ends at its call's end
        await crash(members.servers)
        await asyncio.sleep(0.05)        # replies already on the wire land
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        lost = [cut_to_last_sync(g) for g in members.groups]
        say(f"cluster plane: crashed {n_mem} members {perf() - t_crash:.2f}s "
            f"ago with {len(pending)} adds unanswered, "
            f"{len(acks) - acked_before:,} acknowledged since the checks; "
            f"bytes past the last sync, cut away: {lost}")
        try:                             # nobody is left to answer it
            await asyncio.wait_for(client.close(), 1)
        except (Exception, asyncio.TimeoutError):  # noqa: BLE001
            pass
        client = None
        members.servers = []             # drop the crashed engines
        t_reopen = perf()
        reopened = Members(cfg, root)
        await reopened.open()
        await reopened.leader()
        client2 = reopened.client()
        await client2.open()
        ctr0 = await client2.get(names[0], DistributedAtomicLong)
        ctr0.with_consistency(Consistency.ATOMIC)
        await ctr0.get()
        first_reply_s = perf() - t_reopen
        ctrs2 = [ctr0] + list(await asyncio.gather(*(
            client2.get(name, DistributedAtomicLong) for name in names[1:])))
        for c in ctrs2:
            c.with_consistency(Consistency.ATOMIC)
        recovered = await asyncio.wait_for(asyncio.gather(*(
            c.get() for c in ctrs2)), 120)
        await reopened.caught_up()
        caught_up_s = perf() - t_reopen
        undurable, first_undurable = reference_cluster.differences(
            model, names, recovered, pending)
        say(f"cluster plane: reopened over the cut logs: first reply after "
            f"{first_reply_s:.2f}s, every member caught up after "
            f"{caught_up_s:.2f}s; recovery lane by member: "
            + ", ".join(reopened.lanes()) + "; snapshots restored "
            f"{[g.metrics.counter('snap.restores').value for g in reopened.groups]}"
            f", log first index {[g.log.first_index for g in reopened.groups]}")

        def why(text: str) -> str:
            return f": {text}" if text else ""

        checks = [
            (f"(a) replies of {len(acks):,} that differ from the model's "
             "value after that add" + why(first_wrong),
             state["wrong"], 0),
            (f"(b) counters of {n_ctr} whose ATOMIC read-back differs from "
             "the model" + why(first_unread), unread, 0),
            (f"(c) counters of {n_mem} x {n_ctr} whose value on the member's "
             "own device engine differs from the model" + why(first_off),
             off_model, 0),
            (f"(d) resources of {eligible} not on the device",
             eligible - on_device, 0),
            ("(e) calls that raised, timed out or got no reply within "
             f"{GRACE_S:.0f}s of the window", failed, 0),
            ("(f) elections started inside the window"
             + (f" ({profiler_elections} more in the second after the "
                "profiler held the loop)" if profiler_elections else ""),
             elections, 0),
            (f"(g) members of {n_mem} with no log sync inside the window",
             unsynced, 0),
            ("(h) compilations inside the window", compiled_inside, 0),
            (f"(i) counters of {n_ctr} that a cluster reopened over logs cut "
             "back to their last sync reads otherwise than the model"
             + why(first_undurable), undurable, 0),
            (f"(j) members of {n_mem} with no snapshot taken inside the "
             "window (replay-only recovery)", no_snapshot, 0),
        ]
        correct = acked > 0 and all(v <= lim for _, v, lim in checks)
        p50, p99 = (float(np.percentile(lat_ms, q)) if acked else 0.0
                    for q in (50, 99))
        p99_clear = (float(np.percentile(lat_ms[clear], 99))
                     if clear.any() else None)
        window = t_end - t_start
        fifths = np.histogram(acks_a[inside], bins=5,
                              range=(t_start, t_end))[0] / (window / 5)
        edges = np.linspace(t_start, t_end, 6)[1:-1]
        tails = [float(np.percentile(part, 99)) for part in np.split(
            lat_ms, np.searchsorted(acks_a[inside], edges)) if len(part)]
        say("cluster plane: ack p99 ms by fifths of the window: "
            + ", ".join(f"{t:.1f}" for t in tails))
        say("cluster plane: acknowledged ops/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        say(f"cluster plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{acked:,} acknowledged inside it, ack p50 {p50:.3f} ms p99 "
            f"{p99:.3f} ms over {acked:,} samples; checks and recovery took "
            f"{perf() - t_check:.1f}s")
        if held:
            say("cluster plane: the profiler held the loop "
                + " and ".join(f"{h1 - h0:.1f}s" for h0, h1 in held)
                + f"; ack p99 {p99_clear} ms over the {int(clear.sum()):,} "
                "calls not in flight then")
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window,
                           "ack_p99_ms": p99},
            "clock": {"ack_p50_ms": p50, "ack_p99_ms": p99_clear,
                      "window_s": window, "acked_ops": acked,
                      "first_reply_s": first_reply_s,
                      "caught_up_s": caught_up_s},
            "spans": spans,
            "counters": {k: sum(v) for k, v in inside_window.items()},
        }
    finally:
        for node in (client2, client):
            if node is not None:
                try:
                    await asyncio.wait_for(node.close(), 5)
                except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                    pass
        for cluster in (reopened, members):
            if cluster is not None:
                await cluster.close()
    return out


def run(ctx) -> dict:
    require(ctx)
    base, kind = pick_log_base()
    root = tempfile.mkdtemp(prefix="cluster-logs-", dir=base)
    try:
        out = asyncio.run(asyncio.wait_for(_drive(ctx, root, kind), 1200))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    # each number compared beside its limit, the last lines of standard error
    for what, value, limit in out["checks"]:
        print(f"cluster plane: check: {what}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    return out
