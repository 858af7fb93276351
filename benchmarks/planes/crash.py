"""The replicated, durable deployment losing a member inside the window: the
``cluster`` plane's three ``AtomixServer(executor="tpu")`` members over their
``Storage(DISK)`` directories, its wire and its one ``AtomixClient`` session,
driven by the same closed loop, while a follower is killed and restarted over
its cut log and then the leader is (``traffic/kill-rejoin.json`` has the
schedule). The clients are never told: a call that raises, or waits longer than
``call_deadline_s``, fails the run.

A kill is ``testing/nemesis.crash_server`` (no graceful close, no last sync);
before the restart the dead member's capture thread is waited for off the loop
and its log directory is cut back to its last sync (``cut_to_last_sync``); the
restart is a new ``AtomixServer`` over that directory, built and opened on the
loop its two live neighbours share, and what it held that loop for is printed.

The reference is ``benchmarks/reference_crash.PlainCounters``: a dict of ints
that applies each acknowledged delta once. Checks (a) to (j), all exact, limit
0, are listed in ``benchmarks/README.crash.md``.

What the ``cluster`` plane already has is its own, used from its module
(``planes/cluster.py``, which is not edited): ``pick_log_base``,
``mean_sync_ms``, ``crash``, ``cut_to_last_sync`` and, under this plane's
``Members``, the members' directories, the client, the leader that every
member knows, ``caught_up`` and what each member's device engine holds. The
closed loop, the warm-up and the durability block live inside that plane's
``_drive`` and are written out here again; the profiler's stop on a thread
and the stall probe are ``planes/election.py``'s.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmarks import reference_crash as reference
from benchmarks.planes import cluster as base

#: every set-up step and every wait ends within this, or the run exits
STEP_DEADLINE_S = 300.0
#: a task that sleeps this long, to see how long the loop was held
STALL_PROBE_S = 0.01
#: how often the plane looks at who leads and who has caught up
WATCH_S = 0.01
#: how often a traced run reads the failure's spans out of the tracer's ring
HARVEST_S = 1.0
#: the traced seconds lie this far from every event, on both sides
CLEAR_S = 1.0
#: the spans of a failure, which a traced run prints one by one
FIVE = frozenset(("client.failover", "raft.election", "server.recover",
                  "snapshot.install", "snapshot.restore"))


def require(ctx) -> None:
    """A program that cannot run the deployment fails here, at once: on a
    wire whose closed connection fails nothing in flight on it, a call in
    flight at a killed member waits out the session's timeout or is
    cancelled into its caller."""
    from copycat_tpu.io.local import LocalConnection

    base.require(ctx)
    if not hasattr(LocalConnection, "_abort"):
        raise SystemExit(
            "crash plane: this program's LocalConnection does not fail the "
            "sends in flight on a connection that closes "
            f"(LocalConnection._abort): cell {ctx.cell['name']} kills "
            "members under load, and the deployment is not supported here")


def check_schedule(mix: dict, seconds: float) -> list[tuple[str, float]]:
    """The four events in order, on the window's clock; refuses a window
    too short for them. A run of other length scales nothing."""
    events = [(name, float(mix[name + "_at_s"])) for name in (
        "follower_kill", "follower_restart", "leader_kill", "leader_restart")]
    at = [t for _, t in events]
    if at != sorted(at) or at[0] <= 0:
        raise SystemExit(f"crash plane: the schedule {events} is not in "
                         "order inside the window")
    need = at[-1] + mix["tail_s"]
    if seconds + 1e-9 < need:
        raise SystemExit(
            f"crash plane: a window of {seconds:g} s is shorter than the "
            f"last restart at +{at[-1]:g} s plus {mix['tail_s']:g} s; the "
            f"schedule is not scaled: run at least --seconds {need:g}")
    return events


#: the program's counters the plane reads, by its own short name
COUNTERS = {
    "log_syncs": "log.syncs",
    "log_bytes": "log.bytes_appended",
    "repl_windows": "repl.windows_sent",
    "snapshots": "snap.snapshots_taken",
    "elections": "raft_elections_started",
    "deferred": "raft_elections_deferred",
    "installs": "snap.installs_sent",
    "installs_received": "snap.installs_received",
    "install_chunks": "snap.install_chunks_sent",
    "restores": "snap.restores",
    "rewinds": "repl.rewinds",
    "fast_lane": "commands_fast_lane",
    "general_lane": "commands_general_lane",
}


class Members(base.Members):
    """The ``cluster`` plane's members, of which one can be killed and
    restarted. ``servers[i]`` is the incarnation of member ``i`` that is up
    (or was last); ``past`` keeps the killed ones, whose counters the window
    still owes; what the ``cluster`` plane's ``Members`` reads of ``groups``
    (the leader every member knows, ``caught_up``, the device values) it
    reads here of the members that are up."""

    def __init__(self, cfg: dict, root: str) -> None:
        super().__init__(cfg, root)
        self.down: set[int] = set()
        self.past: list = []

    def build(self, i: int):
        """A new incarnation of member ``i`` over its directory. The
        constructor runs the member's boot recovery."""
        from copycat_tpu.io.local import LocalTransport
        from copycat_tpu.manager.atomix import AtomixServer
        from copycat_tpu.manager.device_executor import DeviceEngineConfig
        from copycat_tpu.server.log import Storage, StorageLevel

        cfg = self.cfg
        sizes = {"capacity": cfg["capacity"], "num_peers": cfg["peers"]}
        segment = {}
        if "log_slots" in cfg:          # tiny test sizes only
            sizes["log_slots"] = cfg["log_slots"]
        if "segment_entries" in cfg:    # tiny test sizes only: a log whose
            # prefix a few hundred operations release
            segment["max_entries_per_segment"] = cfg["segment_entries"]
        return AtomixServer(
            self.addrs[i], self.addrs, LocalTransport(self.registry),
            storage=Storage(StorageLevel[cfg["storage"]],
                            os.path.join(self.root, f"member{i}"),
                            fsync=cfg["fsync"], **segment),
            election_timeout=cfg["election_timeout_s"],
            heartbeat_interval=cfg["heartbeat_interval_s"],
            session_timeout=cfg["session_timeout_s"], executor="tpu",
            engine_config=DeviceEngineConfig(**sizes))

    async def open(self) -> None:
        self.servers = [self.build(i) for i in range(len(self.addrs))]
        await asyncio.gather(*(s.open() for s in self.servers))

    def group(self, i: int):
        return self.servers[i].server.groups[0]

    @property
    def up(self) -> list[int]:
        return [i for i in range(len(self.servers)) if i not in self.down]

    @property
    def groups(self) -> list:
        """The groups of the members that are up."""
        return [self.group(i) for i in self.up]

    def leader_now(self) -> int | None:
        """The member that leads and that every member up knows to lead."""
        groups = {i: self.group(i) for i in self.up}
        leaders = [i for i, g in groups.items() if g.role == "leader"]
        if len(leaders) == 1 and all(
                g.leader_address == self.addrs[leaders[0]]
                for g in groups.values()):
            return leaders[0]
        return None

    async def kill(self, i: int) -> None:
        self.down.add(i)
        await base.crash([self.servers[i]])

    async def restart(self, i: int) -> dict:
        """Member ``i`` again, over what its last sync left: the dead
        incarnation's capture thread is waited for beside the loop, its log
        is cut, and a new server is built (boot recovery: snapshot, then the
        log's tail) and opened (its device engine) on the loop."""
        perf = time.perf_counter
        old = self.servers[i]
        worker = old.server._snap_worker
        if worker is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, worker.shutdown, True)
        lost = base.cut_to_last_sync(old.server.groups[0])
        t0 = perf()
        new = self.build(i)
        t1 = perf()
        await new.open()
        t2 = perf()
        self.past.append(old)
        self.servers[i] = new
        self.down.discard(i)
        group = new.server.groups[0]
        return {"cut_bytes": lost, "build_s": t1 - t0, "open_s": t2 - t1,
                "called": t0, "opened": t2,
                "snapshot_index": group._snap_index,
                "last_index": group.log.last_index,
                "restores": group.metrics.counter("snap.restores").value}

    def counts(self) -> dict[str, int]:
        """The program's own counters, summed over every incarnation of
        every member: a killed member's counts stand as its death left
        them, a restarted one starts at 0, so the difference of two
        readings is what the window saw."""
        every = [s.server.groups[0] for s in self.servers + self.past]
        out = {short: sum(g.metrics.counter(name).value for g in every)
               for short, name in COUNTERS.items()}
        out["rounds"] = sum(
            g.state_machine.device_engine._groups.metrics.counter(
                "rounds").value for g in every)
        return out

    def lanes(self) -> list[str]:
        up = iter(super().lanes())
        return ["down" if i in self.down else next(up)
                for i in range(len(self.servers))]

    async def close(self) -> None:
        self.servers = [self.servers[i] for i in self.up]
        await super().close()


def longest_gap(acks: np.ndarray, t0: float, t1: float
                ) -> tuple[float, float]:
    """The longest time without an acknowledgement between ``t0`` and
    ``t1``, their two instants included: its seconds, and the instant it
    ended (the service answered again)."""
    inside = acks[(acks >= t0) & (acks <= t1)]
    edges = np.concatenate(([t0], inside, [t1]))
    gaps = np.diff(edges)
    k = int(gaps.argmax())
    return float(gaps[k]), float(edges[k + 1])


def checks_of(facts: dict) -> list[tuple[str, int, int]]:
    """Checks (a) to (j) from what a run found: what, value, limit. All
    exact, limit 0."""
    def why(text: str) -> str:
        return f": {text}" if text else ""

    f = facts
    n_ctr, n_mem = f["counters"], f["members"]
    changes, killed_at = f["leader_changes_at"], f["leader_kill_at"]
    bad_schedule = f["events"] - f["events_reached"]
    if f["killed_as_leader_role"] != "leader":
        bad_schedule += 1
    if len(changes) != 1 or killed_at is None or changes[0] < killed_at:
        bad_schedule += 1
    rejoins = f["rejoins_caught_up_at"]
    not_caught_up = (f["events"] // 2 - len(rejoins)) + sum(
        1 for t in rejoins if t is None or t > f["window_s"])
    no_install = sum(1 for n in f["rejoins_installs_received"] if n == 0)
    failed = f["raised"] + f["overdue"] + f["unanswered"]
    return [
        (f"(a) replies of {f['replies']:,} that differ from the model's "
         "value after that add" + why(f["first_wrong"]), f["wrong"], 0),
        (f"(b) counters of {n_ctr} whose ATOMIC read-back differs from "
         "the model" + why(f["first_unread"]), f["unread"], 0),
        (f"(c) counters of {n_mem} x {n_ctr} whose value on the member's "
         "own device engine differs from the model, the two rejoined "
         "members' included" + why(f["first_off"]), f["off_model"], 0),
        (f"(d) resources of {f['eligible']} not on the device, the "
         "restarted members' included", f["eligible"] - f["on_device"], 0),
        ("(e) calls that raised, timed out or had no reply within "
         f"{f['deadline']:g}s ({f['raised']} raised, {f['overdue']} "
         f"overdue, {f['unanswered']} unanswered at the end)"
         + why(f["first_raised"]), failed, 0),
        (f"(f) the schedule: events of {f['events']} not reached, the "
         "member killed as leader not leading, or other than one change "
         f"of leader after that kill ({len(changes)} changes)",
         bad_schedule, 0),
        (f"(g) rejoins of {f['events'] // 2} not caught up before the "
         f"window's end ({not_caught_up}) or served without an install "
         f"({no_install})", not_caught_up + no_install, 0),
        ("(h) programs compiled afresh inside the window "
         f"({f['programs_inside']} compiled or loaded)",
         f["compiled_inside"], 0),
        (f"(i) counters of {n_ctr} that a cluster reopened over logs cut "
         "back to their last sync reads otherwise than the model"
         + why(f["first_undurable"]), f["undurable"], 0),
        ("(j) sessions expired", f["expired"], 0),
    ]


async def _drive(ctx, root: str, kind: str) -> dict:
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
        raise SystemExit(f"crash plane: {clients} clients for {n_ctr} "
                         "counters; the mix drives one client per counter")
    schedule = check_schedule(mix, ctx.seconds)
    deadline = mix["call_deadline_s"]
    if ctx.trace and not all(
            t + CLEAR_S <= mix["profile_at_s"] or mix["profile_at_s"]
            + mix["profile_s"] + CLEAR_S <= t for _, t in schedule):
        raise SystemExit("crash plane: the traced seconds do not lie "
                         f"{CLEAR_S:g} s clear of every event")
    perf = time.perf_counter
    loop = asyncio.get_running_loop()
    t_setup = perf()
    sync_ms = base.mean_sync_ms(root)
    say(f"crash plane: logs under {root} on {kind}"
        + (" -- A TMPFS: A SYNC THERE REACHES NO DEVICE" if kind == "tmpfs"
           else "") + f"; a 4 KiB append and fsync takes {sync_ms:.3f} ms "
        f"(mean of 32); storage {cfg['storage']} fsync={cfg['fsync']}")
    members = Members(cfg, root)
    await asyncio.wait_for(members.open(), STEP_DEADLINE_S)
    t_open = perf() - t_setup
    await members.leader()
    client = members.client()
    await asyncio.wait_for(client.open(), STEP_DEADLINE_S)
    session = client.client.session()
    names = [f"ctr{i}" for i in range(n_ctr)]
    reopened = client2 = None
    helpers: list = []
    out: dict = {}
    try:
        ctrs = await asyncio.wait_for(asyncio.gather(*(
            client.get(name, DistributedAtomicLong) for name in names)),
            STEP_DEADLINE_S)
        for kind_, prefix, n in ((DistributedMap, "map", cfg["maps"]),
                                 (DistributedLock, "lock", cfg["locks"]),
                                 (DistributedLeaderElection, "elect",
                                  cfg["elections"])):
            for i in range(n):
                await asyncio.wait_for(client.get(f"{prefix}{i}", kind_),
                                       STEP_DEADLINE_S)
        for c in ctrs:
            c.with_consistency(Consistency.ATOMIC)
        say(f"crash plane: {n_mem} members, codec="
            f"{'native' if codec.codec() is not None else 'python'}, "
            f"LocalTransport with {cfg['wire_delay_ms']} ms one way on every "
            f"message; capacity {cfg['capacity']} P={cfg['peers']}; {n_ctr} "
            f"longs + {cfg['maps']} maps + {cfg['locks']} locks + "
            f"{cfg['elections']} elections; election timeout "
            f"{cfg['election_timeout_s']} s, heartbeat "
            f"{cfg['heartbeat_interval_s']} s, session timeout "
            f"{cfg['session_timeout_s']} s; members open {t_open:.1f}s, with "
            f"the client and the creates {perf() - t_setup:.1f}s; "
            f"{ctx.compiles.note()}")

        # the traffic, from the seed: one shared ring of draws, each client
        # starting at its own offset
        rng = np.random.default_rng(ctx.seed)
        ring = 1 << 16
        deltas = rng.integers(mix["delta_min"], mix["delta_max"] + 1,
                              ring).tolist()
        offsets = rng.integers(0, ring, clients).tolist()
        pick_second = bool(rng.integers(0, 2))   # which follower dies

        model = reference.PlainCounters()
        calls: list[float] = []           # every reply: call instant
        acks: list[float] = []            # every reply: reply instant
        waiting: dict[int, float] = {}    # client -> its call's instant
        overdue: set[int] = set()         # clients the watchdog cut off
        state = {"stop": False, "issued": 0, "raised": 0, "wrong": 0,
                 "overdue": 0, "first_wrong": "", "first_raised": "",
                 "flip": ctx.fault == "flip-result"}

        async def one(i: int) -> None:
            c, name, mask = ctrs[i], names[i], ring - 1
            while not state["stop"]:
                k = offsets[i] = (offsets[i] + 1) & mask
                d = deltas[k]
                state["issued"] += 1
                waiting[i] = t = perf()
                try:
                    got = await c.add_and_get(d)
                except (Exception, asyncio.CancelledError) as e:
                    waiting.pop(i, None)
                    model.lost(name, d)
                    if isinstance(e, asyncio.CancelledError) and (
                            state["stop"] or i in overdue):
                        raise               # the plane's own doing
                    # counted, not hidden; a cancellation that came over
                    # the wire from a killed member is the caller's too
                    state["raised"] += 1
                    state["first_raised"] = state["first_raised"] or (
                        f"{name} at +{perf() - t_start:.3f}s: {e!r}")
                    continue
                del waiting[i]
                calls.append(t)
                acks.append(perf())
                if state["flip"]:
                    got, state["flip"] = got ^ 1, False
                wrong = model.add(name, d, got)
                if wrong:
                    state["wrong"] += 1
                    state["first_wrong"] = state["first_wrong"] or wrong

        async def watchdog() -> None:
            """No wait is without a deadline: a call still unanswered after
            ``deadline`` seconds is cut off and counted, and its client
            calls again."""
            while True:
                await asyncio.sleep(0.25)
                now = perf()
                for i, t in list(waiting.items()):
                    if now - t > deadline and not state["stop"]:
                        overdue.add(i)
                        state["overdue"] += 1
                        tasks[i].cancel()
                        await asyncio.gather(tasks[i],
                                             return_exceptions=True)
                        overdue.discard(i)
                        tasks[i] = asyncio.ensure_future(one(i))

        stalls: list[tuple[float, float]] = []

        async def stall_probe() -> None:
            """What held the loop: a task that sleeps ``STALL_PROBE_S`` and
            notes every time it woke more than 50 ms late."""
            while True:
                t = perf()
                await asyncio.sleep(STALL_PROBE_S)
                late = perf() - t - STALL_PROBE_S
                if late > 0.05:
                    stalls.append((t, late))

        t_start = perf()                  # moved to the window's first instant
        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]
        helpers.append(asyncio.ensure_future(watchdog()))

        # warm-up: the cell's own traffic until nothing has compiled for
        # ``warmup_quiet_s`` and every member has taken a snapshot, so that
        # the first rejoin needs an image
        t_warm, quiet = perf(), mix["warmup_quiet_s"]
        while True:
            await asyncio.sleep(0.25)
            taken = [g.metrics.counter("snap.snapshots_taken").value
                     for g in members.groups]
            if (ctx.compiles.quiet_for() >= quiet
                    and perf() - t_warm >= quiet and min(taken) > 0):
                break
            if perf() - t_warm > STEP_DEADLINE_S or state["raised"]:
                raise SystemExit(
                    f"crash plane: warm-up not over after "
                    f"{perf() - t_warm:.0f} s: snapshots taken {taken}, "
                    f"{len(acks):,} calls; {state['first_raised']}; "
                    f"{ctx.compiles.note()}")
        ctx.gc_tune()
        settled = len(acks) + clients
        while len(acks) < settled and perf() - t_warm < STEP_DEADLINE_S:
            await asyncio.sleep(0.05)
        await asyncio.sleep(base.SETTLE_S)
        say(f"crash plane: warm-up {perf() - t_warm:.1f}s, "
            f"{len(acks):,} calls; snapshots taken by member {taken}; "
            f"recovery lane by member: " + ", ".join(members.lanes())
            + f"; {ctx.compiles.note()}")

        # -- the window ------------------------------------------------------
        await members.leader()
        leader0 = members.leader_now()
        followers = [i for i in range(n_mem) if i != leader0]
        victim = followers[int(pick_second) % len(followers)]
        five: dict[tuple, tuple] = {}

        def harvest() -> None:
            """The failure's spans are a handful in a window and the tracer's
            ring holds a dozen seconds of this cell: read them out of the
            ring as the window goes (the report's aggregates do not depend
            on the ring)."""
            for trace_id, trace in tracing.TRACER.traces().items():
                for s in trace:
                    if s.name in FIVE:
                        five[(trace_id, s.name, s.start)] = (
                            s.name, trace_id, s.start, s.end, s.meta or {})

        async def harvesting() -> None:
            while True:
                await asyncio.sleep(HARVEST_S)
                harvest()

        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
            helpers.append(asyncio.ensure_future(harvesting()))
        compiled_before = (ctx.compiles.count, ctx.compiles.misses,
                           ctx.compiles.secs)
        before, issued0, first = members.counts(), state["issued"], len(acks)
        resub0 = client.client.metrics.counter("commands_resubmitted").value
        helpers.append(asyncio.ensure_future(stall_probe()))
        t_start = perf()
        log: list[dict] = []              # what each event did
        led: list[tuple[float, int | None]] = [(t_start, leader0)]
        rejoined: dict[int, dict] = {}    # member -> its restart's record

        def off(t: float) -> str:
            return f"+{t - t_start:.3f}s"

        async def watch() -> None:
            """Who leads, and which rejoined member has caught up: its match
            index at the leader has reached the commit index the leader
            held one look earlier."""
            commit_was = 0
            while True:
                await asyncio.sleep(WATCH_S)
                now = perf()
                lead = members.leader_now()
                if lead is not None and lead != led[-1][1]:
                    led.append((now, lead))
                if lead is None:
                    continue
                group = members.group(lead)
                for i, rec in rejoined.items():
                    if "caught_up" in rec or i in members.down:
                        continue
                    match = group.match_index.get(members.addrs[i], 0)
                    if commit_was and match >= commit_was:
                        rec["caught_up"] = now
                        rec["match"] = match
                commit_was = group.commit_index

        helpers.append(asyncio.ensure_future(watch()))

        async def events() -> None:
            killed: int | None = None
            for name, at in schedule:
                await asyncio.sleep(max(0.0, t_start + at - perf()))
                t = perf()
                compiled = (ctx.compiles.count, ctx.compiles.misses,
                            ctx.compiles.secs)
                if name.endswith("_kill"):
                    lead = members.leader_now()
                    killed = victim if name == "follower_kill" else lead
                    rec = {"event": name, "at": t, "member": killed,
                           "led": lead, "inflight": len(waiting)}
                    if killed is None:
                        rec["skipped"] = "nobody led at that instant"
                        log.append(rec)
                        continue
                    group = members.group(killed)
                    rec.update(role=group.role, term=group.term,
                               commit=group.commit_index,
                               last=group.log.last_index)
                    await members.kill(killed)
                    rec["took"] = perf() - t
                else:
                    rec = {"event": name, "at": t, "member": killed}
                    if killed is None:
                        rec["skipped"] = "its kill was skipped"
                        log.append(rec)
                        continue
                    rec.update(await members.restart(killed))
                    rec["took"] = perf() - t
                    rec["programs"] = ctx.compiles.count - compiled[0]
                    rec["compiled_afresh"] = ctx.compiles.misses - compiled[1]
                    rec["compile_s"] = ctx.compiles.secs - compiled[2]
                    rejoined[killed] = rec
                log.append(rec)

        helpers.append(asyncio.ensure_future(events()))
        profiled: list[tuple[float, float]] = []  # the profiler held the loop
        if ctx.trace:
            await asyncio.sleep(max(0.0, t_start + mix["profile_at_s"]
                                    - perf()))
            t = perf()
            ctx.profile_start()
            profiled.append((t, perf()))
            await asyncio.sleep(mix["profile_s"])
            # the stop writes the trace for seconds: on a thread beside the
            # loop, which two live members' heartbeats need, and the window
            # does not wait for it
            t_stop = perf()
            stopping = loop.run_in_executor(None, ctx.profile_stop)
            stopping.add_done_callback(
                lambda _: profiled.append((t_stop, perf())))
        await asyncio.sleep(max(0.0, t_start + ctx.seconds - perf()))
        t_end = perf()
        state["stop"] = True
        after = members.counts()
        inside_window = {k: after[k] - before[k] for k in after}
        issued = state["issued"] - issued0
        programs_inside = ctx.compiles.count - compiled_before[0]
        compiled_inside = ctx.compiles.misses - compiled_before[1]
        compile_s_inside = ctx.compiles.secs - compiled_before[2]
        resubmitted = (client.client.metrics.counter(
            "commands_resubmitted").value - resub0)
        spans: dict[str, list[float]] = {}
        if ctx.trace:
            tracing.disable()
            harvest()
            for trace in tracing.TRACER.traces().values():
                for s in trace:
                    spans.setdefault(s.name, []).append(s.duration_ms)
            report = tracing.TRACER.report()
            say("crash plane: spans over the whole window (program's "
                "report): " + ", ".join(
                    f"{name} x{v['n']} mean {v['mean_ms']:.3f} ms"
                    for name, v in sorted(report["spans"].items())))
            for name, trace_id, start, end, meta in sorted(
                    five.values(), key=lambda f: f[2]):
                say(f"crash plane: span {name} at {off(start)} "
                    f"{(end - start) * 1e3:.1f} ms under id {trace_id} "
                    f"{meta}")
        for h in helpers:
            h.cancel()
        await asyncio.gather(*helpers, return_exceptions=True)
        helpers = []
        if ctx.trace:
            await asyncio.wait_for(stopping, STEP_DEADLINE_S)
        _, unanswered = await asyncio.wait(tasks, timeout=base.GRACE_S)
        for t in unanswered:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

        # -- the checks, outside the window ----------------------------------
        t_check = perf()
        acks_a, calls_a = np.asarray(acks[first:]), np.asarray(calls[first:])
        inside = acks_a <= t_end
        lat_ms = (acks_a[inside] - calls_a[inside]) * 1e3
        acked = int(inside.sum())
        acks_in = np.sort(acks_a[inside])
        if ctx.fault == "drop-ack":
            name = next(n for n in names if model.get(n) > 0)
            model.values[name] -= deltas[0]
        with ctx.annotate("check"):
            back = await asyncio.wait_for(asyncio.gather(*(
                c.get() for c in ctrs)), 60)
        unread, first_unread = reference.differences(model, names, back)
        try:
            await members.caught_up()
            stragglers = ""
        except RuntimeError as e:
            stragglers = str(e)
        held_by = members.device_values(names)
        off_model, first_off = 0, ""
        for i, values in zip(members.up, held_by):
            n, what = reference.differences(model, names, values)
            off_model += n
            first_off = first_off or (
                f"member {members.addrs[i]} {what}" if n else "")
        off_model += n_ctr * len(members.down) + (n_ctr if stragglers else 0)
        eligible = n_mem * (n_ctr + cfg["maps"] + cfg["locks"]
                            + cfg["elections"])
        failed = state["raised"] + state["overdue"] + len(unanswered)
        on_device = members.on_device()

        # the schedule as it went
        leader_kill = next((r for r in log if r["event"] == "leader_kill"
                            and "skipped" not in r), None)
        facts = {
            "counters": n_ctr, "members": n_mem, "deadline": deadline,
            "events": len(schedule),
            "events_reached": sum(1 for r in log if "skipped" not in r),
            "killed_as_leader_role": (leader_kill or {}).get("role"),
            "leader_kill_at": (leader_kill["at"] - t_start
                               if leader_kill else None),
            "leader_changes_at": [t - t_start for t, _ in led[1:]
                                  if t <= t_end],
            "rejoins_caught_up_at": [
                r["caught_up"] - t_start if "caught_up" in r else None
                for r in log if r["event"].endswith("_restart")
                and "skipped" not in r],
            "rejoins_installs_received": [
                members.group(i).metrics.counter(
                    "snap.installs_received").value
                for i in rejoined if i not in members.down],
            "window_s": t_end - t_start,
        }
        for r in log:
            who = ("-" if r["member"] is None
                   else str(members.addrs[r["member"]]))
            if "skipped" in r:
                say(f"crash plane: {r['event']} at {off(r['at'])}: SKIPPED, "
                    f"{r['skipped']}")
            elif r["event"].endswith("_kill"):
                after_it = acks_in[acks_in > r["at"]]
                r["first_ack"] = (float(after_it[0]) if len(after_it)
                                  else None)
                r["gap_s"], r["resumed"] = longest_gap(
                    acks_in, r["at"], min(r["at"] + 3, t_end))
                say(f"crash plane: {r['event']} at {off(r['at'])}: member "
                    f"{who}, role {r['role']}, term {r['term']}, commit "
                    f"index {r['commit']:,} of {r['last']:,} logged, "
                    f"{r['inflight']} calls in flight; the kill took "
                    f"{r['took'] * 1e3:.1f} ms; first acknowledgement after "
                    + ("none" if r["first_ack"] is None else
                       f"{(r['first_ack'] - r['at']) * 1e3:.1f} ms")
                    + " (a reply the dead member had put on the wire, if "
                    "within a round trip); longest time without one in the "
                    f"3 s after it {r['gap_s'] * 1e3:.1f} ms, ended "
                    f"{(r['resumed'] - r['at']) * 1e3:.1f} ms after the kill")
            else:
                group = (members.group(r["member"])
                         if r["member"] not in members.down else None)
                say(f"crash plane: {r['event']} at {off(r['at'])}: member "
                    f"{who}; {r['cut_bytes']:,} bytes past its last sync cut "
                    f"away; the constructor (boot recovery) held the loop "
                    f"{r['build_s'] * 1e3:.0f} ms, open() (the device "
                    f"engine) took {r['open_s'] * 1e3:.0f} ms, in which "
                    f"{r['programs']} programs were compiled or loaded in "
                    f"{r['compile_s']:.2f} s ({r['compiled_afresh']} of "
                    f"them compiled afresh); it came up with snapshot index "
                    f"{r['snapshot_index']:,} (restored "
                    f"{r['restores']}), log to {r['last_index']:,}; caught "
                    "up " + (f"{r['caught_up'] - r['called']:.3f}s after "
                             f"the restart was called, at "
                             f"{off(r['caught_up'])}, match index "
                             f"{r['match']:,}" if "caught_up" in r
                             else "NOT inside the window")
                    + ("" if group is None else
                       "; installs received "
                       f"{group.metrics.counter('snap.installs_received').value}"
                       ", snapshots restored "
                       f"{group.metrics.counter('snap.restores').value}"))
        say("crash plane: who led: " + ", ".join(
            f"{off(t)} {'-' if w is None else members.addrs[w]}"
            for t, w in led))
        if stalls:
            worst = sorted(stalls, key=lambda s: -s[1])[:6]
            say(f"crash plane: the loop stood still more than 50 ms "
                f"{len(stalls)} times inside the window; the longest: "
                + ", ".join(f"{late * 1e3:.0f} ms at {off(t)}"
                            for t, late in sorted(worst)))
        say(f"crash plane: inside the window, over every incarnation: "
            + ", ".join(f"{k} {v:,}" for k, v in inside_window.items())
            + f"; commands the client resubmitted {resubmitted:,}; programs "
            f"compiled or loaded {programs_inside} in "
            f"{compile_s_inside:.2f} s, of them compiled afresh "
            f"{compiled_inside}; recovery lane by member: "
            + ", ".join(members.lanes()))
        expired = 0 if session.is_open else 1

        # -- durability: the traffic again, cut off by a crash of every
        # member; logs cut back to their last sync; a fresh cluster over them
        state["stop"], acked_before = False, len(acks)
        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]
        await asyncio.sleep(mix["crash_burst_s"])
        t_crash = perf()
        state["stop"] = True             # a loop ends at its call's end
        live = [members.servers[i] for i in members.up]
        await base.crash(live)
        await asyncio.sleep(0.05)        # replies already on the wire land
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        lost = [base.cut_to_last_sync(s.server.groups[0]) for s in live]
        say(f"crash plane: crashed {len(live)} members "
            f"{perf() - t_crash:.2f}s ago with {len(model.unanswered)} adds "
            f"unanswered, {len(acks) - acked_before:,} acknowledged since "
            f"the checks; bytes past the last sync, cut away: {lost}")
        try:                             # nobody is left to answer it
            await asyncio.wait_for(client.close(), 1)
        except (Exception, asyncio.TimeoutError):  # noqa: BLE001
            pass
        client = None
        members.down = set(range(n_mem))  # drop the crashed engines
        members.servers, members.past = [], []
        t_reopen = perf()
        reopened = Members(cfg, root)
        await asyncio.wait_for(reopened.open(), STEP_DEADLINE_S)
        await reopened.leader()
        client2 = reopened.client()
        await asyncio.wait_for(client2.open(), STEP_DEADLINE_S)
        ctrs2 = list(await asyncio.wait_for(asyncio.gather(*(
            client2.get(name, DistributedAtomicLong) for name in names)),
            STEP_DEADLINE_S))
        for c in ctrs2:
            c.with_consistency(Consistency.ATOMIC)
        recovered = await asyncio.wait_for(asyncio.gather(*(
            c.get() for c in ctrs2)), 120)
        await reopened.caught_up()
        undurable, first_undurable = reference.differences(
            model, names, recovered)
        say(f"crash plane: reopened over the cut logs: every member caught "
            f"up after {perf() - t_reopen:.2f}s; recovery lane by member: "
            + ", ".join(reopened.lanes()) + "; snapshots restored "
            f"{[g.metrics.counter('snap.restores').value for g in reopened.groups]}"
            f", log first index {[g.log.first_index for g in reopened.groups]}")

        facts.update(
            replies=len(acks), wrong=state["wrong"],
            first_wrong=state["first_wrong"], unread=unread,
            first_unread=first_unread, off_model=off_model,
            first_off=first_off or stragglers, eligible=eligible,
            on_device=on_device, raised=state["raised"],
            overdue=state["overdue"], unanswered=len(unanswered),
            first_raised=state["first_raised"],
            programs_inside=programs_inside,
            compiled_inside=compiled_inside, undurable=undurable,
            first_undurable=first_undurable, expired=expired)
        checks = checks_of(facts)
        correct = acked > 0 and all(v <= lim for _, v, lim in checks)
        p50, p99 = (float(np.percentile(lat_ms, q)) if acked else 0.0
                    for q in (50, 99))
        window = t_end - t_start
        fifths = np.histogram(acks_in, bins=5,
                              range=(t_start, t_end))[0] / (window / 5)
        per_s = np.histogram(acks_in, bins=int(round(window)),
                             range=(t_start, t_end))[0]
        say("crash plane: acknowledged ops/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        say("crash plane: acknowledged by seconds of the window: "
            + " ".join(f"{n}" for n in per_s))
        say(f"crash plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{acked:,} acknowledged inside it, ack p50 {p50:.3f} ms p99 "
            f"{p99:.3f} ms over {acked:,} samples; checks and recovery took "
            f"{perf() - t_check:.1f}s")
        if len(profiled) == 2:
            (a0, a1), (b0, b1) = profiled
            say(f"crash plane: the profiler's start at {off(a0)} held the "
                f"loop {a1 - a0:.2f}s; its stop at {off(b0)} took "
                f"{b1 - b0:.1f}s on a thread beside it")
        by_event = {r["event"]: r for r in log if "skipped" not in r}
        clock = {"ack_p50_ms": p50, "ack_p99_ms": p99, "window_s": window,
                 "acked_ops": acked, "kills": len(by_event) / 2}
        clock["windows"] = 1.0
        r = by_event.get("leader_kill")
        if r:
            clock["leader_gap_ms"] = (r["resumed"] - r["at"]) * 1e3
        r = by_event.get("follower_kill")
        if r:
            clock["follower_gap_ms"] = r["gap_s"] * 1e3
        took = [r["caught_up"] - r["called"] for r in rejoined.values()
                if "caught_up" in r]
        if took:
            clock["catchup_ms"] = sum(took) / len(took) * 1e3
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window},
            "clock": clock,
            "spans": spans,
            "counters": {**inside_window, "resubmitted": resubmitted},
            "facts": facts,
            "five": sorted(five.values(), key=lambda f: f[2]),
        }
    finally:
        for h in helpers:
            h.cancel()
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
    check_schedule(ctx.traffic, ctx.seconds)
    where, kind = base.pick_log_base()
    root = tempfile.mkdtemp(prefix="crash-logs-", dir=where)
    try:
        out = asyncio.run(asyncio.wait_for(_drive(ctx, root, kind), 1200))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    # each number compared beside its limit, the last lines of standard error
    for what, value, limit in out["checks"]:
        print(f"crash plane: check: {what}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    return out
