"""The served path over raced leader elections whose clients die: one
``AtomixServer(executor="tpu")`` member and ``sessions`` ``AtomixClient``
sessions over ``LocalTransport``, every election raced by
``candidates_per_election`` candidacies of as many sessions through
``on_election()``, ``is_leader(epoch)`` and ``resign()``, and a session killed
every ``kill_every_s`` seconds on one clock from the warm-up through the
window.

The deployment, the ``gc_tune`` pause and the result keys are
``planes/lock.py``'s. The reference is ``reference_election.PlainElections``:
a leader and a FIFO of waiting candidates an election, run over the commands
and session ends in the order the member's log committed them.
``benchmarks/README.election.md`` says what is measured, how a kill is made
and how each check is made.
"""

from __future__ import annotations

import asyncio
import importlib.util
import os
import sys
import time
from collections import Counter

import numpy as np

#: warm-up ends when JAX's compile events have been quiet this long
QUIET_S = 2.0
#: at least this long between the collection that ends warm-up and the window
SETTLE_S = 0.5
#: seconds of the window the profiler covers in a traced run
TRACED_S = 3.0
#: every set-up step and every wait ends within this, or the run exits
STEP_DEADLINE_S = 600.0
#: a task that sleeps this long, to see how long the loop was held
STALL_PROBE_S = 0.02


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference_election.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.reference_election", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _gate() -> None:
    """Leave at once, before a server opens, on a program whose election
    runs a generator chain a command and a session's end one chain after
    another: 3,000 candidacies would take three engine rounds a call."""
    from copycat_tpu.manager.device_executor import DeviceLeaderElectionState

    if "vector_spec" not in vars(DeviceLeaderElectionState):
        raise SystemExit(
            "election plane: this program's DeviceLeaderElectionState has no "
            "vector_spec of its own (every ElectionListen and "
            "ElectionUnlisten is a generator chain, and so is every instance "
            "a dead session held); it cannot run the cell")


#: where an election's candidacies sit on the ring of nodes, from its first
SPREAD = (0, 3, 8)


def deal(rng, elections: int, nodes: int, per: int) -> np.ndarray:
    """``[elections, per]`` nodes, by spread placement on a ring: the nodes
    stand on a ring in an order drawn from ``rng``, and an election's
    candidates sit ``SPREAD`` steps from its place on it, no two on
    neighbouring nodes, as a rack-aware service places a shard's replicas.
    Every election is raced by ``per`` different nodes, every node in
    ``elections * per / nodes`` elections (``elections`` a multiple of
    ``nodes``), and every deal has the same shape: a node meets the same
    number of others, as often, whatever the seed. Which place an election
    has, and the order within it (who calls first), are drawn too."""
    steps = np.asarray(SPREAD[:per]) if per <= len(SPREAD) and nodes == 10 \
        else np.arange(per)
    ring = rng.permutation(nodes)
    place = rng.permutation(elections)
    dealt = ring[(place[:, None] + steps[None, :]) % nodes]
    return rng.permuted(dealt, axis=1)


class _Session:
    """One client session on a node: its client, its candidacies, what it
    sent (a code and an instant a command, by sequence number) and how it
    ended."""

    def __init__(self, node: int, client) -> None:
        self.node, self.client = node, client
        self.id = client.client.session().id
        self.cands: list[int] = []
        self.sent: list[int] = []          # seq - 1 -> candidacy * 2 + kind
        self.sent_at: list[float] = []
        self.tasks: list = []
        self.last_ack_sent = 0.0           # a command that was answered
        self.killed_at: float | None = None
        self.at_kill: Counter | None = None
        self.end: dict | None = None       # the unregister entry's record
        self.ended = asyncio.Event()


class _Candidacy:
    __slots__ = ("election", "session", "instance", "instance_id", "state",
                 "told", "waiter")

    def __init__(self, election: int, session: _Session, instance) -> None:
        self.election, self.session, self.instance = election, session, \
            instance
        self.instance_id = instance.client.instance_id
        self.state = "new"
        self.told = 0
        self.waiter = None


async def _drive(ctx) -> dict:
    _gate()
    import jax  # noqa: F401 - the device is taken before the server opens

    from copycat_tpu.coordination import DistributedLeaderElection
    from copycat_tpu.coordination.commands import ElectionUnlisten
    from copycat_tpu.io import codec
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.io.transport import Address
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.manager.device_executor import DeviceEngineConfig
    from copycat_tpu.manager.operations import InstanceCommand
    from copycat_tpu.ops.apply import ResourceConfig
    from copycat_tpu.server.log import CommandEntry
    from copycat_tpu.utils import tracing
    from copycat_tpu.utils.tasks import spawn

    ref = _reference()
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    n_el, n_nodes = cfg["elections"], cfg["sessions"]
    per = cfg["candidates_per_election"]
    if per > n_nodes or n_el % n_nodes or mix["candidacies"] != n_el * per:
        raise SystemExit(
            f"election plane: {mix['candidacies']} candidacies for {n_el} "
            f"elections x {per} an election over {n_nodes} sessions; the mix "
            "deals every election to different sessions, as many to each")
    timeout_s, every = cfg["session_timeout_s"], mix["kill_every_s"]
    opens_after, grace = mix["window_opens_after_kill_s"], mix["grace_s"]
    t_setup = time.perf_counter()
    perf = time.perf_counter
    loop = asyncio.get_running_loop()
    native = codec.codec() is not None
    pools = {f: cfg["other_pool_slots"] for f in ResourceConfig._fields}
    pools["listener_slots"] = cfg["listener_slots"]
    pools["event_slots"] = cfg["event_slots"]
    registry = LocalServerRegistry()
    addr = Address("127.0.0.1", cfg["port"])
    server = AtomixServer(
        addr, [addr], LocalTransport(registry),
        election_timeout=cfg["election_timeout_s"],
        heartbeat_interval=cfg["heartbeat_interval_s"],
        session_timeout=timeout_s, executor="tpu",
        engine_config=DeviceEngineConfig(
            capacity=cfg["capacity"], num_peers=cfg["peers"],
            log_slots=cfg["log_slots"], submit_slots=cfg["submit_slots"],
            resource=ResourceConfig(**pools)))
    await asyncio.wait_for(server.open(), STEP_DEADLINE_S)
    t_open = perf() - t_setup
    group = server.server.groups[0]
    manager = group.state_machine
    engine = manager.device_engine
    groups = engine._groups
    counter = groups.metrics.counter

    rng = np.random.default_rng(ctx.seed)
    dealt = deal(rng, n_el, n_nodes, per)
    # who dies when: drawn now, so that the seed fixes it; never the node
    # whose last session still awaits its expiry
    victims, last = [], -1
    for _ in range(256):
        last = int(rng.choice([n for n in range(n_nodes) if n != last]))
        victims.append(last)

    state = {"stop": False, "kills_on": True, "issued": 0, "raised": 0,
             "first_wrong": "", "not_leader": 0, "holding": 0,
             "unreached": 0, "flip": ctx.fault == "flip-result"}
    cands: list[_Candidacy] = []
    cand_of_instance: dict[int, int] = {}
    sessions: dict[int, _Session] = {}          # session id -> every one
    on_node: list[_Session | None] = [None] * n_nodes
    #: an election's elects as its clients saw them, in that order:
    #: (candidacy, epoch, told at)
    seen: list[list[tuple]] = [[] for _ in range(n_el)]
    ack_at: list[float] = []                    # every call: answered at
    ack_ms: list[float] = []
    ends: list[dict] = []                       # every session end applied
    blocks: list[tuple] = []    # the log: (last index, session, seq, n)

    def wrong(text: str) -> None:
        state["first_wrong"] = state["first_wrong"] or text

    # -- what the harness reads of the member: the order its log committed
    # -- commands in, and each session's end
    log = group.log
    append, append_block = log.append, log.append_block

    def tapped_append(entry):
        index = append(entry)
        if type(entry) is CommandEntry:
            blocks.append((index, entry.session_id, entry.seq, 1))
        return index

    def tapped_block(entries):
        index = append_block(entries)
        first = entries[0]
        blocks.append((index, first.session_id, first.seq, len(entries)))
        return index

    log.append, log.append_block = tapped_append, tapped_block
    apply_unregister = group._apply_unregister
    rounds = counter("rounds")

    def tapped_unregister(entry):
        session = sessions.get(entry.session_id)
        mine = [] if session is None else \
            [cands[c].instance_id for c in session.cands]
        machines = [] if session is None else \
            [manager.instances[i].resource.state_machine
             for i in mine if i in manager.instances]
        dead = set(mine)
        led = sum(1 for m in machines if m._leader in dead)
        r0, t0 = rounds.value, perf()
        apply_unregister(entry)
        t1 = perf()
        record = {"session": entry.session_id, "index": entry.index,
                  "expired": bool(entry.expired), "at": t1,
                  "held_ms": (t1 - t0) * 1e3, "rounds": rounds.value - r0,
                  "instances": len(mine), "led": led,
                  "led_after": sum(1 for m in machines
                                   if m._leader in dead)}
        ends.append(record)
        if session is not None:
            session.end = record
            session.ended.set()

    group._apply_unregister = tapped_unregister

    def tap_commands(session: _Session) -> None:
        """Note every command the session sends, by sequence number: which
        candidacy's it is and whether a listen or an unlisten (``-1``: a
        create), and when."""
        raft = session.client.client
        inner, sent, sent_at = raft.submit_command_nowait, session.sent, \
            session.sent_at

        def submit_command_nowait(operation):
            fut = inner(operation)
            code = -1
            if type(operation) is InstanceCommand:
                cand = cand_of_instance.get(operation.resource)
                if cand is not None:
                    code = cand * 2 + (type(operation.operation.operation)
                                       is ElectionUnlisten)
            sent.append(code)
            sent_at.append(perf())
            return fut

        raft.submit_command_nowait = submit_command_nowait

    async def one(c: int) -> None:
        """A candidacy's closed loop. Once the window is over a leader that
        has checked its token keeps the election."""
        cand = cands[c]
        election, session, e = cand.instance, cand.session, cand.election

        def on_elect(epoch) -> None:
            cand.told += 1
            seen[e].append((c, int(epoch), perf()))
            if cand.waiter is not None and not cand.waiter.done():
                cand.waiter.set_result(int(epoch))

        def answered(t_sent: float, contact: bool = True) -> None:
            now = perf()
            ack_at.append(now)
            ack_ms.append((now - t_sent) * 1e3)
            # a command is contact: the server restarts the session's
            # timeout when it takes one (a query it serves without)
            if contact and t_sent > session.last_ack_sent:
                session.last_ack_sent = t_sent

        try:
            while True:
                cand.waiter = loop.create_future()
                cand.state = "on_election"
                state["issued"] += 1
                t = perf()
                listener = await election.on_election(on_elect)
                answered(t)
                cand.state = "waiting"
                epoch = await cand.waiter
                listener.close()
                cand.state = "is_leader"
                state["issued"] += 1
                t = perf()
                leads = await election.is_leader(epoch)
                answered(t, contact=False)
                if state["flip"]:
                    leads, state["flip"] = not leads, False
                if not leads:
                    state["not_leader"] += 1
                    wrong(f"election {e}: candidacy {c} was told epoch "
                          f"{epoch} and is_leader({epoch}) was false")
                if state["stop"]:
                    cand.state = "holding"
                    state["holding"] += 1
                    return
                cand.state = "resign"
                state["issued"] += 1
                t = perf()
                await election.resign()
                answered(t)
        except asyncio.CancelledError:
            raise
        except Exception as err:  # noqa: BLE001 - counted, not hidden
            if session.killed_at is None:
                state["raised"] += 1
                wrong(f"election {e}: candidacy {c} raised {err!r}")

    async def open_session(node: int) -> _Session:
        """A client on ``node`` with an instance of every election dealt to
        the node, created through the public API, in the elections' order."""
        client = AtomixClient([addr], LocalTransport(registry),
                              session_timeout=timeout_s)
        await asyncio.wait_for(client.open(), STEP_DEADLINE_S)
        session = _Session(node, client)
        sessions[session.id] = session
        tap_commands(session)
        mine = np.nonzero((dealt == node).any(axis=1))[0].tolist()
        instances = await asyncio.wait_for(asyncio.gather(*(
            client.create(f"election{e}", DistributedLeaderElection)
            for e in mine)), STEP_DEADLINE_S)
        for e, instance in zip(mine, instances):
            cand = _Candidacy(e, session, instance)
            cand_of_instance[cand.instance_id] = len(cands)
            session.cands.append(len(cands))
            cands.append(cand)
        on_node[node] = session
        return session

    def race(session: _Session, which=None) -> None:
        session.tasks += [asyncio.ensure_future(one(c))
                          for c in (session.cands if which is None
                                    else which)]

    def kill(session: _Session) -> None:
        """The client's process dies: nothing more is sent, no unregister,
        no keep-alive; the connection drops. Its loops end with it."""
        session.killed_at = perf()
        session.at_kill = Counter(cands[c].state for c in session.cands)
        raft = session.client.client
        if raft._keepalive is not None:
            raft._keepalive.cancel()
            raft._keepalive = None
        raft.members = []               # nothing to dial again
        raft.session()._closed()
        for task in session.tasks:
            task.cancel()
        spawn(raft._client.close(), name="killed-client")

    async def replace(session: _Session) -> None:
        """When the dead session's expiry has applied, a new session takes
        up its candidacies."""
        try:
            await asyncio.wait_for(session.ended.wait(), STEP_DEADLINE_S)
            race(await open_session(session.node))
        except Exception as err:  # noqa: BLE001 - counted, not hidden
            state["raised"] += 1
            wrong(f"node {session.node}: its new session did not come up: "
                  f"{err!r}")

    killed: list[_Session] = []
    clock = {"t0": 0.0}

    async def kill_clock() -> None:
        k = 0
        while True:
            await asyncio.sleep(max(0.0, clock["t0"] + k * every - perf()))
            if not state["kills_on"]:
                return
            session = on_node[victims[k % len(victims)]]
            if session is None or session.killed_at is not None \
                    or session.end is not None:
                state["unreached"] += 1     # no live session on the node
            else:
                kill(session)
                killed.append(session)
                replacing.append(asyncio.ensure_future(replace(session)))
            k += 1

    stalls: list[float] = []

    async def stall_probe() -> None:
        while not state["stop"]:
            t = perf()
            await asyncio.sleep(STALL_PROBE_S)
            stalls.append(perf() - t - STALL_PROBE_S)

    out: dict = {}
    replacing: list = []
    helpers: list = []
    try:
        t_create = perf()
        for node in range(n_nodes):
            await open_session(node)
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(groups.state))
        held = Counter(len(s.cands) for s in sessions.values())
        say(f"election plane: codec={'native' if native else 'python'}, "
            f"LocalTransport, capacity {cfg['capacity']} P={cfg['peers']}, "
            f"{n_el:,} elections, {n_nodes} sessions, {len(cands):,} "
            f"instances created in {perf() - t_create:.1f}s "
            f"({min(held)} to {max(held)} a session); session timeout "
            f"{timeout_s}s; {state_bytes:,} bytes of state; server open "
            f"{t_open:.1f}s, with the clients and the creates "
            f"{perf() - t_setup:.1f}s; {ctx.compiles.note()}")

        # -- the race: an election's k-th candidacy calls once every
        # -- election's (k-1)-th has been answered, so the lines stand in
        # -- the dealt order
        t_deal = perf()
        for k in range(per):
            by_node: dict[int, list[int]] = {}
            for e in range(n_el):
                session = on_node[int(dealt[e, k])]
                c = next(c for c in session.cands if cands[c].election == e)
                by_node.setdefault(session.node, []).append(c)
            for node, which in by_node.items():
                race(on_node[node], which)
            while len(ack_at) < (k + 1) * n_el:
                await asyncio.sleep(0.05)
                if perf() - t_deal > STEP_DEADLINE_S or state["raised"]:
                    raise SystemExit(
                        f"election plane: the candidacies' first calls were "
                        f"not answered after {perf() - t_deal:.0f} s: "
                        f"{len(ack_at):,} of {len(cands):,}; "
                        f"{state['first_wrong']}")

        # -- warm-up: the cell's own traffic and its kills, until nothing
        # -- has compiled for QUIET_S, every election has been handed over
        # -- and the first dead session has expired and been replaced
        t_warm, quiet = perf(), mix.get("warmup_quiet_s", QUIET_S)
        clock["t0"] = t_warm + 1.0
        helpers.append(asyncio.ensure_future(kill_clock()))
        while True:
            await asyncio.sleep(0.1)
            told = sum(map(len, seen))
            replaced = sum(1 for s in killed if s.end is not None
                           and on_node[s.node] is not s
                           and on_node[s.node].last_ack_sent > 0)
            if ctx.compiles.quiet_for() >= quiet and perf() - t_warm >= quiet \
                    and told >= mix["warmup_handovers"] * n_el \
                    and replaced >= mix["warmup_expiries"]:
                break
            if perf() - t_warm > STEP_DEADLINE_S or state["raised"]:
                raise SystemExit(
                    f"election plane: warm-up not over after "
                    f"{perf() - t_warm:.0f} s: {told:,} elects, "
                    f"{len(killed)} sessions killed, {len(ends)} ended, "
                    f"{replaced} replaced; {state['first_wrong']}; "
                    f"{ctx.compiles.note()}")
        ctx.gc_tune()
        # the collection holds the loop: let the calls it delayed be
        # answered, then open the window at its place on the kill clock
        waited = perf() + SETTLE_S - clock["t0"] - opens_after
        t_open_at = clock["t0"] + opens_after \
            + every * max(1, int(np.ceil(waited / every)))
        warm_ends = len(ends)
        say(f"election plane: warm-up {perf() - t_warm:.1f}s and "
            f"{t_open_at - perf():.1f}s to the window's place on the kill "
            f"clock, {sum(map(len, seen)):,} elects, {len(killed)} "
            f"session(s) killed, {warm_ends} expired and replaced (the "
            "entry held the loop "
            + ", ".join(f"{r['held_ms']:.1f}" for r in ends)
            + f" ms); {ctx.compiles.note()}")
        await asyncio.sleep(max(0.0, t_open_at - perf()))

        # -- the window ------------------------------------------------------
        watched = ("rounds", "elect_chain_ops", "elect_vector_ops",
                   "session_end_chain_instances",
                   "session_end_vector_instances")
        publishes = group.metrics.counter("events.publish_requests")
        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
        compiled_before = ctx.compiles.count
        issued0 = state["issued"]
        before = {name: counter(name).value for name in watched}
        publishes0 = publishes.value
        helpers.append(asyncio.ensure_future(stall_probe()))
        t_start = perf()
        profiled: list[tuple[float, float]] = []  # the profiler held the loop
        if ctx.trace:
            await asyncio.sleep(min(1.0, ctx.seconds / 4))
            t = perf()
            ctx.profile_start()
            profiled.append((t, perf()))
            await asyncio.sleep(min(TRACED_S, ctx.seconds / 2))
            # the stop writes the trace for seconds: on a thread beside
            # the loop, which a live session's keep-alives need
            t = perf()
            await loop.run_in_executor(None, ctx.profile_stop)
            profiled.append((t, perf()))
        await asyncio.sleep(max(0.0, t_start + ctx.seconds - perf()))
        t_end = perf()
        state["stop"] = True
        state["kills_on"] = False
        deltas = {name: counter(name).value - before[name]
                  for name in watched}
        n_publishes = publishes.value - publishes0
        issued = state["issued"] - issued0
        compiled_inside = ctx.compiles.count - compiled_before
        spans: dict[str, list[float]] = {}
        if ctx.trace:
            tracing.disable()
            for trace in tracing.TRACER.traces().values():
                for s in trace:
                    spans.setdefault(s.name, []).append(s.duration_ms)
            say("election plane: spans in the tracer's ring at window end: "
                + ", ".join(f"{name} x{len(d)} mean {sum(d) / len(d):.3f} ms"
                            for name, d in sorted(spans.items())))
        # the quiesce: nobody dies any more; who was killed expires and is
        # replaced; a leader that has checked its token keeps its election,
        # so every election comes to rest with one leader that was told
        t_quiesce = perf()
        while perf() - t_quiesce < grace:
            if state["holding"] >= n_el and all(
                    s.end is not None for s in killed) and all(
                    t.done() for t in replacing) and all(
                    cands[c].state in ("holding", "waiting")
                    for s in on_node if s.killed_at is None
                    for c in s.cands):
                break
            await asyncio.sleep(0.05)
        quiesce_s = perf() - t_quiesce
        live = [s for s in on_node if s is not None and s.killed_at is None]
        resting = Counter(cands[c].state for s in live for c in s.cands)
        unanswered = sum(n for what, n in resting.items()
                         if what not in ("holding", "waiting"))

        # -- the checks, outside the window ----------------------------------
        t_check = perf()
        in_win = [r for r in ends if t_start <= r["at"] <= t_end]
        ack_at_a, ack_ms_a = np.asarray(ack_at), np.asarray(ack_ms)
        ack_in = (ack_at_a >= t_start) & (ack_at_a <= t_end)
        acked = int(ack_in.sum())

        # the committed history of every election, from the member's log:
        # a block's commands by the sessions' own sequence numbers
        history: list[list[tuple]] = [[] for _ in range(n_el)]
        sent_of: dict[int, float] = {}          # commit index -> sent at
        by_index = sorted(
            [(index, 0, sid, seq, n) for index, sid, seq, n in blocks]
            + [(r["index"], 1, r["session"], 0, 0) for r in ends])
        for index, is_end, sid, seq, n in by_index:
            session = sessions.get(sid)
            if session is None:
                continue
            if is_end:
                by_election: dict[int, list[int]] = {}
                for c in session.cands:
                    by_election.setdefault(cands[c].election, []).append(c)
                for e, which in by_election.items():
                    history[e].append((index, ref.END, which))
                continue
            if session.end is not None and index > session.end["index"]:
                continue                # logged after its session's end
            for k in range(n):
                code = session.sent[seq - 1 + k]
                if code >= 0:
                    at = index - n + 1 + k
                    history[cands[code >> 1].election].append(
                        (at, code & 1, code >> 1))
                    sent_of[at] = session.sent_at[seq - 1 + k]
        if ctx.fault == "drop-ack":
            victim = next(h for h in history if len(h) > 6)
            del victim[next(k for k, op in enumerate(victim)
                            if op[1] == ref.UNLISTEN and k > 2)]

        def alive(c: int, index: int) -> bool:
            end = cands[c].session.end
            return end is None or index < end["index"]

        model = ref.PlainElections(n_el)
        disordered = falling = untold = 0
        handoff_ms, failover_ms, early, moved = [], [], 0, Counter()
        n_elects = n_voluntary = 0
        for e in range(n_el):
            causes = {index: (kind, who) for index, kind, who in history[e]}
            leaders = ref.replay(model, e, history[e], alive)
            # a candidacy saw its first ``told`` elects; one more, to a
            # session that was dead by then, nobody saw
            count, visible = Counter(), []
            for c, epoch in leaders:
                count[c] += 1
                if count[c] <= cands[c].told:
                    visible.append((c, epoch))
                elif cands[c].session.killed_at is None:
                    untold += 1
                    wrong(f"election {e}: the plain election made candidacy "
                          f"{c} leader at {epoch} and it was never told")
            got = [c for c, _, _ in seen[e]]
            if got != [c for c, _ in visible]:
                disordered += 1
                wrong(f"election {e}: its clients saw leaders {got[:8]}..., "
                      f"the plain election {[c for c, _ in visible][:8]}...")
                continue
            epochs = [epoch for _, epoch, _ in seen[e]]
            if any(b <= a for a, b in zip(epochs, epochs[1:])):
                falling += 1
                wrong(f"election {e}: epochs {epochs[:8]}... do not rise")
            for (c, _, told_at), (_, cause) in zip(seen[e], visible):
                inside = t_start <= told_at <= t_end
                n_elects += inside
                kind, who = causes[cause]
                if kind == ref.UNLISTEN:
                    # told after the leader it follows sent its resign
                    early += told_at < sent_of[cause]
                    if inside:
                        n_voluntary += 1
                        handoff_ms.append((told_at - sent_of[cause]) * 1e3)
                elif kind == ref.END:
                    dead = cands[who[0]].session
                    moved[dead.id] += 1
                    # never sooner than the timeout after the dead
                    # session's last command that was answered
                    early += told_at - dead.last_ack_sent < timeout_s
                    if inside and dead.killed_at is not None:
                        failover_ms.append((told_at - dead.killed_at) * 1e3)

        # every election has come to rest: one leader, told, alive, and the
        # device's replicas and the host's mirrors say what the plain
        # elections say; an older epoch is not taken for the current one
        async def probe(e: int) -> int:
            c = model.leader(e)
            if c is None or cands[c].state != "holding" or len(seen[e]) < 2:
                return 0
            election = cands[c].instance
            stale = await election.is_leader(seen[e][-2][1])
            fresh = await election.is_leader(seen[e][-1][1])
            return int(bool(stale)) + int(not fresh)

        with ctx.annotate("check"):
            try:
                stale = sum(await asyncio.wait_for(asyncio.gather(*(
                    probe(e) for e in range(n_el))), STEP_DEADLINE_S))
            except (asyncio.TimeoutError, Exception) as err:  # noqa: BLE001
                stale = n_el
                wrong(f"the probes of the epochs failed: {err!r}")
        # two empty rounds: a follower lane applies an entry the round
        # after the leader lane committed it
        groups.run(2)
        res = groups.state.resources
        n_live = engine._next_group
        el = np.asarray(res.el_leader)[:n_live]
        ep = np.asarray(res.el_epoch)[:n_live]
        ring_id = np.asarray(res.el_id)[:n_live]
        ring_live = np.asarray(res.el_live)[:n_live]
        head = np.asarray(res.el_head)[:n_live]
        size = np.asarray(res.el_size)[:n_live]
        slots = ring_id.shape[-1]
        ended_ids = {cands[c].instance_id for s in sessions.values()
                     if s.end is not None for c in s.cands}
        holders = {h.key: h.state_machine for h in manager.resources.values()}
        no_leader = dead_leader = differs = listed = 0
        for e in range(n_el):
            c = model.leader(e)
            told_last = seen[e][-1] if seen[e] else None
            if c is None or told_last is None or told_last[0] != c:
                no_leader += 1
                wrong(f"election {e}: at rest the plain election's leader "
                      f"is {c}, the last told {told_last}")
                continue
            if cands[c].session.end is not None \
                    or cands[c].session.killed_at is not None:
                dead_leader += 1
            machine = holders.get(f"election{e}")
            g = getattr(machine, "_group", None)
            if g is None:
                differs += 1
                continue
            want = cands[c].instance_id
            line = [cands[w].instance_id for w in model.waiting(e)]
            same = True
            for p in range(el.shape[1]):
                order = [(int(head[g, p]) + k) % slots
                         for k in range(int(size[g, p]))]
                ring = [int(ring_id[g, p, s]) for s in order
                        if ring_live[g, p, s]]
                same &= int(el[g, p]) == want and ring == line \
                    and int(ep[g, p]) == told_last[1]
                listed += sum(1 for i in [int(el[g, p]), *ring]
                              if i in ended_ids)
            mirror = list(machine._listens)
            same &= machine._leader == want \
                and machine._epoch == told_last[1] \
                and sorted(mirror) == sorted([want, *line])
            listed += sum(1 for i in mirror if i in ended_ids)
            if not same:
                differs += 1
                wrong(f"election {e}: a replica or the host's mirror is not "
                      f"leader {want} epoch {told_last[1]} line {line}")
        machines = [h.state_machine for h in manager.resources.values()]
        on_device = sum(1 for m in machines
                        if type(m).__name__ == "DeviceLeaderElectionState")
        overflow = sum(len(getattr(m, "_overflow", ())) for m in machines)
        chains = deltas["elect_chain_ops"] \
            + deltas["session_end_chain_instances"]
        failed = state["raised"] + unanswered
        expired_in = sum(1 for r in in_win if r["expired"])
        killed_in = sum(1 for s in killed
                        if s.end is not None
                        and t_start <= s.end["at"] <= t_end)
        # an end the harness did not cause: of a session it never killed,
        # or applied before it was
        strays = sum(1 for r in ends
                     if sessions.get(r["session"]) is None
                     or sessions[r["session"]].killed_at is None
                     or r["at"] < sessions[r["session"]].killed_at)
        still_led = sum(r["led_after"] for r in ends)
        never_ended = sum(1 for s in killed if s.end is None)
        checks = [
            (f"(a) elections of {n_el:,} whose leaders, as their clients saw "
             f"them ({sum(map(len, seen)):,} in all), are not the plain "
             "elections' for the listens, the resigns and the session ends "
             f"in the order the log committed them ({disordered}), whose "
             f"epochs do not rise ({falling}), candidacies of a live "
             f"session that the plain elections made leader and nobody told "
             f"({untold})"
             + (f": {state['first_wrong']}" if state["first_wrong"] else ""),
             disordered + falling + untold, 0),
            (f"(b) successors told before the leader sent its resign or, at "
             f"a failover, sooner than the timeout after the dead session's "
             f"last answered command ({early}); just-elected candidates "
             f"whose is_leader(epoch) was false ({state['not_leader']}); "
             f"stale epochs of {n_el:,} that is_leader took for current, "
             f"and current ones it refused ({stale})",
             early + state["not_leader"] + stale, 0),
            (f"(c) after the quiesce: elections of {n_el:,} without exactly "
             f"one leader that was told ({no_leader}), led by a dead session "
             f"({dead_leader}); elections whose leader, epoch or line on "
             f"some replica or in the host's mirror is not the plain "
             f"election's ({differs}); ended sessions' candidacies still "
             f"listed on the device or in a mirror ({listed})",
             no_leader + dead_leader + differs + listed, 0),
            (f"(d) elections of {n_el:,} not on the device",
             n_el - on_device, 0),
            ("(e) candidates in the host overflow, and election commands "
             "and session-end instances run as generator chains inside the "
             "window", overflow + chains, 0),
            ("(f) calls of a live session that raised, or whose reply had "
             f"not come {grace:.0f}s after the window", failed, 0),
            ("(g) compilations inside the window", compiled_inside, 0),
            (f"(h) sessions the server expired inside the window "
             f"({expired_in}) less those of them the harness had killed "
             f"({killed_in}); kills the schedule did not reach "
             f"({state['unreached']}); killed sessions never expired "
             f"({never_ended}); sessions ended that the harness left alive "
             f"({strays}); elections a dead session still led when its "
             f"expiry had applied ({still_led})",
             abs(expired_in - killed_in) + state["unreached"] + never_ended
             + strays + still_led, 0),
        ]
        correct = acked > 0 and all(v <= lim for _, v, lim in checks)

        def pct(values, q):
            return float(np.percentile(values, q)) if len(values) else None

        window = t_end - t_start
        fifths = np.histogram(ack_at_a[ack_in], bins=5,
                              range=(t_start, t_end))[0] / (window / 5)
        say("election plane: operations/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        end_rounds = sum(r["rounds"] for r in in_win)
        say(f"election plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{acked:,} answered inside it ({n_elects:,} elects delivered); "
            f"voluntary hand-overs {n_voluntary:,} "
            f"({n_voluntary / n_el:.2f} an election), failovers "
            f"{len(failover_ms):,} (successors told, of "
            f"{sum(r['led'] for r in in_win):,} elections the dead sessions "
            f"led when they expired); hand-off p50 {pct(handoff_ms, 50)} ms, "
            f"failover p50 {pct(failover_ms, 50)} ms max "
            f"{max(failover_ms, default=None)} ms, ack p50 "
            f"{pct(ack_ms_a[ack_in], 50)} ms; {deltas['rounds']} engine "
            f"rounds, {n_publishes:,} PublishRequests, "
            f"{deltas['elect_vector_ops']:,} election commands on the "
            f"vector lane, {deltas['elect_chain_ops']:,} through a "
            f"generator; the quiesce took {quiesce_s:.1f}s of {grace:.0f}s "
            f"and left {dict(resting)}, checks {perf() - t_check:.1f}s")
        for s in killed:
            r = s.end or {}
            say(f"election plane: session {s.id} (node {s.node}) killed at "
                f"{s.killed_at - t_start:+.3f}s, last answered command sent "
                f"{s.killed_at - s.last_ack_sent:.3f}s before, its end "
                + (f"applied at {r['at'] - t_start:+.3f}s in entry "
                   f"{r['index']} (expired={r['expired']}), which held the "
                   f"loop {r['held_ms']:.1f} ms over {r['rounds']} engine "
                   f"round(s); it led {r['led']} elections before the entry "
                   f"and {r['led_after']} after; the plain elections moved "
                   f"{moved[s.id]} to successors that were told"
                   if r else "never applied")
                + f"; when it was killed its candidacies' loops stood at "
                f"{dict(s.at_kill)}")
        say(f"election plane: session ends in the window: "
            f"{deltas['session_end_vector_instances']:,} instances in a "
            f"vector turn, {deltas['session_end_chain_instances']:,} as "
            f"chains, {end_rounds} engine rounds for {len(in_win)} "
            f"sessions; sessions expired {expired_in}, killed {killed_in}")
        if stalls:
            say(f"election plane: the loop's longest stall inside the window "
                f"{max(stalls) * 1e3:.1f} ms (a task that sleeps "
                f"{STALL_PROBE_S * 1e3:.0f} ms, {len(stalls)} times)")
        if profiled:
            (a0, a1), (b0, b1) = profiled
            say(f"election plane: the profiler's start held the loop "
                f"{a1 - a0:.1f}s; its stop took {b1 - b0:.1f}s on a thread "
                "beside it")
        for what, value, limit in checks:
            print(f"election plane: check: {what}: {value} (limit {limit})",
                  file=sys.stderr, flush=True)
        fail_p50 = pct(failover_ms, 50)
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window},
            "clock": {"handoff_p50_ms": pct(handoff_ms, 50),
                      "failover_p50_ms": fail_p50,
                      "failover_max_ms": max(failover_ms, default=None),
                      "expire_lag_ms": None if fail_p50 is None
                      else fail_p50 - timeout_s * 1e3,
                      "ack_p50_ms": pct(ack_ms_a[ack_in], 50),
                      "window_s": window, "acked_ops": acked,
                      "sessions_ended": len(in_win),
                      "state_bytes": state_bytes,
                      "program": "jit_round", "rounds_per_dispatch": 1},
            "spans": spans,
            "counters": {"rounds": deltas["rounds"],
                         "session_end_rounds": end_rounds},
        }
    finally:
        state["stop"] = True
        state["kills_on"] = False
        for task in (*helpers, *replacing,
                     *(t for s in sessions.values() for t in s.tasks)):
            task.cancel()
        for node in (*(s.client for s in sessions.values()
                       if s.killed_at is None), server):
            try:
                await asyncio.wait_for(node.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass
    return out


def run(ctx) -> dict:
    return asyncio.run(asyncio.wait_for(_drive(ctx), 3000))
