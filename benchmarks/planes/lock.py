"""The served path over contended locks: one ``AtomixServer(executor="tpu")``
member and ``sessions`` ``AtomixClient`` sessions over ``LocalTransport``, every
session holding one ``DistributedLock`` instance of every lock, driven by a
closed loop of contenders through ``lock()`` and ``unlock()``.

The deployment, the warm-up, the window, the ``gc_tune`` pause and the result
keys are ``planes/served.py``'s. The reference is
``reference_lock.PlainLocks``: a FIFO mutex grants in the order its ``Lock``
commands committed, whatever the interleaving. ``benchmarks/README.lock.md``
says what is measured and how each check is made.
"""

from __future__ import annotations

import asyncio
import importlib.util
import os
import sys
import time

import numpy as np

#: warm-up ends when JAX's compile events have been quiet this long
QUIET_S = 2.0
#: between the collection that ends warm-up and the window's first instant
SETTLE_S = 0.5
#: seconds of the window the profiler covers in a traced run
TRACED_S = 3.0
#: every set-up step and every check ends within this, or the run exits
STEP_DEADLINE_S = 600.0


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference_lock.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.reference_lock", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _gate() -> None:
    """Leave at once, before a server opens, on a program whose lock runs a
    generator chain a command: 40,000 contenders would each take three engine
    rounds and a ``PublishRequest`` of their own a hand-over."""
    from copycat_tpu.manager.device_executor import DeviceLockState

    if "vector_spec" not in vars(DeviceLockState):
        raise SystemExit("lock plane: this program's DeviceLockState has no "
                         "vector_spec of its own (every Lock and Unlock is a "
                         "generator chain); it cannot run the cell")


async def _drive(ctx) -> dict:
    _gate()
    import jax  # noqa: F401 - the device is taken before the server opens

    from copycat_tpu.coordination import DistributedLock
    from copycat_tpu.coordination.commands import Lock
    from copycat_tpu.io import codec
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.io.transport import Address
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.manager.device_executor import DeviceEngineConfig
    from copycat_tpu.ops.apply import ResourceConfig
    from copycat_tpu.utils import tracing

    ref = _reference()
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    n_locks, n_sessions = cfg["locks"], cfg["sessions"]
    per_lock = cfg["contenders_per_lock"]
    contenders = mix["contenders"]
    if per_lock != n_sessions or contenders != n_locks * per_lock:
        raise SystemExit(
            f"lock plane: {contenders} contenders for {n_locks} locks x "
            f"{per_lock} a lock over {n_sessions} sessions; the mix drives "
            "one contender a lock a session")
    grace = mix["grace_s"]
    t_setup = time.perf_counter()
    perf = time.perf_counter
    native = codec.codec() is not None
    pools = {f: cfg["other_pool_slots"] for f in ResourceConfig._fields}
    pools["wait_slots"] = cfg["wait_slots"]
    pools["event_slots"] = cfg["event_slots"]
    registry = LocalServerRegistry()
    addr = Address("127.0.0.1", cfg["port"])
    server = AtomixServer(
        addr, [addr], LocalTransport(registry),
        election_timeout=cfg["election_timeout_s"],
        heartbeat_interval=cfg["heartbeat_interval_s"],
        session_timeout=cfg["session_timeout_s"], executor="tpu",
        engine_config=DeviceEngineConfig(
            capacity=cfg["capacity"], num_peers=cfg["peers"],
            log_slots=cfg["log_slots"], submit_slots=cfg["submit_slots"],
            resource=ResourceConfig(**pools)))
    await asyncio.wait_for(server.open(), STEP_DEADLINE_S)
    t_open = perf() - t_setup
    clients = [AtomixClient([addr], LocalTransport(registry),
                            session_timeout=cfg["session_timeout_s"])
               for _ in range(n_sessions)]
    out: dict = {}
    try:
        for client in clients:
            await asyncio.wait_for(client.open(), STEP_DEADLINE_S)
        # contender c = session * n_locks + lock: a session's instance of
        # every lock, created through the public API
        t_create = perf()
        instances = []
        for client in clients:
            instances += await asyncio.wait_for(asyncio.gather(*(
                client.create(f"lock{i}", DistributedLock)
                for i in range(n_locks))), STEP_DEADLINE_S)
        manager = server.server.state_machine
        engine = manager.device_engine
        groups = engine._groups
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(groups.state))
        say(f"lock plane: codec={'native' if native else 'python'}, "
            f"LocalTransport, capacity {cfg['capacity']} P={cfg['peers']}, "
            f"{n_locks:,} locks, {n_sessions} sessions, {contenders:,} "
            f"instances created in {perf() - t_create:.1f}s; "
            f"{state_bytes:,} bytes of state; server open {t_open:.1f}s, "
            f"with the clients and the creates {perf() - t_setup:.1f}s; "
            f"{ctx.compiles.note()}")

        # -- the contenders --------------------------------------------------
        # The seed deals, for every lock, the order in which its contenders
        # first call: which session holds it first and who waits behind
        # whom. ``turn[k]`` are the contenders that call k-th.
        rng = np.random.default_rng(ctx.seed)
        dealt = rng.permuted(np.tile(np.arange(n_sessions), (n_locks, 1)),
                             axis=1)
        turn = (dealt * n_locks + np.arange(n_locks)[:, None]).T.tolist()
        state = {"stop": False, "issued": 0, "raised": 0, "refused": 0,
                 "mismatched": 0, "first_wrong": "",
                 "flip": ctx.fault == "flip-result"}
        replies: list[list[int]] = [[] for _ in range(n_locks)]
        released: list[list[int]] = [[] for _ in range(n_locks)]
        #: a lock's grants as its clients saw them, in that order:
        #: [waiter id, grant received, unlock sent, lock() called]
        seen: list[list[list]] = [[] for _ in range(n_locks)]
        current: list = [None] * contenders
        last_reply = [0] * contenders
        asked = [0] * contenders          # lock() calls
        granted = [0] * contenders        # grants delivered
        let_go = [0] * contenders         # unlocks acknowledged
        sub_calls: list[float] = []       # every command: submit instant
        sub_acks: list[float] = []        # every command: reply instant
        unlock_acks: list[float] = []     # every unlock: acknowledged

        def wrong(text: str) -> None:
            state["first_wrong"] = state["first_wrong"] or text

        def tap(c: int, lock) -> None:
            """Record what the service told contender ``c``: the id its
            ``Lock`` command was answered with, and each grant as the
            session's event stream delivers it."""
            i, inner = c % n_locks, lock.submit

            async def submit(operation):
                t = perf()
                reply = await inner(operation)
                sub_calls.append(t)
                sub_acks.append(perf())
                if type(operation) is Lock:
                    reply = int(reply)
                    last_reply[c] = reply
                    told = reply
                    if state["flip"]:
                        told, state["flip"] = reply ^ 1, False
                    replies[i].append(told)
                return reply

            def on_grant(event) -> None:
                rec = [int(event["id"]), perf(), 0.0, 0.0]
                granted[c] += 1
                if not event["acquired"]:
                    state["refused"] += 1
                seen[i].append(rec)
                current[c] = rec

            lock.submit = submit
            lock.session().on_event("lock", on_grant)

        for c, lock in enumerate(instances):
            tap(c, lock)

        async def one(c: int) -> None:
            lock, i = instances[c], c % n_locks
            while not state["stop"]:
                state["issued"] += 1
                asked[c] += 1
                t = perf()
                try:
                    await lock.lock()
                    rec = current[c]
                    rec[3] = t
                    if rec[0] != last_reply[c]:
                        state["mismatched"] += 1
                        wrong(f"lock {i}: contender {c} was answered id "
                              f"{last_reply[c]} and granted {rec[0]}")
                    state["issued"] += 1
                    rec[2] = perf()
                    await lock.unlock()
                except Exception as e:  # noqa: BLE001 - counted, not hidden
                    state["raised"] += 1
                    wrong(repr(e))
                    return
                unlock_acks.append(perf())
                released[i].append(rec[0])
                let_go[c] += 1

        # a lock's k-th contender calls once its (k-1)-th has been answered,
        # so each lock's queue stands in the dealt order and every session
        # holds a quarter of the locks at any instant
        tasks: list = []
        t_deal = perf()
        for k, wave in enumerate(turn):
            tasks += [asyncio.ensure_future(one(c)) for c in wave]
            while sum(map(len, replies)) < (k + 1) * n_locks:
                await asyncio.sleep(0.05)
                if perf() - t_deal > STEP_DEADLINE_S:
                    raise SystemExit(
                        f"lock plane: the contenders' first calls were not "
                        f"answered after {STEP_DEADLINE_S:.0f} s: "
                        f"{sum(map(len, replies)):,} of {contenders:,}")

        # warm-up: the cell's own traffic until nothing has compiled for
        # QUIET_S (the fused-rounds programs compile on demand)
        t_warm, quiet = perf(), mix.get("warmup_quiet_s", QUIET_S)
        while True:
            await asyncio.sleep(0.25)
            if ctx.compiles.quiet_for() >= quiet and perf() - t_warm >= quiet \
                    and len(unlock_acks) >= mix["warmup_handovers"] * n_locks:
                break
            if perf() - t_warm > STEP_DEADLINE_S:
                raise SystemExit(
                    f"lock plane: warm-up not over after {STEP_DEADLINE_S:.0f}"
                    f" s: {len(unlock_acks):,} hand-overs, "
                    f"{ctx.compiles.note()}")
        ctx.gc_tune()
        # the collection holds the loop: let the calls it delayed be answered
        # before the window opens
        await asyncio.sleep(SETTLE_S)
        say(f"lock plane: warm-up {perf() - t_warm:.1f}s, "
            f"{len(unlock_acks):,} hand-overs; {ctx.compiles.note()}")

        # -- the window ------------------------------------------------------
        counter = groups.metrics.counter
        watched = ("rounds", "lock_chain_ops", "lock_vector_ops")
        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
        compiled_before = ctx.compiles.count
        issued0 = state["issued"]
        before = {name: counter(name).value for name in watched}
        t_start = perf()
        held: list[tuple[float, float]] = []   # the profiler held the loop
        if ctx.trace:
            await asyncio.sleep(min(1.0, ctx.seconds / 4))
            t = perf()
            ctx.profile_start()
            held.append((t, perf()))
            await asyncio.sleep(min(TRACED_S, ctx.seconds / 2))
            t = perf()
            ctx.profile_stop()
            held.append((t, perf()))
        await asyncio.sleep(max(0.0, t_start + ctx.seconds - perf()))
        t_end = perf()
        state["stop"] = True
        deltas = {name: counter(name).value - before[name]
                  for name in watched}
        issued = state["issued"] - issued0
        compiled_inside = ctx.compiles.count - compiled_before
        spans: dict[str, list[float]] = {}
        if ctx.trace:
            tracing.disable()
            for trace in tracing.TRACER.traces().values():
                for s in trace:
                    spans.setdefault(s.name, []).append(s.duration_ms)
            say("lock plane: spans in the tracer's ring at window end: "
                + ", ".join(f"{name} x{len(d)} mean {sum(d) / len(d):.3f} ms"
                            for name, d in sorted(spans.items())))
        # the quiesce: nobody locks again; who waits is granted in turn and
        # lets go, a waiter third in line after three more hand-overs
        t_quiesce = perf()
        _, pending = await asyncio.wait(tasks, timeout=grace)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        quiesce_s = perf() - t_quiesce

        # -- the checks, outside the window ----------------------------------
        t_check = perf()
        recs = [rec for lock_seen in seen for rec in lock_seen]
        grant_at = np.asarray([rec[1] for rec in recs])
        asked_at = np.asarray([rec[3] for rec in recs])
        unlock_at = np.asarray(unlock_acks)
        in_window = (grant_at >= t_start) & (grant_at <= t_end)
        n_grants = int(in_window.sum())
        n_unlocks = int(((unlock_at >= t_start) & (unlock_at <= t_end)).sum())
        acked = n_grants + n_unlocks
        # a grant's wait counts where its lock() was called in this process's
        # measured life (the first call of a contender predates no window)
        timed = in_window & (asked_at > 0)
        grant_ms = (grant_at[timed] - asked_at[timed]) * 1e3
        handoff_ms, overlaps, handovers = [], 0, []
        for lock_seen in seen:
            n_in = 0
            for prev, nxt in zip(lock_seen, lock_seen[1:]):
                # prev let go (unlock sent) before nxt was granted
                if prev[2] == 0.0 or nxt[1] < prev[2]:
                    overlaps += 1
                if t_start <= nxt[1] <= t_end:
                    n_in += 1
                    handoff_ms.append((nxt[1] - prev[2]) * 1e3)
            handovers.append(n_in)
        sub_calls_a, sub_acks_a = np.asarray(sub_calls), np.asarray(sub_acks)
        sub_in = (sub_acks_a >= t_start) & (sub_acks_a <= t_end)
        ack_ms = (sub_acks_a[sub_in] - sub_calls_a[sub_in]) * 1e3

        if ctx.fault == "drop-ack":
            victim = next(r for r in released if len(r) > 2)
            del victim[len(victim) // 2]
        model = ref.PlainLocks(n_locks)
        disordered = not_holder = 0
        for i in range(n_locks):
            want, refused = ref.grant_order(model, i, replies[i], released[i])
            not_holder += refused
            got = [rec[0] for rec in seen[i]]
            if got != want:
                disordered += 1
                wrong(f"lock {i}: its clients saw grants {got[:8]}..., the "
                      f"plain lock grants {want[:8]}...")
        uneven = sum(1 for c in range(contenders)
                     if not asked[c] == granted[c] == let_go[c])

        # every lock is free: a fresh instance takes it at once and lets go
        async def probe(i: int) -> int:
            lock = await clients[0].create(f"lock{i}", DistributedLock)
            if not await lock.try_lock():
                return 1
            await lock.unlock()
            return 0

        with ctx.annotate("check"):
            try:
                not_free = sum(await asyncio.wait_for(asyncio.gather(*(
                    probe(i) for i in range(n_locks))), STEP_DEADLINE_S))
            except asyncio.TimeoutError:
                not_free = n_locks
        # two empty rounds: a follower lane applies an entry the round
        # after the leader lane committed it
        groups.run(2)
        res = groups.state.resources
        live = engine._next_group
        holders = np.asarray(res.lk_holder)[:live]
        queued = np.asarray(res.lk_size)[:live]
        device_busy = int((holders != -1).any(axis=1).sum()
                          + (queued != 0).any(axis=1).sum())
        machines = [h.state_machine for h in manager.resources.values()]
        on_device = sum(1 for m in machines
                        if type(m).__name__ == "DeviceLockState")
        overflow = sum(len(getattr(m, "_overflow", ())) for m in machines)
        failed = state["raised"] + len(pending)
        checks = [
            (f"(a) locks of {n_locks:,} whose grants, as their clients saw "
             f"them ({len(recs):,} in all), differ from the plain locks' for "
             "the acknowledged Lock replies and unlocks, unlocks by another "
             "than the holder, and grants under another id than the reply's"
             + (f": {state['first_wrong']}" if state["first_wrong"] else ""),
             disordered + not_holder + state["mismatched"]
             + state["refused"], 0),
            ("(b) hand-overs in which the next holder was granted before the "
             "last one sent its unlock, on the process's one clock",
             overlaps, 0),
            (f"(c) contenders of {contenders:,} not granted and released "
             f"exactly what they asked for ({uneven}), plain locks not free "
             f"after the quiesce ({n_locks - model.free()}), locks a fresh "
             f"instance's try_lock() did not take ({not_free}), groups whose "
             f"device holder or wait ring is not empty on every replica "
             f"({device_busy})",
             uneven + n_locks - model.free() + not_free + device_busy, 0),
            (f"(d) locks of {n_locks:,} not on the device",
             n_locks - on_device, 0),
            ("(e) waiters in the host overflow, and lock commands run as "
             "generator chains inside the window",
             overflow + deltas["lock_chain_ops"], 0),
            ("(f) calls that raised, or whose reply or grant had not come "
             f"{grace:.0f}s after the window", failed, 0),
            ("(g) compilations inside the window", compiled_inside, 0),
        ]
        correct = acked > 0 and all(v <= lim for _, v, lim in checks)

        def pct(values, q):
            return float(np.percentile(values, q)) if len(values) else None

        window = t_end - t_start
        fifths = (np.histogram(grant_at[in_window], bins=5,
                               range=(t_start, t_end))[0]
                  + np.histogram(unlock_at, bins=5,
                                 range=(t_start, t_end))[0]) / (window / 5)
        say("lock plane: operations/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        say(f"lock plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{n_grants:,} grants delivered and {n_unlocks:,} unlocks "
            f"acknowledged inside it; every lock was handed over at least "
            f"{min(handovers)} times (mean {sum(handovers) / n_locks:.2f}: "
            f"cycles of {2 * n_locks:,} operations); grant p50 "
            f"{pct(grant_ms, 50)} ms p99 {pct(grant_ms, 99)} ms, hand-off "
            f"p50 {pct(handoff_ms, 50)} ms, ack p50 {pct(ack_ms, 50)} ms; "
            f"{deltas['rounds']} engine rounds, "
            f"{deltas['lock_vector_ops']:,} lock commands on the vector "
            f"lane, {deltas['lock_chain_ops']:,} through a generator; the "
            f"quiesce took {quiesce_s:.1f}s of {grace:.0f}s, checks "
            f"{perf() - t_check:.1f}s")
        if held:
            say("lock plane: the profiler held the loop "
                + " and ".join(f"{h1 - h0:.1f}s" for h0, h1 in held))
        for what, value, limit in checks:
            print(f"lock plane: check: {what}: {value} (limit {limit})",
                  file=sys.stderr, flush=True)
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window},
            "clock": {"grant_p50_ms": pct(grant_ms, 50),
                      "grant_p99_ms": pct(grant_ms, 99),
                      "handoff_p50_ms": pct(handoff_ms, 50),
                      "ack_p50_ms": pct(ack_ms, 50),
                      "window_s": window, "acked_ops": acked,
                      "handovers_min": min(handovers),
                      "state_bytes": state_bytes,
                      "program": "jit_round", "rounds_per_dispatch": 1},
            "spans": spans,
            "counters": {"rounds": deltas["rounds"]},
        }
    finally:
        for node in (*clients, server):
            try:
                await asyncio.wait_for(node.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass
    return out


def run(ctx) -> dict:
    return asyncio.run(asyncio.wait_for(_drive(ctx), 3000))
