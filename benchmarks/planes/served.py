"""The served path: one ``AtomixServer(executor="tpu")`` member and one
``AtomixClient`` session over ``LocalTransport``, driven by a closed loop of
clients through the public resource API.

The deployment is ``chip_smoke.served_script``'s (proven on the chip in PR 21);
the traffic is a fixed number of clients, one per counter, one call outstanding
each. The reference is the client's own running sum of its acknowledged deltas:
one call outstanding per counter makes every reply exact.
"""

from __future__ import annotations

import asyncio
import os
import sys
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


async def _drive(ctx) -> dict:
    import jax

    from copycat_tpu.atomic import DistributedAtomicLong
    from copycat_tpu.collections import DistributedMap
    from copycat_tpu.coordination import (
        DistributedLeaderElection, DistributedLock)
    from copycat_tpu.io import codec
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.io.transport import Address
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.manager.device_executor import DeviceEngineConfig
    from copycat_tpu.resource.consistency import Consistency
    from copycat_tpu.utils import tracing

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    n_ctr, n_other = cfg["counters"], cfg["maps"]
    clients = mix["clients"]
    if clients != n_ctr:
        raise SystemExit(f"served plane: {clients} clients for {n_ctr} "
                         "counters; the mix drives one client per counter")
    t_setup = time.perf_counter()
    native = codec.codec() is not None
    registry = LocalServerRegistry()
    addr = Address("127.0.0.1", cfg["port"])
    server = AtomixServer(
        addr, [addr], LocalTransport(registry),
        election_timeout=cfg["election_timeout_s"],
        heartbeat_interval=cfg["heartbeat_interval_s"],
        session_timeout=cfg["session_timeout_s"], executor="tpu",
        engine_config=DeviceEngineConfig(capacity=cfg["capacity"],
                                         num_peers=cfg["peers"]))
    await server.open()
    t_open = time.perf_counter() - t_setup
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=cfg["session_timeout_s"])
    await client.open()
    out: dict = {}
    try:
        ctrs = await asyncio.gather(*(
            client.get(f"ctr{i}", DistributedAtomicLong)
            for i in range(n_ctr)))
        for kind, prefix, n in ((DistributedMap, "map", cfg["maps"]),
                                (DistributedLock, "lock", cfg["locks"]),
                                (DistributedLeaderElection, "elect",
                                 cfg["elections"])):
            for i in range(n):
                await client.get(f"{prefix}{i}", kind)
        for c in ctrs:
            c.with_consistency(Consistency.ATOMIC)
        engine = server.server.state_machine.device_engine
        say(f"served plane: codec={'native' if native else 'python'}, "
            f"LocalTransport, capacity {cfg['capacity']} P={cfg['peers']}; "
            f"{n_ctr} longs + {cfg['maps']} maps + {cfg['locks']} locks + "
            f"{cfg['elections']} elections; server open {t_open:.1f}s, "
            f"with the client and the creates "
            f"{time.perf_counter() - t_setup:.1f}s; {ctx.compiles.note()}")

        # the traffic, from the seed: one shared ring of draws, each client
        # starting at its own offset
        rng = np.random.default_rng(ctx.seed)
        ring = 1 << 16
        deltas = rng.integers(mix["delta_min"], mix["delta_max"] + 1,
                              ring).tolist()
        is_read = (rng.random(ring) < mix["read_share"]).tolist()
        offsets = rng.integers(0, ring, clients).tolist()

        sums = [0] * clients              # acknowledged adds, per counter
        calls: list[float] = []           # every reply: call instant
        acks: list[float] = []            # every reply: reply instant
        state = {"stop": False, "issued": 0, "raised": 0, "wrong": 0,
                 "first_wrong": "", "flip": ctx.fault == "flip-result"}
        perf = time.perf_counter

        async def one(i: int) -> None:
            c, k, mask = ctrs[i], offsets[i], ring - 1
            while not state["stop"]:
                k = (k + 1) & mask
                read, d = is_read[k], deltas[k]
                state["issued"] += 1
                t = perf()
                try:
                    got = await (c.get() if read else c.add_and_get(d))
                except Exception as e:  # noqa: BLE001 - counted, not hidden
                    state["raised"] += 1
                    state["first_wrong"] = state["first_wrong"] or repr(e)
                    continue
                calls.append(t)
                acks.append(perf())
                if not read:
                    sums[i] += d
                if state["flip"] and not read:
                    got, state["flip"] = got ^ 1, False
                if got != sums[i]:
                    state["wrong"] += 1
                    state["first_wrong"] = state["first_wrong"] or (
                        f"counter {i}: reply {got}, running sum {sums[i]}")

        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]

        # warm-up: the cell's own traffic until nothing has compiled for
        # QUIET_S (the fused-rounds programs compile on demand)
        t_warm, quiet = perf(), mix.get("warmup_quiet_s", QUIET_S)
        while True:
            await asyncio.sleep(0.25)
            if ctx.compiles.quiet_for() >= quiet and perf() - t_warm >= quiet:
                break
            if perf() - t_warm > 300:
                raise RuntimeError("served plane: still compiling after "
                                   "300 s of warm-up")
        ctx.gc_tune()
        # the collection holds the loop: let the calls it delayed be answered
        # before the window opens, or they sit in its tail
        await asyncio.sleep(SETTLE_S)
        say(f"served plane: warm-up {perf() - t_warm:.1f}s, "
            f"{len(acks):,} calls; {ctx.compiles.note()}")

        # -- the window ------------------------------------------------------
        rounds_counter = engine._groups.metrics.counter("rounds")
        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
        compiled_before = ctx.compiles.count
        issued0, rounds0, first = state["issued"], rounds_counter.value, len(acks)
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
        rounds = rounds_counter.value - rounds0
        issued = state["issued"] - issued0
        compiled_inside = ctx.compiles.count - compiled_before
        spans: dict[str, list[float]] = {}
        if ctx.trace:
            tracing.disable()
            for trace in tracing.TRACER.traces().values():
                for s in trace:
                    spans.setdefault(s.name, []).append(s.duration_ms)
            say("served plane: spans in the tracer's ring at window end: "
                + ", ".join(f"{name} x{len(d)} mean {sum(d) / len(d):.3f} ms"
                            for name, d in sorted(spans.items())))
        _, pending = await asyncio.wait(tasks, timeout=GRACE_S)
        for t in pending:
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
            sums[int(np.flatnonzero(np.asarray(sums) > 0)[0])] -= deltas[0]
        with ctx.annotate("check"):
            back = await asyncio.wait_for(asyncio.gather(*(
                c.get() for c in ctrs)), 60)
        unread = sum(int(b != s) for b, s in zip(back, sums))
        on_device = engine._next_group - len(engine._free)
        eligible = n_ctr + cfg["maps"] + cfg["locks"] + cfg["elections"]
        failed = state["raised"] + len(pending)
        checks = [
            (f"replies of {len(acks):,} that differ from the client's "
             "running sum" + (f": {state['first_wrong']}"
                              if state["first_wrong"] else ""),
             state["wrong"], 0),
            (f"counters of {n_ctr} whose ATOMIC read-back differs from the "
             "sum of acknowledged adds", unread, 0),
            (f"resources of {eligible} not on the device",
             eligible - on_device, 0),
            ("calls that raised, timed out or got no reply within "
             f"{GRACE_S:.0f}s of the window", failed, 0),
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
        say("served plane: ack p99 ms by fifths of the window: "
            + ", ".join(f"{t:.1f}" for t in tails))
        say("served plane: acknowledged ops/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        say(f"served plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{acked:,} acknowledged inside it, ack p50 {p50:.3f} ms p99 "
            f"{p99:.3f} ms over {acked:,} samples; {rounds} engine rounds; "
            f"compilations inside the window: {compiled_inside} (limit 0); "
            f"checks took {perf() - t_check:.1f}s")
        if held:
            say(f"served plane: the profiler held the loop "
                + " and ".join(f"{h1 - h0:.1f}s" for h0, h1 in held)
                + f"; ack p99 {p99_clear} ms over the {int(clear.sum()):,} "
                f"calls not in flight then")
        # each number compared beside its limit, the last lines of
        # standard error
        for what, value, limit in checks:
            print(f"served plane: check: {what}: {value} (limit {limit})",
                  file=sys.stderr, flush=True)
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window,
                           "ack_p99_ms": p99},
            "clock": {"ack_p50_ms": p50, "ack_p99_ms": p99_clear,
                      "window_s": window, "acked_ops": acked},
            "spans": spans,
            "counters": {"rounds": rounds},
        }
    finally:
        for node in (client, server):
            try:
                await asyncio.wait_for(node.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass
    return out


def run(ctx) -> dict:
    return asyncio.run(asyncio.wait_for(_drive(ctx), 1200))
