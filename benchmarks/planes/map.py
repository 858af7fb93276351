"""The served path over maps: one ``AtomixServer(executor="tpu")`` member and
one ``AtomixClient`` session over ``LocalTransport``, its engine holding one map
table a replica, driven by a closed loop of clients through
``DistributedMap.put`` and ``get``.

The deployment, the window, the ``gc_tune`` pause and the result keys are
``planes/served.py``'s. Set-up loads every key of every map through the public
API. The warm-up ends on work done as well as on quiet (``_warm_up``): a read
window is evaluated by one of two programs, and a loop that the collector
holds over the load's heap reads as seconds of quiet. The reference is
``reference_map.PlainMaps``: one call outstanding a map makes every reply
exact.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import os
import sys
import time

import numpy as np

#: replies still missing this long after the window count as failed
GRACE_S = 5.0
#: warm-up ends when JAX's compile events have been quiet this long
QUIET_S = 2.0
#: ... and for this many acknowledged calls a client, where the traffic file
#: has no ``warmup_quiet_calls_per_client``
QUIET_CALLS_PER_CLIENT = 20
#: a warm-up that has not gone quiet after this long is an error
DEADLINE_S = 300.0
#: between the collection that ends warm-up and the window's first instant
SETTLE_S = 0.5
#: seconds of the window the profiler covers in a traced run
TRACED_S = 3.0
#: draws of the shared traffic ring
RING = 1 << 16


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference_map.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.reference_map", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _resident_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e6


def quiet_calls(mix: dict) -> int:
    """Calls to be acknowledged after the last program was compiled or loaded
    before the warm-up may end."""
    return mix["clients"] * mix.get("warmup_quiet_calls_per_client",
                                    QUIET_CALLS_PER_CLIENT)


async def _warm_up(ctx, acks: list) -> int:
    """The cell's own traffic until nothing has compiled or loaded for
    ``warmup_quiet_s`` AND for ``quiet_calls`` acknowledged calls; returns the
    calls acknowledged since the last program.

    Seconds alone do not say that the traffic ran: the collector's first
    passes over what the load left hold the loop for longer than the quiet,
    and a read window's second program (``jit_query`` alone, or
    ``jit_round_query`` on a parked round, whichever a window finds) then
    loads inside the measured window. A held loop acknowledges no call."""
    perf, compiles, mix = time.perf_counter, ctx.compiles, ctx.traffic
    quiet, floor = mix.get("warmup_quiet_s", QUIET_S), quiet_calls(mix)
    t_warm, programs, mark = perf(), compiles.count, len(acks)
    while True:
        await asyncio.sleep(0.25)
        if compiles.count != programs:
            programs, mark = compiles.count, len(acks)
        since = len(acks) - mark
        if since >= floor and compiles.quiet_for() >= quiet \
                and perf() - t_warm >= quiet:
            return since
        if perf() - t_warm > DEADLINE_S:
            raise RuntimeError(
                f"map plane: warm-up not over after {DEADLINE_S:.0f} s: "
                f"{len(acks):,} calls, {since:,} of the {floor:,} it takes "
                f"since the last program; {compiles.note()}")


async def _drive(ctx) -> dict:
    try:
        from copycat_tpu.ops.apply import ResourceConfig, map_buckets
    except ImportError:
        # a program whose map pool is one sweep a key cannot hold the
        # configuration: it would load 10,000,000 keys at a put a round trip
        raise SystemExit("map plane: this program has no bucketed map table "
                         "(ops.apply.map_buckets); it cannot run the cell")
    import jax  # noqa: F401 - the device is taken before the server opens

    from copycat_tpu.collections import DistributedMap
    from copycat_tpu.io import codec
    from copycat_tpu.io.local import LocalServerRegistry, LocalTransport
    from copycat_tpu.io.transport import Address
    from copycat_tpu.manager.atomix import AtomixClient, AtomixServer
    from copycat_tpu.manager.device_executor import DeviceEngineConfig
    from copycat_tpu.resource.consistency import Consistency
    from copycat_tpu.utils import tracing

    ref = _reference()
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    n_maps, space = cfg["maps"], cfg["keys_per_map"]
    n_keys = cfg["preloaded_keys_per_map"]
    clients = mix["clients"]
    if clients != n_maps:
        raise SystemExit(f"map plane: {clients} clients for {n_maps} maps; "
                         "the mix drives one client per map")
    t_setup = time.perf_counter()
    perf = time.perf_counter
    native = codec.codec() is not None
    pools = {f: cfg["other_pool_slots"] for f in ResourceConfig._fields}
    pools["map_slots"] = cfg["map_slots"]
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
    await server.open()
    t_open = perf() - t_setup
    client = AtomixClient([addr], LocalTransport(registry),
                          session_timeout=cfg["session_timeout_s"])
    await client.open()
    out: dict = {}
    try:
        maps = await asyncio.gather(*(
            client.get(f"map{i}", DistributedMap) for i in range(n_maps)))
        for m in maps:
            m.with_consistency(Consistency.ATOMIC)
        engine = server.server.state_machine.device_engine
        groups = engine._groups
        table = groups.state.resources.map_table
        table_bytes = table.size * table.dtype.itemsize
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(groups.state))
        say(f"map plane: codec={'native' if native else 'python'}, "
            f"LocalTransport, capacity {cfg['capacity']} P={cfg['peers']}, "
            f"{n_maps} maps, map table {cfg['map_slots']} slots = "
            f"{map_buckets(cfg['map_slots'])} buckets a replica, "
            f"{table_bytes:,} bytes of table in {state_bytes:,} of state; "
            f"server open {t_open:.1f}s, with the client and the creates "
            f"{perf() - t_setup:.1f}s; {ctx.compiles.note()}")

        # -- the data, from the seed, and the load ---------------------------
        rng = np.random.default_rng(ctx.seed)
        keys = [ref.keys_of(i, n_keys, space).tolist() for i in range(n_maps)]
        loaded = rng.integers(mix["value_min"], mix["value_max"], (
            n_maps, n_keys), dtype=np.int64, endpoint=True).tolist()
        model = ref.PlainMaps(n_maps)
        state = {"stop": False, "issued": 0, "raised": 0, "wrong": 0,
                 "loaded": 0, "first_wrong": "",
                 "flip": ctx.fault == "flip-result"}

        def wrong(text: str) -> None:
            state["wrong"] += 1
            state["first_wrong"] = state["first_wrong"] or text

        depth = mix["load_outstanding_per_map"]

        async def load(i: int, lane: int) -> None:
            # one of the ``depth`` calls outstanding on map ``i``: a
            # coroutine that awaits its puts one by one (a task a put would
            # cost the loop more than the put)
            m, ks, vs = maps[i], keys[i], loaded[i]
            for at in range(lane, n_keys, depth):
                got = await m.put(ks[at], vs[at])
                state["loaded"] += 1
                if state["loaded"] % tenth == 0:
                    marks.append(perf())
                if got is not None:
                    wrong(f"map {i}: the load's put of new key {ks[at]} "
                          f"answered {got}")

        # ten million puts leave tens of millions of live objects (a log
        # entry, a retained commit, the plain maps' items): the collector's
        # full passes over them would be most of the load, so it rests until
        # the set-up's own collection (``gc_tune``, before the window)
        t_load, rss0 = perf(), _resident_mb()
        tenth = max(1, n_maps * n_keys // 10)
        marks = [t_load]                  # each tenth of the keys loaded
        gc.disable()
        try:
            await asyncio.gather(*(load(i, lane) for i in range(n_maps)
                                   for lane in range(depth)))
        finally:
            gc.enable()
        load_s = perf() - t_load
        for i in range(n_maps):
            model.maps[i] = dict(zip(keys[i], loaded[i]))
        del loaded
        say(f"map plane: loaded {n_maps * n_keys:,} keys through "
            f"DistributedMap.put in {load_s:.1f}s, "
            f"{n_maps * n_keys / load_s:,.0f} keys/s, {depth} calls "
            f"outstanding a map; resident {rss0:,.0f} -> "
            f"{_resident_mb():,.0f} MB; keys/s by tenths of the load: "
            + ", ".join(f"{tenth / t:,.0f}" for t in np.diff(marks))
            + f"; {ctx.compiles.note()}")

        # the traffic: one shared ring of draws, each client starting at its
        # own offset; ranks dealt to a map's keys by a shift of its own
        ranks = ref.zipf_ranks(rng, n_keys, mix["zipfian_constant"], RING)
        deal = rng.permutation(n_keys)
        shifts = rng.integers(0, n_keys, clients).tolist()
        picks = deal[ranks].tolist()
        values = rng.integers(mix["value_min"], mix["value_max"], RING,
                              dtype=np.int64, endpoint=True).tolist()
        is_read = (rng.random(RING) < mix["read_share"]).tolist()
        offsets = rng.integers(0, RING, clients).tolist()
        spare = rng.integers(0, n_keys, (clients, mix[
            "untouched_keys_read_back"])).tolist()

        calls: list[float] = []           # every reply: call instant
        acks: list[float] = []            # every reply: reply instant
        reads: list[bool] = []            # every reply: was it a get
        written: list[set[int]] = [set() for _ in range(clients)]

        async def one(i: int) -> None:
            m, ks, k, mask = maps[i], keys[i], offsets[i], RING - 1
            shift, mine, touched = shifts[i], model.maps[i], written[i]
            while not state["stop"]:
                k = (k + 1) & mask
                key = ks[(picks[k] + shift) % n_keys]
                read, v = is_read[k], values[k]
                state["issued"] += 1
                t = perf()
                try:
                    got = await (m.get(key) if read else m.put(key, v))
                except Exception as e:  # noqa: BLE001 - counted, not hidden
                    state["raised"] += 1
                    state["first_wrong"] = state["first_wrong"] or repr(e)
                    continue
                calls.append(t)
                acks.append(perf())
                reads.append(read)
                want = mine.get(key)
                if not read:
                    mine[key] = v
                    touched.add(key)
                    if state["flip"]:
                        got, state["flip"] = got ^ 1, False
                if got != want:
                    wrong(f"map {i} key {key}: {'get' if read else 'put'} "
                          f"answered {got}, the plain map {want}")

        # everything the load left is young to a collector switched back on:
        # its first passes would hold the loop through the warm-up's traffic.
        # One pass here, and the load's heap is out of every later one
        t_gc = perf()
        gc.collect()
        gc.freeze()
        say(f"map plane: the load's heap collected and frozen in "
            f"{perf() - t_gc:.1f}s")

        tasks = [asyncio.ensure_future(one(i)) for i in range(clients)]

        t_warm = perf()
        try:
            since = await _warm_up(ctx, acks)
        except RuntimeError:
            # or a client whose session is closed under it spins on the error
            state["stop"] = True
            raise
        ctx.gc_tune()
        # the collection holds the loop: let the calls it delayed be answered
        # before the window opens, or they sit in its tail
        await asyncio.sleep(SETTLE_S)
        say(f"map plane: warm-up {perf() - t_warm:.1f}s, {len(acks):,} calls, "
            f"{since:,} of them after the last program was loaded; "
            f"{ctx.compiles.note()}")

        # -- the window ------------------------------------------------------
        counter = groups.metrics.counter
        watched = ("rounds", "map_chain_ops", "map_vector_ops")
        if ctx.trace:
            tracing.TRACER.clear()
            tracing.enable()
        compiled_before = ctx.compiles.count
        issued0, first = state["issued"], len(acks)
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
            say("map plane: spans in the tracer's ring at window end: "
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
        traced = ((acks_a > held[0][1]) & (acks_a < held[1][0])
                  if held else np.zeros(len(acks_a), bool))
        traced_queries = int((traced & np.asarray(reads[first:], bool)).sum())
        traced_commands = int(traced.sum()) - traced_queries
        if ctx.fault == "drop-ack":
            i = next(i for i, w in enumerate(written) if w)
            model.maps[i][next(iter(written[i]))] ^= 1
        unread = unsized = 0

        async def read_back(i: int) -> None:
            nonlocal unread, unsized
            m, mine = maps[i], model.maps[i]
            for key in sorted(written[i] | {keys[i][j] for j in spare[i]}):
                got = await m.get(key)    # awaited before the sum is read
                unread += int(got != mine[key])
            size = await m.size()
            unsized += int(size != model.size(i))

        with ctx.annotate("check"):
            await asyncio.wait_for(asyncio.gather(*(
                read_back(i) for i in range(clients))), 300)
        on_device_keys, shadowed = engine.map_keys()
        on_device = engine._next_group - len(engine._free)
        failed = state["raised"] + len(pending)
        n_back = sum(len(w) for w in written)
        checks = [
            (f"(a) replies of {len(acks):,} and of the load's {n_maps * n_keys:,} "
             "that differ from the plain maps'" + (
                 f": {state['first_wrong']}" if state["first_wrong"] else ""),
             state["wrong"], 0),
            (f"(b) keys of {n_back:,} written in the window and "
             f"{clients * len(spare[0]):,} untouched whose ATOMIC read-back "
             "differs from the plain maps', and maps whose size() does",
             unread + unsized, 0),
            (f"(c) keys the leader lanes' live counts hold on the device "
             f"({on_device_keys:,}) less the plain maps' {model.total():,}, "
             "as a distance", abs(on_device_keys - model.total()), 0),
            ("(d) keys in the host shadow", shadowed, 0),
            ("(e) map commands that took a generator chain inside the window",
             deltas["map_chain_ops"], 0),
            (f"(f) maps of {n_maps} not on the device", n_maps - on_device, 0),
            ("(g) calls that raised, timed out or got no reply within "
             f"{GRACE_S:.0f}s of the window", failed, 0),
            ("(h) compilations inside the window", compiled_inside, 0),
        ]
        correct = acked > 0 and all(v <= lim for _, v, lim in checks)
        p50, p99 = (float(np.percentile(lat_ms, q)) if acked else 0.0
                    for q in (50, 99))
        p99_clear = (float(np.percentile(lat_ms[clear], 99))
                     if clear.any() else None)
        window = t_end - t_start
        fifths = np.histogram(acks_a[inside], bins=5,
                              range=(t_start, t_end))[0] / (window / 5)
        say("map plane: acknowledged ops/s by fifths of the window: "
            + ", ".join(f"{r:,.0f}" for r in fifths)
            + f"; host load average {os.getloadavg()[0]:.2f} on "
            f"{len(os.sched_getaffinity(0))} cores")
        say(f"map plane: window {window:.3f}s, {issued:,} calls issued, "
            f"{acked:,} acknowledged inside it, ack p50 {p50:.3f} ms p99 "
            f"{p99:.3f} ms; {deltas['rounds']} engine rounds, "
            f"{deltas['map_vector_ops']:,} map commands on the vector lane, "
            f"{deltas['map_chain_ops']} through a generator; resident "
            f"{_resident_mb():,.0f} MB; checks took {perf() - t_check:.1f}s")
        if held:
            say(f"map plane: the profiler held the loop "
                + " and ".join(f"{h1 - h0:.1f}s" for h0, h1 in held)
                + f"; ack p99 {p99_clear} ms over the {int(clear.sum()):,} "
                f"calls not in flight then; {traced_commands:,} puts and "
                f"{traced_queries:,} gets acknowledged between its start "
                "and its stop")
        if compiled_inside:
            print("map plane: compile events inside the window, seconds after "
                  "its first instant: " + ", ".join(
                      f"+{at - secs - t_start:.3f} {name} ({secs:.3f}s)"
                      for at, secs, name in ctx.compiles.events[
                          compiled_before:compiled_before + compiled_inside]),
                  file=sys.stderr, flush=True)
        for what, value, limit in checks:
            print(f"map plane: check: {what}: {value} (limit {limit})",
                  file=sys.stderr, flush=True)
        out = {
            "window_start": t_start,
            "correct": correct, "attempted": issued, "failed": failed,
            "checks": checks,
            "end_to_end": {"served_ops_per_s": acked / window},
            "clock": {"ack_p50_ms": p50, "ack_p99_ms": p99_clear,
                      "window_s": window, "acked_ops": acked,
                      "traced_commands": traced_commands,
                      "traced_queries": traced_queries,
                      "replicas": cfg["peers"],
                      "bucket_bytes": table_bytes // (
                          cfg["capacity"] * cfg["peers"]
                          * map_buckets(cfg["map_slots"])),
                      "other_state_bytes": state_bytes - table_bytes,
                      "programs": ["jit_round", "jit_query", "jit_fused"],
                      "round_program": "jit_round",
                      "load_keys_per_s": n_maps * n_keys / load_s},
            "spans": spans,
            "counters": {"rounds": deltas["rounds"]},
        }
    finally:
        for node in (client, server):
            try:
                await asyncio.wait_for(node.close(), 20)
            except (Exception, asyncio.TimeoutError):  # noqa: BLE001
                pass
    return out


def run(ctx) -> dict:
    return asyncio.run(asyncio.wait_for(_drive(ctx), 3000))
