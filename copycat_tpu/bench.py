"""Headline benchmark: committed linearizable ops/sec over batched Raft groups.

BASELINE.md metric: "committed ops/sec over 10k Raft groups". The reference
publishes no numbers (BASELINE.md §published — absence verified), so
``vs_baseline`` is reported against the BASELINE.json north-star target of
1M linearizable ops/sec.

Prints ONE JSON line on stdout; all diagnostics go to stderr.

Scenarios (``COPYCAT_BENCH_SCENARIO``, BASELINE.md benchmark configs):

- ``counter`` (default, config #1 scaled out): every submit slot carries a
  ``DistributedLong.addAndGet``; G groups × 3 peers; R rounds under
  ``lax.scan``. Each committed entry is a quorum-replicated, leader-applied
  linearizable command.
- ``election`` (config #2): 1k groups; a random peer is isolated every few
  rounds (device-side nemesis masks), forcing re-elections; measures
  elections completed/sec (batched RequestVote tally path).
- ``map`` (config #3): put/get mix through the hashed map apply kernel.
- ``lock`` (config #4): acquire→queue→release→grant chains in every group
  (event-push grant path).
- ``mixed`` (config #5): counter+map+lock mix with per-round random peer
  isolation (nemesis) across all groups.
- ``host``: client-visible throughput through the full host runtime
  (queue-managed ``submit_batch`` → harvest → results), the number a
  framework client actually sees.
- ``spi``: client-visible throughput through the PUBLIC resource API —
  ``COPYCAT_BENCH_SPI_INSTANCES`` (default 1000) device-backed
  ``DistributedAtomicLong``s on an ``AtomixServer(executor="tpu")``,
  pipelined increments over real sessions, ``COPYCAT_BENCH_SPI_BURSTS``
  bursts; reports on-device instance count + total engine rounds.
- ``readmix``: read-dominated (90/10) traffic through the public API
  and the batched read pump; headline value is client-visible
  reads/sec.
- ``cluster``: the first REPLICATED-cluster scenario — a 3-member
  ``RaftServer`` cluster over the local transport with a nemesis-injected
  per-message latency (a realistic LAN RTT; without it an in-process
  "network" hides the wire stall pipelined replication
  (docs/REPLICATION.md) exists to cover), writes through the public
  ``RaftClient`` API; headline value is committed ops/sec. ``--storage
  {memory,mapped,disk}`` runs the same workload on a durable log level
  (the durability A/B, docs/DURABILITY.md).
- ``sharded``: the multi-raft keyspace-sharding scenario
  (docs/SHARDING.md) — a 3-member cluster hosting ``--groups N`` Raft
  groups with leadership spread, many clients, zipfian keys, under a
  cross-region wire delay where the bounded replication window caps a
  single ordered log; headline value is committed ops/sec, with
  groups-led / per-group-commit / routing-mix in the artifact. The A/B
  knob is ``--groups 1`` (the single-group plane, which
  ``COPYCAT_MULTI_GROUP=0`` pins bit-identically).
- ``apply``: the apply-limited scenario (docs/SHARDING.md "Apply
  ordering") — a single member hosting ``--groups N`` Raft groups,
  many sessions, hot/cold zipfian device counters, and an interleaved
  eligible/ineligible op stream that the dependency classifier spans
  where a contiguous one would collapse to the per-entry path;
  headline value is committed ops/sec, with the ``apply.*`` family
  (spans, conflicts, fused dispatches, rows/runs per dispatch) in the
  artifact.
- ``recovery``: the crash-recovery scenario — a fresh member catching up
  to a loaded, compacted cluster via snapshot-install streaming vs full
  log replay (``COPYCAT_SNAPSHOTS`` A/B inside one run); headline value
  is the catch-up speedup, with ``snap.*`` metrics in the artifact.
- ``fanout``: the edge read tier scenario (docs/EDGE_READS.md) — few
  writers, a sweep of reader-session counts over zipfian counters;
  with ``COPYCAT_EDGE_READS`` on, SEQUENTIAL reads serve from
  client-local CRDT replicas fed by per-resource deltas and reads/s
  scales with the reader count while cluster commits stay flat; the
  knob-off lane pins reads/s to server read capacity (the A/B).
  The artifact embeds the cache-served-read trace proof (client-side
  spans only) and the aggregated ``edge.*`` client family.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import jax

from .utils import knobs

import jax.numpy as jnp
import numpy as np

from copycat_tpu.ops import apply as ap
from copycat_tpu.ops.apply import ResourceConfig
from copycat_tpu.utils.profiling import xla_trace
from copycat_tpu.ops.consensus import (
    Config,
    Submits,
    current_leader,
    full_delivery,
    init_state,
    install_snapshots,
    make_submits,
    query_step,
    step,
)

# Pool state is carried through every step (HBM traffic), so each scenario
# compiles in only the pools its groups actually host (ResourceConfig
# zero-size pools are compiled out of the kernel).
RESOURCE_CONFIGS = {
    "counter": ResourceConfig.counters_only(),
    "election": ResourceConfig.counters_only(),
    "map": ResourceConfig(set_slots=0, queue_slots=0, wait_slots=0,
                          listener_slots=0, event_slots=0,
                          multimap_slots=0, topic_slots=0),
    "lock": ResourceConfig(map_slots=0, set_slots=0, queue_slots=0,
                           listener_slots=0, multimap_slots=0,
                           topic_slots=0),
    # config #5 keeps its round-2 definition (the six original kernels)
    # so numbers stay comparable; multimap/topic have their own coverage
    "mixed": ResourceConfig(multimap_slots=0, topic_slots=0),
}

SCENARIO = knobs.get_str("COPYCAT_BENCH_SCENARIO")
GROUPS = knobs.get_int(
    "COPYCAT_BENCH_GROUPS", default=1000 if SCENARIO == "election" else 10000)
PEERS = knobs.get_int("COPYCAT_BENCH_PEERS")
# The mixed config is [G,P,L]-bandwidth-bound: L=32 measured +11%
# throughput and p50 106->31 ms vs L=64 at 100k x 5 (PERF.md round-3
# continuation); the ring only needs to cover in-flight depth (S=16 with
# backpressure). Other configs are smaller and keep the roomier default.
LOG_SLOTS = knobs.get_int("COPYCAT_BENCH_LOG_SLOTS",
                          default=32 if SCENARIO == "mixed" else 64)
ROUNDS = knobs.get_int("COPYCAT_BENCH_ROUNDS")
# Best-of-N: 5 reps (~0.3s each) buys insurance against host dispatch
# jitter on the recorded number (a one-chip machine shares its host's
# CPU cores); the per-rep spread is reported beside it.
REPEATS = knobs.get_int("COPYCAT_BENCH_REPEATS")
SUBMIT_SLOTS = knobs.get_int("COPYCAT_BENCH_SUBMIT_SLOTS")
NORTH_STAR_OPS = 1_000_000.0
# Default the Pallas quorum-tally kernel ON for TPU: measured at parity
# with the jnp path after the one-hot rewrite (PERF.md §Pallas A/B — the
# step is dispatch-bound, not tally-bound), and running it keeps the
# production kernel exercised. The CPU keeps the jnp path: the kernel
# compiles for the TPU only (interpret mode is for tests). Resolved from
# the device main() verified, not at import: asking initializes the
# backend.
_PALLAS_ENV = knobs.get_raw("COPYCAT_BENCH_PALLAS")


def use_pallas() -> bool:
    if _PALLAS_ENV is not None:
        return _PALLAS_ENV == "1"
    return jax.devices()[0].platform == "tpu"
# Per-pool apply budgets (value,map,set,queue,lock,election): budgets
# select the conflict-partitioned apply path (ops/consensus.py
# Config.pool_budgets); empty = the single sequential scan.
# - mixed: steady-state arrivals are value 2 / map 4 / set 2 / queue 4 /
#   lock 2 / elect 2 per group per round; budgets give ~2x headroom so
#   post-nemesis backlogs drain while cutting each pool's HBM traffic to
#   budget/A of the sequential scan's.
# - lock: full budgets — partitioning still wins 2.3x because the fully
#   unrolled single-pool fold fuses the 16 applies into few HBM passes.
# - counter/election/map: sequential scan measures equal or better
#   (dispatch-bound or single-pool-dominant with value planes tiny).
_full = str(max(4, SUBMIT_SLOTS))  # = applies_per_round, never a throttle
MIXED_POOL_BUDGETS = "4,6,4,6,4,4,4,4"
MIXED_TIMERS = (2, 4)  # election timeout range; see run_throughput
_default_budgets = {"mixed": MIXED_POOL_BUDGETS,
                    "lock": ",".join([_full] * 8)}.get(SCENARIO, "")
_budgets_env = knobs.get_str("COPYCAT_BENCH_POOL_BUDGETS",
                             default=_default_budgets)
POOL_BUDGETS = (tuple(int(x) for x in _budgets_env.split(","))
                if _budgets_env else None)

# Set to a directory to capture an XLA profiler trace of the first timed
# repetition (open in TensorBoard/XProf, or summarize with
# copycat_tpu.utils.profiling.summarize_trace).
PROFILE_DIR = knobs.get_str("COPYCAT_BENCH_PROFILE")

# COPYCAT_BENCH_TELEMETRY=1: compile the round-8 device telemetry block
# into the measured step (Config(telemetry=True)) — the A/B knob behind
# PERF.md round 8's ≤2% ms/round acceptance bound. run_throughput
# accumulates the telemetry deltas in the scan carry (an unread output
# would be dead-code-eliminated and the A/B would measure nothing) and
# reports the totals; run_host/run_session surface the engine's
# device.* family in the --metrics-json artifact.
TELEMETRY = knobs.get_bool("COPYCAT_BENCH_TELEMETRY")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: per-run registry snapshots scenarios contribute to the
#: ``--metrics-json`` artifact (run_spi adds the server's full
#: stats_snapshot + the client registry), keyed by component name.
METRICS_SNAPSHOTS: dict = {}

#: retained ``/series`` windows scenarios contribute to the artifact
#: (docs/OBSERVABILITY.md "Retrospective telemetry") — where an
#: end-of-run snapshot says WHAT the run cost, the series says WHEN:
#: commit-rate ramp, election spikes mid-run, latency onsets. Keyed by
#: component name like METRICS_SNAPSHOTS; empty when the servers ran
#: with COPYCAT_SERIES=0 or the scenario spins no server.
SERIES_WINDOWS: dict = {}


def capture_series(component: str, server_like: object) -> None:
    """Stash ``server_like``'s retained series window (if it keeps one)
    under ``component`` for the ``--metrics-json`` artifact."""
    store = getattr(server_like, "series", None)
    if store is not None:
        SERIES_WINDOWS[component] = store.payload()


def _bench_gc_tune() -> None:
    """GC tuning shared by the SPI-stack scenarios (the production-server
    treatment): a 1k-op burst allocates ~20k short-lived objects (tasks,
    futures, messages); with default thresholds a gen-2 pass lands
    mid-burst and the collector walks the whole live server — 30+ ms, a
    3-4x swing between otherwise identical reps. Freeze the settled heap
    out of collection and raise gen0 so cyclic garbage is still
    collected, just between bursts."""
    import gc

    gc.collect()
    gc.freeze()
    gc.set_threshold(100_000, 50, 100)


async def _close_spi_stack(client, server, transport=None) -> None:
    """Teardown shared by the SPI-stack scenarios: bounded closes (a
    wedged node must not hang the bench), then the transport's own
    shutdown when it runs background machinery (the native epoll pair)."""
    import asyncio

    try:
        await asyncio.wait_for(client.close(), 10)
    except Exception:
        pass
    try:
        await asyncio.wait_for(server.close(), 10)
    except Exception:
        pass
    if transport is not None:
        shutdown = getattr(transport, "shutdown", None)
        if shutdown is not None:
            shutdown()


def percentiles(hist: np.ndarray, qs) -> list[int]:
    """Percentile values from an exact count histogram (index = value)."""
    total = int(hist.sum())
    if total == 0:
        return [0 for _ in qs]
    cum = np.cumsum(hist)
    return [int(np.searchsorted(cum, q * total)) for q in qs]


def zipf_sampler(rng, n_keys: int, s: float):
    """Deterministic zipfian rank draw: inverse-CDF over 1/rank^s on
    the caller's seeded ``rng``. Shared by the hot/cold-keyspace
    scenarios (``sharded``, ``apply``) so their skew semantics cannot
    drift apart; returns a 0-based rank in ``[0, n_keys)``."""
    import bisect

    weights = [1.0 / (r ** s) for r in range(1, n_keys + 1)]
    total_w = sum(weights)
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w / total_w
        cdf.append(acc)

    def draw() -> int:
        return min(bisect.bisect_left(cdf, rng.random()), n_keys - 1)

    return draw


def empty_submits(G: int) -> Submits:
    return make_submits(G, SUBMIT_SLOTS)


def current_leaders(state) -> jnp.ndarray:
    """[G] leader peer index per group, -1 if none."""
    return current_leader(state)[0]


def tile_pattern(pattern, G: int) -> jnp.ndarray:
    """Tile a short per-slot pattern across [G, SUBMIT_SLOTS]."""
    pat = jnp.asarray(pattern, jnp.int32)
    row = pat[jnp.arange(SUBMIT_SLOTS) % pat.size]
    return jnp.broadcast_to(row, (G, SUBMIT_SLOTS))


def counter_submits(G: int) -> Submits:
    ones = jnp.ones((G, SUBMIT_SLOTS), jnp.int32)
    return Submits(opcode=ones * ap.OP_LONG_ADD, a=ones, b=ones * 0,
                   c=ones * 0, tag=ones, valid=ones.astype(bool))


def map_submits(G: int) -> Submits:
    """put/get mix over 10 rotating keys per group (BASELINE config #3:
    "10k keys × 1k groups" = 10 keys/group at G=1000, hashed-keyspace
    kernel)."""
    ones = jnp.ones((G, SUBMIT_SLOTS), jnp.int32)
    opc = [ap.OP_MAP_PUT, ap.OP_MAP_GET] * 5
    keys = [1, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 2, 3, 6, 8, 10]
    return Submits(opcode=tile_pattern(opc, G), a=tile_pattern(keys, G),
                   b=ones * 7, c=ones * 0, tag=ones,
                   valid=ones.astype(bool))


def lock_submits(G: int) -> Submits:
    """acquire(1) → acquire(2, queued) → release(1) [grants 2] → release(2).

    Every round drives the full grant chain including the event-push path.
    """
    ones = jnp.ones((G, SUBMIT_SLOTS), jnp.int32)
    opc = [ap.OP_LOCK_ACQUIRE, ap.OP_LOCK_ACQUIRE,
           ap.OP_LOCK_RELEASE, ap.OP_LOCK_RELEASE]
    who = [1, 2, 1, 2]
    waitflag = [-1, -1, 0, 0]
    return Submits(opcode=tile_pattern(opc, G), a=tile_pattern(who, G),
                   b=tile_pattern(waitflag, G),
                   c=ones * 0, tag=ones, valid=ones.astype(bool))


def mixed_submits(G: int) -> Submits:
    """Every resource kernel in one round (BASELINE config #5): counter,
    map, set, queue, lock grant chain, election listen/resign — so the
    nemesis run exercises all apply paths plus the event outbox."""
    ones = jnp.ones((G, SUBMIT_SLOTS), jnp.int32)
    opc = [ap.OP_LONG_ADD, ap.OP_MAP_PUT, ap.OP_MAP_GET,
           ap.OP_SET_ADD, ap.OP_SET_REMOVE,
           ap.OP_Q_OFFER, ap.OP_Q_POLL,
           ap.OP_LOCK_ACQUIRE, ap.OP_LOCK_RELEASE,
           ap.OP_ELECT_LISTEN, ap.OP_ELECT_RESIGN,
           ap.OP_LONG_ADD, ap.OP_MAP_PUT,
           ap.OP_Q_OFFER, ap.OP_Q_POLL, ap.OP_MAP_GET]
    a = [1, 3, 3, 5, 5, 6, 0, 9, 9, 4, 4, 1, 7, 6, 0, 7]
    b = [0, 5, 0, 0, 0, 0, 0, -1, 0, 0, 0, 0, 8, 0, 0, 0]
    return Submits(opcode=tile_pattern(opc, G), a=tile_pattern(a, G),
                   b=tile_pattern(b, G),
                   c=ones * 0, tag=ones, valid=ones.astype(bool))


SUBMIT_BUILDERS = {
    "counter": counter_submits,
    "map": map_submits,
    "lock": lock_submits,
    "mixed": mixed_submits,
}


def isolation_masks(rounds: int, G: int, P: int, period: int,
                    seed: int) -> jnp.ndarray:
    """Per-round victim peer per group (-1 = no fault), [R, G] int32."""
    rng = np.random.default_rng(seed)
    victims = np.full((rounds, G), -1, np.int32)
    for r in range(0, rounds, period):
        victims[r: r + period // 2] = rng.integers(0, P, G, dtype=np.int32)
    return jnp.asarray(victims)


def victim_deliver(victim: jnp.ndarray, G: int, P: int) -> jnp.ndarray:
    """deliver[G,P,P] isolating ``victim[G]`` (-1 = fully connected)."""
    peers = jnp.arange(P)
    hit = peers[None, :] == victim[:, None]          # [G,P]
    cut = hit[:, :, None] | hit[:, None, :]
    return ~cut | (victim[:, None, None] < 0)


def elect_all(state, jit_step, empty, deliver, key, G):
    t0 = time.perf_counter()
    for r in range(150):
        key, k = jax.random.split(key)
        state, out = jit_step(state, empty, deliver, k)
        if int((np.asarray(out.leader) >= 0).sum()) == G:
            break
    else:
        raise RuntimeError("not all groups elected a leader")
    log(f"bench: all {G} leaders elected in {r + 1} rounds "
        f"({time.perf_counter() - t0:.1f}s incl. compile)")
    return state, key


def run_throughput(scenario: str) -> dict:
    # Mixed (the nemesis config) defaults to tight election timers: the
    # p99 tail IS failover latency — entries appended the round a
    # partition forms wait out lease-drop + step-down + election. With
    # the lease-gated accept, timers 2-5 measured p99 14→7 rounds and
    # p99.9 18→10 at +13% throughput vs the 4-9 default (round-4 A/B);
    # a second A/B tightened to 2-4 (p99 8→7 rounds and +19% ops at
    # 256×3, +4% at 1024×5). 2-3 is over the edge: the randomization
    # range is too narrow to break vote splits and elections thrash.
    # Partition-only nemesis keeps short timers safe here; lossy
    # environments (the verdict runner) keep the roomier engine default.
    t_min = knobs.get_int("COPYCAT_BENCH_TIMER_MIN",
                          default=MIXED_TIMERS[0] if scenario == "mixed"
                          else 4)
    t_max = knobs.get_int("COPYCAT_BENCH_TIMER_MAX",
                          default=MIXED_TIMERS[1] if scenario == "mixed"
                          else 9)
    config = Config(use_pallas=use_pallas(),
                    append_window=max(4, SUBMIT_SLOTS),
                    applies_per_round=max(4, SUBMIT_SLOTS),
                    pool_budgets=POOL_BUDGETS,
                    timer_min=t_min, timer_max=t_max,
                    telemetry=TELEMETRY,
                    resource=RESOURCE_CONFIGS.get(scenario, ResourceConfig()))
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = init_state(GROUPS, PEERS, LOG_SLOTS, init_key, config)
    deliver = full_delivery(GROUPS, PEERS)
    submits = SUBMIT_BUILDERS[scenario](GROUPS)
    jit_step = jax.jit(partial(step, config=config))

    log(f"bench[{scenario}]: G={GROUPS} P={PEERS} L={LOG_SLOTS} "
        f"rounds={ROUNDS} device={jax.devices()[0].platform}")
    state, key = elect_all(state, jit_step, empty_submits(GROUPS), deliver,
                           key, GROUPS)

    nemesis = scenario == "mixed"
    victims = (isolation_masks(ROUNDS, GROUPS, PEERS, period=20, seed=1)
               if nemesis else None)

    # Commit latency (BASELINE.md metric). DEFINITION: device-measured
    # rounds from leader log APPEND to state-machine APPLY (+1 for the
    # appending round), converted to ms at the measured round cadence.
    # This is the replication+commit+apply cost; the host-observed
    # submit->harvest latency adds host queueing on top (RaftGroups
    # reports it in metrics "commit_latency_rounds" — see
    # BENCH_SCENARIOS.md for both numbers side by side).
    # Histogrammed on device with exact integer buckets; the histogram's
    # one-hot compare scales with the bucket count, so only nemesis runs
    # (whose entries can wait out isolation windows plus the whole
    # backpressure ring) pay for the wide range. The top bucket is a
    # saturation catch-all (warned about below if hit).
    max_lat = LOG_SLOTS + (200 if nemesis else 34)

    # Telemetry A/B (PERF.md round 8): the deltas must be CONSUMED or
    # XLA dead-code-eliminates the whole block and the A/B measures the
    # pre-change program. Accumulate them in the scan carry (per-group
    # int32 sums — the same amortized-fetch shape the drivers use).
    tel0 = None
    if TELEMETRY:
        from copycat_tpu.ops.apply import NUM_POOLS
        from copycat_tpu.ops.consensus import DeviceTelemetry
        zg = jnp.zeros((GROUPS,), jnp.int32)
        tel0 = DeviceTelemetry(
            elections_started=zg, leader_changes=zg, term_bumps=zg,
            leaderless=zg, commit_advance=zg, commit_max=zg, term_max=zg,
            leader_lane=zg, leader_term=zg,
            applies=jnp.zeros((GROUPS, NUM_POOLS + 1), jnp.int32),
            ring_occ_max=zg, submit_rejections=zg, vote_splits=zg,
            events_drained=zg, events_dropped=zg)

    def run(state, key):
        def body(carry, victim):
            state, key, applied_prev, tel_acc = carry
            key, k = jax.random.split(key)
            dl = (victim_deliver(victim, GROUPS, PEERS) if nemesis
                  else deliver)
            state, out = step(state, submits, dl, k, config=config)
            if TELEMETRY:
                tel_acc = jax.tree.map(lambda a, d: a + d, tel_acc,
                                       out.telemetry)
            if nemesis:
                # Followers that fell beyond the ring window during an
                # isolation can never be served by AppendEntries again;
                # without the snapshot-install path (what RaftGroups does
                # host-side) they accumulate until groups lose quorum and
                # throughput decays run over run. Unconditional masked
                # install fuses into the round; a lax.cond every-k-rounds
                # variant measured 1.8x SLOWER (the cond blocks XLA's
                # in-place aliasing of the full state).
                state = install_snapshots(state, out.stale, out.leader,
                                          config=config)
            lat = jnp.clip(out.out_latency.reshape(-1), 0, max_lat - 1)
            # one-hot select-reduce, NOT .at[].add(): XLA lowers the scatter
            # to an element-at-a-time DMA loop that costs more than the whole
            # consensus step (see PERF.md — same pathology as the engine's
            # round-2 gather/scatter rewrite, rediscovered here by profile)
            hist = jnp.sum(
                (lat[:, None] == jnp.arange(max_lat, dtype=jnp.int32)[None, :])
                & out.out_valid.reshape(-1)[:, None],
                axis=0, dtype=jnp.int32)
            # exact-once committed-op count: global applied high-water delta
            # (out_valid reports are at-least-once across leader changes)
            applied_now = jnp.max(state.applied_index, axis=1)
            n = jnp.sum(applied_now - applied_prev, dtype=jnp.int32)
            return (state, key, applied_now, tel_acc), (n, hist)
        applied0 = jnp.max(state.applied_index, axis=1)
        (state, key, _, tel_acc), (counts, hists) = jax.lax.scan(
            body, (state, key, applied0, tel0), victims,
            length=None if nemesis else ROUNDS)
        return state, key, counts.sum(), hists.sum(axis=0), tel_acc

    run_jit = jax.jit(run)
    state, key, n, hist, tel = run_jit(state, key)
    jax.block_until_ready(n)
    log(f"bench[{scenario}]: warmup committed {int(n)} ops")
    best, best_dt, best_hist = 0.0, 1.0, np.asarray(hist)

    reps = []
    tel_totals: dict = {}
    for rep in range(REPEATS):
        with xla_trace(PROFILE_DIR if rep == 0 else None):
            t0 = time.perf_counter()
            state, key, n, hist, tel = run_jit(state, key)
            n = int(jax.block_until_ready(n))
            dt = time.perf_counter() - t0
        ops = n / dt
        reps.append(ops)
        if ops >= best:
            best, best_dt, best_hist = ops, dt, np.asarray(hist)
        if TELEMETRY:
            for name in ("elections_started", "leader_changes",
                         "leaderless", "commit_advance",
                         "submit_rejections", "vote_splits"):
                tel_totals[name] = tel_totals.get(name, 0) + int(
                    np.asarray(getattr(tel, name), np.int64).sum())
        log(f"bench[{scenario}]: rep {rep}: {n} committed ops in {dt:.3f}s "
            f"-> {ops:,.0f} ops/sec ({dt / ROUNDS * 1e3:.2f} ms/round)")
    if best_hist[-1]:
        log(f"bench[{scenario}]: WARNING: {int(best_hist[-1])} samples "
            f"saturated the top latency bucket (>{max_lat - 1} rounds); "
            f"p99 is a lower bound")

    ms_per_round = best_dt / ROUNDS * 1e3
    # out_latency counts rounds the entry sat in the log before apply; the
    # round that appended+replicated+applied it counts too (+1): an op
    # submitted before round r completes after round r finishes.
    p50_r, p99_r = [p + 1 for p in percentiles(best_hist, (0.50, 0.99))]
    log(f"bench[{scenario}]: commit latency p50={p50_r} rounds "
        f"({p50_r * ms_per_round:.2f} ms)  p99={p99_r} rounds "
        f"({p99_r * ms_per_round:.2f} ms) at {ms_per_round:.2f} ms/round")

    suffix = "" if scenario == "counter" else f"_{scenario}"
    out = {
        "metric": (f"committed_linearizable_ops_per_sec_{GROUPS}_groups"
                   f"{suffix}"),
        "value": round(best, 1),
        "unit": "ops/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        "p50_commit_latency_ms": round(p50_r * ms_per_round, 3),
        "p99_commit_latency_ms": round(p99_r * ms_per_round, 3),
        "p50_commit_latency_rounds": int(p50_r),
        "p99_commit_latency_rounds": int(p99_r),
        **spread(reps),
    }
    if TELEMETRY:
        out["telemetry"] = True
        out["device_telemetry"] = tel_totals
    return out


def run_host() -> dict:
    """Client-visible throughput through the host runtime.

    Default mode ``bulk`` (``COPYCAT_BENCH_HOST_MODE``): the pipelined
    vectorized driver (``models/bulk.py``) — double-buffered rounds,
    zero per-op Python — with ``COPYCAT_BENCH_HOST_BURST`` ops per group
    per burst (default 8 bursts' worth of submit slots). Mode ``queued``
    keeps the round-3 queue-managed path (submit_batch → run_until with
    full exactly-once retry bookkeeping) for comparison; both are
    client-visible numbers. BENCH_SCENARIOS.md documents them side by
    side."""
    from .models import BulkDriver, RaftGroups

    mode = knobs.get_str("COPYCAT_BENCH_HOST_MODE")
    if mode not in ("deep", "deepscan", "bulk", "queued"):
        raise SystemExit(
            f"COPYCAT_BENCH_HOST_MODE={mode!r}: deep|deepscan|bulk|queued")
    rg = RaftGroups(GROUPS, PEERS, log_slots=LOG_SLOTS,
                    submit_slots=SUBMIT_SLOTS,
                    config=Config(use_pallas=use_pallas(),
                                  append_window=max(4, SUBMIT_SLOTS),
                                  applies_per_round=max(4, SUBMIT_SLOTS),
                                  pool_budgets=POOL_BUDGETS,
                                  resource=RESOURCE_CONFIGS["counter"],
                                  telemetry=TELEMETRY,
                                  monotone_tag_accept=(
                                      mode in ("deep", "deepscan"))))
    per_group = knobs.get_int(
        "COPYCAT_BENCH_HOST_BURST",
        default=SUBMIT_SLOTS * (8 if mode != "queued" else 1))
    log(f"bench[host:{mode}]: G={GROUPS} P={PEERS} {per_group} "
        f"ops/group/burst; device={jax.devices()[0].platform}")
    rg.wait_for_leaders()
    groups = np.repeat(np.arange(GROUPS), per_group)
    driver = BulkDriver(rg, deep_scan=(mode == "deepscan"))

    lat_p50 = lat_p99 = 0.0

    def burst() -> tuple[float, dict | None]:
        if mode != "queued":
            res = driver.drive(groups, ap.OP_LONG_ADD, 1)
            return groups.size / res.wall_s, res.latency_percentiles_ms()
        t0 = time.perf_counter()
        tags = rg.submit_batch(groups, ap.OP_LONG_ADD, 1).tolist()
        rg.run_until(tags, max_rounds=120)
        return len(tags) / (time.perf_counter() - t0), None

    burst()  # warm (jit compile + first transfers)
    best = 0.0
    reps = []
    for rep in range(REPEATS):
        with xla_trace(PROFILE_DIR if rep == 0 else None):
            ops, pct = burst()
        if ops >= best and pct is not None:
            lat_p50, lat_p99 = pct["p50"], pct["p99"]  # pair with `value`
        best = max(best, ops)
        reps.append(ops)
        log(f"bench[host:{mode}]: rep {rep}: {ops:,.0f} committed "
            f"ops/sec host-observed")
    out = {
        "metric": (f"host_observed_committed_ops_per_sec_{GROUPS}_groups"
                   + {"deep": "", "deepscan": "_scan", "bulk": "_sync",
                      "queued": "_queued"}[mode]),
        "value": round(best, 1),
        "unit": "ops/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        **spread(reps),
    }
    if mode != "queued":
        # client-observed submit->result latency (ms, best-rep cadence)
        out["p50_latency_ms"] = round(lat_p50, 3)
        out["p99_latency_ms"] = round(lat_p99, 3)
    else:
        lat = rg.metrics.histogram("commit_latency_rounds")
        out["p50_commit_latency_rounds"] = lat.percentile(50)
        out["p99_commit_latency_rounds"] = lat.percentile(99)
    METRICS_SNAPSHOTS["driver"] = rg.metrics.snapshot()
    if rg.telemetry is not None:
        METRICS_SNAPSHOTS["device"] = rg.device_snapshot()
    return out


def run_session() -> dict:
    """Client-visible throughput through the SESSIONED client runtime
    (``models/session_client.BulkSessionClient`` — the unified plane,
    VERDICT r4 #2): ``COPYCAT_BENCH_SESSIONS`` sessions over one client
    share one deep drive per flush; every op carries (session, seq), is
    exactly-once deduplicated, and its result is correlated into the
    session cache. This is the reference-shaped client contract
    (Copycat client runtime, SURVEY.md §2.3) riding the north-star
    plane; round-5 target ≥100k committed ops/s on one chip."""
    from .models import BulkSessionClient, RaftGroups

    n_sessions = knobs.get_int("COPYCAT_BENCH_SESSIONS")
    rg = RaftGroups(GROUPS, PEERS, log_slots=LOG_SLOTS,
                    submit_slots=SUBMIT_SLOTS,
                    config=Config(use_pallas=use_pallas(),
                                  append_window=max(4, SUBMIT_SLOTS),
                                  applies_per_round=max(4, SUBMIT_SLOTS),
                                  pool_budgets=POOL_BUDGETS,
                                  resource=RESOURCE_CONFIGS["counter"],
                                  telemetry=TELEMETRY,
                                  monotone_tag_accept=True))
    per_group = knobs.get_int("COPYCAT_BENCH_HOST_BURST",
                              default=SUBMIT_SLOTS * 8)
    log(f"bench[session]: G={GROUPS} P={PEERS} {n_sessions} sessions, "
        f"{per_group} ops/group/burst; "
        f"device={jax.devices()[0].platform}")
    rg.wait_for_leaders()
    client = BulkSessionClient(
        rg, deep_scan=knobs.get_bool("COPYCAT_BENCH_SESSION_SCAN"))
    sessions = [client.open_session() for _ in range(n_sessions)]
    # each session owns an equal slice of the groups (disjoint groups
    # keep per-session FIFO independent of scheduling order)
    slices = np.array_split(np.arange(GROUPS), n_sessions)

    def burst() -> float:
        t0 = time.perf_counter()
        total = 0
        for s, sl in zip(sessions, slices):
            seqs = s.submit_batch(np.repeat(sl, per_group),
                                  ap.OP_LONG_ADD, 1)
            total += seqs.size
        n = client.flush()
        assert n == total
        return total / (time.perf_counter() - t0)

    burst()  # warm (jit compile + first transfers)
    best = 0.0
    reps = []
    for rep in range(REPEATS):
        with xla_trace(PROFILE_DIR if rep == 0 else None):
            ops = burst()
        best = max(best, ops)
        reps.append(ops)
        log(f"bench[session]: rep {rep}: {ops:,.0f} committed "
            f"session ops/sec client-observed")
    # exactly-once spot check: group 0's counter equals its op count
    s0 = sessions[0]
    q = s0.submit(0, ap.OP_VALUE_GET)
    client.flush()
    expect = per_group * (len(reps) + 1)
    assert s0.result(q) == expect, (s0.result(q), expect)
    METRICS_SNAPSHOTS["driver"] = rg.metrics.snapshot()
    if rg.telemetry is not None:
        METRICS_SNAPSHOTS["device"] = rg.device_snapshot()
    return {
        "metric": f"session_committed_ops_per_sec_{GROUPS}_groups",
        "value": round(best, 1),
        "unit": "ops/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        "sessions": n_sessions,
        **spread(reps),
    }


def spread(reps: list[float]) -> dict:
    """Per-rep min/median/max so regressions are distinguishable from
    run-to-run host jitter."""
    s = sorted(reps)
    return {"reps_min": round(s[0], 1),
            "reps_median": round(s[len(s) // 2], 1),
            "reps_max": round(s[-1], 1),
            "reps_n": len(s)}


def run_spi() -> dict:
    """Manager-level throughput THROUGH the public resource API: N
    device-backed ``DistributedAtomicLong`` instances hosted by an
    ``AtomixServer(executor="tpu")``, pipelined increments from real
    client sessions; measures client-visible committed ops/sec through
    the full stack — session protocol → CPU Raft log → shared-window
    device engine. The reference's public API *is* its data path
    (``Atomix.java:205``); this scenario keeps ours honest about that.
    """
    import asyncio

    from .atomic import DistributedAtomicLong
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .manager.atomix import AtomixClient, AtomixServer
    from .manager.device_executor import DeviceEngineConfig

    instances = knobs.get_int("COPYCAT_BENCH_SPI_INSTANCES")
    bursts = knobs.get_int("COPYCAT_BENCH_SPI_BURSTS")
    # int (default): device-resident counters — the device fast path.
    # str: DistributedMap puts with STRING values, which every device-
    # backed map refuses onto int32 lanes and takes through the host
    # SHADOW instead — this measures the documented K/V degradation
    # cliff (VERDICT r4 missing #4; reference DistributedMap.java:54
    # takes arbitrary K/V, so the cliff must be a number, not a
    # surprise).
    payload = knobs.get_str("COPYCAT_BENCH_SPI_PAYLOAD")
    if payload not in ("int", "str"):
        raise SystemExit(f"COPYCAT_BENCH_SPI_PAYLOAD={payload!r}: int|str")
    # Engine pool provisioning (DeviceEngineConfig.resource): the counter
    # scenario hosts only value registers, and pool state is carried
    # through every engine round — counters-only provisioning measured
    # the loaded round 9.3 -> 5.1 ms at capacity 1024 on CPU. The str
    # (shadow-cliff) scenario needs the map pool live, so it keeps all
    # pools; override with COPYCAT_BENCH_SPI_POOLS=counters|all.
    pools = knobs.get_str("COPYCAT_BENCH_SPI_POOLS",
                          default="counters" if payload == "int" else "all")
    if pools not in ("counters", "all"):
        raise SystemExit(f"COPYCAT_BENCH_SPI_POOLS={pools!r}: counters|all")
    engine_pools = (ResourceConfig.counters_only() if pools == "counters"
                    else None)
    # client pipelining depth: each session keeps WAVES commands in
    # flight per instance (sequential per instance — FIFO preserved).
    # Depth 2 overlaps the client/submit stack with the window pump
    # (~+40% measured on CPU); deeper convoys fragment the window into
    # more partial pump cycles and lose it again.
    waves = knobs.get_int("COPYCAT_BENCH_SPI_WAVES")
    # local (in-memory, default) | tcp (asyncio sockets) | native (C++
    # epoll + C codec): same wire format, so the knob isolates the IO
    # stack's share of the client-visible number
    transport_kind = knobs.get_str("COPYCAT_BENCH_SPI_TRANSPORT")
    capacity = 1 << max(4, (instances - 1).bit_length())  # pow2 >= instances
    # Engine ring: the spi steady state keeps ≤1 in-flight entry per
    # group (one public op per instance per burst), so the 32-slot ring
    # round 5 ran was 2x headroom paid in one-hot pass width every
    # round; 16 measured -0.3 ms/loaded round at G=1024 with identical
    # commit behavior. Override for deeper per-group pipelining.
    log_slots = knobs.get_int("COPYCAT_BENCH_SPI_LOG_SLOTS")
    registry = LocalServerRegistry()  # shared by both ends in local mode

    def make_transport():
        if transport_kind == "local":
            return LocalTransport(registry)
        if transport_kind == "tcp":
            from .io.tcp import TcpTransport
            return TcpTransport()
        if transport_kind == "native":
            from .io.native import NativeTcpTransport, native_available
            if not native_available():
                raise SystemExit("native transport unavailable "
                                 "(make -C native)")
            return NativeTcpTransport()
        raise SystemExit(
            f"COPYCAT_BENCH_SPI_TRANSPORT={transport_kind!r}: "
            "local|tcp|native")

    async def drive() -> dict:
        addr = Address("127.0.0.1", 15999)
        # ONE transport shared by both ends (client()/server() hand out
        # independent endpoints): the native kind owns an epoll thread
        # pair, and a second instance would contend for the single core
        # this scenario documents — shut it down in the finally.
        transport = make_transport()
        server = AtomixServer(
            addr, [addr], transport,
            election_timeout=0.5, heartbeat_interval=0.1,
            session_timeout=60.0, executor="tpu",
            engine_config=DeviceEngineConfig(
                capacity=capacity, num_peers=PEERS, log_slots=log_slots,
                submit_slots=4, resource=engine_pools))
        await server.open()
        client = AtomixClient([addr], transport,
                              session_timeout=60.0)
        await client.open()
        try:
            t0 = time.perf_counter()
            if payload == "str":
                from .collections import DistributedMap
                counters = await asyncio.gather(
                    *(client.get(f"map{i}", DistributedMap)
                      for i in range(instances)))
            else:
                counters = await asyncio.gather(
                    *(client.get(f"ctr{i}", DistributedAtomicLong)
                      for i in range(instances)))
            engine = server.server.state_machine.device_engine
            on_device = engine._next_group
            log(f"bench[spi:{payload}]: {instances} instances created in "
                f"{time.perf_counter() - t0:.1f}s; {on_device} on-device "
                f"(capacity {capacity}); device="
                f"{jax.devices()[0].platform}")
            _bench_gc_tune()

            lats: list[float] = []
            n_op = [0]

            async def one(c) -> None:
                for _ in range(waves):
                    t = time.perf_counter()
                    if payload == "str":
                        # string values refuse the int32 lanes -> host
                        # shadow
                        n_op[0] += 1
                        await c.put("k", f"v{n_op[0]}")
                    else:
                        await c.add_and_get(1)
                    lats.append(time.perf_counter() - t)

            reps = []
            best_lats: list[float] = []
            burst_ops = instances * waves
            for rep in range(bursts):
                lats.clear()
                t0 = time.perf_counter()
                await asyncio.gather(*(one(c) for c in counters))
                dt = time.perf_counter() - t0
                ops = burst_ops / dt
                reps.append(ops)
                if ops >= max(reps):
                    best_lats = list(lats)  # latencies pair with `value`
                log(f"bench[spi]: rep {rep}: {burst_ops} ops in {dt:.3f}s "
                    f"-> {ops:,.0f} client-visible ops/sec")
            lat = np.asarray(sorted(best_lats))
            rounds0 = engine._groups.rounds if engine._groups else 0
            # --metrics-json artifact: every bench run leaves an
            # attributable snapshot (server lanes + transport + client)
            METRICS_SNAPSHOTS["server"] = server.server.stats_snapshot()
            METRICS_SNAPSHOTS["client"] = client.client.metrics.snapshot()
            capture_series("server", server.server)
            return {
                "metric": (f"spi_client_visible_ops_per_sec_{instances}"
                           f"_device_instances"
                           + ("" if transport_kind == "local"
                              else f"_{transport_kind}")
                           + ("" if payload == "int" else "_shadow")
                           + ("" if waves == 1 else f"_w{waves}")),
                "transport": transport_kind,
                "payload": payload,
                "pipeline_depth": waves,
                "value": round(max(reps), 1),
                "unit": "ops/sec",
                "vs_baseline": round(max(reps) / NORTH_STAR_OPS, 4),
                "p50_latency_ms": round(float(lat[len(lat) // 2]) * 1e3, 3),
                "p99_latency_ms": round(
                    float(lat[int(len(lat) * 0.99)]) * 1e3, 3),
                "on_device_instances": int(on_device),
                "engine_rounds": int(rounds0),
                **spread(reps),
            }
        finally:
            await _close_spi_stack(client, server, transport)

    return asyncio.run(drive())


def run_readmix() -> dict:
    """Read-dominated (90/10 read/write) traffic THROUGH the public
    resource API: the readmix production coordination workloads actually
    run. N device-backed ``DistributedAtomicLong`` instances on an
    ``AtomixServer(executor="tpu")``; per burst every instance commits
    ONE increment and serves ``COPYCAT_BENCH_READMIX_READS`` (default 9)
    gets. Reads ride the no-append query lane: client-side they coalesce
    into per-consistency ``QueryBatchRequest``s, server-side the batched
    read pump windows them across sessions, pays the
    consistency gate once per window, and evaluates the device-eligible
    set through one ``query_step`` engine round. Headline value =
    client-visible READS/sec; writes and total ops ride along in the
    artifact. ``COPYCAT_BENCH_READMIX_LEVEL`` picks the facade
    consistency (atomic = lease-gated reads, default; sequential;
    linearizable = quorum-confirmed reads)."""
    import asyncio

    from .atomic import DistributedAtomicLong
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .manager.atomix import AtomixClient, AtomixServer
    from .manager.device_executor import DeviceEngineConfig
    from .resource.consistency import Consistency

    instances = knobs.get_int("COPYCAT_BENCH_SPI_INSTANCES")
    bursts = knobs.get_int("COPYCAT_BENCH_SPI_BURSTS")
    reads_per_write = knobs.get_int("COPYCAT_BENCH_READMIX_READS")
    level = knobs.get_str("COPYCAT_BENCH_READMIX_LEVEL")
    facade_level = {"atomic": Consistency.ATOMIC,
                    "sequential": Consistency.SEQUENTIAL,
                    "none": Consistency.NONE}.get(level)
    if facade_level is None and level != "linearizable":
        raise SystemExit(
            f"COPYCAT_BENCH_READMIX_LEVEL={level!r}: "
            "atomic|sequential|none|linearizable")
    capacity = 1 << max(4, (instances - 1).bit_length())
    log_slots = knobs.get_int("COPYCAT_BENCH_SPI_LOG_SLOTS")
    registry = LocalServerRegistry()

    async def drive() -> dict:
        addr = Address("127.0.0.1", 15998)
        transport = LocalTransport(registry)
        server = AtomixServer(
            addr, [addr], transport,
            election_timeout=0.5, heartbeat_interval=0.1,
            session_timeout=60.0, executor="tpu",
            engine_config=DeviceEngineConfig(
                capacity=capacity, num_peers=PEERS, log_slots=log_slots,
                submit_slots=4,
                resource=ResourceConfig.counters_only()))
        await server.open()
        client = AtomixClient([addr], LocalTransport(registry),
                              session_timeout=60.0)
        await client.open()
        try:
            t0 = time.perf_counter()
            counters = await asyncio.gather(
                *(client.get(f"ctr{i}", DistributedAtomicLong)
                  for i in range(instances)))
            if facade_level is not None:
                for c in counters:
                    c.with_consistency(facade_level)
            else:
                # full quorum-confirmed reads: the facade vocabulary tops
                # out at ATOMIC (bounded); override the read level only
                for c in counters:
                    c._read_cl = "linearizable"
            engine = server.server.state_machine.device_engine
            on_device = engine._next_group
            log(f"bench[readmix:{level}]: {instances} instances in "
                f"{time.perf_counter() - t0:.1f}s; {on_device} on-device; "
                f"device={jax.devices()[0].platform}")
            _bench_gc_tune()

            async def one(c) -> None:
                await c.add_and_get(1)
                for _ in range(reads_per_write):
                    await c.get()

            burst_reads = instances * reads_per_write
            burst_ops = instances * (reads_per_write + 1)
            reps = []
            for rep in range(bursts):
                t0 = time.perf_counter()
                await asyncio.gather(*(one(c) for c in counters))
                dt = time.perf_counter() - t0
                reads_s = burst_reads / dt
                reps.append(reads_s)
                log(f"bench[readmix]: rep {rep}: {burst_reads} reads + "
                    f"{instances} writes in {dt:.3f}s -> "
                    f"{reads_s:,.0f} reads/sec "
                    f"({burst_ops / dt:,.0f} ops/sec)")
            # correctness spot check: every counter saw every increment
            v = await counters[0].get()
            assert v == bursts, (v, bursts)
            METRICS_SNAPSHOTS["server"] = server.server.stats_snapshot()
            METRICS_SNAPSHOTS["client"] = client.client.metrics.snapshot()
            best = max(reps)
            return {
                "metric": (f"readmix_client_visible_reads_per_sec_"
                           f"{instances}_device_instances_{level}"),
                "value": round(best, 1),
                "unit": "reads/sec",
                "vs_baseline": round(best / NORTH_STAR_OPS, 4),
                "read_level": level,
                "reads_per_write": reads_per_write,
                "ops_per_sec": round(best * (reads_per_write + 1)
                                     / reads_per_write, 1),
                "on_device_instances": int(on_device),
                **spread(reps),
            }
        finally:
            await _close_spi_stack(client, server)

    return asyncio.run(drive())


def run_fanout() -> dict:
    """Edge read tier bench (docs/EDGE_READS.md): few writers, a sweep
    of reader-session counts, a zipfian key mix — the
    millions-of-readers shape in miniature. With ``COPYCAT_EDGE_READS``
    on (default), each reader's first SEQUENTIAL read per counter
    subscribes and seeds its client-local replica; every later read
    serves from it, so read throughput scales with the reader count
    while the cluster sees only the writers' commits and the
    (reader-count-bounded) seed reads. With the knob off, every read
    pays the server round-trip and reads/s is pinned to the server's
    read-window capacity — the A/B this scenario exists to measure.

    The artifact also carries the trace proof: a cache-served read's
    assembled trace consists solely of client-side spans
    (``client.edge_serve`` — no ``proxy.hop``, no ``quorum.wait``)."""
    import asyncio
    import random as _random

    from .atomic import DistributedAtomicLong
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .manager.atomix import AtomixClient, AtomixServer
    from .resource.consistency import Consistency
    from .utils import tracing
    from .utils.tasks import spawn

    edge_on = knobs.get_bool("COPYCAT_EDGE_READS")
    reader_counts = [int(x) for x in knobs.get_str(
        "COPYCAT_BENCH_FANOUT_READERS").split(",") if x.strip()]
    writers = knobs.get_int("COPYCAT_BENCH_FANOUT_WRITERS")
    n_keys = knobs.get_int("COPYCAT_BENCH_FANOUT_KEYS")
    reads_per_reader = knobs.get_int("COPYCAT_BENCH_FANOUT_READS")
    bursts = knobs.get_int("COPYCAT_BENCH_FANOUT_BURSTS")
    zipf_s = knobs.get_float("COPYCAT_BENCH_FANOUT_ZIPF")
    rng = _random.Random(17)
    draw_rank = zipf_sampler(rng, n_keys, zipf_s)

    async def drive() -> dict:
        registry = LocalServerRegistry()
        addr = Address("127.0.0.1", 15997)
        # the coordination-plane shape: CPU machines, one member — the
        # cluster is deliberately NOT the interesting axis here, the
        # client-side replica is
        server = AtomixServer(addr, [addr], LocalTransport(registry),
                              election_timeout=0.5,
                              heartbeat_interval=0.1,
                              session_timeout=60.0)
        await server.open()
        writer_clients = [AtomixClient([addr], LocalTransport(registry),
                                       session_timeout=60.0)
                          for _ in range(writers)]
        await asyncio.gather(*(c.open() for c in writer_clients))
        readers: list[AtomixClient] = []
        try:
            writer_ctrs = [
                await asyncio.gather(
                    *(c.get(f"ctr{k}", DistributedAtomicLong)
                      for k in range(n_keys)))
                for c in writer_clients]
            log(f"bench[fanout]: edge reads "
                f"{'ON' if edge_on else 'OFF'}; {writers} writers, "
                f"{n_keys} keys, readers sweep {reader_counts}")
            _bench_gc_tune()
            sweep: dict[str, dict] = {}
            reps_largest: list[float] = []
            write_stop = [False]
            writes_done = [0]

            async def write_loop(ctrs) -> None:
                while not write_stop[0]:
                    await ctrs[draw_rank()].add_and_get(1)
                    writes_done[0] += 1

            async def reader_session() -> None:
                c = AtomixClient([addr], LocalTransport(registry),
                                 session_timeout=60.0)
                await c.open()
                readers.append(c)

            def server_reads() -> int:
                snap = server.server.metrics.snapshot()
                return sum(v for k, v in snap.items()
                           if isinstance(v, (int, float))
                           and str(k).startswith("query_reads"))

            for count in reader_counts:
                while len(readers) < count:
                    grow = min(64, count - len(readers))
                    await asyncio.gather(
                        *(reader_session() for _ in range(grow)))
                plans = []
                for c in readers[:count]:
                    keys = [draw_rank() for _ in range(reads_per_reader)]
                    cached = {}
                    for k in set(keys):
                        if k not in cached:
                            h = await c.get(f"ctr{k}",
                                            DistributedAtomicLong)
                            h.with_consistency(Consistency.SEQUENTIAL)
                            cached[k] = h
                    plans.append([cached[k] for k in keys])

                async def read_plan(plan) -> None:
                    for h in plan:
                        await h.get()

                burst_reads = count * reads_per_reader
                reps = []
                for rep in range(bursts):
                    write_stop[0] = False
                    writes_done[0] = 0
                    wtasks = [spawn(write_loop(cs), name="fanout-writer")
                              for cs in writer_ctrs]
                    reads_before = server_reads()
                    t0 = time.perf_counter()
                    await asyncio.gather(*(read_plan(p) for p in plans))
                    dt = time.perf_counter() - t0
                    write_stop[0] = True
                    await asyncio.gather(*wtasks)
                    reads_s = burst_reads / dt
                    reps.append(reads_s)
                    log(f"bench[fanout]: {count} readers rep {rep}: "
                        f"{burst_reads} reads in {dt:.3f}s -> "
                        f"{reads_s:,.0f} reads/s; "
                        f"{writes_done[0] / dt:,.0f} committed writes/s; "
                        f"{server_reads() - reads_before} server reads")
                    if count == reader_counts[-1]:
                        last = (dt, writes_done[0],
                                server_reads() - reads_before)
                sweep[str(count)] = {
                    "reads_per_sec": round(max(reps), 1),
                    "reps": [round(r, 1) for r in reps],
                }
                if count == reader_counts[-1]:
                    reps_largest = reps
                    dt, wd, sr = last
                    sweep[str(count)]["committed_writes_per_sec"] = \
                        round(wd / dt, 1)
                    sweep[str(count)]["server_reads_last_rep"] = sr

            # trace proof: a cache-served read's assembled trace is
            # client-side only (no proxy.hop / quorum.wait / group.*)
            trace_proof = None
            if edge_on:
                tracing.enable()
                try:
                    await plans[0][0].get()  # warmed: serves locally
                    proof_id = next(
                        (tid for tid, spans in tracing.TRACER.traces().items()
                         if any(s.name == "client.edge_serve"
                                for s in spans)), None)
                    if proof_id is not None:
                        spans = tracing.TRACER.spans_for(proof_id)
                        assembly = tracing.assemble_trace(
                            proof_id,
                            {"client": [s.as_dict() for s in spans]})
                        names = sorted({s.name for s in spans})
                        trace_proof = {
                            "spans": names,
                            "members": assembly.get("members", []),
                            "client_only": all(
                                n.startswith("client.") for n in names),
                            "incomplete": assembly.get("incomplete"),
                        }
                finally:
                    tracing.disable()

            # aggregate the reader clients' edge families for the
            # artifact (the CI smoke asserts these keys)
            agg: dict[str, float] = {}
            for c in readers:
                for k, v in c.client.metrics.snapshot().items():
                    if str(k).startswith("edge.") \
                            and isinstance(v, (int, float)):
                        agg[str(k)] = agg.get(str(k), 0) + v
            METRICS_SNAPSHOTS["server"] = server.server.stats_snapshot()
            METRICS_SNAPSHOTS["edge_clients"] = agg
            largest = reader_counts[-1]
            best = max(reps_largest)
            return {
                "metric": (f"fanout_reads_per_sec_{largest}_readers"
                           + ("" if edge_on else "_server")),
                "value": round(best, 1),
                "unit": "reads/sec",
                "vs_baseline": round(best / NORTH_STAR_OPS, 4),
                "edge_reads": edge_on,
                "readers": reader_counts,
                "writers": writers,
                "keys": n_keys,
                "sweep": sweep,
                "trace": trace_proof,
                **spread(reps_largest),
            }
        finally:
            write_stop[0] = True
            for c in readers + writer_clients:
                try:
                    await asyncio.wait_for(c.close(), 5)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            await asyncio.wait_for(server.close(), 10)

    return asyncio.run(drive())


def _cluster_machine_types():
    """Op types + counter machine shared by the cluster-shaped scenarios
    (``cluster``/``sharded``/``recovery``/``compartment``). The classes
    live in ``copycat_tpu.testing.counter_machine`` — jax-free, so the
    compartment scenario's spawned member/ingress processes can host the
    same machine (same serialization ids) without importing this module."""
    from .testing.counter_machine import ClusterAdd, ClusterGet, \
        CounterMachine

    return ClusterAdd, ClusterGet, CounterMachine


def _cluster_storage_factory(level_name: str):
    """(build_storage(i), cleanup) for a bench cluster: MEMORY needs no
    directories; MAPPED/DISK get one temp directory per member, removed
    by ``cleanup()``."""
    import shutil
    import tempfile

    from .server.log import Storage, StorageLevel

    level = StorageLevel(level_name)
    if level is StorageLevel.MEMORY:
        return (lambda i: Storage(StorageLevel.MEMORY)), (lambda: None)
    dirs: list[str] = []

    def build(i: int) -> Storage:
        d = tempfile.mkdtemp(prefix=f"copycat-bench-{level.value}-{i}-")
        dirs.append(d)
        return Storage(level, d)

    def cleanup() -> None:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)

    return build, cleanup


def run_cluster() -> dict:
    """The first replicated-cluster bench: committed ops/sec through a
    REAL N-member ``RaftServer`` cluster (leader election, pipelined
    AppendEntries streams, quorum commit) on the local transport, writes
    through the public ``RaftClient`` API (micro-batched sessioned
    commands, exactly-once seqs).

    A fixed per-message-leg delay (``COPYCAT_BENCH_CLUSTER_DELAY_MS``,
    default 2.0 ms — a realistic same-region cross-AZ RTT of ~4 ms) is
    injected via the transport nemesis so the leader->follower
    replication stream actually pays wire latency: one window in
    flight (``COPYCAT_REPL_DEPTH=1``) is then capped at window/RTT
    entries/s per peer, which is exactly what the pipeline's depth
    exists to break.

    ``--storage {memory,mapped,disk}`` (env
    ``COPYCAT_BENCH_CLUSTER_STORAGE``, default memory) runs the same
    workload on a durable log level, so the durability A/B cost — fsync
    policy, segment persistence, snapshot cadence — is MEASURED, with
    the level and the ``snap.*`` family recorded in the
    ``--metrics-json`` artifact."""
    import asyncio

    from .client.client import RaftClient
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .server.raft import LEADER, RaftServer

    ClusterAdd, ClusterGet, CounterMachine = _cluster_machine_types()
    storage_level = knobs.get_str("COPYCAT_BENCH_CLUSTER_STORAGE").lower()
    members = knobs.get_int("COPYCAT_BENCH_CLUSTER_MEMBERS")
    n_clients = knobs.get_int("COPYCAT_BENCH_CLUSTER_CLIENTS")
    ops_per_client = knobs.get_int("COPYCAT_BENCH_CLUSTER_OPS")
    bursts = knobs.get_int("COPYCAT_BENCH_CLUSTER_BURSTS")
    delay_ms = knobs.get_float("COPYCAT_BENCH_CLUSTER_DELAY_MS")

    async def drive() -> dict:
        registry = LocalServerRegistry()
        addrs = [Address("local", 17000 + i) for i in range(members)]
        build_storage, cleanup_storage = _cluster_storage_factory(
            storage_level)
        servers = [
            RaftServer(addr, addrs,
                       LocalTransport(registry, local_address=addr),
                       CounterMachine(),
                       storage=build_storage(i),
                       election_timeout=0.5, heartbeat_interval=0.1,
                       session_timeout=120.0)
            for i, addr in enumerate(addrs)]
        await asyncio.gather(*(s.open() for s in servers))
        deadline = time.perf_counter() + 30
        leader = None
        while time.perf_counter() < deadline:
            leader = next((s for s in servers if s.role == LEADER), None)
            if leader is not None:
                break
            await asyncio.sleep(0.02)
        assert leader is not None, "no leader elected"
        clients = [RaftClient(addrs, LocalTransport(registry),
                              session_timeout=120.0)
                   for _ in range(n_clients)]
        await asyncio.gather(*(c.open() for c in clients))
        # inject wire latency only once the cluster + sessions are up:
        # the measured path is the replicated write plane, not elections
        nem = registry.attach_nemesis()
        nem.set_delay(delay_ms / 1e3)
        log(f"bench[cluster]: {members} members, {n_clients} clients x "
            f"{ops_per_client} ops/burst, {delay_ms} ms/leg, "
            f"storage={storage_level} (replication "
            f"window {leader._repl_window}, depth {leader._repl_depth})")
        _bench_gc_tune()
        burst_ops = n_clients * ops_per_client
        try:
            async def one(client: RaftClient, key: str) -> None:
                futs = [client.submit_command_nowait(
                    ClusterAdd(key=key, delta=1))
                    for _ in range(ops_per_client)]
                await asyncio.gather(*futs)

            reps = []
            for rep in range(bursts):
                t0 = time.perf_counter()
                await asyncio.gather(*(one(c, f"k{i}")
                                       for i, c in enumerate(clients)))
                dt = time.perf_counter() - t0
                ops = burst_ops / dt
                reps.append(ops)
                log(f"bench[cluster]: rep {rep}: {burst_ops} committed ops "
                    f"in {dt:.3f}s -> {ops:,.0f} ops/sec")
            # exactly-once spot check THROUGH the public read API: every
            # client's counter saw every increment exactly once
            for i, c in enumerate(clients):
                v = await c.submit(ClusterGet(key=f"k{i}"))
                assert v == bursts * ops_per_client, (i, v)
            # replicated-state spot check: a quorum actually holds the data
            await asyncio.sleep(0.3)
            converged = sum(
                1 for s in servers
                if s.state_machine.data.get("k0") == bursts * ops_per_client)
            assert converged >= len(servers) // 2 + 1, converged
            METRICS_SNAPSHOTS["server"] = leader.stats_snapshot()
            METRICS_SNAPSHOTS["client"] = clients[0].metrics.snapshot()
            capture_series("server", leader)
            best = max(reps)
            ack = leader.metrics.histogram("repl.ack_ms")
            raft_snap = METRICS_SNAPSHOTS["server"]["raft"]
            return {
                "metric": (f"cluster_committed_ops_per_sec_{members}_members"
                           + ("" if storage_level == "memory"
                              else f"_{storage_level}")),
                "value": round(best, 1),
                "unit": "ops/sec",
                "vs_baseline": round(best / NORTH_STAR_OPS, 4),
                "repl_window": leader._repl_window,
                "repl_depth": leader._repl_depth,
                "delay_ms_per_leg": delay_ms,
                "clients": n_clients,
                "storage_level": storage_level,
                "fsync": leader.storage.fsync,
                "snapshots_enabled": bool(
                    leader._snap_enabled and leader._snapshots is not None),
                # the durability A/B rides the artifact: every snap.*
                # series the leader registry holds (zeroes on memory)
                "snap": {k: v for k, v in raft_snap.items()
                         if k.startswith("snap.")},
                "p50_repl_ack_ms": round(ack.percentile(50), 3),
                "p99_repl_ack_ms": round(ack.percentile(99), 3),
                **spread(reps),
            }
        finally:
            nem.heal()
            for c in clients:
                try:
                    await asyncio.wait_for(c.close(), 10)
                except Exception:
                    pass
            for s in servers:
                try:
                    await asyncio.wait_for(s.close(), 10)
                except Exception:
                    pass
            cleanup_storage()

    return asyncio.run(drive())


def run_sharded() -> dict:
    """Multi-raft keyspace sharding bench (docs/SHARDING.md): committed
    ops/sec through a 3-member cluster hosting ``--groups N`` Raft
    groups, many clients, zipfian keys, writes through the public
    ``RaftClient`` API.

    The wire shape is CROSS-REGION: a fixed per-leg nemesis delay
    (``COPYCAT_BENCH_SHARDED_DELAY_MS``, default 100 ms -> 200 ms RTT)
    makes the bounded replication pipeline the binding constraint — a
    single ordered log cannot carry more than
    ``COPYCAT_REPL_MAX_INFLIGHT / RTT`` entries/s no matter how fast the
    leader's core is, because the in-flight cap exists to bound
    slow-follower memory (docs/REPLICATION.md). Sharding multiplies
    that ceiling: G groups = G independent windowed streams, with
    leadership spread so each member sequences ~G/N of them. The A/B
    for PERF.md round 12 is this scenario at ``--groups 4`` vs
    ``--groups 1`` (the single-group plane, which
    ``COPYCAT_MULTI_GROUP=0`` pins bit-identically)."""
    import asyncio
    import random as _random

    from .client.client import RaftClient
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .server.raft import LEADER, RaftServer

    ClusterAdd, ClusterGet, CounterMachine = _cluster_machine_types()
    groups = max(1, knobs.get_int("COPYCAT_BENCH_SHARDED_GROUPS"))
    members = knobs.get_int("COPYCAT_BENCH_CLUSTER_MEMBERS")
    n_clients = knobs.get_int("COPYCAT_BENCH_SHARDED_CLIENTS")
    ops_per_client = knobs.get_int("COPYCAT_BENCH_SHARDED_OPS")
    bursts = knobs.get_int("COPYCAT_BENCH_SHARDED_BURSTS")
    n_keys = knobs.get_int("COPYCAT_BENCH_SHARDED_KEYS")
    zipf_s = knobs.get_float("COPYCAT_BENCH_SHARDED_ZIPF")
    delay_ms = knobs.get_float("COPYCAT_BENCH_SHARDED_DELAY_MS")

    # zipfian key draw, deterministic: inverse-CDF over 1/rank^s
    rng = _random.Random(12)
    draw_rank = zipf_sampler(rng, n_keys, zipf_s)

    def draw_key() -> str:
        return f"user:{draw_rank()}"

    async def drive() -> dict:
        registry = LocalServerRegistry()
        addrs = [Address("local", 17100 + i) for i in range(members)]
        servers = [
            RaftServer(addr, addrs,
                       LocalTransport(registry, local_address=addr),
                       (lambda g: CounterMachine()), groups=groups,
                       election_timeout=0.5, heartbeat_interval=0.1,
                       session_timeout=120.0)
            for addr in addrs]
        await asyncio.gather(*(s.open() for s in servers))
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            led = {g.group_id for s in servers for g in s.groups
                   if g.role == LEADER}
            if len(led) == groups:
                break
            await asyncio.sleep(0.02)
        led = {g.group_id for s in servers for g in s.groups
               if g.role == LEADER}
        assert len(led) == groups, \
            f"groups without a leader: {set(range(groups)) - led}"
        clients = [RaftClient(addrs, LocalTransport(registry),
                              session_timeout=120.0)
                   for _ in range(n_clients)]
        await asyncio.gather(*(c.open() for c in clients))
        # inject wire latency only once the cluster + sessions are up
        nem = registry.attach_nemesis()
        nem.set_delay(delay_ms / 1e3)
        groups_led = {str(s.address): sum(1 for g in s.groups
                                          if g.role == LEADER)
                      for s in servers}
        log(f"bench[sharded]: {members} members x {groups} groups "
            f"(led: {groups_led}), {n_clients} clients x "
            f"{ops_per_client} ops/burst, zipf s={zipf_s} over "
            f"{n_keys} keys, {delay_ms} ms/leg")
        _bench_gc_tune()
        burst_ops = n_clients * ops_per_client
        expected: dict[str, int] = {}
        try:
            # streamed micro-batches: each event-loop turn stages one
            # CHUNK-op batch (the client's turn coalescing), many batches
            # in flight per session up to CAP outstanding ops — the
            # pipelined ingress keeps every group's replication window
            # full for the whole burst. A whole-burst gather (or a
            # half-wave gate) serializes on BATCH completion, i.e. on the
            # hottest group's queue, and measures commit latency convoys
            # instead of stream throughput.
            chunk = 64
            cap = max(chunk * 2, 768)

            async def one(client: RaftClient, keys: list) -> None:
                outstanding = 0
                wake = asyncio.Event()
                futs: list = []

                def done(_f) -> None:
                    nonlocal outstanding
                    outstanding -= 1
                    if outstanding <= cap // 2:
                        wake.set()

                i = 0
                while i < len(keys):
                    while outstanding >= cap:
                        wake.clear()
                        await wake.wait()
                    part = keys[i:i + chunk]
                    i += len(part)
                    for k in part:
                        fut = client.submit_command_nowait(
                            ClusterAdd(key=k, delta=1))
                        fut.add_done_callback(done)
                        futs.append(fut)
                    outstanding += len(part)
                    await asyncio.sleep(0)  # turn boundary: one batch
                await asyncio.gather(*futs)

            reps = []
            for rep in range(bursts):
                burst_keys = []
                for _ in range(n_clients):
                    keys = [draw_key() for _ in range(ops_per_client)]
                    for k in keys:
                        expected[k] = expected.get(k, 0) + 1
                    burst_keys.append(keys)
                t0 = time.perf_counter()
                await asyncio.gather(*(one(c, ks) for c, ks
                                       in zip(clients, burst_keys)))
                dt = time.perf_counter() - t0
                ops = burst_ops / dt
                reps.append(ops)
                log(f"bench[sharded]: rep {rep}: {burst_ops} committed "
                    f"ops in {dt:.3f}s -> {ops:,.0f} ops/sec")
            # causal-tracing wave (COPYCAT_BENCH_SHARDED_TRACE=1): one
            # traced micro-batch AFTER the timed bursts (the perf
            # numbers stay untraced) whose keys cover every group in a
            # single event-loop turn — one CommandBatchRequest fanning
            # out across group leaders, assembled into the cross-member
            # waterfall for the --metrics-json artifact.
            trace_section = None
            if knobs.get_bool("COPYCAT_BENCH_SHARDED_TRACE"):
                import zlib

                from .utils import tracing as _tracing

                _tracing.TRACER.clear()
                _tracing.enable()
                try:
                    cover: dict[int, str] = {}
                    i = 0
                    while len(cover) < groups:
                        k = f"trace:{i}"
                        cover.setdefault(zlib.crc32(k.encode()) % groups, k)
                        i += 1
                    tkeys = [cover[g] for g in sorted(cover)]
                    for k in tkeys:
                        expected[k] = expected.get(k, 0) + 1
                    await asyncio.gather(*(
                        clients[0].submit_command_nowait(
                            ClusterAdd(key=k, delta=1)) for k in tkeys))
                finally:
                    _tracing.disable()
                best_asm = None
                for tid, spans in _tracing.TRACER.traces().items():
                    if not any(s.name == "client.submit" for s in spans):
                        continue
                    asm = _tracing.assemble_trace(tid, {"ring": spans})
                    if best_asm is None or (len(asm["members"])
                                            > len(best_asm["members"])):
                        best_asm = asm
                assert best_asm is not None, "traced wave lost its trace"
                trace_section = {
                    "trace_id": best_asm["trace"],
                    "e2e_ms": best_asm["e2e_ms"],
                    "critical_path_ms": best_asm["critical_path_ms"],
                    "incomplete": best_asm["incomplete"],
                    "members": [m for m in best_asm["members"]
                                if m != "client"],
                    "phases": sorted({s["name"]
                                      for s in best_asm["spans"]}),
                    "waterfall": _tracing.render_waterfall(best_asm),
                }
                log("bench[sharded]: traced waterfall\n"
                    + trace_section["waterfall"])
                # the ingress member's snapshot carries the
                # latency.ingress_queue_ms / proxy_hop_ms phases the CI
                # smoke asserts (metrics.server below is member 0, which
                # may not have been the traced client's ingress)
                ingress_addr = clients[0]._connected_to
                ingress = next((s for s in servers
                                if s.address == ingress_addr), servers[0])
                METRICS_SNAPSHOTS["ingress"] = ingress.stats_snapshot()
            # exactly-once spot check THROUGH the public read API:
            # zipfian increments landed exactly once per key
            for k in sorted(expected)[:16]:
                v = await clients[0].submit(ClusterGet(key=k))
                assert v == expected[k], (k, v, expected[k])
            METRICS_SNAPSHOTS["server"] = servers[0].stats_snapshot()
            METRICS_SNAPSHOTS["client"] = clients[0].metrics.snapshot()
            capture_series("server", servers[0])
            best = max(reps)
            # routing mix: commands per owning group, summed over every
            # member's ingress counters
            routing_mix = {str(g): 0 for g in range(groups)}
            if groups > 1:
                for s in servers:
                    for g in range(groups):
                        routing_mix[str(g)] += s._metrics.counter(
                            "shard.routed", group=str(g)).value
            per_group_commit = {
                str(g.group_id): max(s.groups[g.group_id].commit_index
                                     for s in servers)
                for g in servers[0].groups}
            result_extra = ({"trace": trace_section}
                            if trace_section is not None else {})
            return {
                "metric": (f"sharded_committed_ops_per_sec_{members}"
                           f"_members_{groups}_groups"),
                "value": round(best, 1),
                "unit": "ops/sec",
                **result_extra,
                "vs_baseline": round(best / NORTH_STAR_OPS, 4),
                "groups": groups,
                "groups_led": groups_led,
                "per_group_commit": per_group_commit,
                "routing_mix": routing_mix,
                "delay_ms_per_leg": delay_ms,
                "clients": n_clients,
                "zipf_s": zipf_s,
                "keys": n_keys,
                "repl_max_inflight": servers[0]._repl_max_inflight,
                **spread(reps),
            }
        finally:
            nem.heal()
            for c in clients:
                try:
                    await asyncio.wait_for(c.close(), 10)
                except Exception:
                    pass
            for s in servers:
                try:
                    await asyncio.wait_for(s.close(), 10)
                except Exception:
                    pass

    return asyncio.run(drive())


def run_apply() -> dict:
    """Apply-limited bench (docs/SHARDING.md "Apply ordering"):
    committed ops/sec through the public resource API on a single
    member hosting ``--groups N`` Raft groups, many sessions, a
    hot/cold zipfian key mix over device counters, and an INTERLEAVED
    eligible/ineligible op stream — the shape that would collapse a
    contiguous vector classifier to the per-entry path.

    No replication wire, no nemesis delay: commit is immediate, so the
    apply path IS the bottleneck. Eligible sessions stream single-
    command ``get_and_set`` writes (device rows — deliberately NOT the
    ``DistributedAtomicLong`` CAS-retry loop, whose client-side
    contention on hot zipf keys would measure retry storms, not the
    apply plane) against per-session instance handles of a SHARED zipf
    keyspace; a ``COPYCAT_BENCH_APPLY_INELIGIBLE`` fraction of sessions
    streams host-shadow STRING sets instead — every shadow entry is an
    ineligible log entry interleaved between other sessions' device
    rows. The dependency classifier spans them — disjoint keys,
    disjoint sessions — and the fused collector merges all groups'
    staged runs into ONE ``DeviceEngine.run_vector`` per server turn
    (``apply.*`` family in the artifact;
    ``runs_per_dispatch`` ≈ groups is the one-device-round-per-turn
    evidence)."""
    import asyncio
    import random as _random

    from .atomic import DistributedAtomicValue
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .manager.atomix import AtomixClient, AtomixServer
    from .manager.device_executor import DeviceEngineConfig

    groups = max(1, knobs.get_int("COPYCAT_BENCH_APPLY_GROUPS"))
    n_sessions = knobs.get_int("COPYCAT_BENCH_APPLY_SESSIONS")
    ops_per_session = knobs.get_int("COPYCAT_BENCH_APPLY_OPS")
    bursts = knobs.get_int("COPYCAT_BENCH_APPLY_BURSTS")
    n_keys = knobs.get_int("COPYCAT_BENCH_APPLY_KEYS")
    zipf_s = knobs.get_float("COPYCAT_BENCH_APPLY_ZIPF")
    ineligible = knobs.get_float("COPYCAT_BENCH_APPLY_INELIGIBLE")

    # zipfian key draw, deterministic: inverse-CDF over 1/rank^s
    rng = _random.Random(17)
    draw_key = zipf_sampler(rng, n_keys, zipf_s)

    capacity = 1 << max(4, (n_keys + n_sessions - 1).bit_length())

    async def drive() -> dict:
        registry = LocalServerRegistry()
        (addr,) = (Address("local", 17500),)
        server = AtomixServer(
            addr, [addr], LocalTransport(registry),
            election_timeout=0.5, heartbeat_interval=0.1,
            session_timeout=120.0, executor="tpu", groups=groups,
            engine_config=DeviceEngineConfig(
                capacity=capacity, num_peers=3, log_slots=32,
                submit_slots=8,
                resource=ResourceConfig.counters_only()))
        await server.open()
        sessions = [AtomixClient([addr], LocalTransport(registry),
                                 session_timeout=120.0)
                    for _ in range(n_sessions)]
        await asyncio.gather(*(c.open() for c in sessions))
        rs = server.server
        # a positive fraction always yields >= 1 shadow session (the
        # interleave must exist to be measured); exactly 0 yields NONE —
        # the pure-eligible datapoint that isolates fusion gain from
        # spanning gain
        n_shadow = 0 if ineligible <= 0 else min(
            n_sessions - 1, max(1, round(n_sessions * ineligible)))
        n_elig = n_sessions - n_shadow
        try:
            # Per-session instance handles to the SHARED zipf keyspace:
            # instances of one value share a resource (and its device
            # row), so two sessions writing key k are same-key dependent
            # — the hot/cold mix — while every session still submits
            # through its own connection and seq space.
            handles = await asyncio.gather(*(
                asyncio.gather(*(sessions[i].get(
                    f"k{k}", DistributedAtomicValue)
                    for k in range(n_keys)))
                for i in range(n_elig)))
            # Shadow value names brute-forced against the crc32 router
            # so EVERY group's log interleaves ineligible entries —
            # hash-luck leaving a group shadow-free would hand that
            # group contiguous runs even on the knobs-off plane,
            # measuring nothing.
            import zlib as _zlib

            def _shadow_name(j: int) -> str:
                name, t = f"sh{j}", 0
                while _zlib.crc32(name.encode()) % groups != j % groups:
                    t += 1
                    name = f"sh{j}x{t}"
                return name

            shadows = await asyncio.gather(
                *(sessions[n_elig + j].get(
                    _shadow_name(j), DistributedAtomicValue)
                  for j in range(n_shadow)))
            log(f"bench[apply]: 1 member x {groups} groups, "
                f"{n_elig} device + {n_shadow} host-shadow sessions "
                f"x {ops_per_session} ops/burst, zipf s={zipf_s} over "
                f"{n_keys} keys")
            _bench_gc_tune()

            # Continuous submission under a bounded-in-flight window per
            # session (no chunk barriers): barriers lock every session
            # to the commit-turn cadence, collapsing the applied windows
            # to a couple of entries each — a commit-latency bench, not
            # an apply bench. A standing backlog keeps windows large.
            # The shadow window is SHALLOW (2), deliberately: a
            # contiguous flush of N ineligible entries cuts a
            # contiguous-plane run once, not N times, so deep shadow
            # pipelining hides the interleave the scenario exists to
            # measure.
            # Both lanes scatter each submission a few seeded
            # ready-queue iterations deep before sending: sessions
            # woken by the same ack wave otherwise submit in the ack
            # order of the PREVIOUS window — a self-reinforcing pattern
            # that parks every shadow entry at a window EDGE, where it
            # cuts nothing and the interleave the scenario exists to
            # measure never forms. The yields put shadow entries in the
            # MIDDLE of device runs, log-order-for-real.
            async def one_device(i: int, script: list) -> None:
                h = handles[i]
                sem = asyncio.Semaphore(8)

                async def go(k: int, v: int, yields: int) -> None:
                    async with sem:
                        for _ in range(yields):
                            await asyncio.sleep(0)
                        await h[k].get_and_set(v)
                await asyncio.gather(*(go(k, v, rng.randrange(8))
                                       for k, v in script))

            async def one_shadow(j: int, script: list) -> None:
                sh = shadows[j]
                sem = asyncio.Semaphore(2)

                async def go(s: str, yields: int) -> None:
                    async with sem:
                        for _ in range(yields):
                            await asyncio.sleep(0)
                        await sh.set(s)
                await asyncio.gather(*(go(s, rng.randrange(8))
                                       for s in script))

            # a shadow session's shallow (2-deep) stream covers ~1/4
            # the ops of a pipelined (8-deep) device session in the
            # same wall window — shorter scripts keep the two streams
            # co-terminous, so the interleave lasts the whole burst
            shadow_ops = max(2, ops_per_session // 4)
            burst_ops = n_elig * ops_per_session + n_shadow * shadow_ops

            # warmup wave (untimed, untraced): the first engine round
            # pays jit compilation — hundreds of ms that would otherwise
            # dominate BOTH planes' first rep and the apply-latency p99
            await asyncio.gather(
                *(one_device(i, [(draw_key(), 1)
                                 for _ in range(ops_per_session // 2)])
                  for i in range(n_elig)),
                *(one_shadow(j, [f"w{j}x{t}"
                                 for t in range(shadow_ops // 2)])
                  for j in range(n_shadow)))

            # Trace EVERY timed request (both A/B planes pay the same
            # ≤2% overhead — PERF.md round 13): the latency.apply_ms
            # phase histogram is the scenario's tail-latency judge —
            # commit → commit-future resolved, exactly the window the
            # parallel/fused plane compresses.
            from .utils import tracing as _tracing
            _tracing.TRACER.clear()
            _tracing.enable()  # warmup above ran untraced: the phase
            # histograms hold timed-burst samples only
            reps = []
            seq = 0
            for rep in range(bursts):
                escripts = [[(draw_key(), rng.randrange(1 << 20))
                             for _ in range(ops_per_session)]
                            for _ in range(n_elig)]
                sscripts = []
                for _ in range(n_shadow):
                    script = []
                    for _ in range(shadow_ops):
                        seq += 1
                        script.append(f"s{seq}")
                    sscripts.append(script)
                t0 = time.perf_counter()
                await asyncio.gather(
                    *(one_device(i, s) for i, s in enumerate(escripts)),
                    *(one_shadow(j, s) for j, s in enumerate(sscripts)))
                dt = time.perf_counter() - t0
                ops = burst_ops / dt
                reps.append(ops)
                log(f"bench[apply]: rep {rep}: {burst_ops} committed ops "
                    f"in {dt:.3f}s -> {ops:,.0f} ops/sec")
            METRICS_SNAPSHOTS["server"] = rs.stats_snapshot()
            METRICS_SNAPSHOTS["client"] = sessions[0].client \
                .metrics.snapshot()
            _tracing.disable()
            # apply-phase tail latency (commit -> futures resolved) per
            # group; the headline p99 is the worst group's — commands
            # spread across groups, so one group's stalled apply IS the
            # client-visible tail
            lat = {}
            for grp in rs.groups:
                h = grp.metrics.histogram("latency.apply_ms")
                if h.count:
                    lat[str(grp.group_id)] = round(h.percentile(99), 3)
            fused = rs._metrics.counter("apply.fused_dispatches").value
            fused_rows = rs._metrics.histogram("apply.fused_rows")
            fused_groups = rs._metrics.histogram("apply.fused_groups")
            runs = spans = conflicts = vops = 0
            for grp in rs.groups:
                runs += grp.metrics.counter("vector_runs").value
                vops += grp.metrics.counter("vector_ops").value
                spans += grp.metrics.counter("apply.parallel_spans").value
                conflicts += grp.metrics.counter(
                    "apply.conflict_flushes").value
            best = max(reps)
            return {
                "metric": (f"apply_committed_ops_per_sec_{n_sessions}"
                           f"_sessions_{groups}_groups"),
                "value": round(best, 1),
                "unit": "ops/sec",
                "vs_baseline": round(best / NORTH_STAR_OPS, 4),
                "groups": groups,
                "sessions": n_sessions,
                "keys": n_keys,
                "zipf_s": zipf_s,
                "ineligible_fraction": ineligible,
                "latency_apply_p99_ms": max(lat.values()) if lat else 0.0,
                "latency_apply_p99_ms_per_group": lat,
                "apply": {
                    "vector_runs": runs,
                    "vector_ops": vops,
                    "parallel_spans": spans,
                    "conflict_flushes": conflicts,
                    "fused_dispatches": fused,
                    "rows_per_dispatch": round(
                        fused_rows.mean, 2) if fused else 0.0,
                    "groups_per_dispatch": round(
                        fused_groups.mean, 2) if fused else 0.0,
                    "runs_per_dispatch": round(
                        runs / fused, 2) if fused else 0.0,
                },
                **spread(reps),
            }
        finally:
            for c in sessions:
                try:
                    await asyncio.wait_for(c.close(), 10)
                except Exception:
                    pass
            try:
                await asyncio.wait_for(server.close(), 10)
            except Exception:
                pass

    return asyncio.run(drive())


def run_recovery() -> dict:
    """Crash-recovery bench (docs/DURABILITY.md): a fresh member catching
    up to a loaded cluster, snapshot-install vs full log replay.

    Two passes over the same workload on a durable storage level:

    1. **snapshot** (COPYCAT_SNAPSHOTS=1): the running members snapshot at
       the configured cadence and prefix-truncate their logs; the joiner
       catches up via snapshot-install streaming + the retained log tail.
    2. **replay** (COPYCAT_SNAPSHOTS=0): the replay-only plane — the
       joiner receives every entry ever committed through the append
       stream.

    Headline value is the speedup (replay catch-up seconds / snapshot
    catch-up seconds); the artifact carries both times, the log shapes,
    and the leader's + joiner's full ``snap.*`` metric families."""
    import asyncio

    from .client.client import RaftClient
    from .io.local import LocalServerRegistry, LocalTransport
    from .io.transport import Address
    from .server.raft import LEADER, RaftServer

    ClusterAdd, ClusterGet, CounterMachine = _cluster_machine_types()
    ops = knobs.get_int("COPYCAT_BENCH_RECOVERY_OPS")
    storage_level = knobs.get_str("COPYCAT_BENCH_RECOVERY_STORAGE").lower()
    snap_entries = str(knobs.get_int("COPYCAT_BENCH_RECOVERY_SNAP_ENTRIES"))
    n_clients = knobs.get_int("COPYCAT_BENCH_RECOVERY_CLIENTS")

    async def one_pass(snapshots_on: bool, port_base: int) -> dict:
        saved = {k: os.environ.get(k) for k in (
            "COPYCAT_SNAPSHOTS", "COPYCAT_SNAPSHOT_ENTRIES",
            "COPYCAT_SNAPSHOT_RETAIN")}
        os.environ["COPYCAT_SNAPSHOTS"] = "1" if snapshots_on else "0"
        os.environ["COPYCAT_SNAPSHOT_ENTRIES"] = snap_entries
        os.environ["COPYCAT_SNAPSHOT_RETAIN"] = "64"
        build_storage, cleanup_storage = _cluster_storage_factory(
            storage_level)
        registry = LocalServerRegistry()
        addrs = [Address("local", port_base + i) for i in range(3)]

        def build(i: int) -> RaftServer:
            return RaftServer(
                addrs[i], addrs,
                LocalTransport(registry, local_address=addrs[i]),
                CounterMachine(), storage=build_storage(i),
                election_timeout=0.5, heartbeat_interval=0.05,
                session_timeout=120.0)

        # seed: 2 of 3 members carry the workload (still a quorum); the
        # third joins only at catch-up time
        servers = [build(0), build(1)]
        clients: list[RaftClient] = []
        joiner = None
        try:
            await asyncio.gather(*(s.open() for s in servers))
            deadline = time.perf_counter() + 30
            leader = None
            while time.perf_counter() < deadline:
                leader = next((s for s in servers if s.role == LEADER), None)
                if leader is not None:
                    break
                await asyncio.sleep(0.02)
            assert leader is not None, "no leader elected"
            clients = [RaftClient(addrs[:2], LocalTransport(registry),
                                  session_timeout=120.0)
                       for _ in range(n_clients)]
            await asyncio.gather(*(c.open() for c in clients))
            per_client = ops // n_clients
            _bench_gc_tune()

            async def pump(client: RaftClient, key: str) -> None:
                futs = [client.submit_command_nowait(
                    ClusterAdd(key=key, delta=1)) for _ in range(per_client)]
                await asyncio.gather(*futs)

            t0 = time.perf_counter()
            await asyncio.gather(*(pump(c, f"k{i}")
                                   for i, c in enumerate(clients)))
            seed_s = time.perf_counter() - t0
            log(f"bench[recovery]: seeded {per_client * n_clients} ops in "
                f"{seed_s:.2f}s ({'snapshots' if snapshots_on else 'replay'}"
                f" pass); leader log [{leader.log.first_index}, "
                f"{leader.log.last_index}], snap_index "
                f"{leader._snap_index}")
            if snapshots_on:
                assert leader.log.prefix_index > 0, \
                    "cadence never truncated the log — raise OPS or " \
                    "lower COPYCAT_BENCH_RECOVERY_SNAP_ENTRIES"

            # catch-up: the fresh third member boots empty and joins
            joiner = build(2)
            t1 = time.perf_counter()
            await joiner.open()
            target = leader.commit_index
            deadline = time.perf_counter() + 120
            while (joiner.last_applied < target
                   and time.perf_counter() < deadline):
                await asyncio.sleep(0.005)
            catchup_s = time.perf_counter() - t1
            assert joiner.last_applied >= target, \
                (joiner.last_applied, target)
            # correctness: the joiner's machine converged to the truth
            assert joiner.state_machine.data.get("k0") == per_client
            log(f"bench[recovery]: joiner caught up {target} entries in "
                f"{catchup_s:.3f}s "
                f"({'install+tail' if snapshots_on else 'full replay'})")
            return {
                "catchup_s": catchup_s,
                "seed_s": seed_s,
                "commit_index": target,
                "leader_first_index": leader.log.first_index,
                "leader_prefix_index": leader.log.prefix_index,
                "installs_sent": leader.metrics.snapshot().get(
                    "snap.installs_sent", 0),
                "leader_stats": leader.stats_snapshot(),
                "joiner_stats": joiner.stats_snapshot(),
            }
        finally:
            for c in clients:
                try:
                    await asyncio.wait_for(c.close(), 10)
                except Exception:
                    pass
            for s in servers + ([joiner] if joiner is not None else []):
                try:
                    await asyncio.wait_for(s.close(), 10)
                except Exception:
                    pass
            cleanup_storage()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    snap_pass = asyncio.run(one_pass(True, 17100))
    replay_pass = asyncio.run(one_pass(False, 17200))
    assert snap_pass["installs_sent"] >= 1, snap_pass
    speedup = replay_pass["catchup_s"] / max(snap_pass["catchup_s"], 1e-9)
    METRICS_SNAPSHOTS["server"] = snap_pass["leader_stats"]
    METRICS_SNAPSHOTS["joiner"] = snap_pass["joiner_stats"]
    return {
        "metric": f"recovery_catchup_speedup_vs_replay_{storage_level}",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 4),
        "storage_level": storage_level,
        "snapshot_entries": int(snap_entries),
        "seeded_ops": ops,
        "catchup_s_snapshot": round(snap_pass["catchup_s"], 4),
        "catchup_s_replay": round(replay_pass["catchup_s"], 4),
        "commit_index": snap_pass["commit_index"],
        "leader_first_index_snapshot": snap_pass["leader_first_index"],
        "installs_sent": snap_pass["installs_sent"],
        "snap": {k: v
                 for k, v in snap_pass["leader_stats"]["raft"].items()
                 if k.startswith("snap.")},
    }


def run_compartment() -> dict:
    """Compartmentalized deployment bench (docs/DEPLOYMENT.md): committed
    ops/sec through a REAL multi-process topology — one OS process per
    Raft member and per standalone ingress proxy, real sockets, real
    fsync — swept across ingress-tier widths
    (``COPYCAT_BENCH_COMPARTMENT_TIERS``, default ``1,2,4``).

    The compartmentalization claim under test (PAPERS.md, "Scaling
    Replicated State Machines with Compartmentalization"): the ingress
    role — client connections, session fan-out, per-group routing, the
    global ingress batching — scales out independently of the write
    quorums it fronts. In-process benches cannot observe this (every
    tier shares one GIL); here each width is a fresh supervised
    topology and the clients pin round-robin across the tier, so adding
    ingress processes adds real CPU parallelism to exactly one role.

    Per-tier attribution rides the artifact from the existing
    ``latency.*`` plane: every ingress process records
    ``latency.ingress_queue_ms`` / ``latency.proxy_hop_ms`` for every
    forward (scraped over its stats port), and the client records
    ``submit_latency_ms`` end-to-end.

    The nemesis phase (``COPYCAT_BENCH_COMPARTMENT_NEMESIS``, on by
    default, widest tier only) SIGKILLs one member AND one ingress proxy
    mid-load through the supervisor: clients re-route within the tier,
    the supervisor restarts the corpses with backoff, and the read-back
    asserts ZERO lost acknowledged writes — every key's replicated
    counter covers every acked increment, and exceeds it only by
    in-doubt (INDETERMINATE) submissions, the exactly-once contract.

    ``COPYCAT_INGRESS_TIER=0`` is the A/B lane: no ingress processes
    deploy and clients dial the members' in-server ingress directly
    (width 0 in the artifact)."""
    import asyncio
    import random as _random

    from .client.client import PinnedConnectionStrategy, RaftClient
    from .deploy.supervisor import Supervisor
    from .deploy.topology import TopologySpec
    from .io.tcp import TcpTransport
    from .io.transport import Address
    from .server.stats import fetch_stats
    from .testing.counter_machine import ClusterAdd, ClusterGet

    members = max(1, knobs.get_int("COPYCAT_BENCH_COMPARTMENT_MEMBERS"))
    groups = max(1, knobs.get_int("COPYCAT_BENCH_COMPARTMENT_GROUPS"))
    n_clients = knobs.get_int("COPYCAT_BENCH_COMPARTMENT_CLIENTS")
    ops_per_client = knobs.get_int("COPYCAT_BENCH_COMPARTMENT_OPS")
    bursts = knobs.get_int("COPYCAT_BENCH_COMPARTMENT_BURSTS")
    n_keys = knobs.get_int("COPYCAT_BENCH_COMPARTMENT_KEYS")
    zipf_s = knobs.get_float("COPYCAT_BENCH_COMPARTMENT_ZIPF")
    storage = knobs.get_str("COPYCAT_BENCH_COMPARTMENT_STORAGE")
    run_nemesis = knobs.get_bool("COPYCAT_BENCH_COMPARTMENT_NEMESIS")
    if knobs.get_bool("COPYCAT_INGRESS_TIER"):
        tiers = [max(1, int(w)) for w in knobs.get_str(
            "COPYCAT_BENCH_COMPARTMENT_TIERS").split(",") if w.strip()]
    else:
        # the A/B lane: no standalone tier, clients dial the members'
        # in-server ingress directly
        tiers = [0]
    machine = "copycat_tpu.testing.counter_machine:counter_machine"

    rng = _random.Random(12)
    draw_rank = zipf_sampler(rng, n_keys, zipf_s)

    def draw_key() -> str:
        return f"user:{draw_rank()}"

    async def load(client: RaftClient, keys: list,
                   acked: dict, indet: dict) -> None:
        """Streamed micro-batch writer (the sharded scenario's shape)
        that CLASSIFIES every outcome: resolved future = acknowledged
        (the server must never lose it), failed future = in-doubt.
        Chunked so a mid-load process kill leaves a bounded in-flight
        window to classify, not a whole burst."""
        chunk, cap = 64, 768
        pending: list = []
        for i in range(0, len(keys), chunk):
            part = keys[i:i + chunk]
            pending.extend(
                (k, client.submit_command_nowait(ClusterAdd(key=k,
                                                            delta=1)))
                for k in part)
            await asyncio.sleep(0)  # turn boundary: one staged batch
            while len(pending) >= cap:
                k, fut = pending.pop(0)
                try:
                    await fut
                    acked[k] = acked.get(k, 0) + 1
                except Exception:
                    indet[k] = indet.get(k, 0) + 1
        for k, fut in pending:
            try:
                await fut
                acked[k] = acked.get(k, 0) + 1
            except Exception:
                indet[k] = indet.get(k, 0) + 1

    async def scrape(spec: TopologySpec, names: list) -> dict:
        """Per-process ``/stats`` scrape -> the per-tier attribution
        block: ingress latency phases + forward counters per ingress
        process (an unreachable stats port records as ``None``, never
        drops the row)."""
        out: dict = {}
        for name in names:
            try:
                snap = json.loads(await fetch_stats(
                    spec.stats_addrs()[name], "/stats", timeout=5.0))
            except (OSError, RuntimeError, ValueError,
                    asyncio.TimeoutError):
                out[name] = None
                continue
            ing = snap.get("ingress", {})
            out[name] = {
                k: ing.get(k) for k in (
                    "latency.ingress_queue_ms", "latency.proxy_hop_ms",
                    "ingress.commands_forwarded", "ingress.sessions",
                    "ingress.proxy_retries", "ingress.reroutes")}
        return out

    async def run_width(width: int) -> dict:
        spec = TopologySpec.local(
            members=members, ingresses=width, groups=groups,
            storage=storage, machine=machine)
        sup = Supervisor(spec)
        await sup.open()
        clients: list[RaftClient] = []
        try:
            await sup.wait_healthy(timeout=180)
            addrs = [Address.parse(a) for a in spec.client_addrs()]
            clients = [
                RaftClient(addrs, TcpTransport(), session_timeout=120.0,
                           connection_strategy=PinnedConnectionStrategy(
                               addrs[i % len(addrs)]))
                for i in range(n_clients)]
            await asyncio.gather(*(c.open() for c in clients))
            # warmup: one committed write per client primes leader
            # views, session replicas and the disk lanes end to end
            await asyncio.gather(*(
                c.submit(ClusterAdd(key=f"warm:{i}", delta=1))
                for i, c in enumerate(clients)))
            log(f"bench[compartment]: width {width}: {members} member + "
                f"{width} ingress process(es), {groups} group(s), "
                f"{n_clients} clients x {ops_per_client} ops/burst, "
                f"zipf s={zipf_s} over {n_keys} keys, storage={storage}")
            _bench_gc_tune()
            burst_ops = n_clients * ops_per_client
            acked: dict[str, int] = {}
            indet: dict[str, int] = {}
            reps = []
            for rep in range(bursts):
                burst_keys = [[draw_key() for _ in range(ops_per_client)]
                              for _ in range(n_clients)]
                t0 = time.perf_counter()
                await asyncio.gather(*(
                    load(c, ks, acked, indet)
                    for c, ks in zip(clients, burst_keys)))
                dt = time.perf_counter() - t0
                ops = burst_ops / dt
                reps.append(ops)
                log(f"bench[compartment]: width {width} rep {rep}: "
                    f"{burst_ops} ops in {dt:.3f}s -> {ops:,.0f} ops/sec")
            attribution = await scrape(
                spec, [i.name for i in spec.ingresses])
            out = {
                "width": width,
                "ops_per_sec": round(max(reps), 1),
                "client_submit_ms": clients[0].metrics.histogram(
                    "submit_latency_ms").percentile(99),
                "ingress_attribution": attribution,
                **spread(reps),
            }
            if run_nemesis and width == max(tiers) and members >= 3:
                out["nemesis"] = await nemesis_phase(
                    sup, spec, clients, width, acked, indet)
            # zero lost acknowledged writes, every width: each touched
            # key's replicated counter covers every acked increment and
            # exceeds it only by in-doubt submissions
            lost = over = 0
            touched = sorted(acked)
            for i in range(0, len(touched), 256):
                part = touched[i:i + 256]
                got = await asyncio.gather(*(
                    clients[j % len(clients)].submit(ClusterGet(key=k))
                    for j, k in enumerate(part)))
                for k, v in zip(part, got):
                    if v < acked[k]:
                        lost += acked[k] - v
                    if v > acked[k] + indet.get(k, 0):
                        over += v - acked[k] - indet.get(k, 0)
            assert lost == 0, f"LOST {lost} acknowledged write(s)"
            assert over == 0, f"{over} duplicate apply(s) (exactly-once)"
            out["acked_ops"] = sum(acked.values())
            out["indeterminate_ops"] = sum(indet.values())
            out["lost_acked_writes"] = lost
            return out
        finally:
            for c in clients:
                try:
                    await asyncio.wait_for(c.close(), 10)
                except Exception:
                    pass
            await sup.close()

    async def nemesis_phase(sup: Supervisor, spec: TopologySpec,
                            clients: list, width: int,
                            acked: dict, indet: dict) -> dict:
        """kill -9 one member AND one ingress proxy mid-load through the
        supervisor (the process-level nemesis): clients re-route within
        the ingress tier, the supervisor restarts the corpses with
        backoff, and the caller's read-back proves zero lost
        acknowledged writes."""
        from .utils.tasks import spawn as spawn_task

        # A SIGKILLed MEMORY-storage member restarts blank — no log, no
        # (term, voted_for) — which violates Raft's persistence
        # assumptions: the blank member can grant a vote that elects a
        # leader missing an acked entry, a TRUE lost write. The member
        # kill therefore requires a durable level; on memory the
        # nemesis kills only the (stateless-by-design) ingress.
        kill_member = storage != "memory" and members >= 3
        log(f"bench[compartment]: nemesis: kill -9"
            + (" member-1" if kill_member else "")
            + (" + ingress-0" if width else "")
            + f" under load (width {width}, storage={storage})")
        keys = [[draw_key() for _ in range(ops_per_client)]
                for _ in range(n_clients)]
        tasks = [spawn_task(load(c, ks, acked, indet),
                            name="compartment-nemesis-load")
                 for c, ks in zip(clients, keys)]
        try:
            await asyncio.sleep(0.15)  # mid-load, not before it
            ok_m, detail_m = (sup.kill("member-1") if kill_member
                              else (False, f"member kill skipped on "
                                           f"{storage} storage"))
            await asyncio.sleep(0.15)
            ok_i, detail_i = (sup.kill("ingress-0") if width
                              else (False, "no ingress tier"))
            await asyncio.gather(*tasks)
        finally:
            for t in tasks:
                t.cancel()
        # both corpses must come back under supervision before teardown
        # (restart-with-backoff is half the nemesis claim)
        deadline = time.monotonic() + 60
        victims = ((["member-1"] if kill_member else [])
                   + (["ingress-0"] if width else []))
        while time.monotonic() < deadline:
            status = sup.status()["children"]
            if all(status[v]["state"] == "running"
                   and status[v]["pid"] for v in victims):
                break
            await asyncio.sleep(0.25)
        status = sup.status()["children"]
        return {
            "killed": {"member": detail_m if ok_m else None,
                       "ingress": detail_i if ok_i else None},
            "restarts": {v: status[v]["restarts"] for v in victims},
            "restored": all(status[v]["state"] == "running"
                            for v in victims),
        }

    async def drive() -> dict:
        widths = []
        for width in tiers:
            widths.append(await run_width(width))
        by_width = {str(w["width"]): w["ops_per_sec"] for w in widths}
        best = max(w["ops_per_sec"] for w in widths)
        base = widths[0]["ops_per_sec"]
        nemesis = next((w.get("nemesis") for w in widths
                        if "nemesis" in w), None)
        METRICS_SNAPSHOTS["compartment"] = {
            str(w["width"]): w["ingress_attribution"] for w in widths}
        return {
            "metric": (f"compartment_committed_ops_per_sec_{members}"
                       f"_members_{groups}_groups"),
            "value": best,
            "unit": "ops/sec",
            "vs_baseline": round(best / NORTH_STAR_OPS, 4),
            "members": members,
            "groups": groups,
            "storage_level": storage,
            "clients": n_clients,
            "zipf_s": zipf_s,
            "keys": n_keys,
            "ingress_tier": knobs.get_bool("COPYCAT_INGRESS_TIER"),
            "tier_widths": tiers,
            "ops_by_width": by_width,
            "scaling_vs_width1": {
                k: round(v / base, 3) for k, v in by_width.items()},
            "widths": widths,
            **({"nemesis": nemesis} if nemesis is not None else {}),
            "lost_acked_writes": sum(w["lost_acked_writes"]
                                     for w in widths),
        }

    return asyncio.run(drive())


def run_election() -> dict:
    """Config #2: forced leader churn; measures elections completed/sec.

    Election timeout knobs (COPYCAT_BENCH_TIMER_MIN/MAX) default to the
    engine's 4-9 here so the number stays comparable across rounds;
    shorter timers complete forced elections proportionally faster."""
    config = Config(use_pallas=use_pallas(),
                    timer_min=knobs.get_int("COPYCAT_BENCH_TIMER_MIN", default=4),
                    timer_max=knobs.get_int("COPYCAT_BENCH_TIMER_MAX", default=9),
                    resource=RESOURCE_CONFIGS["election"])
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = init_state(GROUPS, PEERS, LOG_SLOTS, init_key, config)
    deliver = full_delivery(GROUPS, PEERS)
    empty = empty_submits(GROUPS)
    jit_step = jax.jit(partial(step, config=config))

    log(f"bench[election]: G={GROUPS} P={PEERS} rounds={ROUNDS} "
        f"device={jax.devices()[0].platform}")
    state, key = elect_all(state, jit_step, empty, deliver, key, GROUPS)
    victims = isolation_masks(ROUNDS, GROUPS, PEERS, period=15, seed=2)

    def run(state, key):
        def body(carry, victim):
            state, key, prev = carry
            key, k = jax.random.split(key)
            dl = victim_deliver(victim, GROUPS, PEERS)
            state, out = step(state, empty, dl, k, config=config)
            changed = ((out.leader >= 0) & (out.leader != prev)).sum(
                dtype=jnp.int32)
            return (state, key, out.leader), changed
        # seed prev with the REAL current leaders so settled groups don't
        # count as spurious elections in the first round
        init = (state, key, current_leaders(state))
        (state, key, _), changes = jax.lax.scan(body, init, victims)
        return state, key, changes.sum()

    run_jit = jax.jit(run)
    state, key, n = run_jit(state, key)
    jax.block_until_ready(n)
    log(f"bench[election]: warmup saw {int(n)} leader changes")

    best = 0.0
    reps = []
    for rep in range(REPEATS):
        with xla_trace(PROFILE_DIR if rep == 0 else None):
            t0 = time.perf_counter()
            state, key, n = run_jit(state, key)
            n = int(jax.block_until_ready(n))
            dt = time.perf_counter() - t0
        rate = n / dt
        best = max(best, rate)
        reps.append(rate)
        log(f"bench[election]: rep {rep}: {n} elections in {dt:.3f}s "
            f"-> {rate:,.0f} elections/sec")

    return {
        "metric": f"elections_per_sec_{GROUPS}_groups_under_nemesis",
        "value": round(best, 1),
        "unit": "elections/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        **spread(reps),
    }


def run_map_read() -> dict:
    """Config #3 variant, get-heavy: puts ride the log, gets ride the
    query lane with no log append — SEQUENTIAL (leader-served) by
    default, or lease-gated ATOMIC/BOUNDED_LINEARIZABLE reads with
    ``COPYCAT_BENCH_READ_LEVEL=atomic`` (reference
    ``Consistency.java:157-176``)."""
    read_level = knobs.get_str("COPYCAT_BENCH_READ_LEVEL")
    if read_level not in ("sequential", "atomic"):
        raise SystemExit(
            f"COPYCAT_BENCH_READ_LEVEL={read_level!r}: pick 'sequential' "
            f"or 'atomic' (a typo here would silently mislabel the metric)")
    config = Config(use_pallas=use_pallas(), append_window=max(4, SUBMIT_SLOTS),
                    applies_per_round=max(4, SUBMIT_SLOTS),
                    resource=RESOURCE_CONFIGS["map"])
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = init_state(GROUPS, PEERS, LOG_SLOTS, init_key, config)
    deliver = full_delivery(GROUPS, PEERS)
    ones = jnp.ones((GROUPS, SUBMIT_SLOTS), jnp.int32)
    puts = Submits(opcode=ones * ap.OP_MAP_PUT, a=tile_pattern([1, 2], GROUPS),
                   b=ones * 7, c=ones * 0, tag=ones, valid=ones.astype(bool))
    gets = Submits(opcode=ones * ap.OP_MAP_GET, a=tile_pattern([1, 2], GROUPS),
                   b=ones * 0, c=ones * 0, tag=ones, valid=ones.astype(bool))
    jit_step = jax.jit(partial(step, config=config))

    log(f"bench[map_read]: G={GROUPS} P={PEERS} rounds={ROUNDS} "
        f"{SUBMIT_SLOTS} puts (log) + {SUBMIT_SLOTS} {read_level} gets "
        f"(query lane) per group per round; "
        f"device={jax.devices()[0].platform}")
    state, key = elect_all(state, jit_step, empty_submits(GROUPS), deliver,
                           key, GROUPS)
    atomic = (jnp.ones((GROUPS, SUBMIT_SLOTS), bool)
              if read_level == "atomic" else None)

    def run(state, key):
        def body(carry, _):
            state, key, applied_prev = carry
            key, k = jax.random.split(key)
            state, _ = step(state, puts, deliver, k, config=config)
            _, served = query_step(state, gets, atomic, config=config)
            applied_now = jnp.max(state.applied_index, axis=1)
            n = jnp.sum(applied_now - applied_prev, dtype=jnp.int32) \
                + served.sum(dtype=jnp.int32)
            return (state, key, applied_now), n
        applied0 = jnp.max(state.applied_index, axis=1)
        (state, key, _), counts = jax.lax.scan(
            body, (state, key, applied0), None, length=ROUNDS)
        return state, key, counts.sum()

    run_jit = jax.jit(run)
    state, key, n = run_jit(state, key)
    jax.block_until_ready(n)
    log(f"bench[map_read]: warmup completed {int(n)} ops")

    best = 0.0
    reps = []
    for rep in range(REPEATS):
        with xla_trace(PROFILE_DIR if rep == 0 else None):
            t0 = time.perf_counter()
            state, key, n = run_jit(state, key)
            n = int(jax.block_until_ready(n))
            dt = time.perf_counter() - t0
        ops = n / dt
        best = max(best, ops)
        reps.append(ops)
        log(f"bench[map_read]: rep {rep}: {n} ops in {dt:.3f}s "
            f"-> {ops:,.0f} ops/sec ({dt / ROUNDS * 1e3:.2f} ms/round)")

    return {
        "metric": (f"map_ops_per_sec_{GROUPS}_groups_half_"
                   f"{read_level}_reads"),
        "value": round(best, 1),
        "unit": "ops/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        **spread(reps),
    }


def run_host_read() -> dict:
    """Client-visible READ throughput: ``drive_queries`` bursts through
    the no-append query lane (``COPYCAT_BENCH_READ_LEVEL=atomic`` gates
    each slot on the leader lease — linearizable reads with zero log
    entries; default ``sequential``). The write path warms each group's
    counter first so reads return real state."""
    from .models import BulkDriver, RaftGroups

    read_level = knobs.get_str("COPYCAT_BENCH_READ_LEVEL")
    if read_level not in ("sequential", "atomic"):
        # causal/process serve identically to sequential here — accepting
        # them would mislabel the metric (same guard as run_map_read)
        raise SystemExit(
            f"COPYCAT_BENCH_READ_LEVEL={read_level!r}: pick 'sequential' "
            "or 'atomic'")
    rg = RaftGroups(GROUPS, PEERS, log_slots=LOG_SLOTS,
                    submit_slots=SUBMIT_SLOTS,
                    config=Config(use_pallas=use_pallas(),
                                  append_window=max(4, SUBMIT_SLOTS),
                                  applies_per_round=max(4, SUBMIT_SLOTS),
                                  monotone_tag_accept=True,
                                  resource=RESOURCE_CONFIGS["counter"]))
    per_group = knobs.get_int("COPYCAT_BENCH_HOST_BURST",
                              default=SUBMIT_SLOTS * 8)
    log(f"bench[host_read:{read_level}]: G={GROUPS} P={PEERS} "
        f"{per_group} reads/group/burst; device={jax.devices()[0].platform}")
    rg.wait_for_leaders()
    driver = BulkDriver(rg)
    driver.drive(np.arange(GROUPS), ap.OP_LONG_ADD, 7)  # warm + real state
    reads = np.repeat(np.arange(GROUPS), per_group)
    driver.drive_queries(reads[:GROUPS], ap.OP_VALUE_GET,
                         consistency=read_level)  # compile warm

    best, reps = 0.0, []
    for rep in range(REPEATS):
        t0 = time.perf_counter()
        got = driver.drive_queries(reads, ap.OP_VALUE_GET,
                                   consistency=read_level)
        dt = time.perf_counter() - t0
        if not (got == 7).all():
            raise SystemExit("host_read: wrong read results")
        ops = reads.size / dt
        best = max(best, ops)
        reps.append(ops)
        log(f"bench[host_read:{read_level}]: rep {rep}: {reads.size:,} "
            f"reads in {dt:.3f}s -> {ops:,.0f} reads/sec host-observed")
    return {
        "metric": (f"host_observed_{read_level}_reads_per_sec_"
                   f"{GROUPS}_groups"),
        "value": round(best, 1),
        "unit": "ops/sec",
        "vs_baseline": round(best / NORTH_STAR_OPS, 4),
        **spread(reps),
    }


def _artifact_meta() -> dict:
    """Attribution block for ``--metrics-json`` artifacts (schema in
    docs/OBSERVABILITY.md "Bench artifacts"): the git SHA, the explicit
    knob overrides, and a host fingerprint — without these two artifacts
    are not comparable (a different host or knob set is a different
    experiment, not a regression; the bench-baseline CI gate keys off
    this block when explaining a miss)."""
    import platform

    from .utils.buildinfo import git_sha
    from .utils.platform import device_info

    return {
        "git_sha": git_sha(),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "knobs": knobs.overrides(),
        "host": {
            "hostname": platform.node(),
            "machine": platform.machine(),
            "python": platform.python_version(),
            "cpus": os.cpu_count(),
            "backend": jax.default_backend(),
            **device_info(),
        },
    }


def main() -> None:
    parser = argparse.ArgumentParser(prog="copycat-bench")
    parser.add_argument(
        "--metrics-json", default=None, metavar="PATH",
        help="write the result plus per-component metrics snapshots "
             "(server/transport/client registries) as one JSON artifact")
    parser.add_argument(
        "--storage", default=None, choices=["memory", "mapped", "disk"],
        help="log storage level for the cluster/recovery scenarios "
             "(envs COPYCAT_BENCH_CLUSTER_STORAGE / "
             "COPYCAT_BENCH_RECOVERY_STORAGE); the durability A/B knob")
    parser.add_argument(
        "--groups", default=None, type=int, metavar="N",
        help="Raft groups for the sharded/apply scenarios (envs "
             "COPYCAT_BENCH_SHARDED_GROUPS / COPYCAT_BENCH_APPLY_GROUPS);"
             " 1 = the single-group baseline, the sharding A/B knob "
             "(docs/SHARDING.md)")
    args, _ = parser.parse_known_args()
    if args.storage:
        os.environ["COPYCAT_BENCH_CLUSTER_STORAGE"] = args.storage
        os.environ["COPYCAT_BENCH_RECOVERY_STORAGE"] = args.storage
        os.environ["COPYCAT_BENCH_COMPARTMENT_STORAGE"] = args.storage
    if args.groups is not None:
        os.environ["COPYCAT_BENCH_SHARDED_GROUPS"] = str(args.groups)
        os.environ["COPYCAT_BENCH_APPLY_GROUPS"] = str(args.groups)
        os.environ["COPYCAT_BENCH_COMPARTMENT_GROUPS"] = str(args.groups)
    # One in-process question, no retry and no other platform: a result
    # names the device it was measured on, and a run that did not get the
    # platform it asked for (JAX_PLATFORMS, else the TPU) exits 2.
    from .utils.platform import enable_compilation_cache, require_platform
    device = require_platform()
    enable_compilation_cache()
    # The bench holds its OWN profiler reference for the whole run: the
    # scenario's servers acquire/release around their lifetime, so by
    # artifact-write time their refs are gone and the singleton would
    # be torn down — this ref keeps the sampled window alive for the
    # top-frame summary below. COPYCAT_PROFILE=0 -> None -> no
    # "profile" key in the artifact (A/B).
    from .utils import profiler as _profiler
    bench_profiler = _profiler.acquire()
    if SCENARIO == "election":
        result = run_election()
    elif SCENARIO == "map_read":
        result = run_map_read()
    elif SCENARIO == "host":
        result = run_host()
    elif SCENARIO == "host_read":
        result = run_host_read()
    elif SCENARIO == "spi":
        result = run_spi()
    elif SCENARIO == "readmix":
        result = run_readmix()
    elif SCENARIO == "cluster":
        result = run_cluster()
    elif SCENARIO == "sharded":
        result = run_sharded()
    elif SCENARIO == "apply":
        result = run_apply()
    elif SCENARIO == "recovery":
        result = run_recovery()
    elif SCENARIO == "compartment":
        result = run_compartment()
    elif SCENARIO == "fanout":
        result = run_fanout()
    elif SCENARIO == "session":
        result = run_session()
    elif SCENARIO in SUBMIT_BUILDERS:
        result = run_throughput(SCENARIO)
    else:
        raise SystemExit(
            f"unknown scenario {SCENARIO!r}; pick one of "
            f"{['election', 'map_read', 'host', 'host_read', 'spi', 'readmix', 'cluster', 'sharded', 'apply', 'recovery', 'compartment', 'fanout', 'session', *SUBMIT_BUILDERS]}")
    result.update(device)
    if args.metrics_json:
        artifact = {**result, "scenario": SCENARIO,
                    "meta": _artifact_meta(),
                    "metrics": METRICS_SNAPSHOTS,
                    # the run's retained /series windows (empty under
                    # COPYCAT_SERIES=0) — the gate reads none of it
                    "series": SERIES_WINDOWS}
        if bench_profiler is not None:
            # where the run's wall time actually went (the continuous
            # profiler's top-frame summary + the plane's own counters);
            # absent under COPYCAT_PROFILE=0 — the gate reads none of it
            artifact["profile"] = bench_profiler.top_summary(top=10)
        with open(args.metrics_json, "w") as f:
            json.dump(artifact, f)
        log(f"bench: metrics snapshot written to {args.metrics_json}")
    _profiler.release(bench_profiler)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
