"""The raw consensus plane: ``ops.consensus.step`` under ``lax.scan``, driven
dispatch after dispatch with the host doing nothing between them.

The program is ``chip_smoke.raw_plane_program`` (ops tagged by round and slot so
the host can replay the commit order) with ``bench.run_throughput``'s counting:
exactly-once committed ops as the applied high-water delta, and the on-device
one-hot histogram of append-to-apply latency in rounds. Sizes come from the
cell's configuration and traffic files alone.
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np

from benchmarks import generators as gen
from benchmarks import reference


def build_config(cfg: dict, mesh=None):
    from copycat_tpu.ops.apply import ResourceConfig
    from copycat_tpu.ops.consensus import Config

    config = Config(
        use_pallas=cfg["use_pallas"],
        pallas_interpret=cfg.get("pallas_interpret", False),
        append_window=cfg["append_window"],
        applies_per_round=cfg["applies_per_round"],
        pool_budgets=tuple(cfg["pool_budgets"]),
        timer_min=cfg["timer_min"], timer_max=cfg["timer_max"],
        resource=ResourceConfig(**cfg["resource"]))
    if mesh is not None and config.use_pallas:
        config = config._replace(kernel_mesh=mesh)
    return config


@functools.lru_cache(maxsize=None)
def scan_program(config, G: int, P: int, S: int, buckets: int):
    """One dispatch: ``victims.shape[0]`` rounds of step + snapshot install."""
    import jax
    import jax.numpy as jnp

    from copycat_tpu.ops import apply as ap
    from copycat_tpu.ops.consensus import install_snapshots, step

    add_slots = jnp.asarray(gen.mixed_pattern(S)[0] == ap.OP_LONG_ADD)
    slot = jnp.arange(S, dtype=jnp.int32)[None, :]
    edges = jnp.arange(buckets, dtype=jnp.int32)[None, :]

    def raw_plane_scan(state, key, add_max, round0, pattern, victims, sample):
        def body(carry, xs):
            state, key, applied_prev, add_max = carry
            victim, r = xs
            key, k = jax.random.split(key)
            sub = pattern._replace(
                tag=jnp.broadcast_to((round0 + r) * S + slot + 1, (G, S)))
            state, out = step(state, sub, gen.victim_deliver(victim, G, P),
                              k, config=config)
            state = install_snapshots(state, out.stale, out.leader,
                                      config=config)
            # exact-once committed-op count: applied high-water delta
            # (out_valid reports are at-least-once across leader changes)
            applied_now = jnp.max(state.applied_index, axis=1)
            n = jnp.sum(applied_now - applied_prev, dtype=jnp.int32)
            # one-hot select-reduce, not .at[].add(): XLA lowers the
            # scatter to an element-at-a-time DMA loop
            lat = jnp.clip(out.out_latency.reshape(-1), 0, buckets - 1)
            hist = jnp.sum((lat[:, None] == edges)
                           & out.out_valid.reshape(-1)[:, None],
                           axis=0, dtype=jnp.int32)
            is_add = out.out_valid & (out.out_tag > 0) \
                & add_slots[(out.out_tag - 1) % S]
            add_max = jnp.maximum(add_max, jnp.max(
                jnp.where(is_add, out.out_result, 0), axis=1))
            report = tuple(x[sample] for x in (
                out.out_valid, out.out_tag, out.out_result, out.out_index))
            return (state, key, applied_now, add_max), (n, hist, report)

        applied0 = jnp.max(state.applied_index, axis=1)
        rounds = jnp.arange(victims.shape[0], dtype=jnp.int32)
        (state, key, _, add_max), (counts, hists, reports) = jax.lax.scan(
            body, (state, key, applied0, add_max), (victims, rounds))
        return (state, key, add_max, counts.sum(), hists.sum(axis=0),
                reports)

    return jax.jit(raw_plane_scan)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec

    from copycat_tpu.ops.consensus import (
        current_leader, init_state, make_submits)

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    G, P, L, S = (cfg["groups"], cfg["peers"], cfg["log_slots"],
                  cfg["submit_slots"])
    R, buckets = mix["rounds_per_dispatch"], L + mix["latency_buckets_over_log"]
    mesh = None
    if ctx.chips > 1:
        from copycat_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(devices=jax.devices()[:ctx.chips])
    config = build_config(cfg, mesh)

    t_setup = time.perf_counter()
    seeds = np.random.SeedSequence(ctx.seed).generate_state(2)
    key, init_key = jax.random.split(jax.random.PRNGKey(int(seeds[0] >> 1)))
    build = partial(init_state, G, P, L, config=config)
    if mesh is None:
        state = jax.jit(build)(init_key)
    else:
        from copycat_tpu.parallel import raft_shardings
        state_sh, _ = raft_shardings(mesh, jax.eval_shape(build, init_key))
        state = jax.jit(build, out_shardings=state_sh)(init_key)
    state_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state)
                      if leaf.size)
    rng = np.random.default_rng(ctx.seed)
    groups = np.sort(rng.choice(G, min(mix["sample_groups"], G),
                                replace=False))
    victims = gen.isolation_masks(R, G, P, period=mix["nemesis_period"],
                                  seed=ctx.seed + 1)
    # every [.., G, ..] input lies as the state does: whole on one chip,
    # or its block of groups on each chip of the mesh
    by_group = lambda x, axis=0: jax.device_put(x) if mesh is None else \
        jax.device_put(x, NamedSharding(mesh, PartitionSpec(
            *([None] * axis), "groups")))
    pattern = jax.tree.map(by_group, gen.mixed_submits(G, S))
    nothing = jax.tree.map(by_group, make_submits(G, S))
    victims = by_group(victims, 1)
    nobody = by_group(np.full((R, G), -1, np.int32), 1)
    sample = jnp.asarray(groups)
    add_max = by_group(np.zeros((G,), np.int32))
    program = scan_program(config, G, P, S, buckets)
    say(f"raw plane: mixed G={G} P={P} L={L} S={S} pallas="
        f"{'on' if config.use_pallas else 'off'} chips={ctx.chips}: state "
        f"{state_bytes:,} bytes in non-empty leaves, {R} rounds a dispatch, "
        f"{buckets} latency buckets, nemesis period {mix['nemesis_period']}")

    # warm-up and election in one: the window's own program with nothing
    # offered and nobody isolated, until every group has a leader
    for attempt in range(6):
        state, key, _, _, _, _ = program(
            state, key, add_max, np.int32(0), nothing, nobody, sample)
        leaders = int((np.asarray(current_leader(state)[0]) >= 0).sum())
        if leaders == G:
            break
    else:
        raise RuntimeError(f"raw plane: {leaders} of {G} groups have a "
                           f"leader after {6 * R} empty rounds")
    jax.block_until_ready(state)
    say(f"raw plane: set-up {time.perf_counter() - t_setup:.1f}s; every "
        f"group elected after {(attempt + 1) * R} empty rounds; "
        f"{ctx.compiles.note()}")

    # -- the window ----------------------------------------------------------
    ctx.gc_tune()
    compiled_before = ctx.compiles.count
    committed, hist_total = 0, np.zeros(buckets, np.int64)
    dispatches, in_dispatch, first_reports = 0, 0.0, None
    # a traced run profiles its second and third dispatch and the gaps
    # after them, and runs at least those
    trace_from, trace_to = (1, 3) if ctx.trace else (-1, -1)
    t_start = time.perf_counter()
    while True:
        if dispatches == trace_from:
            ctx.profile_start()
        t0 = time.perf_counter()
        with ctx.annotate("dispatch"):
            state, key, add_max, n, hist, reports = program(
                state, key, add_max, np.int32(dispatches * R), pattern,
                victims, sample)
        with ctx.annotate("fetch"):
            n, hist = jax.device_get((n, hist))
        now = time.perf_counter()
        in_dispatch += now - t0
        committed += int(n)
        hist_total += hist
        if dispatches == 0:
            first_reports = reports
        dispatches += 1
        if dispatches == trace_to:
            ctx.profile_stop()
        if now - t_start >= ctx.seconds and dispatches >= trace_to:
            break
    t_end = time.perf_counter()
    window = t_end - t_start
    compiled_inside = ctx.compiles.count - compiled_before
    rounds = dispatches * R
    ms_per_round = in_dispatch / rounds * 1e3
    p99_bucket, p99_exact = gen.percentile_rounds(hist_total, 0.99)
    p50_bucket, _ = gen.percentile_rounds(hist_total, 0.50)
    # +1: the round that appended, replicated and applied the entry counts
    p99_rounds, p99_ms = p99_bucket + 1, (p99_exact + 1) * ms_per_round
    say(f"raw plane: window {window:.3f}s, {dispatches} dispatches, "
        f"{rounds} rounds, {committed:,} ops committed, "
        f"{ms_per_round:.3f} ms a round; commit latency p50 "
        f"{p50_bucket + 1} p99 {p99_rounds} ({p99_exact + 1:.3f}) rounds "
        f"over {int(hist_total.sum()):,} samples, {int(hist_total[-1])} of "
        f"them in the top bucket; compilations inside the window: "
        f"{compiled_inside} (should be 0)")

    # -- the checks, outside the window -------------------------------------
    t_check = time.perf_counter()
    with ctx.annotate("check"):
        reports = [np.array(x) for x in jax.device_get(first_reports)]
        value, applied, add_max = (np.array(x) for x in jax.device_get(
            (state.resources.value, state.applied_index, add_max)))
    if ctx.fault == "flip-result":
        r, k, a = (int(x[0]) for x in np.nonzero(reports[0] & (reports[1] > 0)))
        reports[2][r, k, a] ^= 1
    elif ctx.fault == "drop-ack":
        add_max[int(np.flatnonzero(add_max > 0)[0])] -= 1
    compared, wrong, first = reference.replay_reports(
        reports, gen.mixed_pattern(S), S, groups)
    best = value[np.arange(G), applied.argmax(axis=1)]
    lost = int((best != add_max).sum())
    split = int(((applied[:, :, None] == applied[:, None, :])
                 & (value[:, :, None] != value[:, None, :])
                 ).any(axis=(1, 2)).sum())
    checks = [
        ("sampled results that differ from the plain model "
         f"({compared:,} of {groups.size} groups compared, first dispatch)"
         + (f": {first}" if first else ""), wrong, 0),
        (f"groups of {G:,} whose counter differs from its largest reported "
         "add", lost, 0),
        (f"groups of {G:,} whose replicas disagree on an applied prefix",
         split, 0),
        # beyond 1% of the samples the 99th percentile is a lower bound
        ("latency samples in the histogram's top bucket (the 99th "
         "percentile stands while they are under half a percent)",
         int(hist_total[-1]), int(hist_total.sum()) // 200),
    ]
    correct = (compared > 0 and committed > 0 and int(add_max.max()) > 0
               and all(v <= lim for _, v, lim in checks))
    say(f"raw plane: checks took {time.perf_counter() - t_check:.1f}s")
    # each number compared beside its limit, the last lines of standard error
    for what, value, limit in checks:
        print(f"raw plane: check: {what}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    return {
        "window_start": t_start,
        "correct": correct, "attempted": committed,
        "failed": wrong + lost + split, "checks": checks,
        "end_to_end": {"commit_ops_per_s": committed / window,
                       "commit_p99_ms": p99_ms},
        "clock": {"ms_per_round": ms_per_round,
                  "commit_p99_rounds": p99_rounds,
                  "commit_p50_rounds": p50_bucket + 1,
                  "window_s": window, "rounds": rounds,
                  "rounds_per_dispatch": R, "state_bytes": state_bytes,
                  "program": program.__name__},
        "spans": {}, "counters": {},
    }
