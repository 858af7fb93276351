"""Sharded-step scaling measurement over a virtual CPU device mesh.

Round-2 review: "no artifact shows the step's scaling behavior across the
virtual mesh — even a CPU-mesh walltime table would expose a
collective-placement pathology before real multi-chip hardware arrives."
This runner produces that artifact: the SAME consensus step (fixed total
work) jitted over 1/2/4/8-device meshes, group axis sharded, walltime per
round measured after warm-up. CPU devices share host cores, so the point
is not speedup — it is that walltime stays ~flat (no superlinear blow-up
from XLA inserting pathological collectives or resharding on the step's
dataflow) and that the compiled program report shows the expected
communication pattern.

Run: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python -m copycat_tpu.parallel.scaling`` → one JSON line + MULTICHIP_SCALING.md.
"""

from __future__ import annotations

import json
import os
import time

# must land before the first backend init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from ..utils import knobs

GROUPS = knobs.get_int("COPYCAT_SCALING_GROUPS")
PEERS = 3
ROUNDS = knobs.get_int("COPYCAT_SCALING_ROUNDS")


COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
# The census compiles its own (small) module: AOT lower().compile() and
# the jit call cache do not share executables, so running the census at
# measurement size would pay a redundant full compile per device count.
# Collective structure depends only on the sharding pattern, not G.
CENSUS_GROUPS = 256


def census_text(txt: str) -> dict:
    """Tally cross-device collective ops in compiled-module text."""
    import re

    return {op: n for op in COLLECTIVE_OPS
            if (n := len(re.findall(rf"\b{op}\b", txt)))}


def _collective_census(n_devices: int, devices) -> dict:
    """Count cross-device collective ops in the compiled module — the
    direct witness for (non-)resharding: a purely group-sharded step is
    embarrassingly parallel and must compile to ZERO collectives."""
    import re
    from functools import partial

    from jax.sharding import Mesh

    from ..ops.consensus import (
        Config, full_delivery, init_state, make_submits, step)
    from ..parallel.mesh import shard_state, shard_step_inputs

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    config = Config()
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = init_state(CENSUS_GROUPS, PEERS, 32, init_key, config)
    submits = make_submits(CENSUS_GROUPS, 4)
    deliver = full_delivery(CENSUS_GROUPS, PEERS)
    state = shard_state(state, mesh)
    submits, deliver = shard_step_inputs(submits, deliver, mesh)
    fn = jax.jit(partial(step, config=config))
    return census_text(
        fn.lower(state, submits, deliver, key).compile().as_text())


def _query_census(n_devices: int, devices) -> dict:
    """Census the READ plane: the ``query_step`` program (round-9 batched
    read pump's device leg) compiled over the sharded mesh. Reads are
    leader-lane selects + one fused apply pass per group — group-local by
    construction — so the correct compilation target is the same ZERO
    cross-device collectives the step holds."""
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..ops.consensus import (
        Config, init_state, make_submits, query_step)
    from ..parallel.mesh import shard_state, shard_step_inputs

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    config = Config()
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = shard_state(
        init_state(CENSUS_GROUPS, PEERS, 32, init_key, config), mesh)
    queries = make_submits(CENSUS_GROUPS, 4)
    queries, _ = shard_step_inputs(
        queries, jnp.ones((CENSUS_GROUPS, PEERS, PEERS), bool), mesh)
    atomic = jax.device_put(jnp.zeros((CENSUS_GROUPS, 4), bool),
                            NamedSharding(mesh, P("groups", None)))
    fn = jax.jit(partial(query_step, config=config))
    return census_text(
        fn.lower(state, queries, atomic).compile().as_text())


def _measure_bulk(n_devices: int, devices) -> dict:
    """Client-visible deep-drive throughput on the sharded mesh (round-4
    addition): the FULL bulk plane — blind pipelined dispatch, on-device
    [G,B] accumulators, one harvest — runs over group-sharded engines,
    so the client data path scales with devices, not just the raw step.
    Also censuses the deep_step module for cross-device collectives."""
    from jax.sharding import Mesh

    from ..models.bulk import BulkDriver
    from ..models.raft_groups import RaftGroups
    from ..ops import apply as ap
    from ..ops.consensus import Config
    from ..utils.metrics import merge_snapshots

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    # telemetry ON here on purpose: the deep_step/deep_scan censuses
    # below then also verify the round-8 telemetry block compiles
    # without cross-device collectives (its reductions are per-group)
    config = Config(append_window=8, applies_per_round=8,
                    monotone_tag_accept=True, telemetry=True)
    rg = RaftGroups(GROUPS, PEERS, log_slots=32, submit_slots=8,
                    mesh=mesh, config=config)
    rg.wait_for_leaders()
    drv = BulkDriver(rg)
    g = np.repeat(np.arange(GROUPS), 32)
    t0 = time.perf_counter()
    drv.drive(g, ap.OP_LONG_ADD, 1)  # warm (compile + first transfers)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = drv.drive(g, ap.OP_LONG_ADD, 1)
    dt = time.perf_counter() - t0

    collectives = _deep_census(n_devices, devices, config)
    # round 5: the fused scan program is a distinct compiled module —
    # its zero-collective property is verified separately, not inherited
    scan_collectives = _deep_scan_census(n_devices, devices, config)
    # Per-DEVICE telemetry attribution (round 8): the hub's per-group
    # cumulative arrays split into each device's contiguous group block
    # — elections / leader changes / commit advance per shard — and the
    # shard snapshots fold back into one cluster view with the same
    # merge_snapshots the multihost roll-up uses.
    shard_snaps = rg.telemetry.shard_snapshots(n_devices)
    merged = merge_snapshots(
        [{k: v for k, v in s.items() if k.startswith("device.")}
         for s in shard_snaps])
    return {"devices": n_devices,
            "client_visible_ops_per_sec": round(g.size / dt),
            "drive_rounds": res.rounds,
            "warmup_s": round(warm_s, 1),
            "collectives": collectives,
            "scan_collectives": scan_collectives,
            "telemetry_per_shard": shard_snaps,
            "telemetry_merged": merged}


def _deep_census(n_devices: int, devices, config) -> dict:
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..ops.consensus import (
        Submits, deep_step, full_delivery, init_state)
    from ..parallel.mesh import shard_state

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = shard_state(
        init_state(CENSUS_GROUPS, PEERS, 32, init_key, config), mesh)
    sh2 = NamedSharding(mesh, P("groups", None))
    sh1 = NamedSharding(mesh, P("groups"))
    resbuf = jax.device_put(jnp.zeros((CENSUS_GROUPS, 32), jnp.int32), sh2)
    valbuf = jax.device_put(jnp.zeros((CENSUS_GROUPS, 32), bool), sh2)
    rndbuf = jax.device_put(
        jnp.full((CENSUS_GROUPS, 32), np.int32(2**30), jnp.int32), sh2)
    # evflag matches production exactly: a [G] group-sharded vector
    # (a replicated scalar here would census a DIFFERENT program)
    evflag = jax.device_put(jnp.zeros(CENSUS_GROUPS, bool), sh1)
    base = jax.device_put(jnp.zeros(CENSUS_GROUPS, jnp.int32), sh1)
    sub = Submits(opcode=np.int32(5), a=np.int32(1), b=np.int32(0),
                  c=np.int32(0),
                  tag=np.zeros((CENSUS_GROUPS, 1), np.int32),
                  valid=np.zeros((CENSUS_GROUPS, 8), bool))
    deliver = jax.device_put(
        full_delivery(CENSUS_GROUPS, PEERS),
        NamedSharding(mesh, P("groups", None, None)))
    fn = jax.jit(partial(deep_step, config=config, onehot=True))
    return census_text(
        fn.lower(state, resbuf, valbuf, rndbuf, evflag, base,
                 np.int32(0), sub, deliver, key).compile().as_text())


def _deep_scan_census(n_devices: int, devices, config,
                      W: int = 4) -> dict:
    """Census the round-5 ``deep_scan`` program (the whole blind phase
    as one lax.scan) — a new compiled module, so the zero-collective
    property must be re-verified, not inherited from deep_step."""
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..ops.consensus import (
        Submits, deep_scan, full_delivery, init_state)
    from ..parallel.mesh import shard_state

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = shard_state(
        init_state(CENSUS_GROUPS, PEERS, 32, init_key, config), mesh)
    sh2 = NamedSharding(mesh, P("groups", None))
    sh1 = NamedSharding(mesh, P("groups"))
    resbuf = jax.device_put(jnp.zeros((CENSUS_GROUPS, 32), jnp.int32), sh2)
    valbuf = jax.device_put(jnp.zeros((CENSUS_GROUPS, 32), bool), sh2)
    rndbuf = jax.device_put(
        jnp.full((CENSUS_GROUPS, 32), np.int32(2**30), jnp.int32), sh2)
    evflag = jax.device_put(jnp.zeros(CENSUS_GROUPS, bool), sh1)
    base = jax.device_put(jnp.zeros(CENSUS_GROUPS, jnp.int32), sh1)
    sub_w = Submits(
        opcode=np.zeros((W, CENSUS_GROUPS, 8), np.int32),
        a=np.zeros((W, CENSUS_GROUPS, 8), np.int32),
        b=np.zeros((W, CENSUS_GROUPS, 8), np.int32),
        c=np.zeros((W, CENSUS_GROUPS, 8), np.int32),
        tag=np.zeros((W, CENSUS_GROUPS, 1), np.int32),
        valid=np.zeros((W, CENSUS_GROUPS, 8), bool))
    deliver = jax.device_put(
        full_delivery(CENSUS_GROUPS, PEERS),
        NamedSharding(mesh, P("groups", None, None)))
    fn = jax.jit(partial(deep_scan, config=config, onehot=True))
    return census_text(
        fn.lower(state, resbuf, valbuf, rndbuf, evflag, base, sub_w,
                 deliver, key).compile().as_text())


def _measure(n_devices: int, devices) -> dict:
    from functools import partial

    from jax.sharding import Mesh

    from ..ops.consensus import (
        Config, full_delivery, init_state, make_submits, step)
    from ..parallel.mesh import shard_state, shard_step_inputs

    mesh = Mesh(np.asarray(devices[:n_devices]), ("groups",))
    config = Config()
    key = jax.random.PRNGKey(0)
    key, init_key = jax.random.split(key)
    state = init_state(GROUPS, PEERS, 32, init_key, config)
    submits = make_submits(GROUPS, 4)
    deliver = full_delivery(GROUPS, PEERS)
    state = shard_state(state, mesh)
    submits, deliver = shard_step_inputs(submits, deliver, mesh)
    fn = jax.jit(partial(step, config=config))
    collectives = _collective_census(n_devices, devices)
    query_collectives = _query_census(n_devices, devices)

    t0 = time.perf_counter()
    for _ in range(3):  # warm-up (includes compile)
        key, k = jax.random.split(key)
        state, out = fn(state, submits, deliver, k)
    jax.block_until_ready(state)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        key, k = jax.random.split(key)
        state, out = fn(state, submits, deliver, k)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    return {"devices": n_devices,
            "ms_per_round": round(dt / ROUNDS * 1e3, 2),
            "warmup_s": round(compile_s, 1),
            "collectives": collectives,
            "query_collectives": query_collectives}


def main() -> None:
    devices = jax.devices("cpu")
    if len(devices) < 8:
        raise SystemExit("need 8 virtual CPU devices (set XLA_FLAGS before "
                         "any jax import)")
    host_cores = (len(os.sched_getaffinity(0))
                  if hasattr(os, "sched_getaffinity") else os.cpu_count())
    rows = [_measure(n, devices) for n in (1, 2, 4, 8)]
    base = rows[0]["ms_per_round"]
    for row in rows:
        row["vs_1dev"] = round(row["ms_per_round"] / base, 2)
    no_collectives = all(not row["collectives"] for row in rows)
    query_no_coll = all(not row["query_collectives"] for row in rows)
    bulk_rows = [_measure_bulk(n, devices) for n in (1, 2, 4, 8)]
    bulk_no_coll = all(not row["collectives"] for row in bulk_rows)
    scan_no_coll = all(not row["scan_collectives"] for row in bulk_rows)
    result = {"groups": GROUPS, "peers": PEERS, "rounds": ROUNDS,
              "mesh_axis": "groups", "host_cores": host_cores,
              "no_cross_device_collectives": no_collectives,
              "query_no_cross_device_collectives": query_no_coll,
              "bulk_no_cross_device_collectives": bulk_no_coll,
              "deep_scan_no_cross_device_collectives": scan_no_coll,
              "table": rows, "bulk_table": bulk_rows}

    lines = [
        "# MULTICHIP_SCALING — sharded step over the virtual mesh",
        "",
        f"Fixed total work ({GROUPS} groups × {PEERS} peers, full default",
        "pools) jitted over 1/2/4/8 virtual CPU devices, group axis",
        "sharded (`copycat_tpu/parallel/mesh.py`), measured with",
        "`python -m copycat_tpu.parallel.scaling`.",
        "",
        "## Pass criterion (round 4): no cross-device collectives",
        "",
        "The compiled module of the sharded step is inspected per device",
        "count. A purely group-sharded step is embarrassingly parallel —",
        "groups are independent Raft worlds — so the correct compilation",
        "target is ZERO cross-device collectives (no all-reduce /",
        "all-gather / reduce-scatter / collective-permute / all-to-all),",
        "which is the direct witness that XLA inserts no resharding on",
        "the step's dataflow. Measured:",
        "",
        f"- cross-device collectives at 1/2/4/8 devices: "
        + ("**none** ✓" if no_collectives else "**FOUND** ✗ (see JSON)"),
        f"- query_step (round-9 read plane) cross-device collectives at "
        f"1/2/4/8 devices: "
        + ("**none** ✓" if query_no_coll else "**FOUND** ✗ (see JSON)"),
        f"- host cores available to this process: **{host_cores}**",
        "",
        "Walltime on the virtual mesh is diagnostic only: virtual CPU",
        "devices share host cores, so with fewer cores than devices the",
        "per-round time grows with device count from pure host",
        "oversubscription (program launch + inter-device rendezvous on a",
        "shared core), not from communication — the round-3 8-device",
        "\"regression\" reproduced exactly this on a 1-core host while",
        "the compiled modules contain no collectives at all. On real",
        "multi-chip hardware each shard owns a chip and the same program",
        "runs with no cross-chip traffic in the step.",
        "",
        "| devices | ms/round | vs 1 device | collectives |",
        "|---|---|---|---|",
    ]
    for row in rows:
        cl = row["collectives"] or "none"
        lines.append(f"| {row['devices']} | {row['ms_per_round']} "
                     f"| {row['vs_1dev']}× | {cl} |")
    lines += [
        "",
        "The peer axis stays replicated here (P=3 quorum tallies are",
        "cheap reductions); `__graft_entry__.dryrun_multichip` separately",
        "proves the 2D ('groups','peers') sharding compiles and elects",
        "across the mesh every round.",
        "",
        "## The CLIENT data path over the sharded mesh (round 4)",
        "",
        "The deep bulk pipeline (`models/bulk.py` — device-enforced FIFO,",
        "on-device [G,B] result accumulators, one harvest per drive) runs",
        "unchanged over group-sharded engines: the accumulators shard with",
        "the state, the scatter stays shard-local, and the `deep_step`",
        "compiled module is censused for collectives the same way:",
        "",
        f"- deep_step cross-device collectives at 1/2/4/8 devices: "
        + ("**none** ✓" if bulk_no_coll else "**FOUND** ✗ (see JSON)"),
        f"- deep_scan (round 5 — the whole blind phase as one lax.scan"
        f" program) cross-device collectives at 1/2/4/8 devices: "
        + ("**none** ✓" if scan_no_coll else "**FOUND** ✗ (see JSON)"),
        "",
        "| devices | client-visible ops/sec | drive rounds | collectives |",
        "|---|---|---|---|",
    ] + [
        f"| {row['devices']} | {row['client_visible_ops_per_sec']:,} "
        f"| {row['drive_rounds']} | {row['collectives'] or 'none'} |"
        for row in bulk_rows
    ] + [
        "",
        "(Same oversubscription caveat: virtual devices share this host's",
        "core, so ops/sec across device counts measures scheduler overhead",
        "only; zero collectives is the portable witness.)",
        "",
        "The bulk rows run with the round-8 device telemetry block ON",
        "(`Config(telemetry=True)`), so the deep_step/deep_scan censuses",
        "above also witness that the telemetry reductions stay per-group",
        "(zero collectives), and each row's JSON carries",
        "`telemetry_per_shard` — elections / leader changes / commit",
        "advance attributed to every device's group block — plus",
        "`telemetry_merged`, the same shards folded back through",
        "`merge_snapshots` (the multihost roll-up idiom).",
        "",
    ]
    with open("MULTICHIP_SCALING.md", "w") as f:
        f.write("\n".join(lines))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
