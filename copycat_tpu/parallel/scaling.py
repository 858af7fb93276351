"""Collective censuses of the compiled, group-sharded programs.

A purely group-sharded program is embarrassingly parallel: groups are
independent Raft worlds, so its compiled module must hold ZERO cross-device
collectives (no all-reduce / all-gather / reduce-scatter /
collective-permute / all-to-all), the direct witness that XLA inserts no
resharding on its dataflow. Each census here lowers one program over a
caller's mesh of devices at a small size and tallies the collectives in the
module's text; ``tests/test_mesh_bulk.py`` holds them at zero, and the
four-chip benchmark cell reads the program it drove the same way
(``census_text``, ``placement.collectives``). The step's own census is
``tests/test_tpu_compile.py::test_group_sharded_step_has_zero_collectives``.
"""

from __future__ import annotations

import jax
import numpy as np

PEERS = 3

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
# Collective structure depends only on the sharding pattern, not G.
CENSUS_GROUPS = 256


def census_text(txt: str) -> dict:
    """Tally cross-device collective ops in compiled-module text."""
    import re

    return {op: n for op in COLLECTIVE_OPS
            if (n := len(re.findall(rf"\b{op}\b", txt)))}


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
