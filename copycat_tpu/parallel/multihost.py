"""Multi-host (multi-process) execution of the batched consensus engine.

The reference scales by running one server process per machine over
Netty/TCP (SURVEY.md §5.8). The TPU-native equivalent: ONE SPMD program
over a global ``jax.sharding.Mesh`` spanning every process's devices —
`jax.distributed` wires the processes (gRPC coordination over DCN), XLA
inserts the cross-process collectives for the peer-axis tallies, and
each process keeps the CLIENT side (queues, harvest, sessions, retry
protocol) for the groups whose shards it hosts. Client traffic is
host-local; replica traffic is ICI/DCN inside the compiled step —
exactly the split SURVEY.md §5.8 prescribes.

Usage (same program on every process — SPMD):

    from copycat_tpu.parallel import multihost
    multihost.initialize("host0:9100", num_processes=4, process_id=i)
    rg = multihost.MultiHostRaftGroups(groups_per_process=2500)
    rg.wait_for_leaders()            # lockstep-coordinated
    tag = rg.submit(local_group, OP_LONG_ADD, 1)   # local group index
    rg.run_until([tag])              # lockstep-coordinated

LOCKSTEP CONTRACT: ``step_round`` launches a collective program, so all
processes must call it the same number of times. Every stop/branch
decision in the driver loops (`run_until`, `wait_for_leaders`,
`serve_query`, the serve-queries gate inside `step_round`) flows through
the `_agree`/`_any_across` hooks, which allgather here — so the standard
`RaftGroups` API is lockstep-safe as long as each process calls the same
methods (with its own local arguments; `run_until([])` when idle).
Verified end-to-end by ``tests/test_multihost.py`` (two real processes
over a loopback coordinator on the CPU backend).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from functools import partial

from ..models.raft_groups import RaftGroups
from ..ops.consensus import (
    Config,
    Submits,
    init_state,
    install_snapshots,
    query_step,
    step,
)
from .mesh import raft_shardings


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, platform: str | None = None) -> None:
    """Wire this process into the cluster (``jax.distributed``). Call
    before any other JAX use; every process must call it with the same
    coordinator (process 0's address)."""
    if platform:
        jax.config.update("jax_platforms", platform)
    # CPU multiprocess needs an explicit collectives backend: the
    # installed jaxlib defaults jax_cpu_collectives_implementation to
    # "none" and refuses every cross-process program outright
    # ("Multiprocess computations aren't implemented on the CPU
    # backend"). Gloo ships in jaxlib; it is inert for TPU meshes (the
    # knob only picks the CPU backend's collectives transport).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh() -> Mesh:
    """1D ``('groups',)`` mesh over ALL processes' devices, ordered so
    each process's devices form one contiguous block of the group axis
    (jax.devices() orders by process index)."""
    return Mesh(np.asarray(jax.devices()), ("groups",))


class MultiHostRaftGroups(RaftGroups):
    """``RaftGroups`` over a process-spanning mesh: the consensus state
    is ONE global sharded pytree, the step is one collective XLA
    program, and THIS process's host runtime (submit queues, harvest,
    results, events, sessions, exactly-once retry) covers the
    ``groups_per_process`` groups whose shards live on its devices.
    Group indices in the public API are process-LOCAL (0..Gp-1); the
    global group id is ``group + group_offset``."""

    def __init__(self, groups_per_process: int, num_peers: int = 3,
                 log_slots: int = 64, submit_slots: int = 4,
                 config: Config | None = None, seed: int = 0,
                 voters: int | None = None) -> None:
        if jax.process_count() < 2:
            raise RuntimeError(
                "MultiHostRaftGroups needs jax.distributed to be "
                "initialized across >=2 processes (multihost.initialize)")
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        self.global_groups = groups_per_process * self.process_count
        self.group_offset = groups_per_process * self.process_index
        # Base init sizes ALL host bookkeeping to the local block (its
        # num_groups); _build_state=False (subclass protocol, not public
        # API) skips the locally shaped state/deliver/jit wrappers that
        # this __init__ replaces with global sharded versions below.
        super().__init__(groups_per_process, num_peers, log_slots,
                         submit_slots, config, seed, voters=voters,
                         _build_state=False)
        self.mesh = global_mesh()
        self._sub_sharding = NamedSharding(self.mesh, P("groups", None))
        self._dl_sharding = NamedSharding(self.mesh, P("groups", None, None))

        # Global replicated-construction state: every process builds the
        # SAME full-size host arrays (same seed -> identical), then each
        # contributes only the shards its devices own.
        key = jax.random.PRNGKey(seed)
        _, init_key = jax.random.split(key)
        members = None
        if voters is not None and voters < num_peers:
            members = np.arange(num_peers) < voters
        full = init_state(self.global_groups, num_peers, log_slots,
                          init_key, self.config, members=members)
        state_sh, _ = raft_shardings(self.mesh, full)
        self.state = jax.tree.map(
            lambda x, s: jax.make_array_from_callback(
                x.shape, s, lambda idx, x=x: np.asarray(x)[idx]),
            full, state_sh)
        self.deliver = self._stage_deliver(
            np.ones((groups_per_process, num_peers, num_peers), bool))
        # Output shardings are PINNED to group-sharded (leading dim on
        # the mesh, rest replicated): the shard-concat fetch below relies
        # on every output leaf being split by groups, and without the pin
        # the compiler is free to replicate an output.
        out_sh = NamedSharding(self.mesh, P("groups"))
        step_program = jax.jit(partial(step, config=self.config),
                               out_shardings=(state_sh, out_sh))

        def carried(state, submits, deliver, key):
            # the base driver's calling convention (the key goes in and
            # comes back split) around this driver's own program
            key, k = jax.random.split(key)
            state, out = step_program(state, submits, deliver, k)
            return state, key, out

        self._step = carried
        self._query = jax.jit(partial(query_step, config=self.config),
                              out_shardings=out_sh)
        self._install = jax.jit(partial(install_snapshots,
                                        config=self.config),
                                out_shardings=state_sh)
        self._global_any = jax.jit(jnp.any)
        self._state_sh = state_sh
        self._out_sh = out_sh
        self._deep_jit = None   # built on first deep drive (_deep_fn)

    # -- staging/fetch hooks: local block <-> global sharded arrays ------

    def _stage_submits(self, submits: Submits) -> Submits:
        return Submits(*[
            jax.make_array_from_process_local_data(
                self._sub_sharding, np.ascontiguousarray(x))
            for x in submits])

    # this driver's programs take the Submits pytree and donate nothing
    _stage_round = _stage_submits
    round_donates = False

    def _stage_deliver(self, deliver: Any) -> Any:
        return jax.make_array_from_process_local_data(
            self._dl_sharding, np.ascontiguousarray(np.asarray(deliver)))

    def _fetch_outputs(self, raw):
        # overlap the D2H transfers (same rationale as the base hook:
        # lazy per-array fetches each pay a full round-trip), then
        # assemble each leaf's local block
        for leaf in jax.tree.leaves(raw):
            for s in leaf.addressable_shards:
                s.data.copy_to_host_async()
        return self._note_fetch(jax.tree.map(self._local_block, raw))

    def _stale_any(self, raw, out) -> bool:
        # the install decision must be GLOBALLY consistent (install runs
        # a collective program): reduce over the global array — the
        # replicated scalar is addressable on every process
        return bool(np.asarray(self._global_any(raw.stale)))

    def _run_query(self, sub: Submits, atomic):
        g_atomic = jax.make_array_from_process_local_data(
            self._sub_sharding, np.ascontiguousarray(atomic))
        results, served = self._query(self.state, self._stage_submits(sub),
                                      g_atomic)
        return self._note_fetch((self._local_block(results),
                                 self._local_block(served)))

    # -- deep-plane hooks (models/bulk.py _drive_deep) --------------------
    # The deep drive stages submits through _stage_submits (above) and
    # everything else through these: accumulators become GLOBAL
    # group-sharded arrays assembled from each process's local block,
    # fetches return only addressable shards, and the deep program pins
    # its output shardings (an unpinned output is free to replicate,
    # which would break the shard-concat fetch).

    def _global_max_int(self, v: int) -> int:
        from jax.experimental import multihost_utils
        return int(np.asarray(multihost_utils.process_allgather(
            np.asarray(v, np.int64))).max())

    def _stage_acc(self, arr: np.ndarray):
        spec = P("groups", *([None] * (arr.ndim - 1)))
        arr = self._note_stage(np.ascontiguousarray(arr))
        self._m_bulk_early.inc(arr.nbytes)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, spec), arr)

    def _to_host(self, leaves):
        self._ask_acc(leaves)
        return [self._local_block(x) for x in leaves]

    def _deep_fn(self):
        if self._deep_jit is None:
            from ..models.bulk import _named
            from ..ops.consensus import deep_step
            acc2 = NamedSharding(self.mesh, P("groups", None))
            acc1 = NamedSharding(self.mesh, P("groups"))
            # donation mirrors the single-host deep program: state +
            # accumulators are handed back to XLA for in-place reuse
            # (saves a full sharded-state copy per round)
            donate = (0, 1, 2, 3, 4) if self.donate else ()
            self._deep_jit = jax.jit(
                _named(deep_step, config=self.config, onehot=True),
                donate_argnums=donate,
                out_shardings=(self._state_sh, acc2, acc2, acc2, acc1,
                               self._out_sh))
        return self._deep_jit

    # -- lockstep agreement primitives -------------------------------------
    # The base driver loops (run_until, wait_for_leaders, serve_query,
    # the serve-queries gate in step_round) decide through these, so the
    # control flow lives in ONE place; here they allgather so every
    # process takes the same branch around every collective program.

    @staticmethod
    def _gather_flags(mine: bool) -> np.ndarray:
        from jax.experimental import multihost_utils
        return np.asarray(
            multihost_utils.process_allgather(np.asarray(mine, bool)))

    def _agree(self, mine: bool) -> bool:
        return bool(self._gather_flags(mine).all())

    def _any_across(self, mine: bool) -> bool:
        return bool(self._gather_flags(mine).any())

    # -- device-plane telemetry (models/telemetry.py) ---------------------

    def merged_device_snapshot(self) -> dict:
        """Cluster-wide ``device.*`` family: allgather each process's
        local snapshot (the hub eagerly registers every key, so the key
        sets agree) and fold with ``merge_snapshots`` — counters sum
        across shards, gauges take the max except the per-shard-additive
        ones (``ADDITIVE_GAUGES``: commit total, leaderless count),
        which sum. COLLECTIVE: every process must call it together
        (same lockstep contract as step_round)."""
        from jax.experimental import multihost_utils

        from ..utils.metrics import merge_snapshots

        local = self.device_snapshot()
        # The enablement decision must itself be COLLECTIVE: telemetry
        # is a per-process choice (env opt-in), and a telemetry-off
        # process returning early while its peers enter the value
        # allgather would hang the cluster. Every process first agrees
        # whether ALL of them have the family; if any lacks it, all
        # return {} together.
        have = np.asarray(
            multihost_utils.process_allgather(np.asarray(bool(local))))
        if not have.all():
            return {}
        gauge_keys = local.get("_gauge_keys", [])
        keys = sorted(k for k, v in local.items()
                      if k != "_gauge_keys" and not isinstance(v, dict))
        vals = np.asarray([float(local[k]) for k in keys], np.float64)
        gathered = np.asarray(multihost_utils.process_allgather(vals))
        snaps = []
        for p in range(gathered.shape[0]):
            snap: dict = {k: gathered[p, i] for i, k in enumerate(keys)}
            snap["_gauge_keys"] = list(gauge_keys)
            snaps.append(snap)
        out = merge_snapshots(snaps)
        # gauges that are sums over each process's DISJOINT group block
        # (commit total, leaderless count) add across shards; the
        # merge_snapshots gauge default (max) would report only the
        # worst shard
        from ..models.telemetry import ADDITIVE_GAUGES
        for k in ADDITIVE_GAUGES:
            if k in keys:
                out[k] = float(gathered[:, keys.index(k)].sum())
        return out

    # -- local views -------------------------------------------------------

    def leader(self, group: int) -> int:
        """Leader lane of LOCAL ``group`` (reads this process's shard)."""
        role = self._local_block(self.state.role)[group]
        term = self._local_block(self.state.term)[group]
        leaders = np.nonzero(role == 2)[0]
        if len(leaders) == 0:
            return -1
        return int(leaders[np.argmax(term[leaders])])

    def value(self, group: int, peer: int = 0) -> int:
        return int(self._local_block(self.state.resources.value)
                   [group, peer])

    def voting_members(self, group: int) -> list[int]:
        # same lane-selection rule as the base class (_config_mask), over
        # this process's local block of the sharded state
        mask = self._config_mask(
            self._local_block(self.state.member)[group],
            self._local_block(self.state.applied_index)[group],
            self._local_block(self.state.term)[group],
            self._local_block(self.state.role)[group])
        return [p for p in range(self.num_peers) if (mask >> p) & 1]
