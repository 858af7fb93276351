"""Checkpoint/resume for the batched consensus state.

The reference has **no snapshots** — durability is the replicated log with
segmented storage (SURVEY.md §5.4); recovery = replay. The rebuild adds
real snapshots (named there as "a capability gap worth fixing"): the whole
``RaftState`` pytree (logs, indices, every resource pool, event rings) plus
driver counters serializes to one compressed ``.npz``. Restore yields a
driver that continues exactly where the snapshot was taken — in-flight
client ops are *not* checkpointed (clients re-submit, the same contract as
the reference's session recovery).
"""

from __future__ import annotations

import io
import json
import math
import pathlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.consensus import Config
from ..ops.apply import ResourceConfig


def _leaf_name(path) -> str:
    """Dotted field path of a pytree leaf ('resources.mm_key', 'term')."""
    return ".".join(getattr(p, "name", str(p)) for p in path)


@jax.jit
def _cut_program(state, deliver, key):
    """Everything of a driver that lives on the device, in buffers of its
    own: the round's programs donate the state and the key
    (``raft_groups._jitted_programs``), so a cut may hold no reference
    into the live pytree past the next round. One group-leading slab per
    dtype in the round's packing idiom (``raft_groups.PackedOutputs``):
    the host fetches two or three buffers where it fetched one per leaf,
    and a mesh engine's slabs stay shard-local."""
    groups = deliver.shape[0]
    cols: dict[str, list] = {}
    for x in (*jax.tree.leaves(state), deliver):
        cols.setdefault(x.dtype.name, []).append(x.reshape(groups, -1))
    # lax.concatenate is an operation of its own even over one operand: a
    # jitted function's output that IS its input comes back as the
    # caller's own buffer
    return ({name: jax.lax.concatenate(v, 1) for name, v in cols.items()},
            jnp.copy(key))


class StateCut:
    """A driver's image cut at one instant (:func:`cut`): the device
    state as fresh slabs whose transfer to the host has begun, and copies
    of the host fields. The driver may run on — and donate its state —
    while :meth:`write` waits, splits and compresses on any thread."""

    __slots__ = ("_fields", "_slabs", "_key", "_meta")

    def __init__(self, fields: list, slabs: dict, key: Any,
                 meta: dict) -> None:
        self._fields, self._slabs, self._key, self._meta = (
            fields, slabs, key, meta)

    def write(self, target) -> None:
        """The field-path ``.npz`` of :func:`save`, to a path or an open
        binary file."""
        slabs = {name: np.asarray(x) for name, x in self._slabs.items()}
        at = dict.fromkeys(slabs, 0)
        arrays = {}
        for name, shape, dtype in self._fields:
            width = math.prod(shape[1:])
            start = at[dtype]
            at[dtype] = start + width
            arrays[name] = np.ascontiguousarray(
                slabs[dtype][:, start:start + width]).reshape(shape)
        meta = self._meta | {"key": np.asarray(self._key).tolist()}
        np.savez_compressed(target, meta=json.dumps(meta), **arrays)

    def to_bytes(self) -> bytes:
        bio = io.BytesIO()
        self.write(bio)
        return bio.getvalue()


def cut(rg) -> StateCut:
    """Take from a ``RaftGroups`` driver everything a later round can
    change, and nothing else: one dispatch of :func:`_cut_program`, the
    start of its transfer, and copies of the host fields. Costs the
    caller no fetch and no compression: those are :meth:`StateCut.write`'s.

    State leaves are named BY FIELD PATH (``state.resources.mm_key``),
    not positionally, so restoring stays correct no matter where future
    fields are inserted in ``RaftState``/``ResourceState`` — a missing
    (newer) field simply keeps the fresh template value on load.
    """
    flat = jax.tree_util.tree_flatten_with_path(rg.state)[0]
    fields = [(f"state.{_leaf_name(p)}", x.shape, x.dtype.name)
              for p, x in flat]
    fields.append(("deliver", rg.deliver.shape, rg.deliver.dtype.name))
    slabs, key = _cut_program(rg.state, rg.deliver, rg._key)
    for x in (*slabs.values(), key):
        x.copy_to_host_async()
    meta = {
        "num_groups": rg.num_groups,
        "num_peers": rg.num_peers,
        "log_slots": rg.log_slots,
        "submit_slots": rg.submit_slots,
        "config": rg.config._asdict() | {
            "resource": rg.config.resource._asdict()},
        "rounds": rg.rounds,
        "clock": rg.clock,
        "next_tag": rg._next_tag,
        "ev_seen": dict(rg._ev_seen),
        # the host-side event buffer (consumption cursors are facade-local,
        # so this includes consumed events): restores the buffer faithfully
        # and keeps seq dedup (_ev_seen) consistent with it. Facades
        # created after restore start their cursor past these (session
        # events die with the session) and re-query authoritative state.
        "events": {str(g): list(evs) for g, evs in rg.events.items()},
        "num_leaves": len(flat),
    }
    return StateCut(fields, slabs, key, meta)


def save(rg, path: str | pathlib.Path) -> None:
    """Snapshot a ``RaftGroups`` driver to ``path`` (.npz)."""
    cut(rg).write(path if hasattr(path, "write") else str(path))


def save_bytes(rg) -> bytes:
    """Snapshot a ``RaftGroups`` driver to in-memory bytes (the same
    field-path ``.npz`` format as :func:`save`) — the server-plane
    snapshot subsystem embeds this blob for device-backed machines."""
    return cut(rg).to_bytes()


def load_bytes(data: bytes, mesh=None):
    """Restore a ``RaftGroups`` driver from :func:`save_bytes` output."""
    return load(io.BytesIO(data), mesh=mesh)


def load(path: str | pathlib.Path, mesh=None):
    """Restore a ``RaftGroups`` driver from a snapshot."""
    from .raft_groups import RaftGroups

    source = path if hasattr(path, "read") else str(path)
    with np.load(source, allow_pickle=False) as data:
        meta = json.loads(str(data["meta"]))
        cfg = dict(meta["config"])
        cfg["resource"] = ResourceConfig(**cfg["resource"])
        # Tolerate snapshots from older Configs: drop fields that no
        # longer exist (e.g. apply_unroll, removed with the conflict-
        # partitioned apply) instead of failing the whole restore; new
        # fields get their defaults. pool_budgets round-trips through
        # JSON as a list — restore the hashable tuple.
        cfg = {k: v for k, v in cfg.items() if k in Config._fields}
        if isinstance(cfg.get("pool_budgets"), list):
            cfg["pool_budgets"] = tuple(cfg["pool_budgets"])
        config = Config(**cfg)
        rg = RaftGroups(meta["num_groups"], meta["num_peers"],
                        log_slots=meta["log_slots"],
                        submit_slots=meta["submit_slots"],
                        config=config, mesh=mesh)
        template = rg.state
        treedef = jax.tree_util.tree_structure(template)
        if any(k.startswith("state.") for k in data.files):
            # Path-keyed format: robust to fields inserted ANYWHERE — a
            # field absent from the snapshot keeps its fresh template
            # value (e.g. a pool added after the snapshot was taken).
            flat = jax.tree_util.tree_flatten_with_path(template)[0]
            leaves = [data[f"state.{_leaf_name(p)}"]
                      if f"state.{_leaf_name(p)}" in data else np.asarray(x)
                      for p, x in flat]
        else:
            # Legacy positional format (leaf_0..leaf_N in the field order
            # of the SAVING code). Fields were strictly appended while
            # this format was in use, so missing leaves are the trailing
            # ones: pad with the template's fresh arrays.
            leaves = [data[f"leaf_{i}"] for i in range(meta["num_leaves"])]
            expected = jax.tree_util.tree_leaves(template)
            if len(leaves) < len(expected):
                leaves = leaves + expected[len(leaves):]
        state = jax.tree_util.tree_unflatten(treedef, leaves)
        if mesh is not None:
            from ..parallel import shard_state
            state = shard_state(state, mesh)
        else:
            state = jax.tree.map(jnp.asarray, state)
        rg.state = state
        rg.deliver = jnp.asarray(data["deliver"])
        rg.rounds = meta["rounds"]
        rg.clock = meta["clock"]
        rg._next_tag = meta["next_tag"]
        rg._ev_seen = {int(k): int(v) for k, v in meta["ev_seen"].items()}
        rg.events = {int(g): [tuple(e) for e in evs]
                     for g, evs in meta.get("events", {}).items()}
        rg._key = jnp.asarray(np.asarray(meta["key"], np.uint32))
        if config.monotone_tag_accept:
            # the monotone stream cursor is DERIVED, not stored: the
            # restored log ring is authoritative (works for snapshots
            # taken before the cursor existed)
            from .bulk import stream_count_from_state
            rg._stream_count = stream_count_from_state(rg.state)
    return rg
